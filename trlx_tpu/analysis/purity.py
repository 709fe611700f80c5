"""Trace-purity and host-sync-zone checkers (rules ``trace-purity``,
``sync-zone``).

Trace purity: a function handed to ``jit``/``pjit``/``scan``/
``while_loop``/``fori_loop``/``cond``/``switch``/``shard_map``/
``checkpoint`` executes at TRACE time, once — a ``print`` inside it
fires on compilation and never again, ``time.time()`` bakes the
compile-time clock into the graph as a constant, ``np.random`` draws a
single constant sample, and mutating Python state from inside the trace
desynchronizes host bookkeeping from what the compiled graph actually
does on re-execution. All of these are bugs that type-check, run, and
quietly produce wrong numbers.

Host-sync zones: modules that claim "host-side, no device syncs" (the
obs/ flight recorder and the watchdog's beat paths — plus any module
whose docstring makes the claim) must never block the host on the
device: ``.item()``, ``block_until_ready``, ``np.asarray`` on device
arrays, ``jax.device_get``, and module-scope jax imports are all
forbidden there. ``float()``/``bool()`` are flagged only when applied
directly to a jnp/jax call result — host-scalar coercion like
``float(v)`` over dict values is the zones' bread and butter and stays
legal (the narrowing is documented in docs/static_analysis.md).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set

from trlx_tpu.analysis.common import Finding, Module, dotted, resolve

# tracing entry points: {canonical name: positions of traced fn args}
# (None = first positional arg); decorator forms handled separately
TRACED_ARG_POSITIONS = {
    "jax.jit": (0,),
    "jax.pjit": (0,),
    "jax.experimental.pjit.pjit": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.lax.scan": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1,),  # list of branches
    "jax.shard_map": (0,),
    "jax.experimental.shard_map.shard_map": (0,),
    "jax.vmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
}
_TRACE_TAILS = {name.split(".")[-1]: pos for name, pos in TRACED_ARG_POSITIONS.items()}

PARTIAL_FNS = {"functools.partial", "partial"}

# modules that get the sync-zone rule by path; a module whose docstring
# claims "no device sync" opts itself in too
DEFAULT_ZONES = ("trlx_tpu/obs/", "trlx_tpu/utils/watchdog.py")
_ZONE_CLAIM = "no device sync"

IMPURE_CALLS = {
    "print": "print() fires once at trace time, never on execution",
    "input": "input() blocks tracing",
    "open": "file I/O at trace time happens once, not per step",
    "time.time": "the compile-time clock becomes a baked-in constant",
    "time.perf_counter": "the compile-time clock becomes a baked-in constant",
    "time.monotonic": "the compile-time clock becomes a baked-in constant",
    "time.process_time": "the compile-time clock becomes a baked-in constant",
    "time.sleep": "sleeping at trace time delays compilation, not steps",
    "datetime.datetime.now": "the compile-time clock becomes a constant",
    "datetime.datetime.utcnow": "the compile-time clock becomes a constant",
}
IMPURE_PREFIXES = {
    "numpy.random.": "np.random draws ONE constant sample at trace time "
                     "— use jax.random with a threaded key",
    "random.": "the random module draws ONE constant sample at trace "
               "time — use jax.random with a threaded key",
}
SYNC_ATTR_CALLS = {
    "item": ".item() blocks the host on the device",
    "block_until_ready": "block_until_ready() is a host-device sync",
    "copy_to_host_async": "host copies do not belong here",
}
SYNC_CALLS = {
    "numpy.asarray": "np.asarray on a device array downloads it",
    "numpy.array": "np.array on a device array downloads it",
    "jax.device_get": "device_get downloads device buffers",
    "jax.block_until_ready": "a host-device sync",
}

# deliberately NOT including "update": optax's pure
# `tx.update(grads, state)` is ubiquitous inside traced steps and a
# dict.update on closed-over state is caught in review far more easily
# than hundreds of pragmas would be maintained (docs/static_analysis.md)
MUTATING_METHODS = {
    "append", "extend", "insert", "remove", "pop", "clear",
    "add", "setdefault", "popitem", "write", "writelines", "discard",
}

# pallas kernels mutate output/scratch Refs by construction — that IS
# the programming model, not trace-time Python mutation
_REF_ROOT_SUFFIXES = ("_ref", "_scratch")


def _resolve_traced_positions(module: Module, fn_node) -> Optional[Sequence[int]]:
    """Arg positions traced by this callee, or None when not a tracer."""
    if not isinstance(fn_node, (ast.Name, ast.Attribute)):
        return None
    canon = resolve(module, fn_node)
    if canon in TRACED_ARG_POSITIONS:
        return TRACED_ARG_POSITIONS[canon]
    tail = (dotted(fn_node) or "").split(".")[-1]
    # jax.* aliasing is common (from jax.lax import scan; lax.scan);
    # match by tail only when the chain plausibly comes from jax
    if tail in _TRACE_TAILS:
        raw = dotted(fn_node) or ""
        if raw == tail or raw.split(".")[0] in (
            "jax", "lax", "jnp", "pjit", "nn"
        ):
            return _TRACE_TAILS[tail]
    return None


class _FnIndex(ast.NodeVisitor):
    """All function-ish nodes, by name, plus parent links for
    traced-region propagation."""

    def __init__(self):
        self.by_name: Dict[str, List[ast.AST]] = {}
        self.functions: List[ast.AST] = []

    def visit_FunctionDef(self, node):
        self.by_name.setdefault(node.name, []).append(node)
        self.functions.append(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self.functions.append(node)
        self.generic_visit(node)


def _is_traced_decorator(module: Module, dec) -> bool:
    if isinstance(dec, (ast.Name, ast.Attribute)):
        return _resolve_traced_positions(module, dec) is not None
    if isinstance(dec, ast.Call):
        fn = dec.func
        if isinstance(fn, (ast.Name, ast.Attribute)):
            if resolve(module, fn) in PARTIAL_FNS and dec.args:
                inner = dec.args[0]
                return isinstance(inner, (ast.Name, ast.Attribute)) and (
                    _resolve_traced_positions(module, inner) is not None
                )
            return _resolve_traced_positions(module, fn) is not None
    return False


def find_traced_functions(module: Module) -> Set[ast.AST]:
    """Function/Lambda nodes whose bodies execute under a trace."""
    index = _FnIndex()
    index.visit(module.tree)
    traced: Set[ast.AST] = set()

    def mark(node):
        if isinstance(node, ast.Lambda):
            traced.add(node)
        elif isinstance(node, (ast.Name, ast.Attribute)):
            name = node.id if isinstance(node, ast.Name) else node.attr
            for fdef in index.by_name.get(name, []):
                traced.add(fdef)

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_traced_decorator(module, d) for d in node.decorator_list):
                traced.add(node)
        if isinstance(node, ast.Call):
            positions = _resolve_traced_positions(module, node.func)
            if positions is None:
                continue
            for pos in positions:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                if isinstance(arg, (ast.List, ast.Tuple)):  # lax.switch
                    for el in arg.elts:
                        mark(el)
                else:
                    mark(arg)

    # everything nested inside a traced function is traced too
    for fn in list(traced):
        for sub in ast.walk(fn):
            if sub is not fn and isinstance(
                sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                traced.add(sub)
    return traced


def _local_names(fn, include_params: bool = True) -> Set[str]:
    """Names bound inside the function. With ``include_params=False``
    only names *assigned* in the body count: objects a traced function
    constructs itself are trace-local bookkeeping, but mutating state
    reached THROUGH a parameter (``self.x = ...``, ``carry[k] = v``,
    ``history.append(...)``) escapes the trace — the parameter object
    outlives it — and is exactly the runs-once-at-trace-time bug."""
    names: Set[str] = set()
    args = fn.args
    if include_params:
        for a in (
            list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        ):
            names.add(a.arg)
        if args.vararg:
            names.add(args.vararg.arg)
        if args.kwarg:
            names.add(args.kwarg.arg)
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Name) and isinstance(
            sub.ctx, (ast.Store, ast.Del)
        ):
            names.add(sub.id)
        elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            names.add(sub.name)
        elif isinstance(sub, ast.comprehension):
            for el in ast.walk(sub.target):
                if isinstance(el, ast.Name):
                    names.add(el.id)
    return names


def _root_name(node) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def check_traced_purity(module: Module) -> List[Finding]:
    findings: List[Finding] = []
    traced = find_traced_functions(module)
    seen_lines: Set[int] = set()

    def add(node, msg):
        if node.lineno in seen_lines:
            return
        seen_lines.add(node.lineno)
        findings.append(Finding(
            "trace-purity", module.path, node.lineno, msg,
            snippet=module.line_at(node.lineno),
        ))

    for fn in traced:
        fname = getattr(fn, "name", "<lambda>")
        # params are NOT mutation-safe: `self.x = ...` or
        # `carry.append(...)` in a traced method mutates state that
        # outlives the trace (a param rebound in the body first
        # becomes an assigned local and is exempt again)
        local = _local_names(fn, include_params=False)
        for node in ast.walk(fn):
            # skip nested traced fns: they are walked separately, and
            # duplicates are folded by seen_lines anyway
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                add(node, (
                    f"traced function `{fname}` rebinds "
                    f"{'/'.join(node.names)} via "
                    f"{'global' if isinstance(node, ast.Global) else 'nonlocal'}"
                    " — trace-time mutation of Python state runs once, "
                    "not per step"
                ))
            elif isinstance(node, ast.Call):
                canon = resolve(module, node.func) or ""
                raw = dotted(node.func) or ""
                if canon in IMPURE_CALLS or raw in IMPURE_CALLS:
                    why = IMPURE_CALLS.get(canon) or IMPURE_CALLS[raw]
                    add(node, f"traced function `{fname}` calls "
                              f"`{raw or canon}`: {why}")
                    continue
                for prefix, why in IMPURE_PREFIXES.items():
                    if canon.startswith(prefix):
                        add(node, f"traced function `{fname}` calls "
                                  f"`{raw}`: {why}")
                        break
                else:
                    if canon in SYNC_CALLS:
                        add(node, f"traced function `{fname}` calls "
                                  f"`{raw}`: {SYNC_CALLS[canon]} — a "
                                  "tracer here fails at trace time or "
                                  "constant-folds silently")
                    elif (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in SYNC_ATTR_CALLS
                        and not node.args
                    ):
                        add(node, (
                            f"traced function `{fname}` calls "
                            f"`.{node.func.attr}()`: "
                            f"{SYNC_ATTR_CALLS[node.func.attr]}"
                        ))
                    elif (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in MUTATING_METHODS
                        and isinstance(node.func.value, (ast.Name, ast.Attribute))
                    ):
                        root = _root_name(node.func.value)
                        if root is not None and root not in local:
                            add(node, (
                                f"traced function `{fname}` mutates "
                                f"closed-over state via `{raw}(...)` — "
                                "the mutation happens once at trace "
                                "time, not per executed step"
                            ))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for tgt in targets:
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                        root = _root_name(tgt)
                        if (
                            root is not None
                            and root not in local
                            and not root.endswith(_REF_ROOT_SUFFIXES)
                        ):
                            add(node, (
                                f"traced function `{fname}` assigns to "
                                f"`{dotted(tgt) or root + '[...]'}` — "
                                "mutating external Python state from "
                                "inside a trace runs once at trace "
                                "time, not per step"
                            ))
    return findings


def _module_claims_zone(module: Module) -> bool:
    doc = ast.get_docstring(module.tree) or ""
    return _ZONE_CLAIM in doc.lower().replace("syncs", "sync")


def check_sync_zone(
    module: Module, zones: Sequence[str] = DEFAULT_ZONES
) -> List[Finding]:
    """Device-sync constructs in a host-side-only module."""
    in_zone = any(
        module.path.startswith(z) or module.path == z.rstrip("/")
        for z in zones
    ) or _module_claims_zone(module)
    if not in_zone:
        return []

    findings: List[Finding] = []

    def add(node, msg):
        findings.append(Finding(
            "sync-zone", module.path, node.lineno,
            msg + " — this module claims 'host-side, no device syncs'",
            snippet=module.line_at(node.lineno),
        ))

    # module-scope jax imports (zones claim jax-free at module scope;
    # lazy function-scope imports stay legal)
    for stmt in module.tree.body:
        if isinstance(stmt, ast.Import):
            for a in stmt.names:
                if a.name == "jax" or a.name.startswith("jax."):
                    add(stmt, f"module-scope `import {a.name}`")
        elif isinstance(stmt, ast.ImportFrom):
            if stmt.module and (
                stmt.module == "jax" or stmt.module.startswith("jax.")
            ):
                add(stmt, f"module-scope `from {stmt.module} import ...`")

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        canon = resolve(module, node.func) or ""
        raw = dotted(node.func) or ""
        if canon in SYNC_CALLS:
            add(node, f"`{raw}`: {SYNC_CALLS[canon]}")
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in SYNC_ATTR_CALLS
            and not node.args
        ):
            add(node, f"`.{node.func.attr}()`: "
                      f"{SYNC_ATTR_CALLS[node.func.attr]}")
        elif (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "bool", "int")
            and node.args
            and isinstance(node.args[0], ast.Call)
            and (resolve(module, node.args[0].func) or "").startswith(
                ("jax.", "jnp.")
            )
        ):
            add(node, f"`{node.func.id}(<jax call>)` forces a device "
                      "sync on the result")
    return findings


def check_module(
    module: Module, zones: Sequence[str] = DEFAULT_ZONES
) -> List[Finding]:
    return check_traced_purity(module) + check_sync_zone(module, zones)
