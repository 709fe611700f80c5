"""graft-lint acceptance (ISSUE 13): checker units on fixture snippets
(known-bad -> flagged, known-good -> clean), manifest append-only
semantics, pragma parsing, baseline/diff, and the whole-repo clean run
— the tier-1 hook that makes donation safety, trace purity, RNG-stream
discipline and config<->docs sync loud structural failures, the way
test_marker_audit.py already guards test budgets and bench honesty."""

import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from trlx_tpu.analysis import (  # noqa: F401 (runner re-exported surface)
    config_docs,
    donation,
    manifests,
    purity,
    runner,
)
from trlx_tpu.analysis.common import collect_pragmas, parse_module

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)


def _write(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return rel


def _lint(tmp_path, rels, **kw):
    return runner.lint_paths(str(tmp_path), rels, **kw)


def _active_rules(findings):
    return sorted({f.rule for f in runner.active(findings)})


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

PR3_SHAPE = """
    import jax

    def restore(path):
        return {"w": 1}

    def update(params, batch):
        return params, 0.0

    def main(path, batches):
        params = restore(path)            # orbax-restored arrays
        step = jax.jit(update, donate_argnums=(0,))
        new_params, loss = step(params, batches[0])
        return params["w"], new_params    # read of the donated buffer
"""


def test_donation_flags_pr3_restore_reuse(tmp_path):
    """The exact PR 3 bug shape: restored state donated to a train
    step, then read again — must flag the post-call read line."""
    rel = _write(tmp_path, "bug.py", PR3_SHAPE)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["donation"], found
    assert "params" in found[0].message
    assert found[0].line == 14  # the return-line read


def test_donation_tuple_reassign_is_clean(tmp_path):
    rel = _write(tmp_path, "ok.py", """
        import jax

        def update(p, o, b):
            return p, o, 0.0

        def loop(p, o, batches):
            step = jax.jit(update, donate_argnums=(0, 1))
            for b in batches:
                p, o, loss = step(p, o, b)
            return p, o
    """)
    assert runner.active(_lint(tmp_path, [rel])) == []


def test_donation_factory_attribute_binding(tmp_path):
    """The repo's make_train_step idiom: a method returning a donating
    jit, bound to an attribute, called elsewhere. Reads of the donated
    attribute after the call must flag; metadata probes must not."""
    rel = _write(tmp_path, "trainer.py", """
        import jax

        class T:
            def make_train_step(self):
                return jax.jit(self._step, donate_argnums=(0, 1))

            def bad_cycle(self, batch):
                self._train_step = self.make_train_step()
                out = self._train_step(self.params, self.opt_state, batch)
                return self.params          # donated, never reassigned

            def good_cycle(self, batch):
                self._train_step = self.make_train_step()
                self.params, self.opt_state, loss = self._train_step(
                    self.params, self.opt_state, batch
                )
                probed = self.params["w"].is_deleted()  # metadata only
                return loss, probed
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert len(found) == 1, found
    assert found[0].rule == "donation"
    assert "self.params" in found[0].message


def test_donation_argnames_decorator_form(tmp_path):
    """@partial(jax.jit, donate_argnames=...) must resolve against the
    decorated function's own params (review finding: this form was a
    silent false negative)."""
    rel = _write(tmp_path, "named.py", """
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnames=("p",))
        def step(p, b):
            return p

        def run(p, b):
            out = step(p, b)
            return p              # read of the donated buffer
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["donation"], found


def test_lint_error_is_never_filterable(tmp_path):
    """A typo'd path must fail loudly even under a --rules filter
    (review finding: it previously filtered into a clean exit)."""
    findings = runner.run_repo(
        str(tmp_path), paths=["no_such_file.py"], rules=["trace-purity"]
    )
    assert [f.rule for f in runner.active(findings)] == ["lint-error"]


def test_donation_keyword_call_site(tmp_path):
    """Donated buffers passed by KEYWORD must be tracked too (review
    finding: positional indices alone missed `step(params=params)`)."""
    rel = _write(tmp_path, "kwarg.py", """
        import jax

        def f(params, batch):
            return params

        def run(params, batch):
            step = jax.jit(f, donate_argnames=("params",))
            out = step(params=params, batch=batch)
            return params["w"]    # read of the donated buffer
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["donation"], found


def test_purity_mutation_through_self_param(tmp_path):
    """Mutating state reached THROUGH a traced function's parameter
    (self, a scan carry) escapes the trace — params are not
    mutation-safe locals (review finding)."""
    rel = _write(tmp_path, "selfmut.py", """
        import jax

        class T:
            @jax.jit
            def step(self, x):
                self.counter = x          # outlives the trace
                self.history.append(x)    # ditto
                y = []
                y.append(x)               # genuinely local: fine
                return x
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert len(found) == 2, found
    assert all(f.rule == "trace-purity" for f in found)


def test_donation_augassign_reads_old_buffer(tmp_path):
    rel = _write(tmp_path, "aug.py", """
        import jax

        def f(x):
            return x

        def run(x):
            step = jax.jit(f, donate_argnums=(0,))
            y = step(x)
            x += 1            # augassign READS the donated buffer
            return x, y
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["donation"]


# ---------------------------------------------------------------------------
# trace purity
# ---------------------------------------------------------------------------

def test_purity_flags_known_bad(tmp_path):
    rel = _write(tmp_path, "impure.py", """
        import time
        import numpy as np
        import jax
        import jax.numpy as jnp

        calls = []

        @jax.jit
        def step(x):
            print("tracing")                # fires once, at trace time
            t = time.time()                 # compile-time constant
            noise = np.random.normal()      # one constant sample
            calls.append(t)                 # trace-time mutation
            return x + noise

        def body(c, x):
            return c + x.item(), c          # host sync inside scan

        def run(xs):
            return jax.lax.scan(body, 0.0, xs)
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    msgs = "\n".join(f.message for f in found)
    assert {f.rule for f in found} == {"trace-purity"}
    for marker in ("print", "time.time", "np.random", "calls.append", ".item()"):
        assert marker in msgs, (marker, msgs)
    assert len(found) == 5


def test_purity_known_good_is_clean(tmp_path):
    """optax's pure tx.update, local accumulators, trace-time numpy
    constants and pallas Ref writes are all idiomatic — no findings."""
    rel = _write(tmp_path, "pure.py", """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def make_step(tx, loss_fn):
            @jax.jit
            def step(p, o, b):
                grads = jax.grad(loss_fn)(p, b)
                updates, new_o = tx.update(grads, o, p)
                outs = []
                outs.append(jnp.zeros(np.prod((2, 2))))
                return updates, new_o, outs
            return step

        def kernel(q_ref, o_ref):
            def body(j, acc):
                o_ref[j] = acc              # pallas Ref write idiom
                return acc
            jax.lax.fori_loop(0, 4, body, jnp.zeros(4))
    """)
    assert runner.active(_lint(tmp_path, [rel])) == []


def test_purity_nonlocal_and_cond_branches(tmp_path):
    rel = _write(tmp_path, "cond.py", """
        import jax

        def run(pred, x):
            hits = 0

            def yes(v):
                nonlocal hits
                hits += 1
                return v

            def no(v):
                return v

            return jax.lax.cond(pred, yes, no, x)
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["trace-purity"]
    assert "nonlocal" in found[0].message


# ---------------------------------------------------------------------------
# host-sync zones
# ---------------------------------------------------------------------------

def test_sync_zone_item_in_obs_flagged(tmp_path):
    """The acceptance case: a .item() added inside trlx_tpu/obs/."""
    rel = _write(tmp_path, "trlx_tpu/obs/bad.py", """
        def flush(stats):
            return {k: v.item() for k, v in stats.items()}
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["sync-zone"]
    assert "host-side" in found[0].message


def test_sync_zone_outside_zone_is_clean(tmp_path):
    rel = _write(tmp_path, "trlx_tpu/ops/fine.py", """
        def flush(stats):
            return {k: v.item() for k, v in stats.items()}
    """)
    assert runner.active(_lint(tmp_path, [rel])) == []


def test_sync_zone_docstring_claim_opts_in(tmp_path):
    """Any module claiming 'no device syncs' gets the rule — the claim
    is the contract, not the path."""
    rel = _write(tmp_path, "trlx_tpu/misc/claimer.py", '''
        """Event helpers. Host-side only, no device syncs."""
        import jax

        def drain(x):
            return jax.device_get(x)
    ''')
    found = runner.active(_lint(tmp_path, [rel]))
    kinds = sorted(f.snippet.strip() for f in found)
    assert {f.rule for f in found} == {"sync-zone"}
    assert len(found) == 2  # module-scope jax import + device_get
    assert any("import jax" in k for k in kinds)


def test_sync_zone_watchdog_beat_paths_covered():
    assert any(
        z.endswith("utils/watchdog.py") for z in purity.DEFAULT_ZONES
    )
    assert any(z.endswith("obs/") for z in purity.DEFAULT_ZONES)


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------

def test_pragma_with_reason_suppresses(tmp_path):
    rel = _write(tmp_path, "trlx_tpu/obs/waived.py", """
        def flush(stats):
            return stats["x"].item()  # graft-lint: allow[sync-zone] test-only probe
    """)
    found = _lint(tmp_path, [rel])
    assert runner.active(found) == []
    suppressed = [f for f in found if f.suppressed_by]
    assert len(suppressed) == 1
    assert suppressed[0].suppressed_by == "test-only probe"


def test_pragma_without_reason_does_not_suppress(tmp_path):
    rel = _write(tmp_path, "trlx_tpu/obs/lazy.py", """
        def flush(stats):
            return stats["x"].item()  # graft-lint: allow[sync-zone]
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert sorted(f.rule for f in found) == ["bad-pragma", "sync-zone"]


def test_pragma_unknown_rule_is_a_finding(tmp_path):
    rel = _write(tmp_path, "x.py", """
        VALUE = 1  # graft-lint: allow[made-up-rule] whatever
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert [f.rule for f in found] == ["bad-pragma"]


def test_pragma_only_matches_its_own_rule(tmp_path):
    rel = _write(tmp_path, "trlx_tpu/obs/wrong.py", """
        def flush(stats):
            return stats["x"].item()  # graft-lint: allow[donation] wrong rule
    """)
    found = runner.active(_lint(tmp_path, [rel]))
    assert "sync-zone" in [f.rule for f in found]


def test_pragma_parser_multiple_groups_per_line():
    pragmas = collect_pragmas(
        "x = 1  # graft-lint: allow[donation] a-reason "
        "graft-lint: allow[sync-zone] b-reason\n"
    )
    assert [p.rule for p in pragmas[1]] == ["donation", "sync-zone"]


# ---------------------------------------------------------------------------
# RNG-stream manifests
# ---------------------------------------------------------------------------

CHAOS_TMPL = """
FAULT_SITES = (
{sites}
)
"""
GUARD_TMPL = """
STALL_SIGNAL = "stall"
{extra_const}

class Monitor:
    def observe(self):
        self._trip("loss", "detail")
        self._trip("kl", "detail")
"""


def _manifest_repo(tmp_path, sites=("alpha", "beta"), extra_const=""):
    _write(tmp_path, manifests.CHAOS_SOURCE, CHAOS_TMPL.format(
        sites="".join(f'    "{s}",\n' for s in sites)
    ))
    _write(tmp_path, manifests.GUARDRAILS_SOURCE, GUARD_TMPL.format(
        extra_const=extra_const
    ))
    return str(tmp_path)


def test_manifest_update_then_clean(tmp_path):
    repo = _manifest_repo(tmp_path)
    notes = manifests.update(repo)
    assert len(notes) == 2
    assert manifests.check(repo) == []
    data = json.load(open(os.path.join(repo, manifests.CHAOS_MANIFEST)))
    assert data["sites"] == ["alpha", "beta"]
    gdata = json.load(open(os.path.join(repo, manifests.GUARDRAIL_MANIFEST)))
    assert gdata["signals"] == ["kl", "loss", "stall"]


def test_chaos_append_is_legal_but_must_be_manifested(tmp_path):
    repo = _manifest_repo(tmp_path)
    manifests.update(repo)
    _write(tmp_path, manifests.CHAOS_SOURCE, CHAOS_TMPL.format(
        sites='    "alpha",\n    "beta",\n    "gamma",\n'
    ))
    found = manifests.check(repo)
    assert [f.rule for f in found] == ["rng-manifest"]
    assert "gamma" in found[0].message and "append" in found[0].message.lower()
    manifests.update(repo)  # appends are updatable
    assert manifests.check(repo) == []


def test_chaos_insert_mid_registry_fails_and_refuses_update(tmp_path):
    """The acceptance case: a site inserted mid-registry shifts every
    later site's RNG stream — check fails AND --update-manifests
    refuses to paper over it."""
    repo = _manifest_repo(tmp_path)
    manifests.update(repo)
    _write(tmp_path, manifests.CHAOS_SOURCE, CHAOS_TMPL.format(
        sites='    "alpha",\n    "sneaky",\n    "beta",\n'
    ))
    found = manifests.check(repo)
    assert [f.rule for f in found] == ["rng-manifest"]
    assert "index 1" in found[0].message
    try:
        manifests.update(repo)
        raise AssertionError("update must refuse a mid-registry insert")
    except ValueError as e:
        assert "append" in str(e)


def test_chaos_reorder_and_delete_fail(tmp_path):
    repo = _manifest_repo(tmp_path)
    manifests.update(repo)
    for sites in ('    "beta",\n    "alpha",\n', '    "alpha",\n'):
        _write(tmp_path, manifests.CHAOS_SOURCE, CHAOS_TMPL.format(sites=sites))
        found = manifests.check(repo)
        assert [f.rule for f in found] == ["rng-manifest"], sites


def test_guardrail_signal_removal_fails_addition_updates(tmp_path):
    repo = _manifest_repo(
        tmp_path, extra_const='MEMORY_SIGNAL = "memory"'
    )
    manifests.update(repo)
    # removal (constant dropped) -> finding + update refuses
    _write(tmp_path, manifests.GUARDRAILS_SOURCE,
           GUARD_TMPL.format(extra_const=""))
    found = manifests.check(repo)
    assert [f.rule for f in found] == ["rng-manifest"]
    assert "memory" in found[0].message
    try:
        manifests.update(repo)
        raise AssertionError("update must refuse a signal deletion")
    except ValueError as e:
        assert "memory" in str(e)
    # addition -> finding until updated
    _write(tmp_path, manifests.GUARDRAILS_SOURCE, GUARD_TMPL.format(
        extra_const='MEMORY_SIGNAL = "memory"\nNEW_SIGNAL = "newsig"'
    ))
    found = manifests.check(repo)
    assert [f.rule for f in found] == ["rng-manifest"]
    assert "newsig" in found[0].message
    manifests.update(repo)
    assert manifests.check(repo) == []


def test_repo_manifests_match_live_registries():
    """The committed golden manifests stay in sync with chaos.py /
    guardrails.py — the automated per-PR hand-check."""
    found = manifests.check(REPO)
    assert found == [], "\n".join(f.render() for f in found)
    data = json.load(open(os.path.join(REPO, manifests.CHAOS_MANIFEST)))
    # spot-pin the head of the registry: these indices are frozen by
    # recorded chaos schedules since PR 3/5
    assert data["sites"][:3] == ["nan_loss", "sigterm", "nan_reward"]
    gdata = json.load(open(os.path.join(REPO, manifests.GUARDRAIL_MANIFEST)))
    for sig in ("loss", "kl", "stall", "staleness", "fleet", "memory"):
        assert sig in gdata["signals"]


# ---------------------------------------------------------------------------
# config <-> docs sync
# ---------------------------------------------------------------------------

CFG_SRC = """
from dataclasses import dataclass, field
from typing import Any, Dict, Tuple

@dataclass
class TrainConfig:
    steps: int
    knobs: Dict[str, Any] = field(default_factory=dict)
{extra_field}

@dataclass
class TRLConfig:
    train: TrainConfig

_SECTIONS: Tuple = (("train", TrainConfig),)
"""


def _cfg_repo(tmp_path, extra_field="", docs=None, yml=None):
    _write(tmp_path, "configs_mod.py", CFG_SRC.format(extra_field=extra_field))
    _write(tmp_path, "docs.md", docs or
           "`train.steps` sets the budget; `train.knobs` tunes it.\n")
    _write(tmp_path, "cfg.yml", yml or
           "train:\n  steps: 1        # budget\n  knobs: {a: 1}  # free-form\n")
    return str(tmp_path)


def _cfg_check(repo):
    return config_docs.check(
        repo, config_modules=("configs_mod.py",),
        docs_path="docs.md", yml_path="cfg.yml",
    )


def test_config_docs_clean_fixture(tmp_path):
    assert _cfg_check(_cfg_repo(tmp_path)) == []


def test_config_field_without_docs_and_yml_fails(tmp_path):
    """The acceptance case: a field added with neither a docs/api.md
    mention nor a test_config.yml annotation -> two findings."""
    repo = _cfg_repo(tmp_path, extra_field="    sneaky_knob: int = 0")
    found = _cfg_check(repo)
    assert len(found) == 2
    msgs = " ".join(f.message for f in found)
    assert "sneaky_knob" in msgs
    assert "not mentioned" in msgs and "not annotated" in msgs


def test_config_commented_yml_annotation_counts(tmp_path):
    repo = _cfg_repo(
        tmp_path, extra_field="    opt_in: bool = False",
        docs="`train.steps`, `train.knobs` and `train.opt_in`.\n",
        yml="train:\n  steps: 1   # budget\n  knobs: {}\n"
            "  # opt_in: false  # default-off switch\n",
    )
    assert _cfg_check(repo) == []


def test_phantom_yml_key_fails(tmp_path):
    repo = _cfg_repo(
        tmp_path,
        yml="train:\n  steps: 1\n  knobs: {}\n  ghost: 2\n",
    )
    found = _cfg_check(repo)
    assert len(found) == 1 and "ghost" in found[0].message
    assert found[0].file == "cfg.yml" and found[0].line == 4


def test_phantom_doc_reference_fails(tmp_path):
    repo = _cfg_repo(
        tmp_path,
        docs="`train.steps`, `train.knobs`, and `train.gone` (stale).\n",
    )
    found = _cfg_check(repo)
    assert len(found) == 1 and "gone" in found[0].message
    assert found[0].file == "docs.md"


def test_dict_field_subkeys_are_free_form(tmp_path):
    repo = _cfg_repo(
        tmp_path,
        yml="train:\n  steps: 1\n  knobs:\n    anything: {nested: true}\n",
    )
    assert _cfg_check(repo) == []


def test_repo_config_docs_in_sync():
    found = runner.active(config_docs.check(REPO))
    assert found == [], "\n".join(f.render() for f in found)


# ---------------------------------------------------------------------------
# baseline / diff
# ---------------------------------------------------------------------------

def test_baseline_then_diff_reports_only_new(tmp_path):
    rel = _write(tmp_path, "bug.py", PR3_SHAPE)
    first = _lint(tmp_path, [rel])
    baseline = tmp_path / "baseline.json"
    runner.write_baseline(str(baseline), first)
    # same findings -> empty diff, even at shifted line numbers
    shifted = _write(tmp_path, "bug2.py", "\n\n" + textwrap.dedent(PR3_SHAPE))
    again = _lint(tmp_path, [rel])
    assert runner.diff_against(str(baseline), again) == []
    # a new finding in another file -> only it is reported
    both = _lint(tmp_path, [rel, shifted])
    new = runner.diff_against(str(baseline), both)
    assert len(new) == 1 and new[0].file == "bug2.py"


# ---------------------------------------------------------------------------
# whole-repo gates
# ---------------------------------------------------------------------------

def test_whole_repo_lint_is_clean():
    """Loud failure: the tree must lint clean,
    with every suppression carrying a reasoned pragma (bad-pragma
    findings fail here too)."""
    findings = runner.run_repo(REPO)
    live = runner.active(findings)
    assert not live, (
        "graft-lint found unsuppressed findings — fix them or add a "
        "reasoned `# graft-lint: allow[<rule>] <reason>` pragma:\n"
        + "\n".join(f.render() for f in live)
    )


def _imports_under(subpaths, skip=()):
    """(relative path, imported module) for every import statement, at
    any depth, of the .py files under ``trlx_tpu/<subpath>`` (a
    directory or one file), less ``skip``."""
    import ast as _ast

    found = []
    pkg = os.path.join(REPO, "trlx_tpu")
    for sub in subpaths:
        top = os.path.join(pkg, sub)
        paths = [top] if top.endswith(".py") else glob.glob(
            os.path.join(top, "**", "*.py"), recursive=True
        )
        for path in paths:
            rel = os.path.relpath(path, pkg)
            if rel.startswith(tuple(skip)):
                continue
            for node in _ast.walk(_ast.parse(open(path).read())):
                if isinstance(node, _ast.Import):
                    found += [(rel, a.name) for a in node.names]
                elif isinstance(node, _ast.ImportFrom) and node.module:
                    found += [(rel, node.module)]
                    found += [
                        (rel, f"{node.module}.{a.name}") for a in node.names
                    ]
    return found


@pytest.mark.parametrize(
    "importers, skip, forbidden",
    [
        # the lint must add zero runtime import cost to trlx_tpu proper
        # (bench.py --smoke asserts the same at runtime)
        pytest.param([""], ["analysis"], ["trlx_tpu.analysis"],
                     id="training_path_never_imports_analysis"),
        # trainer -> exp -> fleet, arrows one way: the transport's and
        # the fleet's protocols take what the trainer owns as arguments;
        # only the worker builds a trainer
        pytest.param(["exp", "fleet"], ["fleet/worker.py"], ["trlx_tpu.trainer"],
                     id="exp_and_fleet_never_import_the_trainer"),
        pytest.param(["trainer/base.py"], [], ["trlx_tpu.exp", "trlx_tpu.fleet"],
                     id="base_trainer_imports_no_transport_no_fleet"),
        pytest.param(["trainer"], [], ["trlx_tpu.fleet.serde"],
                     id="no_trainer_knows_the_fleet_wire_format"),
    ],
)
def test_import_layering(importers, skip, forbidden):
    offenders = [
        (rel, mod) for rel, mod in _imports_under(importers, skip)
        if any(mod == f or mod.startswith(f + ".") for f in forbidden)
    ]
    assert not offenders, f"{importers} import {forbidden}: {offenders}"


def test_online_trainer_has_one_collection_loop():
    """The rollout half keeps ONE collection loop and names no transport
    verdict: the lease, staleness and dispatch protocols are exp/'s and
    fleet/'s (stdlib ast only)."""
    import ast as _ast

    trainer_dir = os.path.join(REPO, "trlx_tpu", "trainer")
    online = _ast.parse(open(os.path.join(trainer_dir, "online.py")).read())
    defined = {
        n.name for n in _ast.walk(online)
        if isinstance(n, (_ast.FunctionDef, _ast.ClassDef))
    }
    gone = {"_make_experience_exp", "_exp_produce", "_fleet_produce",
            "_fleet_ready", "_fleet_degrade"}
    assert "TPUOnlineTrainer" in defined and not (defined & gone)
    base = _ast.parse(open(os.path.join(trainer_dir, "base.py")).read())
    assert "TPUOnlineTrainer" not in {
        n.name for n in _ast.walk(base) if isinstance(n, _ast.ClassDef)
    }
    loops = [
        fn.name for fn in _ast.walk(online) if isinstance(fn, _ast.FunctionDef)
        for n in _ast.walk(fn)
        if isinstance(n, _ast.While) and "n_collected" in _ast.dump(n.test)
    ]
    assert loops == ["_make_experience"], loops
    for fname in os.listdir(trainer_dir):
        if fname.endswith(".py"):
            names = {
                n.attr for n in _ast.walk(
                    _ast.parse(open(os.path.join(trainer_dir, fname)).read())
                ) if isinstance(n, _ast.Attribute)
            }
            assert not names & {"REJECT", "ADMIT_CLIP"}, fname


def test_cli_exit_codes_and_jax_free(tmp_path):
    """CLI contract: nonzero on a donated-buffer-reuse fixture, zero on
    the repo, and the whole run never imports jax (login-node safe)."""
    bug = tmp_path / "bug.py"
    bug.write_text(textwrap.dedent(PR3_SHAPE))
    script = os.path.join(REPO, "scripts", "graft_lint.py")
    bad = subprocess.run(
        [sys.executable, script, str(bug), "--repo", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "donation" in bad.stdout

    probe = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {REPO!r})
            sys.path.insert(0, {os.path.join(REPO, 'scripts')!r})
            import graft_lint
            rc = graft_lint.main([])
            assert rc == 0, rc
            assert "jax" not in sys.modules, "lint imported jax"
        """)],
        capture_output=True, text=True,
    )
    assert probe.returncode == 0, probe.stdout + probe.stderr
