"""Marker-audit (ISSUE 3 satellite; VERDICT weak #5): enforce the
CONTRIBUTING.md test-tier budgets structurally, so `-m "not slow"`
stays under the 870s tier-1 timeout as the suite grows.

Three invariants, all enforceable without timing anything at test time:

1. every test file carries an explicit tier-1 budget in TIER1_BUDGETS —
   adding a file without declaring (and thinking about) its budget
   fails this audit;
2. the declared budgets sum to under the tier-1 ceiling with headroom;
3. any test function that drives a full learn() loop (`trlx_tpu.train(`
   / `.learn(`) without a `@pytest.mark.slow` marker must be in the
   explicit allowlist below — the "full learn()-loop integration" class
   is exactly what rots the fast tier when it lands unmarked.

Budgets are seconds of CPU wall for the file's TIER-1 PORTION, measured
with `pytest --durations=0 -m "not slow" <file>` on the 8-way virtual
CPU mesh (audit 2026-08-03). A file whose tier-1 portion grows past its
budget must either slow-mark its heavy tests or raise the budget here —
in review, against the total.
"""

import ast
import os

# file -> budgeted seconds for its tier-1 (not-slow) portion
TIER1_BUDGETS = {
    "test_chunked_loss.py": 10,
    "test_configs.py": 4,
    # r14: serving-tier suite (ledger fuzz + engine warm-pool goldens +
    # frontend units + ONE two-learn e2e) — measured ~45s serial on the
    # r13 1-core container (2026-08-04; the 8-way box runs the learns
    # faster). Paid under the unchanged 780 ceiling by trimming files
    # measured FAST EVEN ON THIS SLOWER BOX (examples 0.3s, curves
    # 0.08s, mcts 4.9s serial 2026-08-04) plus r07-measured slack
    # (supervisor 8s) and the version-gated skip files (remat 0.3,
    # multihost 0.05, properties 0.06, pipeline_parallel 4.9 measured
    # 2026-08-03).
    "test_curves.py": 1,
    "test_deferred_stats.py": 1,
    "test_dpo.py": 15,
    # r09 re-baseline: every touched-or-large budget re-measured
    # SERIALLY on the idle 8-way CPU mesh (2026-08-03) to pay for the
    # preference-RL suites under the unchanged ceiling — elastic 32.0s,
    # exp_queue 28.2s, gen_engine 32.6s, fleet 33.7s, fault_tolerance
    # 62.4s, scanned_epochs 42.4s (RAISED 40->50: it was already over),
    # generation 11.5s, seq2seq 16.6s, remat 0.3s, models 16.2s
    # (raised 15->20), peft 13.9s, trainers 7.9s
    "test_elastic.py": 17,
    "test_examples.py": 1,
    "test_exp_queue.py": 21,
    "test_fault_tolerance.py": 24,
    "test_flash_attention.py": 14,
    "test_fleet.py": 21,
    # PR 26: the stop of the backward pass at the hydra branch point —
    # six tiny PPO trainers (hydra, NeoX shape, both value-branch
    # orders, T5) differentiated through the new path and the parent's,
    # five bypass traces and one FLOP count: 101 s alone on the 8-way
    # CPU mesh of this 8-core container, 113 s inside the driver's
    # 6-worker run (2026-09-30), where test_seq2seq (budget 13) took
    # 113 s and test_memdoctor (budget 35) 80 s: budgeted 33 on the
    # table's scale. Paid under the unchanged 780 ceiling with times of
    # the same run: grpo 55->40 (36.0 s), resilient 5->1 (0.0),
    # summarize_eval 5->1 (0.0), pipelines 4->1 (0.0), deferred_stats
    # 5->2 (0.9), remat 2->1 (0.0), configs 5->4 (3.1), examples 2->1
    # (0.1), graft_lint 8->7 (6.2).
    # PR 34: the fused block that holds the trunk against the block that
    # runs it in every step (two block compiles a test: six cases, rows in
    # groups and ragged, microbatches), the bypasses' blocks, the per-step
    # program, the block's FLOP count: 62 tests, 248 s alone, 321.5 s
    # inside the 6-worker run of 399 s (2026-10-03), 96 on the table's
    # scale (0.3 of the in-run seconds). Paid under the unchanged 780
    # ceiling with times of the same run: guardrails 75->55 (134.5 s =
    # 40.3), fault_tolerance 53->35 (62.7 s = 18.8), paged_kernel 48->38
    # (86.0 s = 25.8), elastic 34->26 (50.7 s = 15.2), serve 26->19
    # (28.4 s = 8.5).
    "test_frozen_trunk.py": 96,
    # PR 42: Gated DeltaNet layers beside output-gated attention at head 256
    # and softmax-routed experts against their float32 reference (logits,
    # the top two layers' gradients, the scalar-decay chunked form against
    # the recurrence, prefill then decode through the state and k/v rows,
    # attention at head 256 on both paths, the shares, the set-up balance
    # of the gates, int8 rollout weights, configuration cases, the memory
    # count): one toy stack jitted once, module scope. 87 s alone
    # (2026-10-18), where thirteen files measured alone that hour read a
    # median of 3.9 s a budgeted second: 22. Paid under the unchanged 780
    # ceiling with the three lowest readings that hour on that scale:
    # exp_queue 29->21 (77 s = 19.8), memdoctor 35->27 (100 s = 25.6),
    # guardrails 45->40 (135 s = 34.5).
    "test_gated_delta.py": 22,
    "test_gen_engine.py": 25,
    # PR 30: the fused int8 decode kernel against the XLA branch (seven
    # interpret-mode cases through `Attention`, one on a four-device
    # mesh, the host's chunk arithmetic), one flight-stream test in
    # test_obs and one Mosaic compile in test_latent_moe: 68 s alone for
    # this file (40 s before), 91.5 s inside the driver's 6-worker run
    # (2026-10-02), whose files took 2,140 s against the 780 budgeted:
    # the table's scale is in-run seconds / 2.74. Budgeted 26 (33 on
    # that scale less the slack the other int8 tests had), obs 25->26
    # (76.1 s = 27.7), latent_moe 42->43 (125.3 s = 45.7). Paid under
    # the unchanged 780 ceiling with times of the same run on that
    # scale: fault_tolerance 63->53 (90.8 s = 33.1), guardrails 103->99
    # (196.7 s = 71.7).
    "test_generation.py": 26,
    "test_golden.py": 3,
    # r13: graft-lint suite (pure-AST checker units + one whole-repo
    # lint + two tiny jax-free subprocesses) — measured ~5.2s serial on
    # the 8-way CPU mesh (2026-08-04). Paid for under the unchanged
    # ceiling by trimming r09/r10-measured slack: guardrails 105->103
    # (99.9 measured), fault_tolerance 65->63 (62.4), gen_engine 36->34
    # (32.6), memdoctor 37->35 (32).
    "test_graft_lint.py": 7,
    "test_grpo.py": 20,
    # r09: +4 preference-RL chaos learn() tests (GRPO nan/sigterm, DPO
    # nan/sigterm); whole file re-measured 99.9s serial
    "test_guardrails.py": 40,
    # PR 28: the routed / latent-attention / four-stream family against its
    # float32 reference (logits, trainable gradients, cache decode, shares,
    # hydra cuts, int8 rollout weights, one Mosaic compile at keys 192 /
    # values 128, 1 s): one toy stack built, run and differentiated once
    # under jit and shared by the parity tests. 45 s alone on this 8-core
    # container, 85 s inside a 6-worker run of the driver's command that
    # shared the cores with another job (2026-10-01). That run's other
    # files took 1,496 s against the 738 budgeted, so the table's scale is
    # in-run seconds / 2.0: budgeted 42. Paid under the unchanged 780
    # ceiling with times of the same run on that scale: serve 46->26
    # (34.7 s in the run = 17), scanned_epochs 46->31 (30.1 s = 15),
    # reference_harness 4->1 (0.4 s), curves 2->1 (0.1 s), deferred_stats
    # 2->1 (0.5 s), net 3->2 (2.8 s = 1.4), marker_audit 2->1 (0.2 s).
    "test_latent_moe.py": 43,
    # PR 33: delta-rule linear attention with a recurrent state beside a
    # rotary-free latent cache against its float32 reference (logits,
    # trainable gradients through the chunked form's checkpointed scan,
    # chunked against recurrent in five gate regimes, prefill then decode
    # through both kinds of state, padding, the 32 shares, hydra cuts over
    # mixed segments, the freeze mask, gauges and counts): one toy stack
    # jitted once and one toy trainer, module scope. 105 s alone on this
    # container (test_latent_moe: 75 s alone, 124.9 s inside the 6-worker
    # run of 2026-10-02, whose files took 2,603 s against the 780
    # budgeted: the table's scale is in-run seconds / 3.34), so about
    # 175 s in such a run: budgeted 45. Paid under the unchanged 780
    # ceiling with times of that run on that scale: guardrails 99->75
    # (216.6 s = 64.9), grpo 40->30 (65.4 s = 19.6), scanned_epochs
    # 31->20 (34.7 s = 10.4).
    "test_linear_attention.py": 45,
    "test_marker_audit.py": 1,
    "test_mcts_value_branch.py": 5,
    # r10: memory-doctor suite (ladder units are fake-clock-fast; the
    # cost is the split-grads golden + three tiny trainer builds) —
    # measured 32s serial on the idle 8-way CPU mesh (2026-08-03).
    # Paid for under the unchanged ceiling by re-trimming files whose
    # r09 serial measurements left >=5s slack (fault_tolerance 62.4,
    # elastic 32.0, exp_queue 28.2, fleet 33.7, peft 13.9 measured).
    "test_memdoctor.py": 27,
    "test_models.py": 14,
    # trimmed r07 against serial measurements (the round-6 note asked
    # the next file to trim instead of raising the ceiling): these
    # files' tier-1 portions are mostly version-gated skips/deselects —
    # multihost 0.05s, pipeline_parallel 4.9s, ring_attention 6.3s,
    # sharding 6.1s, properties 0.06s measured 2026-08-03
    "test_multihost.py": 2,
    # r16: transport/fault-injector suite — all tier-1 tests are
    # host-side (loopback TcpHub, fake-clock fault schedules, tiny
    # numpy payloads), measured 3.3s serial on THIS 1-core container
    # (2026-08-07, ~2x budget scale -> ~1.6); the multi-process
    # partition-and-rejoin integration is slow-marked (bench --chaos
    # network leg is its acceptance gate). Paid under the unchanged
    # 780 ceiling by trimming curves 3->2 (0.14s measured here) and
    # examples 4->2 (0.35s measured here), both re-measured same day.
    "test_net.py": 2,
    # r11: flight-recorder suite (fake-clock units + ONE tiny learn()
    # integration) — measured ~20s serial on the 8-way CPU mesh
    # (2026-08-04). Paid for under the unchanged ceiling by trimming
    # files whose r09/r10 serial measurements left slack: guardrails
    # 110->105 (99.9 measured), fault_tolerance 70->65 (62.4),
    # scanned_epochs 50->46 (42.4), gen_engine 40->36 (32.6),
    # memdoctor 40->37 (32), elastic 35->34 (32.0), exp_queue 30->29
    # (28.2), models 18->17 (16.2), peft 15->14 (13.9).
    # PR 37: the set-up spans and the compile records (thirteen more
    # cases: fake-clock units, six that compile one tiny jitted function
    # each, one more tiny learn() whose second cycle recompiles the
    # sampler): 44.6 s alone on this container where the file took 37.6 s
    # before (2026-10-04), so 13 -> 16 on the same scale. Paid under the
    # unchanged 780 ceiling with files measured alone the same day on that
    # scale (0.35 of the seconds alone): ops 5->4 (8.5 s = 3.0), watchdog
    # 8->7 (18.5 s = 6.5).
    "test_obs.py": 16,
    # r15: paged-attention kernel + sharded lanes + trunk-sharing suite
    # (op-level kernel parity grid, engine pallas==xla goldens incl.
    # the spec verify forward, trunk-shared pool accounting, grouped-
    # lane stream equality incl. a 2-way mesh, grouped serve frontend)
    # — measured 88s serial on THIS 1-core container (2026-08-04),
    # which runs ~2x the historical budget scale (test_gen_engine:
    # budget 34, 68s here), so budgeted 48. Paid under the unchanged
    # 780 ceiling by trimming files re-measured on the same container
    # the same day (scaled /2): golden 0.3s -> 10->3, reference_harness
    # 1s -> 10->4, pipelines 2s -> 10->4, ops 6s -> 10->5, seq2seq 16s
    # -> 20->13, mcts 6s -> 8->5, sharding 7s -> 10->7, models 24s ->
    # 17->14, ring_attention 9s -> 10->8, watchdog 11s -> 10->8,
    # sweep 23s -> 15->14, trainers 11s -> 10->9, flash_attention 24s
    # -> 15->14, generation 23s -> 15->14.
    "test_paged_kernel.py": 26,
    "test_ops.py": 4,
    "test_peft.py": 14,
    "test_pipeline_parallel.py": 7,
    "test_pipelines.py": 1,
    "test_properties.py": 2,
    "test_reference_harness.py": 1,
    "test_remat.py": 1,
    "test_resilient.py": 1,
    "test_ring_attention.py": 8,
    "test_scanned_epochs.py": 13,
    "test_seq2seq.py": 13,
    "test_serve.py": 12,
    "test_sharding.py": 7,
    # PR 35: Mamba-2 state-space layers beside grouped-query attention and
    # latent-space experts, every layer ONE sub-layer, against its float32
    # reference (logits, trainable gradients through the chunked form's
    # checkpointed scan, chunked against recurrent in four regimes, prefill
    # then decode through state, tail and k/v rows, padding, the 64 shares,
    # hydra cuts and trunk constants over one-sub-layer segments, the freeze
    # mask, gauges and counts): one toy stack jitted once and one toy
    # trainer, module scope. 72 s alone on this container
    # (test_linear_attention: 105 s alone, 184.3 s inside the driver's
    # 6-worker run of 2026-10-03 (PR 34), whose files took 2,747 s against
    # the 780 budgeted: the table's scale is in-run seconds / 3.52), so
    # about 126 s in such a run (122.9 s measured inside my own 6-worker
    # run of the final tree, 410 s, 720 passed): budgeted 36. Paid under the unchanged 780
    # ceiling with times of that run on that scale: fault_tolerance 35->24
    # (77.0 s = 21.9), grpo 30->20 (62.9 s = 17.9), serve 19->12 (38.5 s =
    # 10.9), scanned_epochs 20->13 (42.7 s = 12.1), obs 26->25 (66.3 s = 18.8).
    "test_state_space.py": 36,
    # Gated short-convolution layers beside QK-normed grouped-query
    # attention and routed SwiGLU experts against their float32 reference
    # (logits, trainable gradients of the top two conv layers, prefill then
    # decode through the conv tails and k/v rows, padding, QK-normed
    # attention at head 64 on both attention paths, the 8 shares, trunk
    # constants, int8 rollout weights, configuration cases, the memory plan,
    # the freeze mask, gauges and counts): one toy stack jitted once and one
    # toy trainer, module scope. 53 s alone (2026-10-15), where
    # test_state_space took 111 s alone the same hour (budget 36): 17.2
    # on that scale, budgeted 18. Paid under the unchanged 780 ceiling with
    # files measured alone that hour on that scale: guardrails 55->45 (127 s
    # = 41.2), trainable_view 45->37 (104 s = 33.7).
    "test_short_conv.py": 18,
    # PR 38: the decode step of a recurrent mixer as one kernel
    # (`ops/state_step.py`): seven interpret-mode cases against `kda_step` /
    # `ssm_step` and the sampler's jaxpr of both families traced twice;
    # beside it one decode on a two-device mesh in test_linear_attention and
    # test_state_space each and one Mosaic compile in test_latent_moe. 28 s
    # alone, 33.9 s inside a 6-worker run of the driver's command (617 s,
    # 2026-10-05), whose files took 3,124 s against the 780 budgeted: 8.5 on
    # the table's scale. Paid under the unchanged 780 ceiling with a time of
    # the same run on that scale: gen_engine 34->25 (70.1 s = 17.5).
    "test_state_step.py": 9,
    "test_summarize_eval.py": 1,
    "test_supervisor.py": 11,
    "test_sweep.py": 14,
    # PR 36: the optimizer step on the trainable view against the walk
    # over every row, on both optimizer paths (twelve marks, ten
    # functional cases of three steps, three toy trainers with a fused
    # block each way, two at width 256 for the streamed count): 38 tests,
    # 106 s alone, 233 s inside the 6-worker run of 507 s (2026-10-04,
    # before the block's result was shared between two tests: 167 s alone
    # then), whose files took 2,696 s against the 780 budgeted: about 150
    # in-run seconds now, 45 on that scale (3.46). Paid under the
    # unchanged 780 ceiling with times of the same run on that scale:
    # fleet 35->21 (71.5 s = 20.7), paged_kernel 38->26 (86.8 s = 25.1),
    # obs 25->13 (41.6 s = 12.0), elastic 26->17 (55.6 s = 16.1).
    "test_trainable_view.py": 37,
    "test_trainers.py": 9,
    "test_utils.py": 5,
    "test_watchdog.py": 7,
}

# ceiling: tier-1 runs under `timeout 870` (ROADMAP); budgets must fit
# with scheduling headroom (raised 700 -> 780 for the decode-engine
# suite in round 6). Round 7 landed the experience-transport +
# supervisor suites (measured ~54s + 8s serial, budgeted 70 + 15)
# WITHOUT raising the ceiling, by trimming 80s of dead budget from the
# version-gated files (see the in-table note) — the ceiling stays 780
# with the same ~90s of headroom, and the trim playbook (measure the
# biggest budgets serially, reclaim the skip-dominated ones) is the
# template for the next landing too.
TIER1_BUDGET_CEILING_S = 780

# test files allowed to run full learn() loops in tier-1 WITHOUT a slow
# marker, because that loop IS the subject under test and the configs
# are tiny (documented tradeoff; everything else slow-marks them)
LEARN_IN_TIER1_ALLOWLIST = {
    "test_elastic.py",          # resharded-resume / quarantine-fallback
    "test_grpo.py",             # engine+transport golden + resume need
                                # tiny learns (the subject under test)
    "test_dpo.py",              # separable-preference convergence IS
                                # the acceptance criterion
    "test_exp_queue.py",        # exp-vs-direct golden needs two tiny learns
    "test_fleet.py",            # fleet-vs-exp goldens (degraded +
                                # multi-process worker-kill) are the
                                # subject under test
    "test_fault_tolerance.py",  # kill/resume + chaos scenarios
    "test_guardrails.py",       # rollback/requeue under chaos
    "test_scanned_epochs.py",   # scanned-vs-looped golden equivalence
    "test_serve.py",            # serving-vs-no-serving loss bit-equality
                                # needs two tiny learns (the acceptance
                                # criterion)
    "test_examples.py",         # example-surface smoke
    "test_sweep.py",            # sweep driver over tiny trials
    "test_curves.py",           # recorded-curve contract
    "test_peft.py",             # adapter roundtrip needs one tiny learn()
    "test_trainers.py",         # unmarked calls raise before training
    "test_memdoctor.py",        # preflight-rejection test calls train()
                                # and must RAISE before the first rollout
    "test_obs.py",              # the flight-recorder acceptance IS a
                                # fault-free tiny learn() end to end
    "test_marker_audit.py",     # this file quotes the pattern it greps
}

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _test_files():
    return sorted(
        f for f in os.listdir(TESTS_DIR)
        if f.startswith("test_") and f.endswith(".py")
    )


def _is_slow_marked(node: ast.FunctionDef) -> bool:
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        parts = []
        while isinstance(target, ast.Attribute):
            parts.append(target.attr)
            target = target.value
        if isinstance(target, ast.Name):
            parts.append(target.id)
        if "slow" in parts and "mark" in parts:
            return True
    return False


def test_every_test_file_declares_a_budget():
    files = set(_test_files())
    missing = files - set(TIER1_BUDGETS)
    assert not missing, (
        f"test files without a tier-1 budget: {sorted(missing)} — add "
        "them to TIER1_BUDGETS (measure with pytest --durations=0 "
        "-m 'not slow' <file>)"
    )
    stale = set(TIER1_BUDGETS) - files
    assert not stale, (
        f"TIER1_BUDGETS lists files that no longer exist: {sorted(stale)}"
    )


def test_total_budget_fits_tier1_timeout():
    total = sum(TIER1_BUDGETS.values())
    assert total <= TIER1_BUDGET_CEILING_S, (
        f"declared tier-1 budgets sum to {total}s > "
        f"{TIER1_BUDGET_CEILING_S}s ceiling — slow-mark something or "
        "shrink a suite; raising the ceiling means renegotiating the "
        "870s tier-1 timeout in ROADMAP.md"
    )


def test_learn_loops_outside_allowlist_are_slow_marked():
    offenders = []
    for fname in _test_files():
        if fname in LEARN_IN_TIER1_ALLOWLIST:
            continue
        path = os.path.join(TESTS_DIR, fname)
        with open(path) as f:
            source = f.read()
        tree = ast.parse(source)
        for node in ast.walk(tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            if not node.name.startswith("test_") or _is_slow_marked(node):
                continue
            body_src = ast.get_source_segment(source, node) or ""
            if "trlx_tpu.train(" in body_src or ".learn()" in body_src:
                offenders.append(f"{fname}::{node.name}")
    assert not offenders, (
        "unmarked full-learn()-loop tests outside the tier-1 allowlist "
        f"(add @pytest.mark.slow or allowlist the file): {offenders}"
    )
