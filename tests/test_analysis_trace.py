"""Tests for repro.analysis.trace: the jaxpr cost model, the TRACE rule
family (positive + negative fixtures per rule), the registered repo
entry points, the Budgets.memory static feasibility gate, and the
tier-1 bracket pin of the static peak against XLA's own
``memory_analysis`` for the real char-LM client step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.trace import (DEFAULT_TRACE_TABLE, EntryPoint,
                                  charlm_trace_setup, collect_entry_points,
                                  cost_of_jaxpr, memory_gate, run_trace,
                                  run_trace_rules, trace_entry,
                                  trace_rule_ids, traced_entries,
                                  unwrap_pjit)
from repro.analysis.trace.gate import build_table, diff_table, load_table

F32 = jnp.float32


def _entry(fn, args, name="fixture.entry", **kw):
    return EntryPoint(name=name, path="tests/test_analysis_trace.py",
                      line=1, build=lambda: (fn, args), **kw)


def _findings(fn, args, **kw):
    return run_trace_rules([trace_entry(_entry(fn, args, **kw))])


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_matmul_cost_exact():
    a = jax.ShapeDtypeStruct((64, 128), F32)
    b = jax.ShapeDtypeStruct((128, 32), F32)
    cost = cost_of_jaxpr(jax.make_jaxpr(lambda x, y: x @ y)(a, b))
    assert cost.flops == 2 * 64 * 32 * 128
    assert cost.input_bytes == (64 * 128 + 128 * 32) * 4
    assert cost.output_bytes == 64 * 32 * 4
    # inputs pinned + output live together
    assert cost.peak_bytes == cost.input_bytes + cost.output_bytes
    assert cost.transfer_bytes == 0


def test_liveness_chain_and_donation():
    """a = x*2; b = a+1; c = b*3 — without donation x is pinned, so the
    worst instant holds x plus two temps; donating x frees it after its
    only read and the peak drops by exactly one buffer."""
    n = 1024

    def chain(x):
        a = x * 2.0
        b = a + 1.0
        return b * 3.0

    closed = jax.make_jaxpr(chain)(jax.ShapeDtypeStruct((n,), F32))
    pinned = cost_of_jaxpr(closed)
    donated = cost_of_jaxpr(closed, donated=[0])
    assert pinned.peak_bytes == 3 * n * 4
    assert donated.peak_bytes == 2 * n * 4
    assert pinned.flops == 3 * n


def test_scan_flops_scale_with_length():
    def body(c, x):
        return c + x, c

    def f(xs):
        return jax.lax.scan(body, jnp.zeros((16,), F32), xs)

    cost = cost_of_jaxpr(unwrap_pjit(
        jax.make_jaxpr(f)(jax.ShapeDtypeStruct((10, 16), F32))))
    # one 16-wide add per iteration, 10 iterations
    assert cost.flops >= 10 * 16
    assert cost.flops < 10 * 16 * 4


def test_unwrap_pjit_exposes_body():
    f = jax.jit(lambda x: x * 2.0)
    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8,), F32))
    assert closed.jaxpr.eqns[0].primitive.name == "jit"
    inner = unwrap_pjit(closed)
    assert all(e.primitive.name != "jit" for e in inner.jaxpr.eqns)


# ---------------------------------------------------------------------------
# TRACE001 dtype promotion
# ---------------------------------------------------------------------------


def test_trace001_fires_on_f64_widening():
    finds = _findings(lambda x: x.astype(jnp.float64) * 2.0,
                      (jax.ShapeDtypeStruct((8,), F32),), x64=True)
    assert any(f.rule == "TRACE001" for f in finds)


def test_trace001_clean_on_f32_path():
    finds = _findings(lambda x: x * 2.0 + 1.0,
                      (jax.ShapeDtypeStruct((8,), F32),), x64=True)
    assert not [f for f in finds if f.rule == "TRACE001"]


# ---------------------------------------------------------------------------
# TRACE002 missed donation
# ---------------------------------------------------------------------------


def _update_like(p, o):
    return p + o, o * 2.0


def test_trace002_fires_without_donation():
    args = (jnp.ones((32,), F32), jnp.ones((32,), F32))
    finds = _findings(jax.jit(_update_like), args, donatable=(1,))
    assert any(f.rule == "TRACE002" for f in finds)


def test_trace002_clean_with_donation():
    args = (jnp.ones((32,), F32), jnp.ones((32,), F32))
    finds = _findings(jax.jit(_update_like, donate_argnums=(1,)), args,
                      donatable=(1,))
    assert not [f for f in finds if f.rule == "TRACE002"]


# ---------------------------------------------------------------------------
# TRACE003 dense cohort materialization
# ---------------------------------------------------------------------------


def test_trace003_fires_on_stacked_combine():
    deltas = tuple(jnp.zeros((256,), F32) for _ in range(4))
    finds = _findings(lambda *ds: jnp.stack(ds).mean(axis=0), deltas,
                      cohort=4)
    assert any(f.rule == "TRACE003" for f in finds)


def test_trace003_clean_on_incremental_combine():
    from repro.core.aggregation import aggregate
    deltas = tuple({"w": jnp.zeros((256,), F32)} for _ in range(4))
    finds = _findings(lambda *ds: aggregate(list(ds)), deltas, cohort=4)
    assert not [f for f in finds if f.rule == "TRACE003"]


# ---------------------------------------------------------------------------
# TRACE004 host callbacks in jit
# ---------------------------------------------------------------------------


def test_trace004_fires_on_debug_callback():
    def noisy(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2.0

    finds = _findings(noisy, (jax.ShapeDtypeStruct((8,), F32),))
    assert any(f.rule == "TRACE004" for f in finds)


def test_trace004_clean_on_pure_fn():
    finds = _findings(lambda x: x * 2.0,
                      (jax.ShapeDtypeStruct((8,), F32),))
    assert not [f for f in finds if f.rule == "TRACE004"]


# ---------------------------------------------------------------------------
# the registered repo entry points
# ---------------------------------------------------------------------------


def test_trace_rule_registry():
    assert trace_rule_ids() == ["TRACE001", "TRACE002", "TRACE003",
                                "TRACE004"]


def test_registry_covers_the_paper_surfaces():
    names = {e.name for e in collect_entry_points()}
    assert {"fl.client_grad_step", "fl.client_update_step",
            "fl.client_local_step", "fl.client_local_step@baseline",
            "fl.executor_batched_round", "fl.aggregate_sync",
            "fl.aggregate_weighted", "kernels.wire_dense",
            "kernels.wire_topk", "kernels.masked_sum",
            "constraints.dual_update"} <= names


def test_repo_entries_trace_clean():
    """Tier-1 gate: no TRACE findings on the registered entry points
    (the committed-baseline equivalent for the traced IR is zero)."""
    traced = traced_entries()
    findings = run_trace_rules(traced)
    assert findings == [], [f.format() for f in findings]


def test_every_entry_costs_something():
    from repro.analysis.trace.rules import DEVICE_PUT_MIN_BYTES
    for t in traced_entries():
        assert t.cost.peak_bytes > 0, t.entry.name
        assert t.cost.eqns > 0, t.entry.name
        # scalar pre-staging only; nothing TRACE004 would flag
        assert t.cost.transfer_bytes < DEVICE_PUT_MIN_BYTES, t.entry.name


def test_client_update_step_actually_donates():
    t = {x.entry.name: x for x in traced_entries()}["fl.client_update_step"]
    assert t.donatable_leaves > 0
    assert t.aliased_outputs == t.donatable_leaves


def test_donation_shrinks_static_peak():
    """The TRACE002 satellite's win, statically visible: the update
    step's peak with donated opt-state/grads is strictly below the
    undonated peak, by at least the opt-state size."""
    t = {x.entry.name: x for x in traced_entries()}["fl.client_update_step"]
    undonated = cost_of_jaxpr(t.closed_jaxpr)
    donated = t.cost
    assert donated.peak_bytes < undonated.peak_bytes


# ---------------------------------------------------------------------------
# the memory gate
# ---------------------------------------------------------------------------


def test_memory_gate_baseline_violates_and_adapted_fits():
    """The paper's Fig. 2 shape, statically: at FedAvg baseline knobs
    the client step exceeds Budgets.memory (0.31 > 0.26 by Table-1
    calibration); at the adapted operating point it fits."""
    rows = {r.entry: r for r in memory_gate(traced_entries())}
    base = rows["fl.client_local_step@baseline"]
    adapted = rows["fl.client_local_step"]
    assert base.memory_units == pytest.approx(0.31)
    assert base.violated and not base.gated       # negative control
    assert adapted.gated and not adapted.violated
    assert adapted.memory_units < base.memory_units


def test_trace_table_committed_and_clean():
    """The committed TRACE_BUDGETS.json matches a fresh trace (the CI
    --trace gate's ratchet) and the full run reports no problems."""
    report = run_trace(root=".")
    assert report.problems == [], report.problems
    assert report.findings == []
    table = load_table(DEFAULT_TRACE_TABLE)
    assert table is not None
    assert set(table["entries"]) == {t.entry.name
                                     for t in report.traced}


def test_diff_table_catches_regression_and_stale_rows():
    traced = list(traced_entries())
    table = build_table(traced, memory_gate(traced))
    name = traced[0].entry.name
    table["entries"][name]["peak_bytes"] = \
        int(table["entries"][name]["peak_bytes"] * 0.5)
    table["entries"]["ghost.entry"] = {"peak_bytes": 1}
    problems = diff_table(table, traced)
    assert any("peak regressed" in p for p in problems)
    assert any("ghost.entry" in p for p in problems)


# ---------------------------------------------------------------------------
# bracket pin: static peak vs XLA memory_analysis (tier-1)
# ---------------------------------------------------------------------------

#: the declared band: the jaxpr-level estimate prices the *unfused*
#: program with ideal liveness, XLA's measured footprint adds buffer
#: alignment and scheduler temporaries but removes fused intermediates
#: — empirically the two agree within a small constant factor (ratio
#: ~0.96 at the declared shapes; the band leaves room for jax/XLA
#: version drift without letting the estimate decouple from reality).
BRACKET_LO = 0.5
BRACKET_HI = 4.0


def test_static_peak_brackets_compiled_high_water():
    entries = {e.name: e for e in collect_entry_points()}
    ep = entries["fl.client_local_step"]
    fn, args = ep.build()
    static_peak = trace_entry(ep).cost.peak_bytes
    stats = fn.lower(*args).compile().memory_analysis()
    measured = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes - stats.alias_size_in_bytes)
    assert measured > 0
    ratio = static_peak / measured
    assert BRACKET_LO <= ratio <= BRACKET_HI, (
        f"static {static_peak} B vs measured {measured} B "
        f"(ratio {ratio:.2f}) outside [{BRACKET_LO}, {BRACKET_HI}]")


# ---------------------------------------------------------------------------
# the traceable dual-update twin
# ---------------------------------------------------------------------------


def test_dual_step_jnp_matches_scalar_law():
    from repro.configs import get_fl_config
    from repro.constraints.controllers import (DeadzoneSubgradient,
                                               dual_step_jnp)

    cfg = get_fl_config().duals
    ctrl = DeadzoneSubgradient()
    ratios = [0.2, 0.89, 0.95, 1.0, 1.04, 1.051, 1.3, 5.0]
    lams = [0.0, 0.5, cfg.lambda_max]
    for lam in lams:
        want = np.array([ctrl.step("k", lam, r, cfg) for r in ratios],
                        np.float32)
        got = dual_step_jnp(jnp.full((len(ratios),), lam, F32),
                            jnp.asarray(ratios, F32),
                            cfg.eta, cfg.deadzone, cfg.lambda_max)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# CLI integration
# ---------------------------------------------------------------------------


def test_cli_trace_exits_clean_on_repo():
    from repro.analysis.cli import EXIT_CLEAN, main
    assert main(["--trace"]) == EXIT_CLEAN


def test_cli_trace_json_shape(capsys):
    from repro.analysis.cli import main
    import json
    main(["--trace", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert "trace" in payload
    entries = payload["trace"]["entries"]
    assert entries and all("peak_bytes" in r and "flops" in r
                           for r in entries)
    assert payload["trace"]["gate"]


def test_charlm_trace_setup_shapes():
    runner, params, batch = charlm_trace_setup(b=4)
    assert batch["tokens"].shape == (4, runner.fl.seq_len)
    assert len(jax.tree.leaves(params)) > 0
