"""Runtime-sanitizer pins: the steady-state engine round loop runs with
zero implicit host<->device transfers and zero jit recompiles after
round 1 (repro.analysis.runtime)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import (RecompileWatchCallback, RecompileWatcher,
                                    TransferGuardCallback, no_transfers)
from repro.configs import get_config, get_fl_config
from repro.data import load_corpus
from repro.fl import FederatedEngine
from repro.models import build

# ---------------------------------------------------------------------------
# the primitives
# ---------------------------------------------------------------------------


def test_no_transfers_blocks_implicit_h2d():
    x = jnp.asarray(np.arange(4, dtype=np.float32))
    with pytest.raises(Exception):
        with no_transfers():
            _ = x + 1               # Python scalar operand: implicit h2d


def test_no_transfers_allows_staged_and_jitted_work():
    x = jnp.asarray(np.arange(4, dtype=np.float32))
    one = jnp.asarray(np.asarray(1.0, np.float32))
    f = jax.jit(lambda a: a * 2)
    _ = f(x)                        # warm the cache outside the guard
    with no_transfers():
        y = f(x + one)
        _ = np.asarray(y)           # explicit d2h stays allowed
    assert float(np.asarray(y)[0]) == pytest.approx(2.0)


def test_recompile_watcher_counts_cache_misses():
    w = RecompileWatcher()

    @jax.jit
    def g(a):
        return a * 3

    x = jnp.asarray(np.arange(8, dtype=np.float32))
    with w:
        g(x)
        first = w.mark("cold")
        g(x)                        # identical shapes: cache hit
        assert w.mark("warm") == 0
        g(jnp.asarray(np.arange(16, dtype=np.float32)))  # new shape
        second = w.mark("reshape")
    assert first >= 1 and second >= 1
    assert w.buckets["warm"] == 0
    assert w.total == first + second


# ---------------------------------------------------------------------------
# the engine pins
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_run():
    """One 3-round fedavg/sequential/sync run under both sanitizers."""
    ds = load_corpus(target_bytes=60_000)
    cfg = get_config("charlm-shakespeare").replace(
        vocab_size=max(ds.vocab_size, 64), num_layers=3, d_model=48,
        num_heads=4, num_kv_heads=4, head_dim=12, d_ff=96)
    fl = get_fl_config().replace(
        rounds=3, num_clients=4, clients_per_round=2, s_base=3, b_base=8,
        seq_len=16, eval_batches=1, eval_batch_size=8)
    fl = fl.replace(duals=dataclasses.replace(fl.duals, s_min=2, b_min=4))
    guard = TransferGuardCallback(from_round=2)
    watch = RecompileWatchCallback()
    try:
        result = FederatedEngine(build(cfg), fl, ds, strategy="fedavg",
                                 executor="sequential",
                                 callbacks=[guard, watch]).run()
    finally:
        guard.close()               # an engine crash must not leak the guard
    return result, guard, watch


def test_engine_steady_state_is_transfer_free(tiny_run):
    """Rounds >= 2 run under jax.transfer_guard("disallow"): the round
    loop finishing at all IS the assertion — any implicit transfer in
    client training, aggregation or eval would have raised."""
    result, guard, _ = tiny_run
    assert len(result.history) == 3
    assert guard.guarded_rounds == [2, 3]


def test_engine_zero_recompiles_after_round_one(tiny_run):
    """Round 1 warms every jit cache (train step, masked apply, eval);
    from round 2 on the same executables must be reused — a drifting
    shape or static argument would show up as a backend compile."""
    _, _, watch = tiny_run
    assert watch.per_round.get(1, 0) > 0, "round 1 should compile"
    assert watch.steady_state_compiles(first_steady_round=2) == 0, (
        f"steady-state rounds recompiled: {watch.per_round}")



@pytest.mark.parametrize("topk", [None, 64])
def test_batched_wire_round_is_transfer_free(topk):
    """Once warm, a batched round at q > 0 (LocalTrain, then the knob
    group's stacked wire: pack, round trip, unpack) runs under the
    guard: nothing in it moves a host value to the device implicitly."""
    from repro.core.client import ClientRunner
    from repro.core.freezing import count_params
    from repro.core.policy import Knobs
    from repro.core.resources import calibrate
    from repro.data.federated import FederatedData
    from repro.fl import ClientInfo, DeviceProfile, make_executor
    ds = load_corpus(target_bytes=60_000)
    cfg = get_config("charlm-shakespeare").replace(
        vocab_size=max(ds.vocab_size, 64), num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=4, head_dim=8, d_ff=64)
    fl = get_fl_config().replace(num_clients=4, seq_len=16, wire_topk=topk)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    resources = calibrate(count_params(params), fl)
    data = FederatedData(ds.train, fl.num_clients, seed=fl.seed)
    ex = make_executor("batched", ClientRunner(model, fl, data, resources))
    profile = DeviceProfile("default", fl.budgets, resources=resources)
    rounds = [[(ClientInfo(c, profile, 1), Knobs(k=1, s=2, b=4, q=q,
                                                 grad_accum=2))
               for c in range(3)] for q in (1, 2)]
    for assignments in rounds:          # warm: masks, programs
        ex.run_round(params, assignments)
    with no_transfers():
        for assignments in rounds:
            outs = ex.run_round(params, assignments)
            assert all(np.isfinite(o.train_loss) for o in outs)
