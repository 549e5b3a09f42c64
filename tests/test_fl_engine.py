"""Tests for the composable federated engine (repro.fl): strategies,
executors (sequential vs batched equivalence), device profiles, and
round callbacks."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.configs import get_config, get_fl_config
from repro.data import load_corpus
from repro.fl import (CAFLL, CheckpointCallback, ClientInfo, DeviceProfile,
                      FedAvg, FederatedEngine, FleetClass,
                      HistoryWriterCallback, LoggingCallback, RoundCallback,
                      ServerOpt, make_executor, make_fleet,
                      make_strategy, uniform_fleet)
from repro.models import build


@pytest.fixture(scope="module")
def tiny_setup():
    ds = load_corpus(target_bytes=60_000)
    cfg = get_config("charlm-shakespeare").replace(
        vocab_size=max(ds.vocab_size, 64), num_layers=3, d_model=48,
        num_heads=4, num_kv_heads=4, head_dim=12, d_ff=96)
    fl = get_fl_config().replace(
        rounds=2, num_clients=4, clients_per_round=2, s_base=3, b_base=8,
        seq_len=16, eval_batches=1, eval_batch_size=8)
    fl = fl.replace(duals=dataclasses.replace(fl.duals, s_min=2, b_min=4))
    return ds, cfg, fl


@pytest.fixture(scope="module")
def tiny_model(tiny_setup):
    _, cfg, _ = tiny_setup
    return build(cfg)


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------


def test_make_strategy_resolution():
    fl = get_fl_config()
    assert isinstance(make_strategy("fedavg", fl), FedAvg)
    assert isinstance(make_strategy("cafl", fl), CAFLL)
    for name, inner in (("fedadam", FedAvg), ("fedavgm", FedAvg),
                        ("cafl+adam", CAFLL)):
        st = make_strategy(name, fl)
        assert isinstance(st, ServerOpt) and isinstance(st.inner, inner)
    # fl.server_opt composes onto a plain method name
    st = make_strategy("cafl", fl.replace(server_opt="momentum"))
    assert isinstance(st, ServerOpt) and st.name == "cafl+momentum"
    with pytest.raises(ValueError):
        make_strategy("nope", fl)


def test_cafl_strategy_keeps_per_profile_duals():
    fl = get_fl_config()
    st = make_strategy("cafl", fl)
    profiles = {
        "a": DeviceProfile("a", fl.budgets),
        "b": DeviceProfile("b", fl.budgets.scaled(0.5)),
    }
    clients = [ClientInfo(0, profiles["a"], 10),
               ClientInfo(1, profiles["b"], 10)]
    knobs = st.configure_round(1, clients)
    assert len(knobs) == 2
    # both start at zero duals -> identical baseline knobs
    assert knobs[0] == knobs[1]
    heavy = {"energy": 9e6, "comm": 9.0, "memory": 9.0, "temp": 9.0}
    snap = st.update_state([heavy, heavy], clients)
    assert set(snap) == {"a", "b"}
    # the tighter-budget profile accumulates larger duals
    assert snap["b"]["comm"] > snap["a"]["comm"]
    kn2 = st.configure_round(2, clients)
    assert kn2[1].s <= kn2[0].s and kn2[1].k <= kn2[0].k


def test_fedavg_weighted_aggregate():
    import jax.numpy as jnp
    fl = get_fl_config()
    deltas = [{"w": jnp.ones(3)}, {"w": jnp.full(3, 3.0)}]
    plain = FedAvg(fl).aggregate(deltas, [1.0, 3.0])
    assert np.allclose(np.asarray(plain["w"]), 2.0)     # weights ignored
    weighted = FedAvg(fl, weighted=True).aggregate(deltas, [1.0, 3.0])
    assert np.allclose(np.asarray(weighted["w"]), 2.5)


def test_server_opt_first_step_direction():
    import jax.numpy as jnp
    fl = get_fl_config()
    st = ServerOpt(FedAvg(fl), "momentum", lr=1.0)
    delta = [{"w": jnp.full(4, 0.5)}]
    out = st.aggregate(delta)
    # momentum step moves WITH the client delta
    assert np.all(np.asarray(out["w"]) > 0)


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------


def test_sequential_and_batched_histories_match(tiny_setup, tiny_model):
    ds, cfg, fl = tiny_setup
    for method in ("fedavg", "cafl"):
        runs = {}
        for ex in ("sequential", "batched"):
            res = FederatedEngine(tiny_model, fl, ds, strategy=method,
                                  executor=ex).run()
            runs[ex] = res
        for a, b in zip(runs["sequential"].history, runs["batched"].history):
            assert a.knobs == b.knobs
            assert a.val_loss == pytest.approx(b.val_loss, abs=2e-3)
            assert a.train_loss == pytest.approx(b.train_loss, abs=2e-3)
            assert a.usage == pytest.approx(b.usage)
            assert a.wire_mb_actual == pytest.approx(b.wire_mb_actual,
                                                     rel=1e-4)


def test_batched_groups_mixed_knobs(tiny_setup, tiny_model):
    """Clients with different knobs land in different vmap groups but the
    result order still matches the assignment order."""
    from repro.core.client import ClientRunner
    from repro.core.freezing import count_params
    from repro.core.policy import Knobs
    from repro.core.resources import calibrate
    from repro.data.federated import FederatedData
    import jax

    ds, cfg, fl = tiny_setup
    params = tiny_model.init(jax.random.PRNGKey(0))
    resources = calibrate(count_params(params), fl)
    data = FederatedData(ds.train, fl.num_clients, seed=fl.seed)
    runner = ClientRunner(tiny_model, fl, data, resources)
    ex = make_executor("batched", runner)
    profile = DeviceProfile("default", fl.budgets, resources=resources)
    kn_a = Knobs(k=2, s=2, b=4, q=0, grad_accum=1)
    kn_b = Knobs(k=1, s=2, b=4, q=2, grad_accum=2)
    assignments = [(ClientInfo(0, profile, 1), kn_a),
                   (ClientInfo(1, profile, 1), kn_b),
                   (ClientInfo(2, profile, 1), kn_a)]
    outs = ex.run_round(params, assignments)
    assert [o.client_id for o in outs] == [0, 1, 2]
    assert outs[0].params_active == outs[2].params_active
    assert outs[1].params_active < outs[0].params_active   # k=1 < k=2
    assert all(np.isfinite(o.train_loss) for o in outs)


@pytest.mark.parametrize("topk", [None, 64])
def test_batched_and_sequential_ship_the_same_cafl_round(tiny_setup,
                                                         tiny_model, topk):
    """A CAFL-L round at q=2 (freezing, grad accumulation, the 2-bit
    wire, dense or top-64): both executors ship the same deltas and the
    same wire MB. LocalTrain's float reassociation may flip a rare code
    by one step; the wire stage adds no difference: on the batched
    LocalTrain's own deltas the group's stacked wire ships exactly what
    the per-client, per-leaf round trip ships."""
    import jax
    from repro.core import compression, freezing
    from repro.core.client import ClientRunner
    from repro.core.freezing import count_params
    from repro.core.policy import Knobs
    from repro.core.resources import calibrate
    from repro.data.federated import FederatedData
    from repro.fl import executor

    ds, _, fl = tiny_setup
    fl = fl.replace(wire_topk=topk)
    params = tiny_model.init(jax.random.PRNGKey(0))
    resources = calibrate(count_params(params), fl)
    profile = DeviceProfile("default", fl.budgets, resources=resources)
    kn = Knobs(k=2, s=2, b=4, q=2, grad_accum=2)
    assignments = [(ClientInfo(c, profile, 1), kn) for c in range(3)]

    def runner():
        data = FederatedData(ds.train, fl.num_clients, seed=fl.seed)
        return ClientRunner(tiny_model, fl, data, resources)

    seq = make_executor("sequential", runner()).run_round(params, assignments)
    ex = make_executor("batched", runner())
    bat = ex.run_round(params, assignments)
    for a, b in zip(seq, bat):
        assert a.client_id == b.client_id
        assert a.wire_mb_actual == b.wire_mb_actual
        assert a.params_active == b.params_active
        for x, y in zip(jax.tree.leaves(a.delta), jax.tree.leaves(b.delta)):
            x, y = np.asarray(x), np.asarray(y)
            assert np.mean(x != y) <= 1e-3
            assert np.max(np.abs(x - y), initial=0.0) <= np.max(
                np.abs(x), initial=0.0)

    # the wire stage alone, on the batched LocalTrain's raw deltas
    fresh = runner()
    mask, _ = fresh.mask_for(params, kn.k)
    batches = executor.BatchedExecutor(fresh)._stack_batches([0, 1, 2], kn)
    raw, _ = ex._batched(params, mask, batches)
    want = [freezing.apply_mask(compression.compress_decompress(
        jax.tree.map(lambda l, i=i: l[i], raw), kn.q, topk=topk), mask)
        for i in range(3)]
    got = executor._compress(raw, mask, kn.q, topk=topk)
    for g, w in zip(got, want):
        for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_make_executor_unknown():
    with pytest.raises(ValueError):
        make_executor("warp", None)


# ---------------------------------------------------------------------------
# device profiles / fleets
# ---------------------------------------------------------------------------


def test_uniform_and_heterogeneous_fleet_specs():
    fl = get_fl_config()
    profiles, assignment = uniform_fleet(fl)
    assert set(assignment) == {"default"} and len(assignment) == fl.num_clients
    profiles, assignment = make_fleet(fl, [
        FleetClass("hi", 0.25, budget_scale=2.0),
        FleetClass("lo", 0.75, budget_scale=0.5, compute_scale=2.0)])
    assert len(assignment) == fl.num_clients
    assert assignment.count("hi") == round(0.25 * fl.num_clients)
    assert profiles["hi"].budgets.energy == pytest.approx(
        2.0 * fl.budgets.energy)
    assert profiles["lo"].budgets.comm_mb == pytest.approx(
        0.5 * fl.budgets.comm_mb)


def test_device_profile_resource_scaling():
    from repro.core.policy import fedavg_knobs
    from repro.core.resources import calibrate
    fl = get_fl_config()
    base = calibrate(1.9e6, fl)
    prof = DeviceProfile("lo", fl.budgets, compute_scale=1.5)
    prof = prof.with_resources(base)
    kn = fedavg_knobs(fl)
    u_base = base.usage(1.9e6, kn)
    u_lo = prof.resources.usage(1.9e6, kn)
    assert u_lo["energy"] == pytest.approx(1.5 * u_base["energy"])
    assert u_lo["temp"] == pytest.approx(1.5 * u_base["temp"])
    assert u_lo["comm"] == pytest.approx(u_base["comm"])   # wire unchanged
    # explicit resources are kept as-is
    assert prof.with_resources(base) is prof


def test_heterogeneous_run_records_per_profile(tiny_setup, tiny_model):
    ds, cfg, fl = tiny_setup
    fl4 = fl.replace(rounds=3, clients_per_round=4)
    profiles, assignment = make_fleet(fl4, [
        FleetClass("hi", 0.5, budget_scale=1.5),
        FleetClass("lo", 0.5, budget_scale=0.25, compute_scale=1.5)])
    res = FederatedEngine(tiny_model, fl4, ds, strategy="cafl",
                          profiles=profiles, client_profiles=assignment).run()
    last = res.history[-1]
    assert set(last.per_profile) == {"hi", "lo"}
    # the tight-budget tier must be driven to a cheaper operating point
    hi, lo = last.per_profile["hi"], last.per_profile["lo"]
    assert lo["duals"]["energy"] >= hi["duals"]["energy"]
    assert (lo["knobs"]["s"] < hi["knobs"]["s"]
            or lo["knobs"]["k"] < hi["knobs"]["k"]
            or lo["knobs"]["q"] > hi["knobs"]["q"])


# ---------------------------------------------------------------------------
# callbacks + wrapper compat
# ---------------------------------------------------------------------------


def test_callbacks_fire_and_write(tiny_setup, tiny_model, tmp_path):
    ds, cfg, fl = tiny_setup
    lines = []
    hist_path = str(tmp_path / "hist.json")
    ckpt_path = str(tmp_path / "final.ckpt")

    class Counter(RoundCallback):
        def __init__(self):
            self.starts = self.ends = 0
            self.train_started = self.train_ended = False

        def on_train_start(self, engine):
            self.train_started = True

        def on_round_start(self, engine, rnd):
            self.starts += 1

        def on_round_end(self, engine, record):
            self.ends += 1

        def on_train_end(self, engine, result):
            self.train_ended = True

    counter = Counter()
    res = FederatedEngine(
        tiny_model, fl, ds, strategy="fedavg",
        callbacks=[LoggingCallback(lines.append),
                   HistoryWriterCallback(hist_path),
                   CheckpointCallback(ckpt_path), counter]).run()
    assert counter.train_started and counter.train_ended
    assert counter.starts == fl.rounds and counter.ends == fl.rounds
    assert len(lines) == fl.rounds and "round" in lines[0]
    assert all(r.seconds > 0 for r in res.history)
    assert os.path.exists(ckpt_path)
    with open(hist_path) as f:
        payload = json.load(f)
    assert payload["method"] == "fedavg"
    assert len(payload["history"]) == fl.rounds
    assert payload["summary"]["val_loss"] == pytest.approx(
        res.summary()["val_loss"])


def test_run_federated_wrapper_unchanged(tiny_setup, tiny_model):
    """The seed entry point still works, including custom strategies via
    the method string."""
    from repro.core import run_federated
    ds, cfg, fl = tiny_setup
    res = run_federated(tiny_model, fl, ds, method="fedadam", rounds=2,
                        log=None)
    assert res.method == "fedavg+adam"
    assert len(res.history) == 2
    assert all(np.isfinite(r.val_loss) for r in res.history)
    assert res.history[0].per_profile == {}      # homogeneous fleet
