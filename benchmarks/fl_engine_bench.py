"""Federated-engine benchmarks on the ``repro.bench`` harness (area
``fl_engine``), snapshotted to ``BENCH_fl_engine.json``:

- ``fl.executor`` — sequential Python loop vs the batched
  (jit + vmap-of-scan) LocalTrain path on the same tiny char-LM round;
  the speedup is a typed ``batched_speedup`` metric (higher is better —
  it regresses *downward*).
- ``fl.dynamics`` — engine-level round cost of K-of-N sampling with
  deadline stragglers vs the full static fleet, retraces included
  (survivor-group shapes change between rounds; that cost is the
  scenario's, not warmup).
- ``fl.aggregator`` — sync barrier vs FedBuff buffered async under
  stragglers: mean round wall-clock plus ``rounds_to_target`` (the
  metric async actually buys; a miss records as rounds+1 so later
  regressions stay visible).
- ``fl.wall_clock`` — simulated *seconds* to a target loss under
  ``time_mode="wall_clock"`` for wait-for-all / deadline-discard /
  FedBuff: deterministic given the seed, so these ``du`` metrics
  ratchet tightly.
- ``fl.controller`` — dual-controller laws (deadzone/adaptive/PI) on
  the calibrated proxy control loop: rounds until the deadzone band and
  tail violation ratio; host-side float math, tightest bands of all.

    PYTHONPATH=src:. python benchmarks/fl_engine_bench.py [--scale smoke|full|tiny]
"""
from __future__ import annotations

import dataclasses
import time

from repro.bench import MetricSpec, TimingStats, benchmark

AREA = "fl_engine"

# Wall-clock metrics move across machines: 2x band. Simulated /
# derived metrics are seed-deterministic: tight bands (the small atol
# absorbs cross-BLAS loss wiggle flipping a hit by one round).
_US = dict(unit="us", direction="lower", rtol=1.0)

_MODEL_KEYS = ("corpus_bytes", "num_layers", "d_model", "num_heads",
               "head_dim", "d_ff", "num_clients", "clients_per_round",
               "s_base", "b_base", "seq_len")

_FULL_MODEL = {"corpus_bytes": 120_000, "num_layers": 3, "d_model": 96,
               "num_heads": 4, "head_dim": 24, "d_ff": 192,
               "num_clients": 8, "clients_per_round": 6,
               "s_base": 10, "b_base": 16, "seq_len": 32}
_SMOKE_MODEL = {"corpus_bytes": 60_000, "num_layers": 2, "d_model": 64,
                "num_heads": 4, "head_dim": 16, "d_ff": 128,
                "num_clients": 6, "clients_per_round": 4,
                "s_base": 6, "b_base": 8, "seq_len": 32}
_TINY_MODEL = {"corpus_bytes": 30_000, "num_layers": 2, "d_model": 32,
               "num_heads": 2, "head_dim": 16, "d_ff": 64,
               "num_clients": 4, "clients_per_round": 2,
               "s_base": 3, "b_base": 4, "seq_len": 16}


def _setup(params):
    """Shared model/config/data setup for the engine benchmarks."""
    from repro.configs import get_config, get_fl_config
    from repro.data import load_corpus
    from repro.models import build

    ds = load_corpus(target_bytes=params["corpus_bytes"])
    cfg = get_config("charlm-shakespeare").replace(
        vocab_size=max(ds.vocab_size, 64), num_layers=params["num_layers"],
        d_model=params["d_model"], num_heads=params["num_heads"],
        num_kv_heads=params["num_heads"], head_dim=params["head_dim"],
        d_ff=params["d_ff"])
    fl = get_fl_config().replace(
        num_clients=params["num_clients"],
        clients_per_round=params["clients_per_round"],
        s_base=params["s_base"], b_base=params["b_base"],
        seq_len=params["seq_len"])
    fl = fl.replace(duals=dataclasses.replace(fl.duals, s_min=4, b_min=4))
    return build(cfg), fl, ds


def _round_mean_us(history):
    """Mean ``RoundRecord.seconds`` as a pseudo-TimingStats (first round
    dropped as compile when more than one was timed), in microseconds."""
    seconds = [r.seconds for r in history]
    seconds = seconds[1:] or seconds
    mean = sum(seconds) / len(seconds)
    lo, hi = min(seconds), max(seconds)
    return TimingStats(median_us=mean * 1e6, iqr_us=(hi - lo) * 1e6,
                       n=len(seconds))


@benchmark(
    "fl.executor", AREA,
    metrics=[MetricSpec("sequential_round_us", **_US),
             MetricSpec("batched_round_us", **_US),
             MetricSpec("batched_speedup", unit="x", direction="higher",
                        rtol=0.35, atol=0.15)],
    presets={"full": {**_FULL_MODEL, "repeats": 3},
             "smoke": {**_SMOKE_MODEL, "repeats": 3},
             "tiny": {**_TINY_MODEL, "repeats": 2}},
    description="sequential vs batched (jit+vmap-of-scan) LocalTrain round")
def executor_bench(params):
    from repro.core.client import ClientRunner
    from repro.core.policy import fedavg_knobs
    from repro.core.resources import calibrate
    from repro.data.federated import FederatedData
    from repro.fl import ClientInfo, DeviceProfile, make_executor

    model, fl, ds = _setup(params)
    import jax
    model_params = model.init(jax.random.PRNGKey(0))
    from repro.core.freezing import count_params
    resources = calibrate(count_params(model_params), fl)
    data = FederatedData(ds.train, fl.num_clients, seed=fl.seed)
    knobs = fedavg_knobs(fl)
    profile = DeviceProfile("default", fl.budgets, resources=resources)
    clients = [ClientInfo(i, profile, data.shard_size(i))
               for i in range(fl.clients_per_round)]
    assignments = [(ci, knobs) for ci in clients]

    out = {"context": {"cohort":
                       f"{fl.clients_per_round}clients*s{knobs.s}*b{knobs.b}"}}
    medians = {}
    for name in ("sequential", "batched"):
        runner = ClientRunner(model, fl, data, resources)
        executor = make_executor(name, runner)
        executor.run_round(model_params, assignments)    # warmup / compile
        times = []
        for _ in range(params["repeats"]):
            t0 = time.perf_counter()
            executor.run_round(model_params, assignments)
            times.append(time.perf_counter() - t0)
        times.sort()
        med = times[len(times) // 2]
        medians[name] = med
        out[f"{name}_round_us"] = TimingStats(
            median_us=med * 1e6, iqr_us=(times[-1] - times[0]) * 1e6,
            n=len(times))
    out["batched_speedup"] = medians["sequential"] / medians["batched"]
    return out


@benchmark(
    "fl.dynamics", AREA,
    metrics=[MetricSpec("full_round_mean_us", **_US),
             MetricSpec("sampled_round_mean_us", **_US)],
    presets={"full": {**_FULL_MODEL, "rounds": 4, "cohort": 4,
                      "deadline": 2.0},
             "smoke": {**_SMOKE_MODEL, "rounds": 3, "cohort": 3,
                       "deadline": 2.0},
             "tiny": {**_TINY_MODEL, "rounds": 2, "cohort": 2,
                      "deadline": 2.0}},
    description="static full cohort vs K-of-N sampling with deadline "
                "stragglers, mean round time incl. retraces")
def dynamics_bench(params):
    from repro.fl import (DeadlineStragglers, FederatedEngine, FleetDynamics,
                          FullParticipation, UniformSampler)

    model, fl, ds = _setup(params)
    fl_bench = fl.replace(rounds=params["rounds"], eval_batches=1,
                          eval_batch_size=16,
                          clients_per_round=params["cohort"])
    scenarios = {
        "full": FleetDynamics(sampler=FullParticipation()),
        "sampled": FleetDynamics(
            sampler=UniformSampler(fl_bench.clients_per_round),
            stragglers=DeadlineStragglers.for_config(
                fl_bench, deadline=params["deadline"], jitter=0.3)),
    }
    out = {"context": {}}
    for name, dyn in scenarios.items():
        res = FederatedEngine(model, fl_bench, ds, strategy="cafl",
                              executor="batched", dynamics=dyn).run()
        out[f"{name}_round_mean_us"] = _round_mean_us(res.history)
        parts = sum(len(r.participants) for r in res.history)
        drops = sum(len(r.dropped) for r in res.history)
        out["context"][name] = f"{parts}reported+{drops}dropped,incl-retraces"
    return out


@benchmark(
    "fl.aggregator", AREA,
    metrics=[MetricSpec("sync_round_mean_us", **_US),
             MetricSpec("fedbuff_round_mean_us", **_US),
             MetricSpec("sync_rounds_to_target", unit="rounds",
                        direction="lower", rtol=0.0, atol=1.0),
             MetricSpec("fedbuff_rounds_to_target", unit="rounds",
                        direction="lower", rtol=0.0, atol=1.0)],
    presets={"full": {**_FULL_MODEL, "rounds": 6, "cohort": 4,
                      "deadline": 1.1, "buffer_size": 3},
             "smoke": {**_SMOKE_MODEL, "rounds": 4, "cohort": 3,
                       "deadline": 1.1, "buffer_size": 2},
             "tiny": {**_TINY_MODEL, "rounds": 2, "cohort": 2,
                      "deadline": 1.1, "buffer_size": 2}},
    description="sync barrier vs FedBuff under stragglers: round cost and "
                "rounds-to-target-loss (miss records as rounds+1)")
def aggregator_bench(params):
    from repro.fl import (DeadlineStragglers, FedBuffAggregator,
                          FederatedEngine, FleetDynamics, UniformSampler)

    model, fl, ds = _setup(params)
    fl_bench = fl.replace(rounds=params["rounds"], eval_batches=1,
                          eval_batch_size=16,
                          clients_per_round=params["cohort"])

    def dyn():
        return FleetDynamics(
            sampler=UniformSampler(fl_bench.clients_per_round),
            stragglers=DeadlineStragglers.for_config(
                fl_bench, deadline=params["deadline"], jitter=0.3))

    runs, out = {}, {"context": {}}
    for name, agg in (("sync", "sync"),
                      ("fedbuff",
                       FedBuffAggregator(buffer_size=params["buffer_size"]))):
        res = FederatedEngine(model, fl_bench, ds, strategy="fedavg",
                              executor="batched", dynamics=dyn(),
                              aggregator=agg).run()
        runs[name] = res
        out[f"{name}_round_mean_us"] = _round_mean_us(res.history)
        applied = sum(r.reports_applied for r in res.history)
        late = sum(len(r.late_arrivals) for r in res.history)
        out["context"][name] = f"{applied}applied({late}late)"
    # rounds to the sync run's final loss: the async path's win metric
    target = runs["sync"].history[-1].val_loss
    out["context"]["target"] = f"{target:.4f}"
    for name, res in runs.items():
        hit = next((r.round for r in res.history if r.val_loss <= target),
                   None)
        out[f"{name}_rounds_to_target"] = float(
            hit if hit is not None else fl_bench.rounds + 1)
    return out


@benchmark(
    "fl.wall_clock", AREA,
    metrics=[MetricSpec(f"{p}_{m}", unit="du", direction="lower",
                        rtol=0.25, atol=a)
             for p in ("sync", "deadline_discard", "fedbuff")
             for m, a in (("du_per_round", 0.1),
                          ("seconds_to_target", 1.0))],
    presets={"full": {**_FULL_MODEL, "rounds": 6, "cohort": 4,
                      "buffer_size": 3},
             "smoke": {**_SMOKE_MODEL, "rounds": 4, "cohort": 3,
                       "buffer_size": 2},
             "tiny": {**_TINY_MODEL, "rounds": 2, "cohort": 2,
                      "buffer_size": 2}},
    description="simulated seconds to target loss (wall_clock mode): "
                "wait-for-all vs deadline-discard vs FedBuff; a miss "
                "records as the run's total simulated time + 1du")
def wall_clock_bench(params):
    from repro.fl import (DeadlineStragglers, FedBuffAggregator,
                          FederatedEngine, FleetClass, FleetDynamics,
                          UniformSampler, make_fleet, seconds_to_target)

    model, fl, ds = _setup(params)
    fl_bench = fl.replace(rounds=params["rounds"], eval_batches=1,
                          eval_batch_size=16,
                          clients_per_round=params["cohort"])
    profiles, cp = make_fleet(fl_bench, [
        FleetClass("fast", fraction=0.5),
        FleetClass("slow", fraction=0.5, compute_scale=2.0)])

    def dyn(deadline):
        return FleetDynamics(
            sampler=UniformSampler(fl_bench.clients_per_round),
            stragglers=DeadlineStragglers.for_config(fl_bench,
                                                     deadline=deadline,
                                                     jitter=0.3))

    scenarios = {
        "sync": ("sync", 4.0),                 # wait-for-all barrier
        "deadline_discard": ("sync", 1.1),     # tight barrier, discards
        "fedbuff": (FedBuffAggregator(buffer_size=params["buffer_size"]),
                    1.1),
    }
    runs, out = {}, {"context": {}}
    for name, (agg, deadline) in scenarios.items():
        res = FederatedEngine(model, fl_bench, ds, strategy="fedavg",
                              executor="batched", profiles=profiles,
                              client_profiles=cp, dynamics=dyn(deadline),
                              aggregator=agg).run(time_mode="wall_clock")
        runs[name] = res
        sim = res.history[-1].sim_time
        out[f"{name}_du_per_round"] = sim / len(res.history)
        out["context"][name] = f"{sim:.2f}du,{len(res.history)}rounds"
    # seconds to the weakest policy's final loss (deadline units: 1.0 =
    # one baseline round on calibration silicon)
    target = max(res.history[-1].val_loss for res in runs.values())
    out["context"]["target"] = f"{target:.4f}"
    for name, res in runs.items():
        hit = seconds_to_target(res, target)
        out[f"{name}_seconds_to_target"] = (
            hit if hit is not None else res.history[-1].sim_time + 1.0)
    return out


@benchmark(
    "fl.controller", AREA,
    metrics=[MetricSpec(f"{c}_{m}", unit=u, direction="lower",
                        rtol=r, atol=a)
             for c in ("deadzone", "adaptive", "pi")
             for m, u, r, a in (("rounds_to_satisfaction", "rounds",
                                 0.0, 2.0),
                                ("tail_violation", "ratio", 0.05, 0.01))],
    presets={"full": {"rounds": 80, "tail": 10},
             "smoke": {"rounds": 80, "tail": 10},
             "tiny": {"rounds": 40, "tail": 5}},
    description="dual-controller laws on the calibrated proxy loop: rounds "
                "until the deadzone band, tail violation ratio (host-side "
                "float math; a miss records as rounds+1)")
def controller_bench(params):
    from repro.configs import get_fl_config
    from repro.constraints import (proxy_control_loop, rounds_to_band,
                                   tail_worst_ratio)

    fl = get_fl_config()
    rounds, tail = params["rounds"], params["tail"]
    band = 1.0 + fl.duals.deadzone
    out = {"context": {"band": f"<={band:.2f}"}}
    for name in ("deadzone", "adaptive", "pi"):
        history = proxy_control_loop(fl, controller=name, rounds=rounds)
        hit = rounds_to_band(history, band)
        out[f"{name}_rounds_to_satisfaction"] = float(
            hit if hit is not None else rounds + 1)
        out[f"{name}_tail_violation"] = tail_worst_ratio(history, tail)
    return out


@benchmark(
    "fl.memory_static", AREA,
    metrics=[MetricSpec("undonated_peak_bytes", unit="B",
                        direction="lower", rtol=0.05),
             MetricSpec("donated_peak_bytes", unit="B",
                        direction="lower", rtol=0.05),
             MetricSpec("donation_saving", unit="x", direction="higher",
                        rtol=0.05)],
    presets={"full": {}, "smoke": {}, "tiny": {}},
    description="static (jaxpr cost model) peak of the client update "
                "step with vs without opt-state/grad donation — the "
                "PR-9 donation win, ratcheted so it cannot silently "
                "regress")
def memory_static_bench(params):
    from repro.analysis.trace import cost_of_jaxpr, traced_entries

    t = {x.entry.name: x for x in traced_entries()}["fl.client_update_step"]
    undonated = cost_of_jaxpr(t.closed_jaxpr).peak_bytes
    donated = t.cost.peak_bytes
    return {
        "context": {"entry": t.entry.name,
                    "aliased": f"{t.aliased_outputs}/{t.donatable_leaves}"},
        "undonated_peak_bytes": float(undonated),
        "donated_peak_bytes": float(donated),
        "donation_saving": undonated / donated,
    }


def main(argv=None):
    from benchmarks.common import emit_snapshot, run_area_cli
    emit_snapshot(run_area_cli(AREA, argv))


if __name__ == "__main__":
    main()
