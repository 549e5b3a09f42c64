"""Host spans and counters of the federated round (``repro.spans``).

Off the profiler nothing is recorded. Under ``jax.profiler.trace`` a
tiny CAFL-L run records every span of the round, nested where the work
happens, counts each wire-kernel, LocalTrain and aggregation launch,
writes the same spans into the profiler's own trace on a host plane,
and leaves the run's records as they are without the profiler."""
import dataclasses
from collections import Counter

import jax
import pytest

from repro.configs import get_config, get_fl_config
from repro.core.duals import DualState
from repro.data import load_corpus
from repro.fl import FederatedEngine, RoundCallback, spans
from repro.kernels import ops
from repro.models import build

#: span -> its parent, as the round nests them
NESTING = {"round": None,
           "eval": "round", "compose": "round", "execute": "round",
           "report": "round", "aggregate": "round", "accounting": "round",
           "dual_update": "round",
           "stage": "execute", "local_train_wait": "execute",
           "unstack": "execute", "wire": "execute", "wire_bytes": "execute"}


def _engine():
    ds = load_corpus(target_bytes=40_000)
    cfg = get_config("charlm-shakespeare").replace(
        vocab_size=max(ds.vocab_size, 64), num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=4, head_dim=8, d_ff=64)
    fl = get_fl_config().replace(
        rounds=2, num_clients=4, clients_per_round=2, s_base=2, b_base=4,
        seq_len=16, eval_batches=1, eval_batch_size=4)
    fl = fl.replace(duals=dataclasses.replace(fl.duals, s_min=1, b_min=2))
    # a comm dual above the 2-bit threshold: every round ships at q > 0
    duals = DualState(lam={"energy": 0.0, "comm": 5.0, "memory": 0.0,
                           "temp": 0.0})
    return FederatedEngine(build(cfg), fl, ds, strategy="cafl",
                           executor="batched", init_duals=duals)


class Groups(RoundCallback):
    """Per round: the clients trained, their distinct knob groups and
    their wire levels."""

    def __init__(self):
        self.clients, self.groups, self.q = {}, {}, {}

    def on_server_update(self, engine, update):
        knobs = [r.knobs for r in update.reports]
        self.clients[update.round] = len(knobs)
        self.groups[update.round] = len(set(knobs))
        self.q[update.round] = {kn.q for kn in knobs}


@dataclasses.dataclass
class Run:
    engine: FederatedEngine
    history: list          # RoundRecord dicts, wall seconds left out
    rounds: list           # spans.records() after the run
    groups: Groups
    trace_dir: object = None


def _run(trace_dir=None) -> Run:
    """A fresh 2-round run (a reused engine carries its data streams on),
    under ``jax.profiler.trace`` where ``trace_dir`` is given."""
    spans.clear()
    engine, groups = _engine(), Groups()
    engine.callbacks = [groups]
    if trace_dir is None:
        result = engine.run()
    else:
        with jax.profiler.trace(str(trace_dir)):
            result = engine.run()
    history = []
    for r in result.history:
        d = dataclasses.asdict(r)
        d.pop("seconds")
        history.append(d)
    run = Run(engine, history, spans.records(), groups, trace_dir)
    spans.clear()
    return run


@pytest.fixture(scope="module")
def unprofiled():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    return _run()


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("trace"))


@pytest.fixture(autouse=True)
def empty_record():
    spans.clear()
    yield
    spans.clear()


def test_off_the_profiler_records_and_counts_nothing(unprofiled):
    assert unprofiled.rounds == []
    assert spans.span("round", rnd=1) is spans.span("wire")
    spans.count("wire_calls")
    assert spans.records() == []


def _check_rounds(run: Run, launches: int) -> None:
    """Every q > 0 knob group makes one stage, LocalTrain, wire round
    trip (``launches`` kernel launches over the group's packed blocks),
    unstack and wire-byte count."""
    groups = run.groups
    assert [r.round for r in run.rounds] == list(
        range(1, run.engine.fl.rounds + 1))
    for rnd in run.rounds:
        assert rnd.complete
        n_groups = groups.groups[rnd.round]
        assert 0 not in groups.q[rnd.round]
        assert Counter(s.name for s in rnd.spans) == {
            **{n: 1 for n in NESTING},
            **{n: n_groups for n in ("stage", "local_train_wait", "unstack",
                                     "wire", "wire_bytes")}}
        for s in rnd.spans:
            assert s.round == rnd.round
            assert s.parent == NESTING[s.name]
            assert s.start_ns <= s.end_ns
        assert rnd.counters == {
            "wire_calls": n_groups * launches,
            "localtrain_calls": n_groups,
            "aggregate_calls": groups.clients[rnd.round] + 1}
        # execute's children lie inside it, one after another
        (execute,) = [s for s in rnd.spans if s.name == "execute"]
        inner = sorted((s for s in rnd.spans if s.parent == "execute"),
                       key=lambda s: s.start_ns)
        assert execute.start_ns <= inner[0].start_ns
        assert inner[-1].end_ns <= execute.end_ns
        assert all(a.end_ns <= b.start_ns for a, b in zip(inner, inner[1:]))


def test_profiled_run_records_every_span_and_count(profiled):
    """Off the TPU the wire runs the ref twin: one launch per group."""
    _check_rounds(profiled, launches=1)


def test_pallas_wire_counts_quantize_and_dequantize(tmp_path, monkeypatch):
    monkeypatch.setattr(ops, "FORCE_BACKEND", "pallas")
    _check_rounds(_run(tmp_path), launches=2)


def test_profiler_leaves_round_records_unchanged(unprofiled, profiled):
    assert profiled.history == unprofiled.history


def test_spans_land_in_the_profilers_trace(profiled):
    from jax.profiler import ProfileData

    recorded = Counter(spans.PREFIX + s.name for r in profiled.rounds
                       for s in r.spans)
    (path,) = profiled.trace_dir.glob("plugins/profile/*/*.xplane.pb")
    traced = Counter()
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            traced.update(ev.name for ev in line.events
                          if ev.name.startswith(spans.PREFIX))
    assert traced == recorded


def test_only_rounds_opened_while_recording_are_kept(tmp_path):
    with spans.span("round", rnd=1):
        jax.profiler.start_trace(str(tmp_path))
        with spans.span("aggregate"):
            spans.count("wire_calls")
    with spans.span("round", rnd=2):
        with spans.span("execute"):
            spans.count("wire_calls", 3)
        jax.profiler.stop_trace()
        with spans.span("accounting"):
            spans.count("wire_calls")
    (rnd,) = spans.records()
    assert rnd.round == 2 and rnd.complete
    assert [(s.name, s.parent) for s in rnd.spans] == [("execute", "round"),
                                                      ("round", None)]
    assert rnd.counters == {"wire_calls": 3}


def test_record_keeps_the_last_rounds(tmp_path):
    extra = 5
    with jax.profiler.trace(str(tmp_path)):
        for t in range(1, spans.MAX_ROUNDS + extra + 1):
            with spans.span("round", rnd=t):
                spans.count("localtrain_calls")
    rounds = spans.records()
    assert len(rounds) == spans.MAX_ROUNDS
    assert rounds[0].round == extra + 1
    spans.clear()
    assert spans.records() == []
