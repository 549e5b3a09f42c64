"""The program's spans and counters reduced per round
(``harness/program_spans.py``): self seconds by hand on a built record,
and the eight readers end to end in a traced run of the tiny CAFL-L cell
on the CPU."""
import json
import os
from types import SimpleNamespace

import pytest

from harness import program_spans
from harness.main import load_reader
from repro.fl import spans

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000      # one millisecond in nanoseconds

#: name -> (unit, source, layer, moves)
METRICS = {
    "staging_s_per_round": ("s", "program_span", "LocalTrain step",
                            "client_tokens_per_s"),
    "localtrain_wait_s_per_round": ("s", "program_span", "LocalTrain step",
                                    "client_tokens_per_s"),
    "localtrain_calls_per_round": ("calls", "program_counter",
                                   "LocalTrain step", "client_tokens_per_s"),
    "wire_host_s_per_round": ("s", "program_span", "wire kernels",
                              "client_tokens_per_s"),
    "wire_calls_per_round": ("calls", "program_counter", "wire kernels",
                             "client_tokens_per_s"),
    "aggregation_s_per_round": ("s", "program_span", "aggregation",
                                "round_s"),
    "control_plane_s_per_round": ("s", "program_span", "control plane",
                                  "round_s"),
    "dual_update_s_per_round": ("s", "program_span", "control plane",
                                "round_s"),
}


@pytest.fixture(autouse=True)
def empty_record():
    spans.clear()
    yield
    spans.clear()


def _round(rnd, complete=True):
    """``execute`` 0-10 ms in a 0-12 ms round, with a ``stage`` child at
    1-3 ms and a ``wire`` child at 4-8 ms, and 192 wire calls."""
    span = lambda name, parent, a, b: spans.Span(rnd, name, parent,
                                                 a * MS, b * MS)
    return spans.Round(
        rnd, spans=[span("stage", "execute", 1, 3),
                    span("wire", "execute", 4, 8),
                    span("execute", "round", 0, 10),
                    span("round", None, 0, 12)],
        counters={"wire_calls": 192}, complete=complete)


def test_self_seconds_by_hand():
    own = program_spans.self_seconds(_round(1))
    assert own == pytest.approx({"stage": 0.002, "wire": 0.004,
                                 "execute": 0.004, "round": 0.002})
    # execute's children and its own self time make its duration
    assert own["stage"] + own["wire"] + own["execute"] == pytest.approx(0.010)


def test_per_round_over_completed_rounds(monkeypatch):
    rounds = [_round(1), _round(2), _round(3, complete=False)]
    monkeypatch.setattr(spans, "records", lambda: rounds)
    run = SimpleNamespace(rounds=2)
    assert program_spans.seconds_per_round(run, ["stage", "wire"]) == \
        pytest.approx(0.006)
    assert program_spans.count_per_round(run, "wire_calls") == 192
    assert program_spans.count_per_round(run, "localtrain_calls") == 0
    assert load_reader("wire_calls_per_round")(run) == 192
    # as many recorded rounds as the trace covers, or an error
    run.rounds = 3
    with pytest.raises(RuntimeError, match="recorded 2 rounds"):
        load_reader("staging_s_per_round")(run)


def test_nothing_recorded_reads_nothing():
    run = SimpleNamespace(rounds=4)
    for name in METRICS:
        assert load_reader(name)(run) is None


def test_traced_run_prints_the_eight(tiny, tmp_path, monkeypatch, capsys):
    from harness import main
    with open(os.path.join(HERE, "data", "BENCHMARK.json")) as f:
        bench = json.load(f)
    for conf in bench["configs"]:
        conf["file"] = os.path.join(HERE, "data", conf["file"])
    bench["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": moves, "workloads": ["tiny-cafl"]}
        for name, (unit, source, layer, moves) in METRICS.items()]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    monkeypatch.setattr(tiny, "BENCH_PATH", str(path))
    assert main.main(["--workload", "tiny-cafl", "--seed", "2147483659",
                      "--seconds", "0.5", "--trace", "1"],
                     require_chip=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    metrics = res["metrics"]
    assert set(METRICS) <= set(metrics)
    for name, (unit, *_rest) in METRICS.items():
        assert metrics[name]["unit"] == unit
        assert metrics[name]["value"] >= 0.0
    assert metrics["localtrain_calls_per_round"]["value"] >= 1.0
    assert metrics["wire_calls_per_round"]["value"] > 0.0
