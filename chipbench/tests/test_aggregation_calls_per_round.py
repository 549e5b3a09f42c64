"""The ``aggregation_calls_per_round`` reader
(``metrics/aggregation_calls_per_round.py``): the program's
``aggregate_calls`` counter per recorded round on a built record, 0
where a program records rounds without that counter, nothing where
nothing was recorded, and at least one fold step and one server update
a round in a traced run of the tiny CAFL-L cell on the CPU."""
import json
import os
from types import SimpleNamespace

import pytest

from harness.main import load_reader
from repro.fl import spans

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "aggregation_calls_per_round"


@pytest.fixture(autouse=True)
def empty_record():
    spans.clear()
    yield
    spans.clear()


def _round(rnd, counters, complete=True):
    return spans.Round(
        rnd, spans=[spans.Span(rnd, "round", None, 0, 12_000_000)],
        counters=dict(counters), complete=complete)


@pytest.mark.parametrize("counters, per_round", [
    ({"wire_calls": 192, "aggregate_calls": 7}, 7),
    ({"wire_calls": 192}, 0)], ids=["counted", "absent"])
def test_counter_per_recorded_round(counters, per_round, monkeypatch):
    rounds = [_round(1, counters), _round(2, counters),
              _round(3, counters, complete=False)]
    monkeypatch.setattr(spans, "records", lambda: rounds)
    assert load_reader(NAME)(SimpleNamespace(rounds=2)) == per_round


def test_nothing_recorded_reads_nothing():
    assert load_reader(NAME)(SimpleNamespace(rounds=4)) is None


def test_traced_run_prints_it(tiny, tmp_path, monkeypatch, capsys):
    from harness import main
    with open(os.path.join(HERE, "data", "BENCHMARK.json")) as f:
        bench = json.load(f)
    for conf in bench["configs"]:
        conf["file"] = os.path.join(HERE, "data", conf["file"])
    bench["per_layer"].append(
        {"name": NAME, "unit": "calls", "better": "lower",
         "source": "program_counter", "layer": "aggregation",
         "moves": "round_s", "workloads": ["tiny-cafl"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    monkeypatch.setattr(tiny, "BENCH_PATH", str(path))
    assert main.main(["--workload", "tiny-cafl", "--seed", "2147483659",
                      "--seconds", "0.5", "--trace", "1"],
                     require_chip=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"][NAME]["unit"] == "calls"
    # one fold step per report and one server update
    assert res["metrics"][NAME]["value"] >= 2.0
