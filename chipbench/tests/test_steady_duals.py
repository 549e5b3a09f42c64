"""The steady-state traffic's starting duals are reproducible, and its
warm-up covers every program shape the window can reach."""
import json
import os

import pytest

import steady_duals

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config, traffic",
                         [("charlm-shakespeare", "paper-cafl-steady")])
def test_init_duals_follow_from_zero(config, traffic):
    t = _load("traffic", traffic)
    start = t["init_duals_round"]
    traj = steady_duals.trajectory(_load("configs", config), t, start + 1000)
    assert traj[start - 1][1] == t["init_duals"]
    after = [kn for kn, _ in traj[start:]]
    warm = after[:t["warmup_rounds"]]
    # (s, b, ga) fixes the LocalTrain program's shapes, q the wire's
    shape = lambda kn: (kn[1], kn[2], kn[4])
    assert {shape(kn) for kn in after} <= {shape(kn) for kn in warm}
    assert {kn[3] for kn in after} <= {kn[3] for kn in warm}
