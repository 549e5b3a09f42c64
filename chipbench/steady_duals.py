"""Where CAFL-L's control plane goes from zero duals, and the duals a
steady-state traffic file starts from. Not part of a benchmark run.

    JAX_PLATFORMS=cpu python3 chipbench/steady_duals.py \\
        --config charlm-shakespeare --traffic paper-cafl-steady [--rounds 60]

The trajectory does not depend on the seed or the data: every client of
a round gets the same knobs, the usage proxies read only the knobs and
the trainable share of the parameters, and the duals read only the
usage. So it is followed here on the host with the reference's control
plane (``reference/fl.py``: Eq. 5-8 knob map, the Appendix-A.1 proxies,
Eq. 4's dual step), from zero duals, with the traffic's budgets and dual
settings. Prints one line per round (knobs, duals, whether the knob
shape is new, i.e. a retrace) and, last, the duals after the traffic's
``init_duals_round`` as JSON: the traffic file's ``init_duals``.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def trajectory(config, t, rounds):
    """-> per round: (knobs the round trains with, duals after it)."""
    import jax
    from reference import fl
    from repro.configs.base import ModelConfig
    from repro.models import build

    from harness.check import reference_module
    model = build(ModelConfig(**config["model"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ref = reference_module(config)
    consts = fl.calibrate(sum(int(l.size) for l in jax.tree.leaves(shapes)),
                          t)
    budgets = {"energy": t["budgets"]["energy"],
               "comm": t["budgets"]["comm_mb"],
               "memory": t["budgets"]["memory"],
               "temp": t["budgets"]["temp"]}
    lam = {c: 0.0 for c in fl.CONSTRAINTS}
    out = []
    for _ in range(rounds):
        kn = fl.knobs(t, lam)
        active = fl.count_active(shapes, ref.trainable_mask(
            shapes, config["model"], kn[0]))
        use = fl.usage(consts, active, kn)
        for c in fl.CONSTRAINTS:
            lam[c] = fl.dual_step(lam[c], use[c] / budgets[c], t["duals"])
        out.append((kn, dict(lam)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rounds", type=int, default=60)
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", args.traffic + ".json")) as f:
        t = json.load(f)
    start = t["init_duals_round"]
    seen = set()
    traj = trajectory(config, t, max(args.rounds, start))
    for rnd, (kn, lam) in enumerate(traj, start=1):
        shape = (kn[1], kn[2], kn[4])           # s, b, ga: the traced shape
        print(json.dumps({"round": rnd, "knobs": kn, "retrace": shape not in seen,
                          "duals": lam}))
        seen.add(shape)
    print(json.dumps(traj[start - 1][1]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
