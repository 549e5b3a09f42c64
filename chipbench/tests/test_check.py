"""The check that decides ``correct`` fails what it must.

At the tiny size of ``data/`` on the CPU, with the look for a chip
skipped, a whole run (warm-up, window, reference, verdict) comes out
correct as the system stands, and not correct with the timed path
broken underneath in each way a training cell can break: a step that
leaves the state unchanged, half of every batch left out with the mean
taken over the rest, and an answer (the shipped delta) altered where it
is produced. The control, the reference one precision step down in the
system's place, is not correct either. (The cells run on one chip, so
there is no exchange between chips to leave out.)
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(capsys, cell="tiny-cafl", seed=11):
    from harness import main
    assert main.main(["--workload", cell, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", "0"],
                     require_chip=False) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


@pytest.mark.parametrize("cell", ["tiny-cafl", "tiny-fedavg"])
def test_sound_run_is_correct(tiny, capsys, cell):
    res = _run(capsys, cell)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "client_tokens_per_s",
                                   "round_s", "peak_hbm_gb"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_state_left_unchanged_is_not_correct(tiny, capsys, monkeypatch):
    from repro.core import aggregation
    monkeypatch.setattr(aggregation, "apply_delta", lambda p, d: p)
    res = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(tiny, capsys, monkeypatch):
    import repro.models as models
    build = models.build

    def halving_build(cfg):
        model = build(cfg)

        def half(params, batch):
            b = batch["tokens"].shape[0] // 2
            return model.train_loss(params, {k: v[:b] for k, v in batch.items()})

        return model.__class__(**{**model.__dict__, "train_loss": half})

    monkeypatch.setattr(models, "build", halving_build)
    res = _run(capsys)
    assert res["correct"] is False


def test_altered_delta_is_not_correct(tiny, capsys, monkeypatch):
    from repro.fl import executor
    compress = executor._compress

    def altered(raw, mask, q, topk=None):
        out = compress(raw, mask, q, topk=topk)
        return jax.tree.map(lambda l: l * jnp.float32(1.01), out)

    monkeypatch.setattr(executor, "_compress", altered)
    res = _run(capsys)
    assert res["correct"] is False


def test_control_is_not_correct(tiny):
    """The reference in bfloat16 (the configuration states float32) in
    the system's place fails the tiny cell's limits on three seeds."""
    from harness import check, files
    from harness.session import Session
    cell = files.cell("tiny-cafl")
    for seed in (1, 2, 3):
        sess = Session(cell, seed, seconds=0.0).build()
        values = check.against_reference(cell, seed, sess.data.train,
                                         sess.initial_weights(), "control")
        ok, _ = check.verdict(values, cell.limits)
        assert not ok, values


def test_no_tpu_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "charlm-paper-cafl-steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
