"""The Minitron-8B cell (``minitron8b-silo-fedavg``) in small: a tiny
Nemotron configuration under cross-silo FedAvg (``data/``, with a
benchmark file of its own), run whole on the CPU with the look for a
chip skipped.

A sound run comes out correct against ``reference/nemotron.py``; the
timed path broken underneath in each way a training cell can break (half
of every batch left out, the loss over half of each sequence, the state
left unchanged, the shipped delta altered) and the reference in bfloat16
in the system's place come out not correct. A traced run prints the
cell's per-layer metrics. The counts of the cell's configuration
(``configs/minitron-8b-tp8.json``) are pinned by hand.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "tiny-silo"
SEED = 3_000_000_019


@pytest.fixture
def silo(tiny):
    """``tiny``, with the cells looked up in the Nemotron benchmark file."""
    tiny.BENCH_PATH = os.path.join(HERE, "data", "BENCHMARK-nemotron.json")
    return tiny


def _run(capsys, trace=0, seed=SEED):
    from harness import main
    assert main.main(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", str(trace)],
                     require_chip=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(silo, capsys):
    res = _run(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "client_tokens_per_s",
                                   "round_s", "peak_hbm_gb"}
    assert res["attempted"] > 0 and res["failed"] == 0


def _with_batch(monkeypatch, change):
    """Every model built trains on ``change(batch)`` instead of the
    batch it is given."""
    import repro.models as models
    build = models.build

    def changed_build(cfg):
        model = build(cfg)

        def train_loss(params, batch):
            return model.train_loss(params, change(batch))

        return model.__class__(**{**model.__dict__,
                                  "train_loss": train_loss})

    monkeypatch.setattr(models, "build", changed_build)


def _half_batch(monkeypatch):
    def half(batch):
        b = batch["tokens"].shape[0] // 2
        return {k: v[:b] for k, v in batch.items()}

    _with_batch(monkeypatch, half)


def _half_sequence(monkeypatch):
    """The loss over the first half of each sequence alone (its second
    half masked out): the fault the cell can have at one sequence a
    step, where the half batch leaves nothing."""
    def half(batch):
        t = batch["targets"]
        first = jnp.arange(t.shape[-1]) < t.shape[-1] // 2
        return {**batch, "loss_mask": jnp.broadcast_to(
            first, t.shape).astype(jnp.float32)}

    _with_batch(monkeypatch, half)


def _state_unchanged(monkeypatch):
    from repro.core import aggregation
    monkeypatch.setattr(aggregation, "apply_delta", lambda p, d: p)


def _altered_delta(monkeypatch):
    from repro.fl import executor
    compress = executor._compress

    def altered(raw, mask, q, topk=None):
        out = compress(raw, mask, q, topk=topk)
        return jax.tree.map(lambda l: l * jnp.float32(1.01), out)

    monkeypatch.setattr(executor, "_compress", altered)


@pytest.mark.parametrize("fault", [_half_batch, _half_sequence,
                                   _state_unchanged, _altered_delta],
                         ids=["half_batch", "half_sequence",
                              "state_unchanged", "altered_delta"])
def test_planted_fault_is_not_correct(silo, capsys, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(capsys)
    assert res["correct"] is False
    if fault is _state_unchanged:
        assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_control_is_not_correct(silo):
    """The Nemotron reference in bfloat16 (the configuration states
    float32) in the system's place fails the limits on three seeds."""
    from harness import check, files
    from harness.session import Session
    cell = files.cell(CELL)
    for seed in (1, 2, SEED):
        sess = Session(cell, seed, seconds=0.0).build()
        values = check.against_reference(cell, seed, sess.data.train,
                                         sess.initial_weights(), "control")
        ok, _ = check.verdict(values, cell.limits)
        assert not ok, values


def test_traced_run_prints_the_cells_metrics(silo, capsys, monkeypatch):
    """With a stand-in row of peaks (there is no chip here) and the
    trace stopped after the window's first round, the later rounds give
    ``mfu`` (the cell's model FLOPs over their host seconds), and the
    traced round the program's spans and counters and the window's
    compile seconds: every per-layer metric the cell lists, but the
    device's idle share, which needs a device in the trace."""
    from harness import main, session
    monkeypatch.setattr(session, "TRACE_SECONDS", 0.0)
    context = main.RunContext
    monkeypatch.setattr(main, "RunContext", lambda cell, sess, reduced, cw,
                        _peaks: context(cell, sess, reduced, cw,
                                        {"bf16_flops": 197e12}))
    res = _run(capsys, trace=1)
    assert res["correct"] is True
    listed = {m["name"] for m in silo.cell(CELL).per_layer}
    metrics = res["metrics"]
    assert listed - {"device_idle_share"} <= set(metrics) <= listed
    assert metrics["mfu"]["unit"] == "%" and metrics["mfu"]["value"] > 0.0
    # FedAvg at q 0: one LocalTrain launch a round, no compile in it
    assert metrics["localtrain_calls_per_round"]["value"] == 1.0
    assert metrics["compile_s_per_round"]["value"] == 0.0
    assert all(v["value"] >= 0.0 for v in metrics.values())


# ---------------------------------------------------------------------------
# the cell's configuration, counted by hand
# ---------------------------------------------------------------------------


def _share():
    with open(os.path.join(BENCH, "configs", "minitron-8b-tp8.json")) as f:
        return json.load(f)


def _share_shapes():
    from repro.configs.base import ModelConfig
    from repro.models import build
    return jax.eval_shape(build(ModelConfig(**_share()["model"])).init,
                          jax.random.PRNGKey(0))


def test_share_param_count_by_hand():
    from harness import counts
    d, q, kv, f, vocab, layers = 4096, 6 * 128, 1 * 128, 2048, 32000, 4
    layer = (d * q + 2 * d * kv + q * d     # wq, wk, wv, wo
             + 2 * d * f                    # w_up, w_down, no biases
             + 2 * 2 * d)                   # ln1, ln2: scale and bias
    assert layer == 24_133_632
    by_hand = layers * layer + 2 * vocab * d + 2 * d   # embed, head, norm
    assert counts.param_count(_share_shapes()) == by_hand == 358_686_720
    assert _share()["assumed"]["param_count"] == 358_686_720


def test_share_flops_by_hand():
    from harness import counts
    m, seq = _share()["model"], 4096
    per_token_layer = 2 * (4096 * 768 + 2 * 4096 * 128 + 768 * 4096
                           + 2 * 4096 * 2048)          # squared ReLU: m = 2
    attn = 2 * 6 * 128 * seq * (seq + 1)
    layer = seq * per_token_layer + attn
    head = seq * 2 * 4096 * 32000
    forward = 4 * layer + head
    by_hand = forward + 2 * forward                    # k = 4: every layer
    assert counts.seq_flops(m, seq, 4) == by_hand
    assert by_hand == pytest.approx(5.90e12, rel=1e-3)
    assert head / forward == pytest.approx(0.546, abs=1e-3)
    # a round: one silo, 8 steps of 1 sequence
    assert counts.round_flops(m, seq, [(4, 8, 1, 0, 1)]) == 8 * by_hand
    assert counts.round_tokens(seq, [(4, 8, 1, 0, 1)]) == 32_768
