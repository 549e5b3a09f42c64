"""The system's Nemotron decoder (Minitron-8B's equations) against the
plain reference ``chipbench/reference/nemotron.py`` on seeded weights
from ``chipbench/harness/weights.py``, at a small size on the CPU: the
training loss and the gradient of every leaf.

Cases: rotary over half of each head and over all of it, GQA 6:1 and
2:1, always the bias-free squared-ReLU MLP and LayerNorm eps 1e-5; one
case cuts the sequence into several attention chunks on both sides.
The cell's 6 heads, 1 KV head and 2,048 MLP columns are one of 8
Megatron shares: the shares' outputs sum to the uncut layer's.
The defaults of the three switches (``rope_fraction`` 1.0, ``mlp_bias``
True, ``norm_eps`` 1e-6) leave the older configurations as they were,
bit for bit.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
if CHIPBENCH not in sys.path:
    sys.path.append(CHIPBENCH)

from harness import weights  # noqa: E402
from reference import nemotron  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.models import build  # noqa: E402
from repro.models import layers as L  # noqa: E402

SMALL = dict(name="nemotron-small", family="dense", num_layers=2,
             d_model=64, num_heads=6, num_kv_heads=1, head_dim=16, d_ff=128,
             vocab_size=96, mlp_type="relu2", mlp_bias=False,
             norm_type="layer", norm_eps=1e-5, tie_embeddings=False,
             rope_theta=10000.0, rope_fraction=0.5, decode_window=None,
             max_seq_len=64, q_chunk=64, param_dtype="float32",
             compute_dtype="float32")

CASES = {
    "half-rotary-6to1": {},
    "full-rotary-6to1": {"rope_fraction": 1.0},
    "half-rotary-2to1": {"num_heads": 4, "num_kv_heads": 2},
    # several query chunks in the system, several blocks in the reference
    "half-rotary-6to1-chunked": {"q_chunk": 8},
}

#: Both sides compute in float32 (the reference at HIGHEST, the CPU's
#: float32 products are exact to float32 anyway); they differ only in
#: the order of their sums (blockwise online softmax against full rows,
#: chunked cross entropy, scan against an unrolled loop), a few ulps of
#: float32 (2**-24 ~ 6e-8) grown over sums of some hundred terms:
#: 1e-5 on the loss and 1e-4 of each leaf's gradient norm leave room
#: for that and none for a step of bfloat16 (2**-8 ~ 4e-3).
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


def _setup(overrides, seed, seq=32):
    cfg = {**SMALL, **overrides}
    model = build(ModelConfig(**cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_weights(shapes, seed)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, seq)),
                       jnp.int32)
    tgts = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, seq)),
                       jnp.int32)
    return cfg, model, params, toks, tgts


def _system(model, params, toks, tgts):
    f = lambda p: model.train_loss(p, {"tokens": toks, "targets": tgts})[0]
    return jax.value_and_grad(f)(params)


def _reference(cfg, params, toks, tgts, kind="reference"):
    prec = nemotron.precision(cfg, kind)
    p = jax.tree.map(lambda a: a.astype(prec.param), params)
    f = lambda p: nemotron.loss(p, toks, tgts, cfg, prec)
    loss, grads = jax.value_and_grad(f)(p)
    return loss, jax.tree.map(lambda g: g.astype(jnp.float32), grads)


def _gaps(sys_out, ref_out):
    """-> (loss gap over |loss|, worst leaf's gradient gap over the
    leaf's gradient norm)."""
    (ls, gs), (lr, gr) = sys_out, ref_out
    loss_gap = abs(float(ls) - float(lr)) / abs(float(lr))
    grad_gap = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                   for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gr)))
    return loss_gap, grad_gap


@pytest.mark.parametrize("case", sorted(CASES))
def test_system_matches_reference(case, monkeypatch):
    if case.endswith("chunked"):
        monkeypatch.setattr(nemotron, "Q_BLOCK", 8)
    cfg, model, params, toks, tgts = _setup(CASES[case], seed=2 ** 33 + 7)
    sys_out = _system(model, params, toks, tgts)
    loss_gap, grad_gap = _gaps(sys_out, _reference(cfg, params, toks, tgts))
    assert loss_gap <= LOSS_RTOL, loss_gap
    assert grad_gap <= GRAD_RTOL, grad_gap
    # every leaf moves: a leaf left out of the model would read 0
    assert all(float(jnp.linalg.norm(g)) > 0
               for g in jax.tree.leaves(sys_out[1]))


def test_bf16_control_fails_the_tolerances():
    """The reference one precision step down (bfloat16) in the system's
    place reads outside at least one tolerance."""
    cfg, model, params, toks, tgts = _setup({}, seed=11)
    ref = _reference(cfg, params, toks, tgts)
    loss_gap, grad_gap = _gaps(_reference(cfg, params, toks, tgts, "control"),
                               ref)
    assert loss_gap > LOSS_RTOL or grad_gap > GRAD_RTOL, (loss_gap, grad_gap)
    assert grad_gap > 10 * GRAD_RTOL


def test_tree_has_no_mlp_bias_and_the_published_split():
    cfg, _model, params, _t, _g = _setup({}, seed=0)
    ffn = params["stack"]["units"]["b0"]["ffn"]
    assert set(ffn) == {"w_up", "w_down"}
    attn = params["stack"]["units"]["b0"]["attn"]
    assert attn["wq"].shape[-1] == cfg["num_heads"] * cfg["head_dim"]
    assert attn["wk"].shape[-1] == cfg["num_kv_heads"] * cfg["head_dim"]
    assert "head" in params["io"]


# ---------------------------------------------------------------------------
# the cell's configuration is one chip's share of an uncut layer
# ---------------------------------------------------------------------------

#: An uncut layer at a CPU size with the published grouping (48 query
#: heads on 8 KV heads, half rotary, an MLP 8 shares wide), and the 8
#: Megatron shares of ``configs/minitron-8b-tp8.json``: share ``i`` holds
#: query heads ``6i..6i+5`` (the group of KV head ``i``), KV head ``i``,
#: the rows of ``wo`` those heads write, MLP columns ``i*f..(i+1)*f`` of
#: ``w_up`` and the matching rows of ``w_down``, and vocabulary rows
#: ``i*v..(i+1)*v`` of the head.
TP = 8
UNCUT = {**SMALL, "d_model": 32, "num_heads": 48, "num_kv_heads": 8,
         "head_dim": 8, "d_ff": TP * 16, "vocab_size": TP * 12}
SHARE = {**UNCUT, "num_heads": 6, "num_kv_heads": 1, "d_ff": 16,
         "vocab_size": 12}


def _shard(w, i, axis):
    n = w.shape[axis] // TP
    return jax.lax.slice_in_dim(w, i * n, (i + 1) * n, axis=axis)


def _share_of(attn, ffn, head, i):
    return ({"wq": _shard(attn["wq"], i, 1), "wk": _shard(attn["wk"], i, 1),
             "wv": _shard(attn["wv"], i, 1), "wo": _shard(attn["wo"], i, 0)},
            {"w_up": _shard(ffn["w_up"], i, 1),
             "w_down": _shard(ffn["w_down"], i, 0)},
            _shard(head, i, 1))


def _layer_parts(side, cfg_dict):
    """-> attention(p, h), mlp(p, h) of the system or the reference."""
    if side == "system":
        cfg = ModelConfig(**cfg_dict)
        pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
        return (lambda p, h: L.attn_apply_full(p, h, pos, cfg)[0],
                lambda p, h: L.mlp_apply(p, h, cfg))
    prec = nemotron.precision(cfg_dict)
    return (lambda p, h: nemotron._attention(p, h, cfg_dict, prec),
            lambda p, h: nemotron._mlp(p, h, cfg_dict, prec))


@pytest.mark.parametrize("side", ["system", "reference"])
def test_tp8_shares_sum_to_the_uncut_layer(side):
    """The 8 shares' attention and MLP outputs sum to the uncut layer's
    (the all-reduce that the cell leaves to the other 7 chips), and their
    head logits side by side are the uncut head's: the cell's 6 heads, 1
    KV head, 2,048 MLP columns and 32,000 vocabulary rows are a share of
    the published layer, not narrower widths."""
    shapes = jax.eval_shape(build(ModelConfig(**UNCUT)).init,
                            jax.random.PRNGKey(0))
    params = weights.make_weights(shapes, 2 ** 35 + 3)
    unit = jax.tree.map(lambda a: a[0], params["stack"]["units"]["b0"])
    attn, ffn, head = unit["attn"], unit["ffn"], params["io"]["head"]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(2, 16, 32)),
                    jnp.float32)
    attn_f, mlp_f = _layer_parts(side, UNCUT)
    share_attn_f, share_mlp_f = _layer_parts(side, SHARE)
    shares = [_share_of(attn, ffn, head, i) for i in range(TP)]
    gap = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    for whole, parts in (
            (attn_f(attn, h), [share_attn_f(a, h) for a, _f, _h in shares]),
            (mlp_f(ffn, h), [share_mlp_f(f, h) for _a, f, _h in shares])):
        # the same products summed in another order: float32 round-off
        assert gap(sum(parts), whole) < 1e-5
        # each share carries a part of it, none the whole
        assert all(float(jnp.linalg.norm(p)) < 0.9 * float(
            jnp.linalg.norm(whole)) for p in parts)
    logits = jnp.concatenate([h @ hd for _a, _f, hd in shares], -1)
    assert gap(logits, h @ head) < 1e-5


# ---------------------------------------------------------------------------
# the defaults leave the older configurations as they were
# ---------------------------------------------------------------------------


def _rope_before(x, positions, theta):
    """``layers.rope`` as it was before partial rotary."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _norm_before(p, x):
    """``layers.norm_apply`` (LayerNorm) as it was, eps 1e-6."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    return (out * p["scale"] + p["bias"]).astype(x.dtype)


def test_defaults_are_bit_for_bit_the_old_paths():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 4, 32)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    old = np.asarray(jax.jit(_rope_before, static_argnums=2)(x, pos, 1e4))
    for new in (jax.jit(L.rope, static_argnums=2)(x, pos, 1e4),
                jax.jit(L.rope, static_argnums=(2, 3))(x, pos, 1e4, 1.0)):
        np.testing.assert_array_equal(np.asarray(new), old)

    cfg = ModelConfig(name="d", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, head_dim=8, d_ff=64,
                      vocab_size=16, mlp_type="gelu", norm_type="layer")
    assert (cfg.rope_fraction, cfg.mlp_bias, cfg.norm_eps) == (1.0, True,
                                                               1e-6)
    p = {"scale": jnp.asarray(rng.normal(size=32), jnp.float32),
         "bias": jnp.asarray(rng.normal(size=32), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda p, h: L.norm_apply(p, h, cfg))(p, h)),
        np.asarray(jax.jit(_norm_before)(p, h)))

    ffn = L.mlp_init(jax.random.PRNGKey(0), cfg)
    assert set(ffn) == {"w_up", "b_up", "w_down", "b_down"}
    ffn = {**ffn, "b_up": ffn["b_up"] + 0.5, "b_down": ffn["b_down"] - 0.25}
    want = jax.nn.gelu(h @ ffn["w_up"] + ffn["b_up"]) @ ffn["w_down"] \
        + ffn["b_down"]
    np.testing.assert_array_equal(np.asarray(L.mlp_apply(ffn, h, cfg)),
                                  np.asarray(want))


def test_partial_rotary_leaves_the_rest_of_each_head():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    pos = jnp.arange(8)[None]
    out = L.rope(x, pos, 1e4, 0.5)
    np.testing.assert_array_equal(np.asarray(out[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_array_equal(np.asarray(out[..., :8]),
                                  np.asarray(L.rope(x[..., :8], pos, 1e4)))
    with pytest.raises(ValueError, match="rotary dims"):
        L.rope(x, pos, 1e4, 0.2)


def test_named_scopes_are_metadata_only(monkeypatch):
    """The decoder's and the executor's ``jax.named_scope``s change no
    instruction of the lowered LocalTrain, only names and metadata."""
    import contextlib
    import re

    from repro.configs import get_fl_config
    from repro.core.client import ClientRunner
    from repro.fl.executor import BatchedExecutor

    model = build(ModelConfig(**SMALL))
    runner = ClientRunner(model, get_fl_config(), data=None, resources=None)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mask, _ = runner.mask_for(params, 1)
    batch = jax.ShapeDtypeStruct((2, 2, 1, 2, 16), jnp.int32)

    def hlo():
        low = BatchedExecutor(runner)._batched.lower(
            params, mask, {"tokens": batch, "targets": batch})
        text = low.compiler_ir("hlo").as_hlo_text()
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        # instruction and computation names, numbered as they appear
        names = {}
        for m in re.finditer(r"^\s*(?:ROOT\s+)?([\w.\-]+)\s*=|"
                             r"^(?:ENTRY\s+)?([\w.\-]+)\s.*\{\s*$", text,
                             re.M):
            names.setdefault(m.group(1) or m.group(2), f"v{len(names)}")
        return re.sub(r"[\w.\-]+", lambda m: names.get(m.group(0),
                                                       m.group(0)), text)

    scoped = hlo()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert "dot(" in scoped and hlo() == scoped
