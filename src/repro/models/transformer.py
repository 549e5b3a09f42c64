"""Decoder stack: block specs, scan-over-units layout, train/prefill/decode.

The layer stack is organised as ``prefix | scanned units | suffix``:
* ``prefix`` — leading non-uniform layers (e.g. DeepSeek's 3 dense layers);
* ``units`` — the architecture's repeating pattern (e.g. gemma2's
  (local, global), RecurrentGemma's (rec, rec, attn), xLSTM's 7xmLSTM+sLSTM)
  stacked along a leading axis and driven by ``jax.lax.scan`` — this keeps
  the HLO size O(pattern) instead of O(layers), which is what makes the
  512-virtual-device dry-run compile on one CPU core;
* ``suffix`` — remainder layers when the pattern does not divide the depth
  (RecurrentGemma-2B: 26 = 8*(rec,rec,attn) + (rec, rec)).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rg
from repro.models import ssm


class BlockSpec(NamedTuple):
    kind: str                  # attn | rec | mlstm | slstm
    window: Optional[int]      # attention window (None = global)
    use_moe: bool


def block_spec(cfg: ModelConfig, i: int) -> BlockSpec:
    kind = cfg.layer_kind(i)
    window = None
    if kind == "attn":
        window = cfg.window if cfg.attn_type(i) == "local" else None
    use_moe = (cfg.moe is not None and kind == "attn"
               and i >= (cfg.moe.first_dense_layers if cfg.moe else 0))
    return BlockSpec(kind, window, use_moe)


def stack_plan(cfg: ModelConfig):
    """-> (prefix_specs, unit_specs, n_units, suffix_specs)."""
    n_prefix = cfg.moe.first_dense_layers if cfg.moe else 0
    pat = len(cfg.block_pattern) if cfg.block_pattern else 1
    pat = _lcm(pat, len(cfg.attn_pattern))
    body = cfg.num_layers - n_prefix
    n_units = body // pat
    n_suffix = body % pat
    specs = [block_spec(cfg, i) for i in range(cfg.num_layers)]
    prefix = specs[:n_prefix]
    unit = specs[n_prefix:n_prefix + pat]
    suffix = specs[cfg.num_layers - n_suffix:] if n_suffix else []
    # all units must share the spec sequence for scan-stacking
    for u in range(n_units):
        got = specs[n_prefix + u * pat: n_prefix + (u + 1) * pat]
        assert got == unit, f"non-uniform unit {u}: {got} != {unit}"
    return prefix, unit, n_units, suffix


def _lcm(a, b):
    import math
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def block_init(rng, cfg: ModelConfig, spec: BlockSpec):
    r = jax.random.split(rng, 4)
    p = {"ln1": L.norm_init(cfg)}
    if spec.kind == "attn":
        p["attn"] = L.mla_init(r[0], cfg) if cfg.mla else L.attn_init(r[0], cfg)
        p["ln2"] = L.norm_init(cfg)
        if spec.use_moe:
            p["ffn"] = moe_lib.moe_init(r[1], cfg)
        elif cfg.mlp_type != "none":
            d_ff = cfg.d_ff
            if cfg.moe and cfg.moe.first_dense_layers and cfg.moe.d_ff_dense:
                d_ff = cfg.moe.d_ff_dense
            p["ffn"] = L.mlp_init(r[1], cfg, d_ff=d_ff)
        if cfg.post_norms:
            p["post1"] = L.norm_init(cfg)
            p["post2"] = L.norm_init(cfg)
    elif spec.kind == "rec":
        p["rec"] = rg.rglru_init(r[0], cfg)
        p["ln2"] = L.norm_init(cfg)
        p["ffn"] = L.mlp_init(r[1], cfg)
    elif spec.kind == "mlstm":
        p["mlstm"] = ssm.mlstm_init(r[0], cfg)
    elif spec.kind == "slstm":
        p["slstm"] = ssm.slstm_init(r[0], cfg)
    else:
        raise ValueError(spec.kind)
    return p


def _ffn(p, x, cfg, spec):
    if spec.use_moe:
        return moe_lib.moe_apply(p["ffn"], x, cfg)
    if cfg.mlp_type == "none" or "ffn" not in p:
        return jnp.zeros_like(x), 0.0
    return L.mlp_apply(p["ffn"], x, cfg), 0.0


def block_apply_full(p, x, positions, cfg: ModelConfig, spec: BlockSpec,
                     want_cache: bool):
    """-> (x, cache_entry_or_None, aux_loss)."""
    aux = 0.0
    if spec.kind == "attn":
        h = L.norm_apply(p["ln1"], x, cfg)
        # scope names reach the device trace's op metadata only
        with jax.named_scope("attention"):
            if cfg.mla:
                a, (c_kv, k_rope) = L.mla_apply_full(p["attn"], h, positions,
                                                     cfg)
            else:
                a, (k, v) = L.attn_apply_full(p["attn"], h, positions, cfg,
                                              window=spec.window)
        if cfg.post_norms:
            a = L.norm_apply(p["post1"], a, cfg)
        x = x + a
        h = L.norm_apply(p["ln2"], x, cfg)
        with jax.named_scope("mlp"):
            f, aux = _ffn(p, h, cfg, spec)
        if cfg.post_norms:
            f = L.norm_apply(p["post2"], f, cfg)
        x = x + f
        cache = None
        if want_cache:
            if cfg.mla:
                cache = {"c_kv": c_kv, "k_rope": k_rope}
            else:
                cache = {"k": k, "v": v}
        return x, cache, aux
    if spec.kind == "rec":
        h = L.norm_apply(p["ln1"], x, cfg)
        a, rec_cache = rg.rglru_apply_full(p["rec"], h, cfg)
        x = x + a
        h = L.norm_apply(p["ln2"], x, cfg)
        f, _ = _ffn(p, h, cfg, spec)
        x = x + f
        return x, (rec_cache if want_cache else None), aux
    if spec.kind == "mlstm":
        h = L.norm_apply(p["ln1"], x, cfg)
        a, ml_cache = ssm.mlstm_apply_full(p["mlstm"], h, cfg)
        return x + a, (ml_cache if want_cache else None), aux
    if spec.kind == "slstm":
        h = L.norm_apply(p["ln1"], x, cfg)
        a, st = ssm.slstm_apply_full(p["slstm"], h, cfg)
        cache = ({"h": st[0], "c": st[1], "n": st[2], "m": st[3]}
                 if want_cache else None)
        return x + a, cache, aux
    raise ValueError(spec.kind)


def block_cache_init(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     ctx_len: int, use_decode_window: bool):
    if spec.kind == "attn":
        s_buf = ctx_len
        if spec.window is not None:
            s_buf = min(s_buf, spec.window)
        elif use_decode_window and cfg.decode_window:
            s_buf = min(s_buf, cfg.decode_window)
        if cfg.mla:
            return L.mla_cache_init(cfg, batch, s_buf)
        return L.attn_cache_init(cfg, batch, s_buf)
    if spec.kind == "rec":
        return rg.rglru_cache_init(cfg, batch)
    if spec.kind == "mlstm":
        return ssm.mlstm_cache_init(cfg, batch)
    if spec.kind == "slstm":
        return ssm.slstm_cache_init(cfg, batch)
    raise ValueError(spec.kind)


def block_apply_decode(p, x, cache, cfg: ModelConfig, spec: BlockSpec):
    if spec.kind == "attn":
        h = L.norm_apply(p["ln1"], x, cfg)
        window = spec.window
        if window is None and cfg.decode_window and cache_buf_len(cache) <= cfg.decode_window:
            # rolling global cache acts as a sliding window (long_500k variant)
            window = None
        if cfg.mla:
            a, cache = L.mla_apply_decode(p["attn"], h, cache, cfg)
        else:
            a, cache = L.attn_apply_decode(p["attn"], h, cache, cfg, window=window)
        if cfg.post_norms:
            a = L.norm_apply(p["post1"], a, cfg)
        x = x + a
        h = L.norm_apply(p["ln2"], x, cfg)
        f, _ = _ffn(p, h, cfg, spec)
        if cfg.post_norms:
            f = L.norm_apply(p["post2"], f, cfg)
        return x + f, cache
    if spec.kind == "rec":
        h = L.norm_apply(p["ln1"], x, cfg)
        a, cache = rg.rglru_apply_decode(p["rec"], h, cache, cfg)
        x = x + a
        h = L.norm_apply(p["ln2"], x, cfg)
        f, _ = _ffn(p, h, cfg, spec)
        return x + f, cache
    if spec.kind == "mlstm":
        h = L.norm_apply(p["ln1"], x, cfg)
        a, cache = ssm.mlstm_apply_decode(p["mlstm"], h, cache, cfg)
        return x + a, cache
    if spec.kind == "slstm":
        h = L.norm_apply(p["ln1"], x, cfg)
        a, cache = ssm.slstm_apply_decode(p["slstm"], h, cache, cfg)
        return x + a, cache
    raise ValueError(spec.kind)


def cache_buf_len(cache) -> int:
    for key in ("k", "c_kv"):
        if key in cache:
            return cache[key].shape[1]
    return 0


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ModelConfig):
    prefix, unit, n_units, suffix = stack_plan(cfg)
    rngs = jax.random.split(rng, 3)
    params = {}
    if prefix:
        rp = jax.random.split(rngs[0], len(prefix))
        params["prefix"] = [block_init(rp[i], cfg, s) for i, s in enumerate(prefix)]
    if n_units:
        def unit_init(r):
            rs = jax.random.split(r, len(unit))
            return {f"b{j}": block_init(rs[j], cfg, s)
                    for j, s in enumerate(unit)}
        params["units"] = jax.vmap(unit_init)(jax.random.split(rngs[1], n_units))
    if suffix:
        rs = jax.random.split(rngs[2], len(suffix))
        params["suffix"] = [block_init(rs[i], cfg, s) for i, s in enumerate(suffix)]
    return params


def stack_apply_full(params, x, positions, cfg: ModelConfig,
                     want_cache: bool = False, remat: bool = True):
    """-> (x, caches, aux). caches = {prefix: [...], units: stacked, suffix: [...]}"""
    prefix, unit, n_units, suffix = stack_plan(cfg)
    caches = {"prefix": [], "suffix": []}
    aux_total = 0.0
    for p, s in zip(params.get("prefix", []), prefix):
        x, c, aux = block_apply_full(p, x, positions, cfg, s, want_cache)
        caches["prefix"].append(c)
        aux_total += aux
    if n_units:
        def body(carry, unit_params):
            h, aux_acc = carry
            unit_caches = {}
            for j, s in enumerate(unit):
                h, c, aux = block_apply_full(unit_params[f"b{j}"], h, positions,
                                             cfg, s, want_cache)
                if want_cache:
                    unit_caches[f"b{j}"] = c
            return (h, aux_acc + aux), unit_caches

        body_fn = jax.checkpoint(body) if remat else body
        (x, aux_total), unit_caches = jax.lax.scan(
            body_fn, (x, aux_total), params["units"])
        caches["units"] = unit_caches if want_cache else None
    for p, s in zip(params.get("suffix", []), suffix):
        x, c, aux = block_apply_full(p, x, positions, cfg, s, want_cache)
        caches["suffix"].append(c)
        aux_total += aux
    return x, (caches if want_cache else None), aux_total


def stack_apply_decode(params, x, caches, cfg: ModelConfig):
    prefix, unit, n_units, suffix = stack_plan(cfg)
    new_caches = {"prefix": [], "suffix": []}
    for p, s, c in zip(params.get("prefix", []), prefix, caches.get("prefix", [])):
        x, c = block_apply_decode(p, x, c, cfg, s)
        new_caches["prefix"].append(c)
    if n_units:
        def body(h, xs):
            unit_params, unit_cache = xs
            new_unit_cache = {}
            for j, s in enumerate(unit):
                h, nc = block_apply_decode(unit_params[f"b{j}"], h,
                                           unit_cache[f"b{j}"], cfg, s)
                new_unit_cache[f"b{j}"] = nc
            return h, new_unit_cache

        x, unit_caches = jax.lax.scan(body, x, (params["units"], caches["units"]))
        new_caches["units"] = unit_caches
    for p, s, c in zip(params.get("suffix", []), suffix, caches.get("suffix", [])):
        x, c = block_apply_decode(p, x, c, cfg, s)
        new_caches["suffix"].append(c)
    return x, new_caches


def stack_cache_init(cfg: ModelConfig, batch: int, ctx_len: int,
                     use_decode_window: bool = False):
    prefix, unit, n_units, suffix = stack_plan(cfg)
    caches = {"prefix": [block_cache_init(cfg, s, batch, ctx_len, use_decode_window)
                         for s in prefix],
              "suffix": [block_cache_init(cfg, s, batch, ctx_len, use_decode_window)
                         for s in suffix]}
    if n_units:
        unit_cache = {f"b{j}": block_cache_init(cfg, s, batch, ctx_len,
                                                use_decode_window)
                      for j, s in enumerate(unit)}
        caches["units"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_units,) + a.shape).copy(),
            unit_cache)
    return caches


def caches_from_prefill(cfg: ModelConfig, full_caches, ctx_len: int,
                        use_decode_window: bool, max_new_tokens: int = 0):
    """Convert prefill (k,v per layer over S) into rolling decode caches."""
    prefix, unit, n_units, suffix = stack_plan(cfg)

    def convert(spec, c):
        if c is None:
            return None
        if spec.kind == "attn":
            s_buf = ctx_len + max_new_tokens
            if spec.window is not None:
                s_buf = min(s_buf, spec.window)
            elif use_decode_window and cfg.decode_window:
                s_buf = min(s_buf, cfg.decode_window)
            if cfg.mla:
                kv = L.attn_cache_from_full(c["c_kv"][..., None, :],
                                            c["k_rope"][..., None, :], s_buf)
                return {"c_kv": kv["k"][..., 0, :], "k_rope": kv["v"][..., 0, :],
                        "index": kv["index"]}
            return L.attn_cache_from_full(c["k"], c["v"], s_buf)
        return c  # rec/mlstm/slstm caches already decode-ready

    out = {"prefix": [convert(s, c) for s, c in zip(prefix, full_caches["prefix"])],
           "suffix": [convert(s, c) for s, c in zip(suffix, full_caches["suffix"])]}
    if n_units:
        def convert_unit(unit_caches):
            return {f"b{j}": convert(s, unit_caches[f"b{j}"])
                    for j, s in enumerate(unit)}
        out["units"] = jax.vmap(convert_unit)(full_caches["units"])
    return out
