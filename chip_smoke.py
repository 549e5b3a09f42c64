"""Smoke run of the federated round on one TPU chip. Not a benchmark.

    python chip_smoke.py [--out DIR]

Runs in one process, which holds the chip, at the paper's full char-LM
configuration (``configs/charlm_shakespeare.py``: 6 layers, d=192, 16
clients, 6 per round, s=40, b=32, seq_len=32):

1. refuses to run unless JAX's first device is a TPU (no CPU fallback);
2. places the persistent compile cache (``repro.launch.compile_cache``);
3. FedAvg and CAFL-L, 3 rounds each, through ``repro.launch.train.main``
   with the batched executor;
4. one CAFL-L phase through ``FederatedEngine`` with the top-k wire
   format (``fl.wire_topk=64``) and the ``masked`` aggregator, so the
   top-k and limb-fold kernels run compiled;
5. checks that some CAFL-L round shipped at ``q>0``, that the lowered
   wire program holds a Pallas kernel (``tpu_custom_call``), that every
   wire kernel equals its ``kernels/ref.py`` twin bit for bit on real
   client deltas, and that every loss is finite.

It prints compile seconds, round seconds, the knob trajectory and peak
device memory, writes them to ``DIR/smoke.json``, and ends with one
JSON line ``{"ok": true, "device": {...}}``. Any failed phase raises,
and the process exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ROUNDS = 3
TOPK = 64
COHORT_SEED = 1234


class CompileLog:
    """Backend compiles and persistent-cache hits, from jax.monitoring.

    A cache hit still reports a (short) backend-compile duration: the
    time it took to load the executable."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def state(self):
        return self.seconds, self.count, self.cache_hits

    def since(self, start):
        s, c, h = start
        return {"compile_seconds": self.seconds - s,
                "compiles": self.count - c,
                "cache_hits": self.cache_hits - h}


def require(ok, what):
    """A failed check ends the run (an assert would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu(jax):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found (JAX's first device is "
                 f"{dev.platform!r}); this smoke run needs one TPU chip and "
                 f"does not fall back to the CPU")
    return dev


def round_rows(history):
    return [{"round": r["round"], "seconds": r["seconds"],
             "val_loss": r["val_loss"], "train_loss": r["train_loss"],
             "knobs": r["knobs"]} for r in history]


def print_rounds(label, rows):
    for r in rows:
        kn = r["knobs"]
        knobs = (f"k={kn['k']} s={kn['s']} b={kn['b']} q={kn['q']} "
                 f"ga={kn['grad_accum']}" if kn else "no cohort")
        print(f"[smoke] {label} round {r['round']}: {r['seconds']:.3f}s "
              f"val={r['val_loss']:.4f} train={r['train_loss']:.4f} "
              f"{knobs}")


def phase_train(method, out, clog):
    """FedAvg or CAFL-L through the production launcher."""
    from repro.launch import train
    start = clog.state()
    prefix = os.path.join(out, "fl")
    train.main(["--method", method, "--executor", "batched",
                "--rounds", str(ROUNDS), "--out", prefix, "--quiet"])
    with open(f"{prefix}_{method}.json") as f:
        rows = round_rows(json.load(f)["history"])
    print_rounds(method, rows)
    return {"rounds": rows, **clog.since(start)}


def phase_masked_topk(clog):
    """CAFL-L with the sparse wire format and the masked aggregator;
    keeps the first round's client deltas (q=0 there, so uncompressed)
    for the kernel-vs-ref checks."""
    from repro.configs import get_config, get_fl_config
    from repro.data import load_corpus
    from repro.fl import FederatedEngine
    from repro.fl.aggregator import MaskedSumAggregator
    from repro.models import build

    class FirstCohort(MaskedSumAggregator):
        def __init__(self):
            super().__init__()
            self.deltas = []

        def submit(self, report):
            if report.round_trained == 1:
                self.deltas.append(report.delta)
            return super().submit(report)

    start = clog.state()
    ds = load_corpus()
    cfg = get_config("charlm-shakespeare")
    cfg = cfg.replace(vocab_size=max(cfg.vocab_size, ds.vocab_size))
    fl = get_fl_config().replace(executor="batched", rounds=ROUNDS,
                                 wire_topk=TOPK)
    agg = FirstCohort()
    result = FederatedEngine(build(cfg), fl, ds, strategy="cafl",
                             aggregator=agg).run()
    rows = round_rows([dataclasses.asdict(r) for r in result.history])
    print_rounds(f"cafl+masked+top{TOPK}", rows)
    return {"rounds": rows, **clog.since(start)}, agg.deltas


def flat(tree):
    import jax
    import jax.numpy as jnp
    return jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(tree)])


def on_both_backends(fn):
    """fn() with the dispatch's own choice (the kernel, on the chip),
    then pinned to the pure-jnp twins."""
    import numpy as np
    from repro.kernels import ops
    require(ops.FORCE_BACKEND is None, "FORCE_BACKEND is pinned")
    try:
        kernel = fn()
        ops.FORCE_BACKEND = "ref"
        ref = fn()
    finally:
        ops.FORCE_BACKEND = None
    as_np = lambda t: [None if a is None else np.asarray(a) for a in t]
    return as_np(kernel), as_np(ref)


def check_kernels(deltas):
    """Every wire kernel equals its ref twin bit for bit on real client
    deltas; returns the number of elements compared per kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    require(deltas, "no client delta was captured in round 1")
    x = flat(deltas[0])
    checked = {}

    def same(name, kernel, ref, n=x.size):
        for i, (a, b) in enumerate(zip(kernel, ref)):
            require((a is None) == (b is None), f"{name}[{i}]: format differs")
            if a is not None:
                require(a.dtype == b.dtype and a.shape == b.shape,
                        f"{name}[{i}]: dtype or shape differs")
                require(np.array_equal(a, b),
                        f"{name}[{i}]: kernel differs from its ref twin")
        checked[name] = int(n)

    for bits, topk in ((8, None), (2, None), (2, TOPK)):
        name = f"quantize_wire(bits={bits}, topk={topk})"
        k, r = on_both_backends(
            lambda: ops.quantize_wire(x, bits=bits, topk=topk)[:3])
        same(name, k, r)
        codes, scales = jnp.asarray(k[0]), jnp.asarray(k[1])
        k, r = on_both_backends(
            lambda: (ops.dequantize_blocks(codes, scales),))
        same(f"dequantize_blocks(bits={bits}, topk={topk})", k, r)

    # top-k on tie-heavy blocks: few magnitudes, +/- pairs, and so few
    # normal values per block that the k-th pick falls among zeros and
    # denormals (the kernel keys denormals as zero, like the twin's
    # flushed float compares)
    rng = np.random.default_rng(COHORT_SEED)
    levels = np.float32([0.0, 1e-45, 1e-38, 0.5, -0.5, 1.0, -1.0, 3e38])
    adv = jnp.asarray(rng.choice(levels, size=64 * 256,
                                 p=[0.3] * 3 + [0.02] * 5))
    k, r = on_both_backends(
        lambda: ops.quantize_wire(adv, bits=2, topk=TOPK)[:3])
    same(f"quantize_wire(bits=2, topk={TOPK}, ties)", k, r, adv.size)

    # the secure-aggregation fold over the cohort's fixed-point deltas,
    # masked with seeded uniform uint64 so every limb bit is exercised
    fixed = np.stack([np.rint(np.asarray(flat(d), np.float64) * 2.0 ** 32)
                      .astype(np.int64).view(np.uint64) for d in deltas])
    vals = fixed + rng.integers(0, 2 ** 64, size=fixed.shape, dtype=np.uint64)
    hi, lo = ops.split_limbs(vals)
    k, r = on_both_backends(lambda: ops.masked_sum(hi, lo))
    same(f"masked_sum(C={len(deltas)})", k, r)
    require(np.array_equal(ops.merge_limbs(*k), np.add.reduce(vals, axis=0)),
            "masked_sum differs from the native uint64 sum")

    check_lowering(x, jnp.asarray(hi), jnp.asarray(lo))
    return checked


def check_lowering(x, hi, lo):
    """The wire programs the chip runs hold the Pallas kernels."""
    import jax
    from repro.kernels import ops
    for name, fn, args in (
            ("wire_topk", lambda t: ops.quantize_dequantize(
                t, bits=2, topk=TOPK), (x,)),
            ("masked_sum", ops.masked_sum, (hi, lo))):
        text = jax.jit(fn).lower(*args).as_text()
        require("tpu_custom_call" in text,
                f"lowered {name} program holds no Pallas kernel")


def check_losses(phases):
    for label, phase in phases.items():
        for r in phase["rounds"]:
            for key in ("val_loss", "train_loss"):
                require(math.isfinite(r[key]),
                        f"{label} round {r['round']}: {key}={r[key]}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "smoke_out"),
                    help="output directory (git ignores the default)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    dev = require_tpu(jax)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    clog = CompileLog(jax)
    os.makedirs(args.out, exist_ok=True)
    print(f"[smoke] smoke run, not a benchmark: {dev.device_kind} "
          f"x{len(jax.devices())}, compile cache {cache_dir}")

    phases = {m: phase_train(m, args.out, clog) for m in ("fedavg", "cafl")}
    phases["cafl_masked_topk"], deltas = phase_masked_topk(clog)

    for label in ("cafl", "cafl_masked_topk"):
        qs = [r["knobs"]["q"] for r in phases[label]["rounds"] if r["knobs"]]
        require(any(q > 0 for q in qs), f"{label}: no round shipped at q>0")
    checked = check_kernels(deltas)
    check_losses(phases)

    stats = dev.memory_stats() or {}
    summary = {
        "note": "smoke run, not a benchmark",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "compile_cache": cache_dir,
        "phases": phases,
        "kernels_equal_ref": checked,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "wall_seconds": time.perf_counter() - t_start,
    }
    with open(os.path.join(args.out, "smoke.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for label, phase in phases.items():
        secs = [r["seconds"] for r in phase["rounds"]]
        print(f"[smoke] {label}: compile {phase['compile_seconds']:.3f}s "
              f"over {phase['compiles']} compiles "
              f"({phase['cache_hits']} cache hits); round seconds {secs}")
    for name, n in checked.items():
        print(f"[smoke] {name}: kernel == ref on {n} elements")
    print(f"[smoke] peak device memory {summary['peak_bytes_in_use']} B; "
          f"wall {summary['wall_seconds']:.3f}s")
    print(json.dumps({"ok": True, "device": summary["device"]}))


if __name__ == "__main__":
    main()
