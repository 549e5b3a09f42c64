"""``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one process, one chip, one cell.

1. refuse to run unless JAX's devices are TPUs, as many as the cell asks;
2. keep the persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
   points, else at ``<checkout>/.chipbench/jax_cache``;
3. build the cell's model, weights (on the device, from the seed), data,
   fleet and ``FederatedEngine``;
4. warm up, then measure whole rounds for ``--seconds`` (one engine run);
5. read the peak memory, free the system's state, and follow the first
   rounds with the plain reference to decide ``correct``;
6. ``--trace 0`` prints the end-to-end metrics, ``--trace 1`` the
   per-layer ones read from a profiler trace of the window;
7. print each compared number beside its limit on stderr, and the
   result as the last line of stdout.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, Optional

from harness import check, counts, device, files, tracing
from harness.compile_log import CompileLog

STATE_DIR = os.path.join(files.CHECKOUT, ".chipbench")


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_cache(jax) -> str:
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(STATE_DIR, "jax_cache"))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class RunContext:
    """What a per-layer metric's reader may read."""

    def __init__(self, cell, sess, reduced, compile_in_window, peaks):
        self.cell = cell
        self.model = cell.model
        self.traffic = cell.traffic
        # the traced rounds at the head of the window
        self.cohorts = sess.window_cohorts(traced=True)
        self.rounds = len(self.cohorts)
        self.window_rounds = len(sess.window_cohorts())
        # the rest of the window, run with the profiler off
        rest = sess.window_cohorts()[self.rounds:]
        self.untraced_rounds = len(rest)
        self.untraced_s = sess.untraced_s if rest else 0.0
        self.untraced_flops = sum(counts.round_flops(
            cell.model, cell.traffic["seq_len"], c) for c in rest)
        self.trace = reduced
        self.compile_in_window = compile_in_window
        self.peaks = peaks
        self.shapes = sess.shapes

    def mask(self, k: int):
        return check.reference_module(self.cell.config).trainable_mask(
            self.shapes, self.model, k)


def load_reader(name: str):
    path = os.path.join(files.BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(cell, sess, setup_s: float, peak: int) -> Dict:
    tokens = sum(counts.round_tokens(cell.traffic["seq_len"], c)
                 for c in sess.window_cohorts())
    values = {"setup_s": setup_s,
              "client_tokens_per_s": tokens / sess.window_s,
              "round_s": sess.window_s / len(sess.window_cohorts()),
              "peak_hbm_gb": peak / 1e9}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def main(argv=None, t_process: Optional[float] = None,
         require_chip: bool = True) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    cell = files.cell(args.workload)
    import jax
    devs = (device.require_accelerator(jax, cell.chips) if require_chip
            else jax.devices()[:cell.chips])
    desc = device.describe(devs)
    peaks = files.peaks(desc["kind"]) if require_chip else None
    setup_cache(jax)
    clog = CompileLog(jax)

    from harness.session import Session
    trace_dir = (os.path.join(STATE_DIR, "trace", f"{cell.name}-{args.seed}")
                 if args.trace else None)
    t_build = time.perf_counter()
    sess = Session(cell, args.seed, args.seconds, trace_dir, clog).build()
    times = {"imports": t_build - t_process,
             "build": time.perf_counter() - t_build}
    sess.run()
    setup_s = sess.t_open - t_process
    setup_compile = clog.since((0.0, 0, 0))
    setup_compile = {k: setup_compile[k] - clog.since(
        sess.compile_marks["open"])[k] for k in setup_compile}
    peak = device.peak_bytes(devs)
    opened, closed = (clog.since(sess.compile_marks[k])
                      for k in ("open", "close"))
    compile_in_window = {k: opened[k] - closed[k] for k in opened}
    attempted = sum(len(c) for c in sess.window_cohorts())
    train = sess.data.train
    w0_maker = sess.initial_weights
    sess.free()

    t_ref = time.perf_counter()
    w0 = w0_maker()
    ok, table, readings = check.check(cell, args.seed, train, sess.capture,
                                      w0, rounds=True)
    del w0
    times["reference"] = time.perf_counter() - t_ref

    result = {"correct": ok, "attempted": attempted, "failed": 0}
    if args.trace:
        path = tracing.xplane_file(trace_dir)
        t_read = time.perf_counter()
        reduced = tracing.reduce(tracing.load(path))
        times["trace_read"] = time.perf_counter() - t_read
        times["trace_mb"] = os.path.getsize(path) / 1e6
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = RunContext(cell, sess, reduced, compile_in_window, peaks)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = {**desc, "memory_peak_bytes": peak,
                            "busy_s": reduced.busy_s,
                            "window_s": reduced.window_s}
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = end_to_end(cell, sess, setup_s, peak)
        result["device"] = {**desc, "memory_peak_bytes": peak}
    result["checks"] = {k: {"value": float(v["value"]),
                            "limit": float(v["limit"])}
                        for k, v in table.items()}
    ends = sess.window.round_ends
    warm = [b - a for a, b in zip([sess.t_build_done] + ends, ends)][
        :cell.traffic["warmup_rounds"]]
    window = [b - a for a, b in zip(ends, ends[1:])][
        cell.traffic["warmup_rounds"] - 1:]
    print(f"timing {json.dumps(times)} warmup_rounds_s {warm} "
          f"window_rounds_s {window} "
          f"setup_compile {json.dumps(setup_compile)} "
          f"window_compile {json.dumps(compile_in_window)}", file=sys.stderr)
    print(f"readings {json.dumps(readings)}", file=sys.stderr)
    for name, row in table.items():
        print(f"check {name} {float(row['value'])!r} limit "
              f"{float(row['limit'])!r}",
              file=sys.stderr)
    print(f"correct {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
