"""One cell's engine, built from its files and the seed, and run once:
warm-up rounds, then the measured window, in a single ``engine.run``."""
from __future__ import annotations

import os
import shutil
import time
from typing import Optional

import jax

from harness import traffic, weights
from harness.window import (Capture, Ledger, Spans, SpannedExecutor, StopRun,
                            Window)


#: a traced run keeps the profiler on for the window's first rounds,
#: up to the first round boundary past this many seconds
TRACE_SECONDS = 4.0


class Session:
    def __init__(self, cell, seed: int, seconds: Optional[float],
                 trace_dir: Optional[str] = None, compile_log=None):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.compile_log = compile_log
        self.t_open = self.t_close = self.t_trace_end = None
        self.t_untraced_from = None
        self.traced_rounds = 0
        self.compile_marks = {}

    # -- build -------------------------------------------------------------
    def build(self) -> "Session":
        from repro.configs.base import ModelConfig
        from repro.fl import FederatedEngine
        from repro.fl.executor import make_executor
        from repro.models import build

        t = self.cell.traffic
        if t["warmup_rounds"] < t["check_rounds"]:
            raise SystemExit("chipbench: warm-up must cover the checked rounds")
        self.model_cfg = ModelConfig(**self.cell.model)
        self.model = build(self.model_cfg)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.data = traffic.dataset(t, self.model_cfg.vocab_size)
        fl = traffic.fl_config(t, self.seed)
        self.spans = Spans()
        self.ledger = Ledger()
        self.capture = Capture(t["check_rounds"])
        self.window = Window(t["warmup_rounds"], self.seconds,
                             on_open=self._open, on_close=self._close,
                             on_round=self._round)
        spans = self.spans
        self.engine = FederatedEngine(
            self.model, fl, self.data, strategy=t["strategy"],
            executor=lambda runner: SpannedExecutor(
                make_executor(t["executor"], runner), spans),
            aggregator=t["aggregator"],
            callbacks=[self.spans, self.ledger, self.capture, self.window],
            init_duals=traffic.init_duals(t))
        return self

    def initial_weights(self):
        return weights.make_weights(self.shapes, self.seed)

    # -- run ---------------------------------------------------------------
    def _open(self):
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self.spans.on = True
        if self.compile_log is not None:
            self.compile_marks["open"] = self.compile_log.mark()
        self.t_open = time.perf_counter()

    def _trace_off(self, rnd: int) -> None:
        if self.spans.on:
            jax.block_until_ready(self.engine.params)
            self.t_trace_end = time.perf_counter()
            self.traced_rounds = rnd - self.window.warmup
            self.spans.close()
            self.spans.on = False
            jax.profiler.stop_trace()
            # writing the trace out takes host seconds in which nothing
            # runs: the untraced rounds start after it, and the window
            # is lengthened by it so that as many of them run
            self.t_untraced_from = time.perf_counter()
            self.window.seconds += self.t_untraced_from - self.t_trace_end

    def _round(self, rnd: int, elapsed: float) -> None:
        if elapsed >= TRACE_SECONDS:
            self._trace_off(rnd)

    def _close(self):
        self.t_close = time.perf_counter()
        if self.compile_log is not None:
            self.compile_marks["close"] = self.compile_log.mark()
        self._trace_off(self.window.warmup + self.window.rounds)

    def run(self) -> None:
        w0 = jax.block_until_ready(self.initial_weights())
        self.t_build_done = time.perf_counter()
        try:
            self.engine.run(rounds=10 ** 9, init_params=w0)
        except StopRun:
            pass
        else:
            raise RuntimeError("the engine ended before the window closed")

    def free(self) -> None:
        """Drop the system's state so the reference has the chip."""
        self.engine = None
        self.model = None

    # -- what the window saw -----------------------------------------------
    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def untraced_s(self) -> float:
        """Seconds of the window's rounds after the profiler stopped."""
        return self.t_close - self.t_untraced_from

    def window_cohorts(self, traced: bool = False):
        """Per window round (or per traced round): the knob tuple of each
        client trained."""
        rounds = self.window.window_rounds
        if traced:
            rounds = rounds[:self.traced_rounds]
        out = []
        for rnd in rounds:
            out.append([(kn.k, kn.s, kn.b, kn.q, kn.grad_accum)
                        for _cid, kn in self.ledger.rounds.get(rnd, [])])
        return out
