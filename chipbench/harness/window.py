"""Round callbacks and the executor wrapper: the seams through which the
benchmark times, traces and stops one engine run without editing it.

``Spans``   names what the host is doing, as profiler annotations on
            the device trace's clock: ``round`` around each round and,
            inside it, ``eval_compose`` (evaluation and cohort
            composition), ``dispatch``, ``run_round`` (the executor:
            staging, LocalTrain, wire), ``aggregate`` (up to the server
            update), ``accounting`` (usage, duals) and ``tail``.
``Ledger``  every round's cohort and knobs as the server applied them.
``Capture`` what the check compares, over the rounds the reference
            follows (``check_rounds``) and the knobs of the round after.
``Window``  opens at the end of the last warm-up round and stops the
            run at the first round boundary past ``seconds``.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.callbacks import RoundCallback

SPAN_PREFIX = "chipbench."


class StopRun(Exception):
    """Raised from a callback to end the engine's run at a boundary."""


class Spans(RoundCallback):
    def __init__(self):
        self.on = False
        self._round = None
        self._phase = None

    def phase(self, name: Optional[str]) -> None:
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
            self._phase = None
        if name is not None and self.on:
            self._phase = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            self._phase.__enter__()

    def close(self) -> None:
        self.phase(None)
        if self._round is not None:
            self._round.__exit__(None, None, None)
            self._round = None

    def on_round_start(self, engine, rnd):
        if self.on:
            self._round = jax.profiler.TraceAnnotation(SPAN_PREFIX + "round")
            self._round.__enter__()
        self.phase("eval_compose")

    def on_round_composed(self, engine, plan):
        self.phase("dispatch")

    def on_server_update(self, engine, update):
        self.phase("accounting")

    def on_dual_update(self, engine, rnd, reports):
        self.phase("tail")

    def on_round_end(self, engine, record):
        self.close()


class SpannedExecutor:
    """Wraps the engine's executor: one span around ``run_round``."""

    def __init__(self, inner, spans: Spans):
        self.inner = inner
        self.spans = spans

    def run_round(self, params, assignments):
        self.spans.phase("run_round")
        out = self.inner.run_round(params, assignments)
        self.spans.phase("aggregate")
        return out


class Ledger(RoundCallback):
    def __init__(self):
        self.rounds: Dict[int, List] = {}

    def on_server_update(self, engine, update):
        self.rounds.setdefault(update.round, []).extend(
            (r.client.client_id, r.knobs) for r in update.reports)


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


def _knob_tuple(kn) -> tuple:
    return (kn.k, kn.s, kn.b, kn.q, kn.grad_accum)


class Capture(RoundCallback):
    """The system's answers over the rounds the reference follows."""

    def __init__(self, rounds: int):
        self.n = rounds
        self.sampled: Dict[int, List[int]] = {}
        self.knobs: Dict[int, Dict[int, tuple]] = {}
        self.losses: Dict[int, Dict[int, float]] = {}
        self.wire_mb: Dict[int, Dict[int, float]] = {}
        self.client_norms: Dict[int, Dict[int, np.ndarray]] = {}
        self.update_norms: Dict[int, np.ndarray] = {}
        self.duals: Dict[int, Dict[str, float]] = {}
        self.params = None            # host copy after round ``n``

    def on_round_composed(self, engine, plan):
        if plan.round <= self.n:
            self.sampled[plan.round] = [int(c) for c in plan.sampled]

    def on_server_update(self, engine, update):
        rnd = update.round
        if rnd > self.n + 1:
            return
        self.knobs[rnd] = {r.client.client_id: _knob_tuple(r.knobs)
                           for r in update.reports}
        if rnd > self.n:
            return
        self.losses[rnd] = {r.client.client_id: float(r.train_loss)
                            for r in update.reports}
        self.wire_mb[rnd] = {r.client.client_id: float(r.wire_mb_actual)
                             for r in update.reports}
        self.client_norms[rnd] = {r.client.client_id:
                                  np.asarray(_leaf_norms(r.delta))
                                  for r in update.reports}
        self.update_norms[rnd] = np.asarray(_leaf_norms(update.delta))

    def on_dual_update(self, engine, rnd, reports):
        if rnd <= self.n:
            (profile,) = reports.values()
            self.duals[rnd] = {c.name: float(c.lam) for c in profile}

    def on_round_end(self, engine, record):
        if record.round == self.n:
            self.params = jax.device_get(engine.params)


class Window(RoundCallback):
    """Warm-up, then the measured window, then a stop.

    ``seconds=None`` stops at the end of warm-up (the check alone)."""

    def __init__(self, warmup: int, seconds: Optional[float],
                 on_open: Callable[[], None] = lambda: None,
                 on_close: Callable[[], None] = lambda: None,
                 on_round: Callable[[int, float], None] = lambda r, s: None):
        self.warmup = warmup
        self.seconds = seconds
        self.on_open = on_open
        self.on_close = on_close
        self.on_round = on_round
        self.t_open = self.t_close = None
        self.rounds = 0
        self.round_ends: List[float] = []

    def on_round_end(self, engine, record):
        rnd = record.round
        self.round_ends.append(time.perf_counter())
        if rnd == self.warmup:
            jax.block_until_ready(engine.params)
            if self.seconds is None:
                raise StopRun
            self.on_open()
            self.t_open = time.perf_counter()
        elif rnd > self.warmup:
            elapsed = time.perf_counter() - self.t_open
            if elapsed < self.seconds:
                self.on_round(rnd, elapsed)
                return
            jax.block_until_ready(engine.params)
            self.t_close = time.perf_counter()
            self.rounds = rnd - self.warmup
            self.on_close()
            raise StopRun

    @property
    def window_rounds(self) -> range:
        return range(self.warmup + 1, self.warmup + 1 + self.rounds)
