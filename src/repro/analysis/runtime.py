"""Runtime sanitizers: transfer guards and a jit recompile watcher.

The static rules can't see dynamic behavior: a round loop that silently
bounces arrays host<->device, or a jit cache that misses every round
because a shape or static argument drifts. These opt-in contexts pin
both at test time:

``no_transfers()``            — ``jax.transfer_guard("disallow")`` as a
                                context manager: any *implicit* host
                                transfer inside raises (explicit
                                ``device_put`` / numpy-array ingestion
                                stays allowed).
``RecompileWatcher``          — counts XLA backend compiles via
                                ``jax.monitoring`` events; ``mark()``
                                buckets them (e.g. per round) so a test
                                can assert "zero after round 1".
``TransferGuardCallback``     — engine ``RoundCallback`` entering the
                                guard from ``from_round`` on (round 1
                                warms jit caches, masks and constants —
                                the steady state must be transfer-free).
``RecompileWatchCallback``    — engine ``RoundCallback`` recording the
                                compile count of every round.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator, List, Optional

import jax

from repro.fl.callbacks import RoundCallback

#: the jax.monitoring duration event XLA emits once per backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def no_transfers(level: str = "disallow") -> Iterator[None]:
    """Disallow implicit host<->device transfers inside the block."""
    with jax.transfer_guard(level):
        yield


# ---------------------------------------------------------------------------
# recompile watching
# ---------------------------------------------------------------------------

_COMPILES = 0
_LISTENER_INSTALLED = False


def _on_duration_event(name: str, *args, **kwargs) -> None:
    global _COMPILES
    if name == COMPILE_EVENT:
        _COMPILES += 1


def _install_listener() -> None:
    """Register the global compile listener once."""
    global _LISTENER_INSTALLED
    if not _LISTENER_INSTALLED:
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _LISTENER_INSTALLED = True


def compile_count() -> int:
    """Process-wide backend compiles observed so far (0 until a
    watcher installs the listener)."""
    return _COMPILES


class RecompileWatcher:
    """Counts jit cache misses (backend compiles) between marks.

    >>> w = RecompileWatcher()
    >>> with w:                     # doctest: +SKIP
    ...     step()                  # round 1: compiles
    ...     w.mark("round1")
    ...     step()                  # round 2: cache hit expected
    ...     w.mark("round2")
    >>> w.buckets                   # doctest: +SKIP
    {'round1': 2, 'round2': 0}
    """

    def __init__(self):
        _install_listener()
        self.buckets: Dict[str, int] = {}
        self._start: Optional[int] = None
        self._last: int = 0

    def __enter__(self) -> "RecompileWatcher":
        self._start = self._last = compile_count()
        return self

    def __exit__(self, *exc) -> None:
        pass

    def mark(self, label: str) -> int:
        """Close a bucket: compiles since the previous mark (or enter)."""
        now = compile_count()
        delta = now - self._last
        self._last = now
        self.buckets[label] = self.buckets.get(label, 0) + delta
        return delta

    @property
    def total(self) -> int:
        base = self._start if self._start is not None else 0
        return compile_count() - base


# ---------------------------------------------------------------------------
# engine callbacks
# ---------------------------------------------------------------------------


class RecompileWatchCallback(RoundCallback):
    """Records per-round backend-compile counts during an engine run.

    ``per_round[t]`` = compiles observed while round ``t`` executed
    (including its evaluation step). The steady-state pin asserts
    ``all(c == 0 for c in per_round values after round 1)``.
    """

    def __init__(self):
        self.watcher = RecompileWatcher()
        self.per_round: Dict[int, int] = {}
        self._round: Optional[int] = None

    def on_train_start(self, engine) -> None:
        self.watcher.__enter__()
        self._round = None

    def on_round_start(self, engine, rnd: int) -> None:
        if self._round is not None:
            self.per_round[self._round] = self.watcher.mark(
                f"round{self._round}")
        else:
            self.watcher.mark("setup")
        self._round = rnd

    def on_train_end(self, engine, result) -> None:
        if self._round is not None:
            self.per_round[self._round] = self.watcher.mark(
                f"round{self._round}")
            self._round = None

    def steady_state_compiles(self, first_steady_round: int = 2) -> int:
        return sum(c for t, c in self.per_round.items()
                   if t >= first_steady_round)


class TransferGuardCallback(RoundCallback):
    """Runs engine rounds >= ``from_round`` under the transfer guard.

    Round 1 stays unguarded: it legitimately materializes constants,
    freezing masks and jit executables. From ``from_round`` on, any
    implicit host<->device transfer raises — the steady-state round
    loop must live entirely on device + pre-staged host buffers.

    The guard is released at ``on_train_end``; ``close()`` is
    idempotent and should sit in a ``finally`` in tests so an engine
    exception can't leak the guard into later tests.
    """

    def __init__(self, from_round: int = 2, level: str = "disallow"):
        self.from_round = from_round
        self.level = level
        self.guarded_rounds: List[int] = []
        self._stack: Optional[contextlib.ExitStack] = None

    def on_round_start(self, engine, rnd: int) -> None:
        if self._stack is None and rnd >= self.from_round:
            self._stack = contextlib.ExitStack()
            self._stack.enter_context(jax.transfer_guard(self.level))
        if self._stack is not None:
            self.guarded_rounds.append(rnd)

    def on_train_end(self, engine, result) -> None:
        self.close()

    def close(self) -> None:
        if self._stack is not None:
            self._stack.close()
            self._stack = None
