"""Host spans and counters of the federated round, for the profiler.

While a JAX profiler trace is recording (``jax.profiler.trace`` or
``jax.profiler.start_trace``), ``span(name)`` opens a
``jax.profiler.TraceAnnotation("repro." + name)``, so that the span
lands in the trace on the profiler's clock beside the device's
operations, and keeps ``(round, name, parent, start_ns, end_ns)`` in
memory, the parent being the enclosing open span. ``count(name, n)``
adds to the current round's counters. The profiler is the one switch:
an operator who records a trace gets the spans too, and off the
profiler a span is one ``is_enabled`` check and a shared no-op object.

Spans time the host. No span or counter waits for the device or reads
a device value, so the program overlaps with the device the same way
with the profiler on and off.

The record keeps the rounds whose ``round`` span opened while the
profiler was recording, the last ``MAX_ROUNDS`` of them: ``records()``
returns them, ``clear()`` empties it. Like the profiler, the record is
one per process.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "repro."
MAX_ROUNDS = 1000

_recording = TraceAnnotation.is_enabled


class Span(NamedTuple):
    round: int
    name: str
    parent: Optional[str]      # the enclosing span's name; None for ``round``
    start_ns: int
    end_ns: int


@dataclass
class Round:
    round: int
    spans: List[Span] = field(default_factory=list)
    counters: Dict[str, int] = field(default_factory=dict)
    complete: bool = False     # its ``round`` span has closed


class _Record:
    def __init__(self):
        self.rounds: Deque[Round] = deque(maxlen=MAX_ROUNDS)
        self.current: Optional[Round] = None
        self.open: List[str] = []      # names of the recorded spans open now


_RECORD = _Record()
_NO_SPAN = contextlib.nullcontext()


class _Span:
    __slots__ = ("name", "rnd", "annotation", "round", "parent", "start")

    def __init__(self, name: str, rnd: Optional[int]):
        self.name = name
        self.rnd = rnd

    def __enter__(self):
        self.annotation = TraceAnnotation(PREFIX + self.name)
        self.annotation.__enter__()
        rec = _RECORD
        if self.rnd is not None:
            rec.current = Round(self.rnd)
            rec.rounds.append(rec.current)
            rec.open = []
        self.round = rec.current
        if self.round is not None:
            self.parent = rec.open[-1] if rec.open else None
            rec.open.append(self.name)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        rnd = self.round
        if rnd is not None:
            _RECORD.open.pop()
            rnd.spans.append(Span(rnd.round, self.name, self.parent,
                                  self.start, end))
            if self.rnd is not None:
                rnd.complete = True
                _RECORD.current = None
        return False


def span(name: str, rnd: Optional[int] = None):
    """A span named ``name``; ``rnd`` marks the ``round`` span that
    opens round ``rnd``'s record."""
    if not _recording():
        return _NO_SPAN
    return _Span(name, rnd)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the current round's counter ``name`` while the
    profiler is recording."""
    rnd = _RECORD.current
    if rnd is not None and _recording():
        rnd.counters[name] = rnd.counters.get(name, 0) + n


def records() -> List[Round]:
    return list(_RECORD.rounds)


def clear() -> None:
    _RECORD.rounds.clear()
    _RECORD.current = None
    _RECORD.open = []
