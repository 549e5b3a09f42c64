"""The program's own host spans and counters (``repro.fl.spans``), as
recorded while the profiler traced the window's first rounds, reduced
to seconds and counts per round.

A span's self seconds are its duration less what its child spans cover
(the spans whose ``parent`` names it). The rounds counted are those
whose ``round`` span completed, and there have to be as many as the
traced rounds the harness counted. A program without these spans gives
nothing to read.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence


def self_seconds(rnd) -> Dict[str, float]:
    """Self seconds of each span name in one recorded round."""
    out: Dict[str, float] = defaultdict(float)
    for s in rnd.spans:
        seconds = (s.end_ns - s.start_ns) * 1e-9
        out[s.name] += seconds
        if s.parent is not None:
            out[s.parent] -= seconds
    return dict(out)


def recorded_rounds(run) -> Optional[List]:
    """The completed rounds of the record; None where nothing was
    recorded."""
    try:
        from repro.fl import spans
    except ImportError:
        return None
    rounds = [r for r in spans.records() if r.complete]
    if not rounds:
        return None
    if len(rounds) != run.rounds:
        raise RuntimeError(
            f"program_spans: the program recorded {len(rounds)} rounds "
            f"(rounds {[r.round for r in rounds]}), the trace covers "
            f"{run.rounds}")
    return rounds


def seconds_per_round(run, names: Sequence[str]) -> Optional[float]:
    """Self seconds of the spans ``names``, summed, per recorded round."""
    rounds = recorded_rounds(run)
    if rounds is None:
        return None
    total = 0.0
    for rnd in rounds:
        own = self_seconds(rnd)
        total += sum(own.get(n, 0.0) for n in names)
    return total / len(rounds)


def count_per_round(run, name: str) -> Optional[float]:
    """The counter ``name`` per recorded round."""
    rounds = recorded_rounds(run)
    if rounds is None:
        return None
    return sum(r.counters.get(name, 0) for r in rounds) / len(rounds)
