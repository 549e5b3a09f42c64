"""Profiler traces: record the window, read the ``.xplane.pb`` back, and
reduce it to the device's busy time, the device time of each jitted
program, and the idle gaps, each named by what the host was doing.

Busy time is the union of the intervals in which an operation ran on a
device, inside the traced window (first ``chipbench.round`` span start
to last span end); with several chips it is averaged over them. An idle
gap is a stretch of the window with no operation on the device; it is
named by the benchmark's host span (``harness/window.py``) that covers
most of it, or ``other``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from harness.window import SPAN_PREFIX

#: where a TPU trace keeps the operations each chip ran
TPU_PLANE = "/device:TPU:"
TPU_OPS_LINE = "XLA Ops"
#: the line of whole jitted programs (``jit_<name>(<fingerprint>)``)
TPU_MODULES_LINE = "XLA Modules"
ROUND = SPAN_PREFIX + "round"


@dataclass
class Trace:
    #: per device: (names, start_ns, end_ns) of the operations it ran
    devices: List[Tuple[List[str], np.ndarray, np.ndarray]]
    #: host spans of the benchmark: (name, start_ns, end_ns)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    #: per device: (names, start_ns, end_ns) of the programs it ran
    modules: List[Tuple[List[str], np.ndarray, np.ndarray]] = field(
        default_factory=list)


def module_name(name: str) -> str:
    """``jit_quantize_blocks(123...)`` -> ``jit_quantize_blocks``."""
    return name.split("(", 1)[0]


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(plane, line_prefix: str):
    names, starts, ends = [], [], []
    for line in plane.lines:
        if not line.name.startswith(line_prefix):
            continue
        for ev in line.events:
            if ev.duration_ns <= 0:
                continue
            names.append(ev.name)
            starts.append(ev.start_ns)
            ends.append(ev.start_ns + ev.duration_ns)
    return (names, np.asarray(starts, np.float64),
            np.asarray(ends, np.float64))


def load(path: str, plane_prefix: str = TPU_PLANE,
         ops_line: str = TPU_OPS_LINE,
         modules_line: str = TPU_MODULES_LINE) -> Trace:
    """Read a trace: operations and programs from every plane whose name
    starts with ``plane_prefix``, on lines whose names start with
    ``ops_line`` and ``modules_line``; the benchmark's spans from any
    host line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith(plane_prefix):
            devices.append(_events(plane, ops_line))
            modules.append(_events(plane, modules_line))
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(devices=devices, spans=spans, modules=modules)


def window_bounds(trace: Trace) -> Tuple[float, float]:
    rounds = [(s, e) for n, s, e in trace.spans if n == ROUND]
    if not rounds:
        raise ValueError("trace holds no round span")
    return min(s for s, _ in rounds), max(e for _, e in rounds)


def merge(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Union of intervals, clipped to [lo, hi], as sorted disjoint ones."""
    s = np.clip(starts, lo, hi)
    e = np.clip(ends, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    ms = s[idx]
    me = np.append(reach[idx[1:] - 1], reach[-1])
    return ms, me


def gaps(ms: np.ndarray, me: np.ndarray, lo: float, hi: float
         ) -> Tuple[np.ndarray, np.ndarray]:
    """The stretches of [lo, hi] that the merged intervals leave idle."""
    gs = np.concatenate([[lo], me])
    ge = np.concatenate([ms, [hi]])
    keep = ge > gs
    return gs[keep], ge[keep]


def _attribute(gs, ge, spans) -> Dict[str, float]:
    """Idle nanoseconds per host span name (the span covering most of
    each gap; ``other`` where none does)."""
    phases = sorted((s, e, n[len(SPAN_PREFIX):]) for n, s, e in spans
                    if n != ROUND)
    out: Dict[str, float] = defaultdict(float)
    if not phases:
        out["other"] = float(np.sum(ge - gs))
        return out
    ps = np.asarray([p[0] for p in phases])
    pe = np.asarray([p[1] for p in phases])
    for a, b in zip(gs, ge):
        i0 = max(int(np.searchsorted(ps, a, side="right")) - 1, 0)
        i1 = int(np.searchsorted(ps, b, side="left"))
        best, name = 0.0, "other"
        for i in range(i0, max(i1, i0 + 1)):
            ov = min(b, pe[i]) - max(a, ps[i])
            if ov > best:
                best, name = ov, phases[i][2]
        out[name] += b - a
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over the devices
    module_seconds: Dict[str, float]    # device time by jitted program
    idle_by_span: Dict[str, float]      # idle seconds by host span
    n_devices: int

    def seconds_of(self, pred: Callable[[str], bool]) -> float:
        """Device seconds of the programs whose name ``pred`` accepts."""
        return sum(v for k, v in self.module_seconds.items() if pred(k))

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """Device time by jitted program, and idle seconds by host span."""
        ops = sorted(self.module_seconds.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(trace: Trace, bounds: Optional[Tuple[float, float]] = None
           ) -> Reduced:
    lo, hi = bounds if bounds is not None else window_bounds(trace)
    busy, mods = [], defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for names, starts, ends in trace.modules:
        dur = np.minimum(ends, hi) - np.maximum(starts, lo)
        for i in np.flatnonzero(dur > 0):
            mods[module_name(names[i])] += dur[i] * 1e-9
    for _names, starts, ends in trace.devices:
        ms, me = merge(starts, ends, lo, hi)
        busy.append(float(np.sum(me - ms)))
        gs, ge = gaps(ms, me, lo, hi)
        for k, v in _attribute(gs, ge, trace.spans).items():
            idle[k] += v * 1e-9
    n = max(len(trace.devices), 1)
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=(sum(busy) / n) * 1e-9 if busy else 0.0,
                   module_seconds=dict(mods),
                   idle_by_span={k: v / n for k, v in idle.items()},
                   n_devices=len(trace.devices))
