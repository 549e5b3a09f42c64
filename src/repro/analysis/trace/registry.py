"""Traceable entry points: what gets traced, and under which shapes.

Hot modules *declare* their own entry points by defining a module-level
``trace_entry_points() -> list[EntryPoint]`` hook (``repro.core.client``,
``repro.fl.executor``, ``repro.fl.aggregator``, ``repro.kernels.ops``,
``repro.constraints.controllers``); ``collect_entry_points`` imports
those modules and gathers the declarations, so the shapes live next to
the code they describe and this package never hard-codes model guts.

An ``EntryPoint`` is lazy: ``build()`` constructs the callable and its
example arguments (real tiny-model params where cheap,
``jax.ShapeDtypeStruct`` where only shapes matter) on first trace.
Declared example shapes are the contract — the committed
``TRACE_BUDGETS.json`` rows are only comparable while the declarations
stay fixed, so changing a declaration is a table re-record, same as the
bench ratchet.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.analysis.trace.cost import (JaxprCost, cost_of_jaxpr,
                                       unwrap_pjit)

#: modules whose ``trace_entry_points()`` hooks feed the registry
TRACE_ENTRY_MODULES: Tuple[str, ...] = (
    "repro.core.client",
    "repro.fl.executor",
    "repro.fl.aggregator",
    "repro.kernels.ops",
    "repro.constraints.controllers",
)

#: charlm dims every declared entry shares (kept tiny so tracing is
#: cheap; the *ratios* between operating points are what the gate uses)
TRACE_MODEL = {"vocab": 64, "num_layers": 2, "d_model": 32, "num_heads": 2,
               "head_dim": 16, "d_ff": 64, "seq_len": 64}


@dataclass(frozen=True)
class EntryPoint:
    """One registered traceable callable + its declared example shapes."""

    name: str                     # e.g. "fl.client_update_step"
    path: str                     # repo-relative module declaring it
    line: int                     # decl anchor for findings
    build: Callable[[], Tuple[Callable[..., Any], Tuple[Any, ...]]]
    #: argnums whose buffers an update-style step *should* donate
    #: (TRACE002 verifies the compiled artifact actually aliases them)
    donatable: Tuple[int, ...] = ()
    #: >=2 marks an aggregation combine over a client cohort (TRACE003)
    cohort: int = 0
    #: participates in the Budgets.memory static feasibility gate
    gated: bool = False
    #: the baseline-knobs twin whose peak defines bytes-per-memory-unit
    calibration: bool = False
    #: trace under jax.enable_x64(True) (fixture entries)
    x64: bool = False
    #: TRACE rule ids intentionally suppressed for this entry
    allow: Tuple[str, ...] = ()
    note: str = ""


@dataclass
class TracedEntry:
    """One entry point after tracing: the IR plus its static cost."""

    entry: EntryPoint
    closed_jaxpr: Any
    cost: JaxprCost
    donatable_leaves: int = 0     # leaves under the donatable argnums
    aliased_outputs: int = -1     # buffers XLA aliased; -1 = not a jit
    unit_bytes: int = 0           # largest per-client leaf (TRACE003)


def charlm_trace_setup(b: int, seq: Optional[int] = None,
                       model: Optional[Dict[str, int]] = None) -> Any:
    """Shared tiny char-LM fixture for the fl.* entry declarations:
    a real ``ClientRunner`` (params initialised — they are a few kB)
    plus a shape-only batch."""
    from repro.configs import get_config, get_fl_config
    from repro.core.client import ClientRunner
    from repro.models import build

    dims = dict(TRACE_MODEL, **(model or {}))
    seq = dims["seq_len"] if seq is None else seq
    cfg = get_config("charlm-shakespeare").replace(
        vocab_size=dims["vocab"], num_layers=dims["num_layers"],
        d_model=dims["d_model"], num_heads=dims["num_heads"],
        num_kv_heads=dims["num_heads"], head_dim=dims["head_dim"],
        d_ff=dims["d_ff"])
    fl = get_fl_config().replace(seq_len=seq)
    mdl = build(cfg)
    runner = ClientRunner(mdl, fl, data=None, resources=None)
    params = mdl.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.ShapeDtypeStruct((b, seq), jax.numpy.int32),
        "targets": jax.ShapeDtypeStruct((b, seq), jax.numpy.int32),
    }
    return runner, params, batch


def collect_entry_points(
        extra_modules: Sequence[str] = ()) -> List[EntryPoint]:
    """Import the declaring modules and gather every entry point."""
    entries: List[EntryPoint] = []
    for modname in tuple(TRACE_ENTRY_MODULES) + tuple(extra_modules):
        mod = importlib.import_module(modname)
        hook = getattr(mod, "trace_entry_points", None)
        if hook is None:
            continue
        entries.extend(hook())
    names = [e.name for e in entries]
    if len(set(names)) != len(names):
        dupes = sorted({n for n in names if names.count(n) > 1})
        raise ValueError(f"duplicate trace entry points: {dupes}")
    return entries


def _leaf_count(args: Tuple[Any, ...], argnums: Sequence[int]) -> int:
    return sum(len(jax.tree.leaves(args[i])) for i in argnums)


def _count_aliased(fn: Callable[..., Any],
                   args: Tuple[Any, ...]) -> int:
    """How many output buffers the lowered artifact aliases to donated
    inputs (``tf.aliasing_output`` in the StableHLO text) — the ground
    truth TRACE002 compares the declaration against."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return -1
    try:
        text = lower(*args).as_text()
    except Exception:                                  # pragma: no cover
        return -1
    return text.count("tf.aliasing_output")


def trace_entry(entry: EntryPoint) -> TracedEntry:
    """Trace one entry point to a jaxpr and run the cost model on it."""
    fn, args = entry.build()

    def ctx() -> Any:
        return (jax.enable_x64(True) if entry.x64
                else contextlib.nullcontext())

    with ctx():
        closed = unwrap_pjit(jax.make_jaxpr(fn)(*args))

    # map donated argnums -> flattened invar indices (pytree args
    # flatten in order, matching the unwrapped jaxpr's invars)
    donated_leaves: List[int] = []
    offset = 0
    for i, a in enumerate(args):
        n = len(jax.tree.leaves(a))
        if i in entry.donatable:
            donated_leaves.extend(range(offset, offset + n))
        offset += n

    cost = cost_of_jaxpr(closed, donated=donated_leaves)
    traced = TracedEntry(
        entry=entry, closed_jaxpr=closed, cost=cost,
        donatable_leaves=len(donated_leaves),
        unit_bytes=_cohort_unit_bytes(entry, args))
    if entry.donatable:
        with ctx():
            traced.aliased_outputs = _count_aliased(fn, args)
    return traced


def _cohort_unit_bytes(entry: EntryPoint, args: Tuple[Any, ...]) -> int:
    """Largest single-client leaf for TRACE003's O(C*P) threshold: an
    aggregation combine materializing ``cohort * max_leaf`` bytes in one
    value has stacked the cohort densely."""
    if entry.cohort < 2:
        return 0
    leaves = [leaf for a in args for leaf in jax.tree.leaves(a)]
    sizes = [int(leaf.size) * int(leaf.dtype.itemsize)
             for leaf in leaves
             if hasattr(leaf, "size") and hasattr(leaf, "dtype")]
    return max(sizes, default=0)


@functools.lru_cache(maxsize=1)
def traced_entries() -> Tuple[TracedEntry, ...]:
    """Trace every registered entry once per process (tests, the CLI
    gate and the bench all share the result)."""
    return tuple(trace_entry(e) for e in collect_entry_points())
