"""Static cost model over jaxprs: peak live bytes, flops, transfers.

The estimate is *pre-XLA*: it walks the traced jaxpr, not the compiled
executable, so it is an upper-ish bound on what an unfused execution
would materialize. That is exactly the right side to gate on — XLA
fusion only shrinks the live set, so a jaxpr-level peak under the
memory budget stays under it after compilation (the bracket test in
``tests/test_analysis_trace.py`` pins the relation against
``Compiled.memory_analysis()`` on the real client step).

Peak live bytes come from a linear-scan liveness pass over the
equations: every value's lifetime is [defining eqn, last reading eqn],
jaxpr outputs and *non-donated* inputs live to the end (the caller
holds them), donated inputs die at their last read — which is how
buffer donation turns into a statically visible memory win. Control
flow recurses: ``scan``/``while`` bodies contribute their own peak on
top of the carried operands (flops scaled by the trip count where it
is known), ``cond`` contributes its worst branch.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr, JaxprEqn, Literal, Var

#: primitives that are pure data movement: no flops charged.
_MOVEMENT = {
    "broadcast_in_dim", "reshape", "transpose", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "rev", "squeeze",
    "gather", "scatter", "iota", "copy", "stop_gradient", "split",
}

#: host-boundary primitives: bytes crossing them count as transfers
#: (and trip TRACE004 — nothing inside a steady-state jit should).
TRANSFER_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "device_put",
}


def aval_bytes(aval: Any) -> int:
    """Concrete byte size of an abstract value (0 for tokens etc.)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * int(dtype.itemsize)


def aval_elems(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(math.prod(shape))


@dataclass
class JaxprCost:
    """What one traced entry point statically costs."""

    peak_bytes: int = 0          # max live set incl. inputs/outputs
    flops: int = 0               # scan-scaled floating/integer op count
    transfer_bytes: int = 0      # bytes crossing host boundaries in-jit
    input_bytes: int = 0         # h2d at call boundary (args + consts)
    output_bytes: int = 0        # d2h/result at call boundary
    eqns: int = 0                # total equations walked (recursive)
    notes: List[str] = field(default_factory=list)

    def to_json(self) -> Dict[str, Any]:
        return {
            "peak_bytes": self.peak_bytes, "flops": self.flops,
            "transfer_bytes": self.transfer_bytes,
            "input_bytes": self.input_bytes,
            "output_bytes": self.output_bytes, "eqns": self.eqns,
        }


# ---------------------------------------------------------------------------
# per-equation flop model
# ---------------------------------------------------------------------------


def _dot_general_flops(eqn: JaxprEqn) -> int:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[d] for d in lb) if lb else 1
    contract = math.prod(lhs.shape[d] for d in lc) if lc else 1
    m = math.prod(s for d, s in enumerate(lhs.shape)
                  if d not in set(lc) | set(lb))
    n = math.prod(s for d, s in enumerate(rhs.shape)
                  if d not in set(rc) | set(_rb))
    return 2 * batch * m * n * contract


def eqn_flops(eqn: JaxprEqn) -> int:
    """Flops for one equation, its own sub-jaxprs excluded (those are
    charged by the recursive walk)."""
    name = eqn.primitive.name
    if name in _MOVEMENT or _sub_jaxprs(eqn):
        return 0
    if name == "dot_general":
        return _dot_general_flops(eqn)
    if name.startswith("reduce_") or name.startswith("cum")\
            or name == "argmax" or name == "argmin":
        return sum(aval_elems(v.aval) for v in eqn.invars
                   if not isinstance(v, Literal))
    if name in ("sort", "top_k"):
        n = max((aval_elems(v.aval) for v in eqn.invars
                 if not isinstance(v, Literal)), default=0)
        return n * max(1, int(math.log2(n)) if n > 1 else 1)
    return sum(aval_elems(v.aval) for v in eqn.outvars)


# ---------------------------------------------------------------------------
# sub-jaxpr discovery + recursive walk
# ---------------------------------------------------------------------------


def _as_closed(j: Any) -> Optional[ClosedJaxpr]:
    if isinstance(j, ClosedJaxpr):
        return j
    if isinstance(j, Jaxpr):
        return ClosedJaxpr(j, [])
    return None


def _sub_jaxprs(eqn: JaxprEqn) -> List[Tuple[ClosedJaxpr, int, bool]]:
    """-> [(sub_jaxpr, flop_multiplier, alternative)] for control-flow /
    call primitives. ``alternative`` marks mutually-exclusive bodies
    (cond branches): their peaks max instead of summing."""
    name = eqn.primitive.name
    if name == "scan":
        length = int(eqn.params.get("length", 1))
        sub = _as_closed(eqn.params["jaxpr"])
        return [(sub, length, False)] if sub else []
    if name == "while":
        out = []
        for key in ("cond_jaxpr", "body_jaxpr"):
            sub = _as_closed(eqn.params.get(key))
            if sub:
                out.append((sub, 1, False))
        return out
    if name == "cond":
        return [(s, 1, True) for b in eqn.params.get("branches", ())
                if (s := _as_closed(b))]
    out = []
    for val in eqn.params.values():
        sub = _as_closed(val)
        if sub is not None:
            out.append((sub, 1, False))
    return out


def iter_eqns(closed: ClosedJaxpr) -> Iterator[Tuple[JaxprEqn, int]]:
    """Every equation in the jaxpr, recursively, with its nesting depth
    — the traversal the TRACE rules share."""

    def walk(jaxpr: Jaxpr, depth: int) -> Iterator[Tuple[JaxprEqn, int]]:
        for eqn in jaxpr.eqns:
            yield eqn, depth
            for sub, _, _ in _sub_jaxprs(eqn):
                yield from walk(sub.jaxpr, depth + 1)

    yield from walk(closed.jaxpr, 0)


def unwrap_pjit(closed: ClosedJaxpr) -> ClosedJaxpr:
    """Peel the trivial outer ``jit`` wrapper ``make_jaxpr(jit(f))``
    produces, so liveness sees the real equations and donated argument
    indices line up with the inner jaxpr's invars."""
    while (len(closed.jaxpr.eqns) == 1
           and closed.jaxpr.eqns[0].primitive.name == "jit"
           and list(closed.jaxpr.eqns[0].invars) == list(closed.jaxpr.invars)
           and list(closed.jaxpr.eqns[0].outvars)
           == list(closed.jaxpr.outvars)):
        closed = closed.jaxpr.eqns[0].params["jaxpr"]
    return closed


def _eqn_io_bytes(eqn: JaxprEqn) -> Tuple[int, int]:
    in_b = sum(aval_bytes(v.aval) for v in eqn.invars
               if not isinstance(v, Literal))
    out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
    return in_b, out_b


def cost_of_jaxpr(closed: ClosedJaxpr,
                  donated: Sequence[int] = ()) -> JaxprCost:
    """Static cost of one traced callable.

    ``donated`` indexes the (flattened) jaxpr invars whose buffers the
    caller donates: those die at their last read instead of being
    pinned for the whole call.
    """
    cost = JaxprCost()
    donated_set = set(donated)
    jaxpr = closed.jaxpr
    invars: List[Var] = list(jaxpr.invars)
    const_bytes = sum(aval_bytes(v.aval) for v in jaxpr.constvars)
    cost.input_bytes = sum(aval_bytes(v.aval) for v in invars) + const_bytes
    cost.output_bytes = sum(aval_bytes(v.aval) for v in jaxpr.outvars
                            if not isinstance(v, Literal))
    peak, flops, xfer, neqns, notes = _walk_cost(
        jaxpr, const_bytes,
        pinned={id(v) for i, v in enumerate(invars)
                if i not in donated_set})
    cost.peak_bytes = peak
    cost.flops = flops
    cost.transfer_bytes = xfer
    cost.eqns = neqns
    cost.notes = notes
    return cost


def _walk_cost(jaxpr: Jaxpr, const_bytes: int,
               pinned: Set[int]) -> Tuple[int, int, int, int, List[str]]:
    """Linear-scan liveness over one jaxpr body.

    -> (peak_bytes, flops, transfer_bytes, eqn_count, notes). ``pinned``
    holds ``id()``s of invars the caller still owns (non-donated).
    """
    eqns = jaxpr.eqns
    last_use: Dict[int, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, Var):
                last_use[id(v)] = i
    end = len(eqns)
    outvar_ids = {id(v) for v in jaxpr.outvars if isinstance(v, Var)}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if id(v) in pinned or id(v) in outvar_ids:
            last_use[id(v)] = end
    for v in jaxpr.outvars:
        if isinstance(v, Var):
            last_use[id(v)] = end

    live = const_bytes + sum(aval_bytes(v.aval) for v in jaxpr.invars)
    peak = live
    flops = 0
    xfer = 0
    neqns = 0
    notes: List[str] = []
    for i, eqn in enumerate(eqns):
        neqns += 1
        in_b, out_b = _eqn_io_bytes(eqn)
        name = eqn.primitive.name
        if name in TRANSFER_PRIMITIVES:
            xfer += in_b + out_b
        flops += eqn_flops(eqn)

        # control flow: the body's internal peak rides on top of the
        # operands already counted in the outer live set
        extra = 0
        alt_extra = 0
        for sub, mult, alternative in _sub_jaxprs(eqn):
            s_const = sum(aval_bytes(v.aval)
                          for v in sub.jaxpr.constvars)
            s_peak, s_flops, s_xfer, s_eqns, s_notes = _walk_cost(
                sub.jaxpr, s_const,
                pinned={id(v) for v in sub.jaxpr.invars})
            s_extra = max(0, s_peak - in_b - out_b)
            if alternative:
                alt_extra = max(alt_extra, s_extra)
            else:
                extra += s_extra
            flops += s_flops * mult
            xfer += s_xfer * mult
            neqns += s_eqns
            notes.extend(s_notes)
        if name == "while":
            notes.append("while-loop trip count unknown: flops counted "
                         "for one iteration")
        extra += alt_extra

        live += out_b
        peak = max(peak, live + extra)
        for v in eqn.invars:
            if isinstance(v, Var) and last_use.get(id(v)) == i:
                live -= aval_bytes(v.aval)
                last_use[id(v)] = -1        # freed once
        for v in eqn.outvars:
            if id(v) not in last_use:        # never read, not an output
                live -= aval_bytes(v.aval)
    return peak, flops, xfer, neqns, notes
