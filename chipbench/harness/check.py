"""The comparison that decides ``correct``.

The system's answers over the first ``check_rounds`` rounds of the very
engine run the window continues (``window.Capture``) against the plain
reference (``reference/``) followed from the same seed, weights and
data. Numbers compared, each with its limit from ``limits/<cell>.json``:

``loss_gap``       worst client-round training loss, |system - ref| / |ref|
``loss_gap_first`` the same over the first round's clients alone, which
                   train from the same weights on both sides
``client_gap``     worst leaf of any client's shipped delta (LocalTrain
                   and the wire): the gap between the two leaf norms
``client_gap_first`` the same over the first round's clients alone
``update_gap``     worst leaf of the first round's server update
``change_gap``     worst leaf of the parameters' change after the rounds
``knob_mismatch``  cohort or knob tuples that differ, rounds 1 to
                   ``check_rounds + 1`` (exact)
``dual_gap``       largest difference of a dual after a round (exact)
``wire_gap``       largest difference of a client's wire MB (exact)

A leaf's norm gap is measured against the reference's norm of that leaf
or of the median leaf, whichever is larger. Leaves whose reference
gradient at the start is under a thousandth of the median leaf's move
by round-off alone and are left out of the leaf gaps.

A cell compares the numbers its limits file names (``check.ORDER``
order); one that the control and the faults cannot separate from sound
runs is left out there, with its readings in PERF.md. The ``_first``
numbers stand in where the later rounds' readings swing from seed to
seed: from the second round on, both sides train from server weights
that already differ by the first round's rounding, passed through the
wire's coarse codes.
"""
from __future__ import annotations

import importlib
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.window import Capture

ORDER = ("loss_gap", "loss_gap_first", "client_gap", "client_gap_first",
         "update_gap", "change_gap",
         "knob_mismatch", "dual_gap", "wire_gap")
NOUGHT = 1e-3


def reference_module(config: Dict):
    return importlib.import_module("reference." + config["reference"])


def leaf_gap(sys_norms, ref_norms, keep) -> float:
    sys_norms = np.asarray(sys_norms, np.float64)
    ref_norms = np.asarray(ref_norms, np.float64)
    live = ref_norms[keep & (ref_norms > 0)]
    median = float(np.median(live)) if live.size else 0.0
    worst = 0.0
    for i in np.flatnonzero(keep):
        base = max(ref_norms[i], median)
        gap = abs(sys_norms[i] - ref_norms[i])
        if base == 0.0:
            if gap > 0.0:
                return math.inf
            continue
        worst = max(worst, gap / base)
    return worst


def kept_leaves(grad_norms) -> np.ndarray:
    g = np.asarray(grad_norms, np.float64)
    return g >= NOUGHT * float(np.median(g))


@jax.jit
def _change_norms(w, w0):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                                  - b.astype(jnp.float32))))
                      for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w0))])


def numbers(cap: Capture, ref: Dict, sys_change, keep) -> Dict[str, float]:
    n = cap.n
    rr = ref["rounds"]
    out = {k: 0.0 for k in ORDER}
    mism = 0
    for r in range(1, n + 2):
        want = rr[r - 1]["knobs"] if r <= n else ref["next_knobs"]
        got = cap.knobs.get(r, {})
        if r <= n and cap.sampled.get(r) != rr[r - 1]["sampled"]:
            mism += 1
        if not got:
            mism += 1
        mism += sum(1 for kn in got.values() if kn != want)
    out["knob_mismatch"] = float(mism)
    for r in range(1, n + 1):
        ref_r = rr[r - 1]
        for cid, loss in ref_r["losses"].items():
            got = cap.losses.get(r, {}).get(cid, math.nan)
            gap = (abs(got - loss) / abs(loss) if math.isfinite(got)
                   else math.inf)
            out["loss_gap"] = max(out["loss_gap"], gap)
            if r == 1:
                out["loss_gap_first"] = max(out["loss_gap_first"], gap)
            got_mb = cap.wire_mb.get(r, {}).get(cid, math.nan)
            out["wire_gap"] = max(out["wire_gap"],
                                  abs(got_mb - ref_r["wire_mb"])
                                  if math.isfinite(got_mb) else math.inf)
            sys_n = cap.client_norms.get(r, {}).get(cid)
            cgap = (math.inf if sys_n is None else
                    leaf_gap(sys_n, ref_r["client_norms"][cid], keep))
            out["client_gap"] = max(out["client_gap"], cgap)
            if r == 1:
                out["client_gap_first"] = max(out["client_gap_first"], cgap)
        for name, lam in ref_r["duals"].items():
            got = cap.duals.get(r, {}).get(name, lam if not cap.duals else
                                           math.nan)
            out["dual_gap"] = max(out["dual_gap"], abs(got - lam)
                                  if math.isfinite(got) else math.inf)
    first = cap.update_norms.get(1)
    out["update_gap"] = (math.inf if first is None else
                         leaf_gap(first, rr[0]["update_norms"], keep))
    out["change_gap"] = leaf_gap(sys_change, ref["change_norms"], keep)
    return out


def by_round(cap: Capture, ref: Dict, keep) -> Dict[str, float]:
    """``loss_gap`` and ``client_gap`` of each checked round alone
    (``loss_gap_r1``, ...): where a number's worst reading comes from."""
    out = {}
    for r, ref_r in enumerate(ref["rounds"], start=1):
        lg = cg = 0.0
        for cid, loss in ref_r["losses"].items():
            got = cap.losses.get(r, {}).get(cid, math.nan)
            lg = max(lg, abs(got - loss) / abs(loss) if math.isfinite(got)
                     else math.inf)
            sys_n = cap.client_norms.get(r, {}).get(cid)
            cg = max(cg, math.inf if sys_n is None else
                     leaf_gap(sys_n, ref_r["client_norms"][cid], keep))
        out[f"loss_gap_r{r}"] = lg
        out[f"client_gap_r{r}"] = cg
    return out


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    table = {k: {"value": values[k], "limit": limits[k]} for k in ORDER
             if k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table


def run_reference(cell, seed: int, train: np.ndarray, w0, prec_name: str = "reference",
                  half_batch: bool = False) -> Tuple[Dict, np.ndarray]:
    """Follow the cell's first rounds with the reference (or one of its
    controls) -> (answers, kept-leaf mask)."""
    from reference import fl
    model = reference_module(cell.config)
    cfg = cell.model
    prec = model.precision(cfg, prec_name)
    t = cell.traffic
    n = t["check_rounds"]
    ref = fl.run(model, cfg, t, seed, train, w0, n, prec,
                 half_batch=half_batch)
    first = ref["rounds"][0]["sampled"][0]
    fleet = fl.Fleet(train, t["num_clients"], seed)
    tk, tg = fleet.batch(first, t["b_base"], t["seq_len"])
    grads = fl.first_grad_norms(model, cfg, w0, jnp.asarray(tk),
                                jnp.asarray(tg), model.precision(cfg))
    return ref, kept_leaves(grads)


def check(cell, seed: int, train: np.ndarray, cap: Capture, w0,
          prec_name: str = "reference", half_batch: bool = False,
          rounds: bool = False):
    """-> (correct, {name: {value, limit}}, raw numbers); ``rounds``
    adds ``by_round``'s numbers to the raw ones."""
    ref, keep = run_reference(cell, seed, train, w0, prec_name, half_batch)
    sys_change = np.asarray(_change_norms(jax.device_put(cap.params), w0))
    values = numbers(cap, ref, sys_change, keep)
    ok, table = verdict(values, cell.limits)
    if rounds:
        values.update(by_round(cap, ref, keep))
    return ok, table, values


def against_reference(cell, seed: int, train: np.ndarray, w0, variant: str,
                      ref=None) -> Dict[str, float]:
    """A variant of the reference put in the system's place, against the
    reference: the upper readings of the limits. ``variant`` is
    ``half_batch`` (a planted fault) or a precision kind of the
    configuration's reference module (``control``). ``ref`` is the
    reference's own run, ``run_reference``'s result, where it is at
    hand."""
    ref, keep = ref or run_reference(cell, seed, train, w0)
    half = variant == "half_batch"
    var, _ = run_reference(cell, seed, train, w0,
                           "reference" if half else variant, half_batch=half)
    cap = as_capture(var, cell.traffic["check_rounds"])
    return {**numbers(cap, ref, var["change_norms"], keep),
            **by_round(cap, ref, keep)}


def as_capture(ans: Dict, n: int) -> Capture:
    cap = Capture(n)
    for r, rec in enumerate(ans["rounds"], start=1):
        cap.sampled[r] = list(rec["sampled"])
        cap.knobs[r] = {cid: rec["knobs"] for cid in rec["sampled"]}
        cap.losses[r] = dict(rec["losses"])
        cap.wire_mb[r] = {cid: rec["wire_mb"] for cid in rec["sampled"]}
        cap.client_norms[r] = dict(rec["client_norms"])
        cap.update_norms[r] = rec["update_norms"]
        cap.duals[r] = dict(rec["duals"])
    cap.knobs[n + 1] = {0: ans["next_knobs"]}
    return cap
