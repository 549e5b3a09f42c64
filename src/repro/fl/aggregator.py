"""Server-update policies: *when* client reports become server updates.

PR 1/2 hard-wired the synchronous round barrier (collect every
survivor, average once per round) into ``FederatedEngine.run``. This
module makes the server-update path a first-class, pluggable axis: the
engine turns every finished client into a ``ClientReport`` event
(delta, weight, arrival time, staleness, device profile) and feeds it
to an ``Aggregator``, which decides when those reports are combined
into ``ServerUpdate``s:

    submit(report) -> Optional[ServerUpdate]   per-arrival (async paths)
    flush(rnd)     -> Optional[ServerUpdate]   end-of-round barrier
    state_snapshot()                           observability

Four policies ship:

    SyncAggregator        the paper's barrier — buffer the round, apply
                          once (bit-for-bit the PR 1/2 behaviour; the
                          golden trajectories pin it)
    FedBuffAggregator     buffered async (Nguyen et al., "Federated
                          Learning with Buffered Asynchronous
                          Aggregation"): apply every K arrivals with
                          staleness-discounted deltas; deadline-missers
                          deliver late instead of being discarded
    StalenessWeighted-    the barrier, but late reports are folded into
    Aggregator            a later round's update under a composable
                          ``StalenessPolicy`` discount
    MaskedSumAggregator   pairwise-mask secure-aggregation simulation
                          (Bonawitz et al., "Practical Secure
                          Aggregation"): fixed-point masked sums whose
                          mask reconstruction stays *exact* under any
                          PR 2 churn/deadline dropout pattern

*How* deltas are combined stays with ``FederatedStrategy.aggregate``
(pure delta combination); the engine binds it via ``reset(combine)``
so ``ServerOpt`` and weighted variants compose with every policy.

Determinism contract (checked by ``repro.analysis.sched``): float
combines are order-sensitive (reassociation changes bits), so every
policy folds its buffered reports in *canonical report order* —
``(round_trained, arrival_time, client_id)``, a total order over any
report set — before touching the combine. That makes the applied
update a pure function of the report *set*, never of delivery order;
each class declares how via ``commutativity``:

    "exact"      order-free by construction (uint64 masked sums are
                 associative/commutative mod 2^64)
    "canonical"  floats folded in canonical order (sync, staleness)
    "tiebreak"   the *buffer composition* depends on delivery order
                 (FedBuff fills every K arrivals), which the engine
                 makes deterministic via ``TimedReport.sort_key``;
                 each fill's fold is canonical-ordered
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import Knobs
from repro.fl.device import ClientInfo

Combine = Callable[[Sequence, Optional[List[float]]], Any]


def report_order_key(report: "ClientReport") -> Tuple[int, float, int]:
    """The canonical total order over client reports: params version
    first (oldest work folds first), then simulated arrival, then the
    client id as the final tie-break. No two distinct reports compare
    equal — client ids are unique within a fold — so a sort under this
    key is schedule-independent."""
    return (report.round_trained, report.arrival_time,
            report.client.client_id)


def canonical_order(reports: Sequence["ClientReport"]
                    ) -> List["ClientReport"]:
    """Sort reports into canonical order (``report_order_key``) so any
    float fold over them is a function of the report *set*, not of the
    delivery schedule. Every aggregator calls this before combining."""
    return sorted(reports, key=report_order_key)


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------


@dataclass
class ClientReport:
    """One finished LocalTrain, as the server receives it.

    ``weight`` is the client's example count (shard size) — the single
    place it is routed from; every aggregator hands it to the combine
    function, which renormalizes over whatever subset is present.
    ``staleness`` is ``round_submitted - round_trained``: 0 for clients
    that made the deadline, >0 for late reports delivered by an
    ``accepts_late`` aggregator.
    """
    client: ClientInfo
    delta: Any                    # masked, wire-compressed update tree
    weight: float                 # client example count (|D_i|)
    knobs: Knobs                  # knobs actually trained (incl. carry boost)
    policy_knobs: Knobs           # the strategy's policy knobs (no boost)
    round_trained: int            # params version the delta was computed on
    arrival_time: float = 0.0     # straggler wall-clock draw (0 if untimed)
    round_submitted: int = -1     # set when the server takes delivery
    staleness: int = 0            # round_submitted - round_trained
    train_loss: float = 0.0
    wire_mb_actual: float = 0.0
    params_active: float = 0.0
    usage: Dict[str, float] = field(default_factory=dict)
    energy_true: float = 0.0


@dataclass(frozen=True)
class ServerUpdate:
    """One application of client work to the server params."""
    delta: Any                          # tree to add to params
    reports: Tuple[ClientReport, ...]   # the reports folded in
    round: int                          # server round it was applied
    mean_staleness: float = 0.0


# ---------------------------------------------------------------------------
# staleness policies
# ---------------------------------------------------------------------------


class StalenessPolicy:
    """Maps a report's staleness (rounds late) to a discount in (0, 1].
    Discounts must be non-increasing in staleness and 1.0 at 0."""

    name = "base"

    def discount(self, staleness: int) -> float:
        raise NotImplementedError


class PolynomialStaleness(StalenessPolicy):
    """FedBuff's s(tau) = (1 + tau)^(-alpha); alpha=0 disables."""

    name = "polynomial"

    def __init__(self, alpha: float = 0.5):
        assert alpha >= 0.0
        self.alpha = alpha

    def discount(self, staleness: int) -> float:
        assert staleness >= 0
        return float((1.0 + staleness) ** (-self.alpha))


class ConstantStaleness(StalenessPolicy):
    """Fresh reports count fully; any late report a constant factor."""

    name = "constant"

    def __init__(self, factor: float = 0.5):
        assert 0.0 < factor <= 1.0
        self.factor = factor

    def discount(self, staleness: int) -> float:
        assert staleness >= 0
        return 1.0 if staleness == 0 else self.factor


def make_staleness_policy(spec) -> StalenessPolicy:
    if isinstance(spec, StalenessPolicy):
        return spec
    name = spec.lower()
    if name in ("polynomial", "poly"):
        return PolynomialStaleness()
    if name == "constant":
        return ConstantStaleness()
    if name == "none":
        return PolynomialStaleness(alpha=0.0)
    raise ValueError(f"unknown staleness policy {spec!r}; "
                     f"options: polynomial, constant, none")


def _scale_delta(delta, factor: float):
    if factor == 1.0:
        return delta
    return jax.tree.map(lambda l: l.astype(jnp.float32) * factor, delta)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class Aggregator:
    """Server-update policy. The engine drives one instance per run:

        reset(combine)          bind the strategy's pure combine fn
        begin_round(rnd, cohort)  the sampled cohort is fixed (secure
                                aggregation needs it to agree masks)
        submit(report)          one report arrived; may emit an update
        flush(rnd)              the round barrier; may emit an update

    ``accepts_late = True`` tells the engine to *execute* deadline
    missers and deliver their reports in the round their simulated
    wall clock lands in, instead of discarding them.

    ``applies_mid_round = True`` marks policies whose ``submit`` can
    emit an update before the round barrier (FedBuff). Under
    ``time_mode="wall_clock"`` such an update is the "buffer completes"
    event that *ends* the round: the next round begins at its simulated
    time, so buffered-async rounds are exactly as long as their fills.

    ``commutativity`` is the policy's certificate under report-order
    permutation (see the module docstring): "exact", "canonical" or
    "tiebreak". ``repro.analysis.sched`` reads it to decide whether two
    HB-unordered deliveries into the same aggregator state are benign;
    a policy that declares none is flagged as a schedule race.
    """

    name = "base"
    accepts_late = False
    applies_mid_round = False
    commutativity: Optional[str] = None

    def __init__(self):
        self._combine: Optional[Combine] = None
        self._applied = 0

    def reset(self, combine: Combine) -> None:
        self._combine = combine
        self._applied = 0

    def begin_round(self, rnd: int, cohort: Sequence[ClientInfo]) -> None:
        pass

    def submit(self, report: ClientReport) -> Optional[ServerUpdate]:
        raise NotImplementedError

    def flush(self, rnd: int) -> Optional[ServerUpdate]:
        return None

    def finalize(self, rnd: int) -> Optional[ServerUpdate]:
        """Training is over: drain whatever the policy still buffers so
        executed work is never silently discarded. Barrier aggregators
        have nothing left after ``flush``; FedBuff applies its partial
        buffer."""
        return None

    def state_snapshot(self) -> Dict[str, Any]:
        return {"name": self.name, "updates_applied": self._applied}

    # -- helpers -------------------------------------------------------------
    def _emit(self, rnd: int, reports: Sequence[ClientReport],
              delta) -> ServerUpdate:
        self._applied += 1
        # canonical order all the way out: ServerUpdate.reports and the
        # staleness fold are schedule-independent like the delta itself
        reports = canonical_order(reports)
        stale = (float(np.mean([r.staleness for r in reports]))
                 if reports else 0.0)
        return ServerUpdate(delta=delta, reports=tuple(reports), round=rnd,
                            mean_staleness=stale)


class SyncAggregator(Aggregator):
    """The paper's round barrier: buffer every report of the round and
    apply one combined update at ``flush``. Default. Its updates match
    the original monolithic loop's eager fold to one ulp per element,
    the compiled fold's fused multiply-add being the only difference
    (golden trajectories pin it within their tolerances)."""

    name = "sync"
    commutativity = "canonical"

    def __init__(self):
        super().__init__()
        self._buf: List[ClientReport] = []

    def reset(self, combine):
        super().reset(combine)
        self._buf = []

    def submit(self, report):
        self._buf.append(report)
        return None

    def flush(self, rnd):
        if not self._buf:
            return None
        reports, self._buf = self._buf, []
        reports = canonical_order(reports)
        delta = self._combine([r.delta for r in reports],
                              [r.weight for r in reports])
        return self._emit(rnd, reports, delta)

    def state_snapshot(self):
        return {**super().state_snapshot(), "buffered": len(self._buf)}


class StalenessWeightedAggregator(Aggregator):
    """The barrier, minus the discard: deadline-missers deliver in the
    round their wall clock lands in and are folded into that round's
    update under a ``StalenessPolicy`` discount.

    ``mode="scale"`` (default) multiplies the late delta itself by the
    discount — an absolute attenuation that works under any combine,
    including the paper's unweighted mean. ``mode="weight"`` multiplies
    the report's example-count weight instead — a relative reweighting
    that only bites with weight-respecting combines (FedAvg
    ``weighted=True``)."""

    name = "staleness"
    accepts_late = True
    commutativity = "canonical"

    def __init__(self, policy: Optional[StalenessPolicy] = None,
                 mode: str = "scale"):
        super().__init__()
        assert mode in ("scale", "weight")
        self.policy = policy or PolynomialStaleness()
        self.mode = mode
        self._buf: List[ClientReport] = []

    def reset(self, combine):
        super().reset(combine)
        self._buf = []

    def submit(self, report):
        self._buf.append(report)
        return None

    def flush(self, rnd):
        if not self._buf:
            return None
        reports, self._buf = self._buf, []
        reports = canonical_order(reports)
        discounts = [self.policy.discount(r.staleness) for r in reports]
        if self.mode == "scale":
            deltas = [_scale_delta(r.delta, d)
                      for r, d in zip(reports, discounts)]
            weights = [r.weight for r in reports]
        else:
            deltas = [r.delta for r in reports]
            weights = [r.weight * d for r, d in zip(reports, discounts)]
        return self._emit(rnd, reports, self._combine(deltas, weights))

    def state_snapshot(self):
        return {**super().state_snapshot(), "buffered": len(self._buf),
                "policy": self.policy.name, "mode": self.mode}


class FedBuffAggregator(Aggregator):
    """Buffered asynchronous aggregation (FedBuff): every report lands
    in a buffer; once ``buffer_size`` reports have arrived the server
    applies their combined, staleness-discounted update immediately —
    mid-round, without waiting for the barrier. Late reporters are
    *used* (discounted by ``policy``) instead of discarded; the buffer
    persists across round boundaries, so ``flush`` is a no-op."""

    name = "fedbuff"
    accepts_late = True
    applies_mid_round = True
    commutativity = "tiebreak"

    def __init__(self, buffer_size: int = 4,
                 policy: Optional[StalenessPolicy] = None):
        super().__init__()
        assert buffer_size >= 1
        self.buffer_size = buffer_size
        self.policy = policy or PolynomialStaleness()
        self._buf: List[ClientReport] = []

    def reset(self, combine):
        super().reset(combine)
        self._buf = []

    def submit(self, report):
        self._buf.append(report)
        if len(self._buf) < self.buffer_size:
            return None
        return self._apply_buffer(report.round_submitted)

    def finalize(self, rnd):
        """Drain the partial buffer at run end: those clients trained,
        were accounted as participants, and repaid debt — their work
        must reach the model."""
        if not self._buf:
            return None
        return self._apply_buffer(rnd)

    def _apply_buffer(self, rnd):
        reports, self._buf = self._buf, []
        reports = canonical_order(reports)
        # staleness is measured at APPLY time (FedBuff's tau): a report
        # that sat in the buffer across rounds aged while earlier fills
        # moved the params, so its discount must keep accruing
        for r in reports:
            r.staleness = max(r.staleness, rnd - r.round_trained)
        deltas = [_scale_delta(r.delta, self.policy.discount(r.staleness))
                  for r in reports]
        delta = self._combine(deltas, [r.weight for r in reports])
        return self._emit(rnd, reports, delta)

    def state_snapshot(self):
        return {**super().state_snapshot(), "buffered": len(self._buf),
                "buffer_size": self.buffer_size, "policy": self.policy.name}


class MaskedSumAggregator(Aggregator):
    """Pairwise-mask secure-aggregation simulation (Bonawitz et al.).

    Every sampled client's weighted delta is quantized to a fixed-point
    grid (``scale_bits`` fractional bits) and blinded with one pairwise
    mask per cohort partner: client ``min(i,j)`` adds ``m_ij``, client
    ``max(i,j)`` subtracts it, all mod 2^64. The server only ever sums
    masked vectors — modular integer arithmetic, so cancellation is
    *exact*, not approximate. When a sampled client drops (churn or
    deadline), the server reconstructs the dropped client's pairwise
    masks (standing in for the protocol's secret-share recovery) and
    removes them, so the unmasked total equals the plain fixed-point
    weighted sum of the reporters bit-for-bit under every dropout
    combination.

    The unmasked mean then flows through the strategy's combine as a
    single pre-combined delta, so ``ServerOpt`` still composes. The
    default is the paper's unweighted mean — the same combination rule
    every other aggregator defaults to, so swapping ``"sync"`` for
    ``"masked"`` changes only *how securely*, not *what* is computed;
    ``use_weights=True`` gives the |D_i|-weighted variant.

    ``path`` picks the cohort-fold backend: ``"kernel"`` (default)
    buffers each client's masked uint64 vector and folds the stacked
    cohort through ``repro.kernels.ops.masked_sum`` (the Pallas
    fixed-point masked-sum kernel — one bandwidth-bound pass) at
    flush; ``"numpy"`` keeps the sequential per-arrival uint64
    accumulation as the exactness oracle. Modular sums are
    associative, so the two paths are bit-identical under every
    dropout combination.
    """

    name = "masked"
    commutativity = "exact"

    def __init__(self, scale_bits: int = 32, use_weights: bool = False,
                 seed: int = 0, path: str = "kernel"):
        super().__init__()
        # the *weighted* fixed-point values must fit int64 with headroom
        # for the cohort sum; _quantize guards this at runtime, since
        # the bound depends on the weights (shard sizes) actually seen
        assert 1 <= scale_bits <= 52
        assert path in ("kernel", "numpy"), path
        self.scale = float(2 ** scale_bits)
        self.use_weights = use_weights
        self.seed = seed
        self.path = path
        self._round = 0
        self._cohort: List[int] = []
        self._reporters: List[ClientReport] = []
        self._sum: Optional[List[np.ndarray]] = None
        self._pending: List[List[np.ndarray]] = []
        self._treedef = None
        self._reconstructed = 0

    def reset(self, combine):
        super().reset(combine)
        self._cohort, self._reporters, self._sum = [], [], None
        self._pending = []
        self._reconstructed = 0

    def begin_round(self, rnd, cohort):
        self._round = rnd
        self._cohort = [ci.client_id for ci in cohort]
        self._reporters = []
        self._sum = None
        self._pending = []
        self._treedef = None

    # -- fixed-point + masks -------------------------------------------------
    def _weight(self, report: ClientReport) -> float:
        return report.weight if self.use_weights else 1.0

    def _quantize(self, delta, weight: float) -> Tuple[List[np.ndarray], Any]:
        leaves, treedef = jax.tree.flatten(delta)
        # np.int64 casts of out-of-range floats are silent garbage, so
        # the exactness guarantee needs an explicit overflow guard: each
        # weighted value must leave room for the whole cohort to sum
        # without leaving int64 (drop scale_bits or pre-scale weights
        # when this trips)
        limit = 2.0 ** 62 / max(1, len(self._cohort))
        q = []
        for leaf in leaves:
            vals = np.rint(np.asarray(leaf, np.float64) * weight * self.scale)
            assert np.all(np.abs(vals) < limit), \
                (f"masked-sum fixed point overflow: |delta * weight| * "
                 f"2^scale_bits exceeds int64 headroom ({self.scale:g} * "
                 f"weight {weight:g}); lower scale_bits or the weights")
            q.append(vals.astype(np.int64).view(np.uint64))
        return q, treedef

    def _pair_masks(self, a: int, b: int,
                    like: List[np.ndarray]) -> List[np.ndarray]:
        lo, hi = (a, b) if a < b else (b, a)
        rng = np.random.default_rng([self.seed, self._round, lo, hi])
        return [rng.integers(0, 2 ** 64, size=l.shape, dtype=np.uint64)
                for l in like]

    def _add_masks(self, vec: List[np.ndarray], me: int, partner: int,
                   sign: int) -> List[np.ndarray]:
        masks = self._pair_masks(me, partner, vec)
        flip = 1 if me < partner else -1
        if sign * flip > 0:
            return [v + m for v, m in zip(vec, masks)]
        return [v - m for v, m in zip(vec, masks)]

    # -- protocol ------------------------------------------------------------
    def submit(self, report):
        assert report.client.client_id in self._cohort, \
            "masked sums need the cohort fixed before reports arrive"
        vec, treedef = self._quantize(report.delta, self._weight(report))
        me = report.client.client_id
        for partner in self._cohort:
            if partner != me:
                vec = self._add_masks(vec, me, partner, sign=+1)
        if self.path == "kernel":
            # buffer the masked vector; the cohort folds in one kernel
            # pass at flush instead of C sequential accumulations
            self._pending.append(vec)
            self._treedef = treedef
        elif self._sum is None:
            self._sum, self._treedef = vec, treedef
        else:
            self._sum = [a + b for a, b in zip(self._sum, vec)]
        self._reporters.append(report)
        return None

    def _kernel_fold(self) -> List[np.ndarray]:
        """Fold the buffered cohort mod 2^64 via the masked-sum kernel."""
        from repro.kernels import ops
        shapes = [v.shape for v in self._pending[0]]
        sizes = [v.size for v in self._pending[0]]
        stacked = np.stack([np.concatenate([l.reshape(-1) for l in vec])
                            for vec in self._pending])
        tot = ops.masked_sum_u64(stacked)
        out, off = [], 0
        for shape, size in zip(shapes, sizes):
            out.append(tot[off:off + size].reshape(shape))
            off += size
        return out

    def flush(self, rnd):
        if not self._reporters:
            return None
        total = self._kernel_fold() if self.path == "kernel" else self._sum
        reported = {r.client.client_id for r in self._reporters}
        for dropped in (c for c in self._cohort if c not in reported):
            # mask recovery: remove the masks reporters shared with the
            # dropped client (the live pairs already cancelled in-sum)
            for alive in sorted(reported):
                total = self._add_masks(total, alive, dropped, sign=-1)
                self._reconstructed += 1
        # the masked fold itself is exact mod 2^64 in any order; the
        # float weight total still folds canonically so the dequantized
        # mean is schedule-independent bit-for-bit too
        reports = canonical_order(self._reporters)
        tot_w = sum(self._weight(r) for r in reports)
        leaves = [jnp.asarray(
            (x.view(np.int64).astype(np.float64)
             / (self.scale * tot_w)).astype(np.float32))
            for x in total]
        mean = jax.tree.unflatten(self._treedef, leaves)
        self._reporters, self._sum, self._pending = [], None, []
        # the masked protocol fixes the combination to a weighted mean;
        # hand it through combine as one delta so ServerOpt composes
        return self._emit(rnd, reports, self._combine([mean], [1.0]))

    def state_snapshot(self):
        return {**super().state_snapshot(), "cohort": len(self._cohort),
                "pending": len(self._reporters), "path": self.path,
                "masks_reconstructed": self._reconstructed}


# ---------------------------------------------------------------------------
# trace-analysis entry points (repro.analysis.trace)
# ---------------------------------------------------------------------------

#: cohort size the combine entries are traced at (TRACE003 scales its
#: dense-materialization threshold with this)
TRACE_COHORT = 4


def _combine_build(weighted: bool):
    def build():
        from repro.core.aggregation import aggregate
        delta = {"w": jnp.zeros((64, 64), jnp.float32),
                 "b": jnp.zeros((64,), jnp.float32)}
        deltas = tuple(jax.tree.map(jnp.array, delta)
                       for _ in range(TRACE_COHORT))
        weights = ([1.0, 2.0, 3.0, 4.0] if weighted else None)

        def combine(*ds):
            return aggregate(list(ds), weights)

        return combine, deltas
    return build


def trace_entry_points() -> List[object]:
    """Declared traceable surfaces: the pure delta combines every
    aggregator policy funnels through (O(P) incremental folds — the
    TRACE003 rule proves no O(C*P) stack sneaks back in)."""
    from repro.analysis.trace.registry import EntryPoint
    path = "src/repro/fl/aggregator.py"
    return [
        EntryPoint(name="fl.aggregate_sync", path=path, line=246,
                   build=_combine_build(False), cohort=TRACE_COHORT,
                   note=f"unweighted mean combine, C={TRACE_COHORT}"),
        EntryPoint(name="fl.aggregate_weighted", path=path, line=246,
                   build=_combine_build(True), cohort=TRACE_COHORT,
                   note=f"|D_i|-weighted combine, C={TRACE_COHORT}"),
    ]


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

AGGREGATORS = ("sync", "fedbuff", "staleness", "masked")


def make_aggregator(spec, fl=None, **kw) -> Aggregator:
    """Resolve an aggregator spec: an instance passes through; strings
    name a policy ("sync", "fedbuff", "staleness", "masked"). ``fl``
    sizes FedBuff's default buffer at half the sampled cohort."""
    if isinstance(spec, Aggregator):
        return spec
    name = spec.lower()
    if name == "sync":
        return SyncAggregator(**kw)
    if name == "fedbuff":
        if "buffer_size" not in kw and fl is not None:
            kw["buffer_size"] = max(2, (fl.clients_per_round + 1) // 2)
        return FedBuffAggregator(**kw)
    if name in ("staleness", "staleness_weighted"):
        return StalenessWeightedAggregator(**kw)
    if name in ("masked", "masked_sum", "secagg"):
        return MaskedSumAggregator(**kw)
    raise ValueError(f"unknown aggregator {spec!r}; "
                     f"options: {', '.join(AGGREGATORS)}")
