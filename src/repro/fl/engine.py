"""The composable federated engine (Algorithm 1 as pure control flow).

``FederatedEngine`` wires six independently replaceable pieces:

    strategy   — FederatedStrategy: knobs / pure delta combination /
                 dual state. A CAFLL strategy carries its own pluggable
                 constraint stack (repro.constraints): the engine asks
                 it what to *measure* (strategy.constraints), feeds the
                 per-report measurements back for the dual update, then
                 emits on_dual_update with the per-constraint reports
                 and lets the strategy observe the round (plan, reports,
                 dynamics) so knob policies can steer server-side knobs
                 like the straggler deadline
    executor   — ClientExecutor: how LocalTrain actually runs
                 (sequential Python loop vs one jitted vmap over
                 stacked clients)
    profiles   — DeviceProfile map: per-device-class budgets + resource
                 models (the paper's homogeneous fleet is the default)
    dynamics   — FleetDynamics: availability gating x client sampling x
                 deadline stragglers (the default bundle reproduces the
                 always-available uniform-K-of-N loop bit-for-bit)
    aggregator — Aggregator: *when* client reports become server
                 updates (sync barrier / FedBuff buffered async /
                 staleness-discounted late delivery / masked sums)
    callbacks  — RoundCallback hooks for logging / checkpoints / timing

The loop is event-driven over client reports: every finished client
becomes a ``ClientReport`` (delta, weight, arrival time, staleness,
profile) fed to ``aggregator.submit``; the aggregator decides when a
``ServerUpdate`` is applied. With an ``accepts_late`` aggregator,
clients that miss the round deadline are still executed and their
report is delivered in the round their ``StragglerModel`` wall-clock
draw lands in, with ``staleness = delivery_round - training_round`` —
late work is *used* instead of discarded. While the report is in
flight the client is busy (off the sampling roster); at run end the
engine drains any partial async buffer (``Aggregator.finalize``).
Only truly lost clients (no arrival time, a barrier aggregator, or a
delivery past the run horizon) feed the dropout ledger.

The engine runs in one of two *time modes* (``repro.fl.clock``):

    time_mode="rounds"      the seed semantics — the loop advances in
                            abstract rounds, late reports deliver a
                            ``ceil(t/deadline) - 1`` round delay after
                            their training round. Bit-for-bit identical
                            to the pre-clock engine (golden-pinned).
    time_mode="wall_clock"  a ``SimClock`` advances on events: a round
                            begins when the previous barrier/buffer
                            event completes, barrier rounds last until
                            their survivors finished (or the deadline,
                            when someone missed it), a buffered-async
                            round ends at its first mid-round server
                            update, and late reports land at their
                            simulated *arrival time* instead of a round
                            delay. ``run(horizon_seconds=...)`` replaces
                            the fixed round count with a simulated-
                            seconds budget.

While a profiler trace records, each round is a host span
(``repro.spans``): ``round`` around its body and, inside it,
``eval``, ``compose``, ``execute`` (the executor), ``report``,
``aggregate``, ``accounting`` and ``dual_update``.

``repro.core.server.run_federated`` is a thin wrapper over this class
that preserves the seed API exactly.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.configs.base import FLConfig
from repro.constraints import ConstraintSet, paper_constraints
from repro.core import aggregation
from repro.core.client import ClientRunner
from repro.core.duals import DualState
from repro.core.resources import ResourceModel, calibrate
from repro.core.server import FLResult, RoundRecord, make_eval_fn
from repro.data.federated import FederatedData
from repro.data.shakespeare import CharDataset
from repro import spans
from repro.fl.aggregator import (Aggregator, ClientReport, ServerUpdate,
                                 canonical_order, make_aggregator)
from repro.fl.callbacks import RoundCallback
from repro.fl.clock import (TIME_MODES, EventQueue, RoundTimeModel, SimClock,
                            make_round_time)
from repro.fl.device import (DEFAULT_PROFILE, ClientInfo, DeviceProfile,
                             uniform_fleet)
from repro.fl.dynamics import FleetDynamics, RoundPlan
from repro.fl.executor import ClientExecutor, make_executor
from repro.fl.strategy import FederatedStrategy, make_strategy
from repro.models.zoo import Model

ExecutorSpec = Union[str, Callable[[ClientRunner], ClientExecutor]]


class FederatedEngine:
    def __init__(self, model: Model, fl: FLConfig, dataset: CharDataset,
                 strategy: Union[str, FederatedStrategy, None] = None,
                 executor: Optional[ExecutorSpec] = None,
                 profiles: Optional[Dict[str, DeviceProfile]] = None,
                 client_profiles: Optional[Sequence[str]] = None,
                 dynamics: Optional[FleetDynamics] = None,
                 aggregator: Union[str, Aggregator, None] = None,
                 callbacks: Sequence[RoundCallback] = (),
                 resources: Optional[ResourceModel] = None,
                 init_duals: Optional[DualState] = None,
                 round_time: Union[str, RoundTimeModel, None] = None,
                 event_queue: Optional[Callable[[], EventQueue]] = None):
        self.model = model
        self.fl = fl
        self.dataset = dataset
        if strategy is None:
            strategy = fl.method
        self.strategy = (make_strategy(strategy, fl, init_duals=init_duals)
                         if isinstance(strategy, str) else strategy)
        self._executor_spec: ExecutorSpec = executor or fl.executor
        if profiles is None:
            profiles, client_profiles = uniform_fleet(fl)
        assert client_profiles is not None and \
            len(client_profiles) == fl.num_clients, \
            "client_profiles must name a profile for every client"
        self._profiles_raw = profiles
        self._client_profiles = list(client_profiles)
        self.dynamics = dynamics or FleetDynamics.default(fl)
        self.aggregator = make_aggregator(aggregator or fl.aggregator, fl)
        self.callbacks = list(callbacks)
        self._base_resources = resources
        self.round_time = make_round_time(round_time, fl)
        # wall-clock event-queue factory: the schedule sanitizer
        # (repro.analysis.sched) swaps in a queue that stamps
        # adversarial tie-breaks; None keeps the plain EventQueue
        self.event_queue_factory = event_queue

        self.data = FederatedData(dataset.train, fl.num_clients, seed=fl.seed,
                                  noniid_alpha=fl.noniid_alpha)
        self.params = None            # live during run(); callbacks read it
        self.profiles: Dict[str, DeviceProfile] = {}
        self.time_mode = fl.time_mode  # resolved per run()
        self.clock: Optional[SimClock] = None
        self._runner_cache = None     # (params0, runner, executor)

    # ------------------------------------------------------------------
    def _setup(self, init_params):
        fl = self.fl
        params = init_params if init_params is not None else \
            self.model.init(jax.random.PRNGKey(fl.seed))
        # calibrate proxies at the baseline operating point (all layers
        # active) and specialize per device profile
        base = self._base_resources
        if base is None:
            from repro.core.freezing import count_params
            base = calibrate(count_params(params), fl)
        self.profiles = {name: p.with_resources(base)
                         for name, p in self._profiles_raw.items()}
        # the runner/executor pair is stateless across runs (it holds
        # only jit caches) — reuse it so repeated run() calls on one
        # engine (the schedule sanitizer replays a run many times) pay
        # compilation once
        if self._runner_cache is None:
            runner = ClientRunner(self.model, fl, self.data, base)
            executor = (make_executor(self._executor_spec, runner)
                        if isinstance(self._executor_spec, str)
                        else self._executor_spec(runner))
            self._runner_cache = (runner, executor)
        runner, executor = self._runner_cache
        return params, runner, executor

    def _client_info(self, cid: int) -> ClientInfo:
        profile = self.profiles[self._client_profiles[cid]]
        return ClientInfo(client_id=cid, profile=profile,
                          shard_size=self.data.shard_size(cid))

    def _emit(self, hook: str, *args) -> None:
        for cb in self.callbacks:
            getattr(cb, hook)(self, *args)

    def _report(self, ci: ClientInfo, kn, policy_kn, out, rnd: int,
                arrival: float) -> ClientReport:
        """Wrap one executor result as the server-side report event.
        ``weight`` routes the client's example count into aggregation —
        the single source every combine path reads it from."""
        usage = ci.profile.resources.usage(out.params_active, kn)
        energy = ci.profile.resources.usage(out.params_active, kn,
                                            include_accum=True)["energy"]
        return ClientReport(client=ci, delta=out.delta,
                            weight=float(ci.shard_size), knobs=kn,
                            policy_knobs=policy_kn, round_trained=rnd,
                            arrival_time=arrival,
                            train_loss=out.train_loss,
                            wire_mb_actual=out.wire_mb_actual,
                            params_active=out.params_active,
                            usage=usage, energy_true=energy)

    # ------------------------------------------------------------------
    def run(self, rounds: Optional[int] = None, init_params=None,
            time_mode: Optional[str] = None,
            horizon_seconds: Optional[float] = None) -> FLResult:
        """Run the federated loop.

        ``time_mode`` overrides ``fl.time_mode`` ("rounds" default;
        "wall_clock" advances a ``SimClock`` on events). A
        ``horizon_seconds`` budget (argument or ``fl.horizon_seconds``)
        implies wall-clock mode and replaces the fixed round count: the
        loop runs until the clock passes the horizon, and late reports
        that could only land beyond it are lost, exactly like rounds
        past ``rounds`` in the seed semantics. Explicit arguments beat
        the config: ``run(time_mode="rounds")`` stays in rounds mode
        even when ``fl.horizon_seconds`` is set (the config horizon is
        ignored), while combining an explicit horizon *argument* with
        an explicit non-wall-clock mode is a contradiction and raises.
        """
        fl = self.fl
        if time_mode is None:
            # no explicit mode: the config decides, and a horizon
            # (argument or config) implies wall clock
            if horizon_seconds is None:
                horizon_seconds = fl.horizon_seconds
            time_mode = ("wall_clock" if horizon_seconds is not None
                         else fl.time_mode)
        else:
            # explicit mode wins over the config horizon
            if horizon_seconds is None and time_mode == "wall_clock":
                horizon_seconds = fl.horizon_seconds
            if horizon_seconds is not None and time_mode != "wall_clock":
                raise ValueError(
                    f"horizon_seconds requires time_mode='wall_clock', "
                    f"got {time_mode!r}")
        if time_mode not in TIME_MODES:
            raise ValueError(f"unknown time_mode {time_mode!r}; "
                             f"options: {', '.join(TIME_MODES)}")
        wall = time_mode == "wall_clock"
        self.time_mode = time_mode
        explicit_rounds = rounds is not None
        rounds = rounds or fl.rounds
        # a horizon run is bounded by simulated seconds, not the round
        # count — unless the caller ALSO passed an explicit round count,
        # which stays a hard cap (arguments beat the config here too).
        # The backstop only stops a zero-length-round bug from spinning
        # forever (round durations are validated positive below).
        max_rounds = (rounds if horizon_seconds is None or explicit_rounds
                      else 100_000)
        rng = np.random.default_rng(fl.seed)
        params, runner, executor = self._setup(init_params)
        evaluate = make_eval_fn(self.model, self.dataset, fl)
        result = FLResult(method=self.strategy.name)
        heterogeneous = len(self.profiles) > 1
        # what the server measures each round: the strategy's constraint
        # set when it carries one (CAFLL), else the paper's four proxies
        cset: ConstraintSet = (getattr(self.strategy, "constraints", None)
                               or paper_constraints())

        dynamics = self.dynamics
        dynamics.reset()
        self.strategy.reset()
        agg = self.aggregator
        agg.reset(self.strategy.aggregate)
        fleet = [self._client_info(c) for c in range(fl.num_clients)]
        clock = self.clock = SimClock()
        rtm = self.round_time
        server_cost = getattr(rtm, "server_seconds", 0.0)
        # in-flight late reports. rounds mode: delivery round -> reports,
        # plus the busy map (client_id -> delivery round). wall-clock
        # mode: an arrival-time event queue plus the busy set (freed the
        # moment the report is delivered). Either way a straggler is
        # still *training* until its wall clock ends, so it cannot be
        # offered to the sampler again before its report lands —
        # otherwise a 2x slow device would contribute 2x concurrent
        # client-rounds
        pending: Dict[int, List[ClientReport]] = {}
        busy_until: Dict[int, int] = {}
        pending_q = (self.event_queue_factory()
                     if self.event_queue_factory is not None
                     else EventQueue())
        busy: set = set()

        self.params = params
        self._emit("on_train_start")
        t = 0
        while t < max_rounds:
            if wall and horizon_seconds is not None and result.history \
                    and clock.now >= horizon_seconds:
                break
            t += 1
            with spans.span("round", rnd=t):
                t0 = time.time()
                round_start = clock.now
                self._emit("on_round_start", t)
                with spans.span("eval"):
                    val_loss = evaluate(params)

                # --- round composition: gate, sample, deadline ---------
                with spans.span("compose"):
                    if wall:
                        roster = ([ci for ci in fleet
                                   if ci.client_id not in busy]
                                  if busy else fleet)
                    else:
                        # sorted: dict order here is insertion (= past
                        # delivery) order; expiry must not depend on it
                        for cid in sorted(c for c, due in busy_until.items()
                                          if due < t):
                            del busy_until[cid]
                        roster = ([ci for ci in fleet
                                   if ci.client_id not in busy_until]
                                  if busy_until else fleet)
                    avail, clients = dynamics.compose(
                        t, roster, rng, self.strategy.duals_snapshot())
                    base_knobs = self.strategy.configure_round(t, clients)
                    knobs = dynamics.adjust_knobs(clients, base_knobs)
                    surv_idx, drop_idx, times = dynamics.finish(
                        t, clients, knobs, rng)
                    # the deadline in force DURING this round (a
                    # deadline-aware knob policy may widen it in
                    # observe_round, which must only affect the next
                    # round's duration)
                    deadline = getattr(dynamics.stragglers, "deadline", None)
                    # deadline-missers split into late (report still arrives,
                    # if the aggregator takes it and the run is still going at
                    # delivery time) vs lost (discarded for good: no arrival
                    # clock, a barrier aggregator, or due past the horizon —
                    # work the simulation would pay for but could never apply)
                    late_idx: List[int] = []
                    lost_idx: List[int] = []
                    due_round: Dict[int, int] = {}
                    if wall:
                        # a late report lands at its absolute arrival time; it
                        # is lost only when that time is past the horizon (with
                        # a round-count budget the end time is unknown, so the
                        # report stays in flight and undelivered leftovers are
                        # counted lost at run end)
                        for i in drop_idx:
                            if agg.accepts_late and times and (
                                    horizon_seconds is None
                                    or round_start + times[i]
                                    <= horizon_seconds):
                                late_idx.append(i)
                            else:
                                lost_idx.append(i)
                    else:
                        for i in drop_idx:
                            delay = (dynamics.stragglers.late_rounds(times[i])
                                     if agg.accepts_late and times else None)
                            if delay is not None and t + delay <= rounds:
                                late_idx.append(i)
                                due_round[i] = t + delay
                            else:
                                lost_idx.append(i)
                    survivors = [clients[i] for i in surv_idx]
                    plan = RoundPlan(
                        round=t,
                        available=tuple(ci.client_id for ci in avail),
                        sampled=tuple(ci.client_id for ci in clients),
                        survivors=tuple(ci.client_id for ci in survivors),
                        dropped=tuple(clients[i].client_id for i in drop_idx),
                        times=tuple(times),
                        late=tuple(clients[i].client_id for i in late_idx))
                self._emit("on_round_composed", plan)
                if lost_idx:
                    self.strategy.on_dropout([clients[i] for i in lost_idx])
                agg.begin_round(t, clients)

                # --- LocalTrain: survivors report now, late clients'
                # reports are queued for the round their clock lands in ----
                exec_idx = list(surv_idx) + late_idx
                with spans.span("execute"):
                    outs = (executor.run_round(
                        params, [(clients[i], knobs[i]) for i in exec_idx])
                        if exec_idx else [])
                with spans.span("report"):
                    reports = {
                        i: self._report(clients[i], knobs[i], base_knobs[i],
                                        o, t, times[i] if times else 0.0)
                        for i, o in zip(exec_idx, outs)}
                if not wall:
                    for i in late_idx:
                        pending.setdefault(due_round[i], []).append(reports[i])
                        busy_until[clients[i].client_id] = due_round[i]

                # --- deliver reports; the aggregator decides when they
                # become server updates ------------------------------------
                # the barrier's duration: min(deadline, slowest survivor)
                # under a straggler clock, the knob-derived cohort time
                # otherwise (see RoundTimeModel)
                base_dur = rtm.round_seconds(clients, knobs, times, surv_idx,
                                             deadline)
                if wall and base_dur <= 0.0:
                    # a custom model returning non-positive durations would
                    # spin the horizon loop into the round backstop and
                    # return a normal-looking result well short of the
                    # horizon — fail loudly instead (KnobRoundTime enforces
                    # this itself via its idle floor)
                    raise ValueError(
                        f"{type(rtm).__name__}.round_seconds returned "
                        f"{base_dur!r}; wall-clock rounds need positive "
                        f"durations")
                applied: List[ServerUpdate] = []

                def _apply(update, params):
                    params = aggregation.apply_delta(params, update.delta)
                    self.params = params
                    applied.append(update)
                    self._emit("on_server_update", update)
                    return params

                with spans.span("aggregate"):
                    if wall:
                        round_end_cap = round_start + base_dur
                        # earlier rounds' in-flight reports landing inside this
                        # round's window — popped BEFORE this round's missers
                        # join the queue, so a deadline-misser can never be
                        # delivered in its own round (e.g. through the server-
                        # cost tail of the cap); like rounds mode, a miss is
                        # always at least one round late
                        due = pending_q.pop_until(round_end_cap)
                        for i in late_idx:
                            pending_q.push(round_start + times[i], reports[i])
                            busy.add(clients[i].client_id)
                        events = [pending_q.stamp(
                            round_start + (times[i] if times
                                           else rtm.client_seconds(clients[i],
                                                                   knobs[i])),
                            reports[i]) for i in surv_idx]
                        events = sorted(events + due,
                                        key=lambda e: e.sort_key())
                        arrived = []
                        inbox: List[ClientReport] = []
                        round_end = round_end_cap
                        cut = None
                        for k, ev in enumerate(events):
                            rep = ev.report
                            clock.advance_to(
                                ev.arrival, f"deliver:c{rep.client.client_id}")
                            if rep.round_trained < t:
                                arrived.append(rep)
                            busy.discard(rep.client.client_id)
                            rep.round_submitted = t
                            rep.staleness = t - rep.round_trained
                            inbox.append(rep)
                            update = agg.submit(rep)
                            if update is not None:
                                params = _apply(update, params)
                                if agg.applies_mid_round:
                                    # the buffer event completes this round:
                                    # deliveries after it belong to the next
                                    # round's inbox (their owners stay busy)
                                    round_end = ev.arrival + server_cost
                                    cut = k + 1
                                    break
                        if cut is not None:
                            for ev in events[cut:]:
                                pending_q.push_event(ev)
                                busy.add(ev.report.client.client_id)
                        else:
                            update = agg.flush(t)
                            if update is not None:
                                params = _apply(update, params)
                        clock.advance_to(round_end, f"round_end:{t}")
                    else:
                        arrived = sorted(pending.pop(t, ()),
                                         key=lambda r: (r.round_trained,
                                                        r.arrival_time))
                        inbox = arrived + [reports[i] for i in surv_idx]
                        for rep in inbox:
                            rep.round_submitted = t
                            rep.staleness = t - rep.round_trained
                            update = agg.submit(rep)
                            if update is not None:
                                params = _apply(update, params)
                        update = agg.flush(t)
                        if update is not None:
                            params = _apply(update, params)
                        # pure accounting in rounds mode: the clock advances by
                        # the same barrier duration wall-clock mode would bill,
                        # so sim_time / round_seconds stay comparable across
                        # modes without touching the seed loop semantics
                        clock.advance_to(round_start + base_dur,
                                         f"round_end:{t}")
                dynamics.settle(clients, base_knobs, knobs,
                                list(surv_idx) + late_idx, lost_idx)

                # --- constraint accounting over the reports delivered -----
                # folded over the *canonical* report order, not the
                # delivery order: the float means (and through them the
                # dual trajectory) are a function of the report set, so a
                # schedule permutation that only reorders simultaneous
                # deliveries cannot move a single bit of the accounting.
                # `inbox` itself keeps delivery order — participants /
                # late_arrivals are schedule telemetry and record it.
                with spans.span("accounting"):
                    stats = canonical_order(inbox)
                    usages = [cset.measure(rep) for rep in stats]
                    if stats:
                        usage = {n: float(np.mean([u[n] for u in usages]))
                                 for n in cset.names}
                        train_loss = float(np.mean([rep.train_loss
                                                    for rep in stats]))
                        wire_mb = float(np.mean([rep.wire_mb_actual
                                                 for rep in stats]))
                        energy = float(np.mean([rep.energy_true
                                                for rep in stats]))
                    else:               # everyone dropped / nobody reachable
                        usage = cset.zero_usage()
                        train_loss = wire_mb = energy = 0.0
                    ratios = cset.ratios(usage, fl.budgets)
                with spans.span("dual_update"):
                    duals_by_profile = self.strategy.update_state(
                        usages, [rep.client for rep in stats])
                    creports = self.strategy.constraint_reports()
                if creports:
                    self._emit("on_dual_update", t, creports)
                # round telemetry back to the strategy (knob policies may
                # steer server-side knobs, e.g. widen the straggler
                # deadline, before the next round is composed)
                self.strategy.observe_round(plan, inbox, dynamics)

                # record the strategy's policy knobs, not any one client's
                # private carry boost (that stays visible via RoundPlan)
                duals_rec = _default_duals(duals_by_profile, cset.names)
                record = RoundRecord(
                    round=t, val_loss=val_loss,
                    knobs=base_knobs[0].as_dict() if base_knobs else {},
                    usage=usage, ratios=ratios,
                    duals=duals_rec,
                    constraints={n: {"ratio": ratios[n],
                                     "lam": duals_rec.get(n, 0.0),
                                     "violated": ratios[n] > 1.0}
                                 for n in cset.names},
                    train_loss=train_loss,
                    wire_mb_actual=wire_mb,
                    energy_true=energy,
                    seconds=time.time() - t0,
                    sim_time=clock.now,
                    round_seconds=clock.now - round_start,
                    per_profile=_per_profile_record(
                        [rep.client for rep in stats],
                        [rep.policy_knobs for rep in stats], usages,
                        duals_by_profile, cset)
                    if heterogeneous and stats else {},
                    participants=[rep.client.client_id for rep in inbox],
                    dropped=[clients[i].client_id for i in lost_idx],
                    num_available=len(avail),
                    updates_applied=len(applied),
                    reports_applied=sum(len(u.reports) for u in applied),
                    mean_staleness=(float(np.mean([rep.staleness
                                                   for rep in stats]))
                                    if stats else 0.0),
                    late_arrivals=[rep.client.client_id for rep in arrived])
                result.history.append(record)
                self._emit("on_round_end", record)

        # drain whatever the policy still buffers (e.g. FedBuff's
        # partial buffer): those clients were executed, accounted and
        # debt-settled, so their work must reach the final params
        update = agg.finalize(t)
        if update is not None:
            params = aggregation.apply_delta(params, update.delta)
            self.params = params
            self._emit("on_server_update", update)
            last = result.history[-1]
            last.updates_applied += 1
            last.reports_applied += len(update.reports)
        if wall and len(pending_q):
            # in-flight reports whose arrival never fell inside a round:
            # the run ended first. The work was executed and accounted,
            # but — like rounds-mode losses past the horizon — it never
            # reaches the model; the final record owns the loss.
            leftovers = pending_q.drain()
            if result.history:
                last = result.history[-1]
                last.dropped = (list(last.dropped)
                                + [ev.report.client.client_id
                                   for ev in leftovers])
            self.strategy.on_dropout([ev.report.client for ev in leftovers])

        result.final_params = params
        result.history[-1].val_loss = evaluate(params)
        self._emit("on_train_end", result)
        return result


def _default_duals(duals_by_profile: Dict[str, Dict[str, float]],
                   names) -> Dict[str, float]:
    """The record's back-compat scalar dual dict: the default profile's
    duals, the sole profile's, or zeros (fedavg keeps no duals)."""
    if DEFAULT_PROFILE in duals_by_profile:
        return dict(duals_by_profile[DEFAULT_PROFILE])
    if duals_by_profile:
        return dict(next(iter(duals_by_profile.values())))
    return {n: 0.0 for n in names}


def _per_profile_record(clients: List[ClientInfo], knobs, usages,
                        duals_by_profile,
                        cset: ConstraintSet) -> Dict[str, Dict]:
    """Per-device-profile round record: usage means grouped by profile
    as one masked array reduction over the (client, constraint) usage
    matrix — the grouping is O(profiles) Python, never O(clients)."""
    profiles = {ci.profile.name: ci.profile for ci in clients}
    name_arr = np.asarray([ci.profile.name for ci in clients])
    usage_mat = np.asarray([[u[n] for n in cset.names] for u in usages],
                           dtype=np.float64)
    out: Dict[str, Dict] = {}
    for pname in sorted(profiles):
        mask = name_arr == pname
        mean = usage_mat[mask].mean(axis=0)
        usage = {n: float(v) for n, v in zip(cset.names, mean)}
        slot = {"clients": int(mask.sum()),
                "knobs": knobs[int(np.argmax(mask))].as_dict(),
                "usage": usage,
                "ratios": cset.ratios(usage, profiles[pname].budgets)}
        if pname in duals_by_profile:
            slot["duals"] = dict(duals_by_profile[pname])
        out[pname] = slot
    return out
