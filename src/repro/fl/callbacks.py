"""Round callbacks: side effects hooked out of the engine loop.

The seed hardcoded ``log=print`` into ``run_federated``; everything
observational (logging, checkpointing, history export) is now a
``RoundCallback`` so the engine itself stays pure control flow.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable


class RoundCallback:
    """Override any subset; all hooks default to no-ops."""

    def on_train_start(self, engine) -> None:
        pass

    def on_round_start(self, engine, rnd: int) -> None:
        pass

    def on_round_composed(self, engine, plan) -> None:
        """Fires once the round's fleet composition is fixed: ``plan``
        is a ``repro.fl.dynamics.RoundPlan`` (available / sampled /
        survivors / dropped / late client ids + straggler time draws)."""

    def on_server_update(self, engine, update) -> None:
        """Fires every time the aggregator turns buffered client
        reports into an applied ``ServerUpdate`` — once per round under
        the sync barrier, possibly several times (or zero) per round
        under FedBuff. ``engine.params`` already includes the update."""

    def on_dual_update(self, engine, rnd: int, constraint_reports) -> None:
        """Fires after the strategy's dual update, rounds where one ran
        (a dual-free strategy, or a round with no delivered reports,
        never fires it). ``constraint_reports`` maps each device-profile
        name to its list of ``repro.constraints.ConstraintReport``
        (usage / budget / ratio / lam move / violated, one per
        registered constraint)."""

    def on_round_end(self, engine, record) -> None:
        pass

    def on_train_end(self, engine, result) -> None:
        pass


class LoggingCallback(RoundCallback):
    """The seed's per-round log line, format preserved."""

    def __init__(self, log: Callable[[str], None] = print):
        self.log = log

    def on_round_end(self, engine, r) -> None:
        kn, rat, lam = r.knobs, r.ratios, r.duals
        if not kn:          # dynamics left the round with no cohort
            self.log(f"[{engine.strategy.name}] round {r.round:3d} "
                     f"val={r.val_loss:.4f} no clients reachable "
                     f"(available={r.num_available}) {r.seconds:.1f}s")
            return
        line = (
            f"[{engine.strategy.name}] round {r.round:3d} "
            f"val={r.val_loss:.4f} "
            f"knobs=(k={kn['k']},s={kn['s']},b={kn['b']},q={kn['q']},"
            f"ga={kn['grad_accum']}) "
            f"ratios=E{rat['energy']:.2f}/C{rat['comm']:.2f}/"
            f"M{rat['memory']:.2f}/T{rat['temp']:.2f} "
            f"lam=({lam['energy']:.2f},{lam['comm']:.2f},"
            f"{lam['memory']:.2f},{lam['temp']:.2f}) "
            f"{r.seconds:.1f}s")
        if r.dropped:       # seed format preserved unless dynamics bite
            line += (f" part={len(r.participants)}/{len(r.participants) + len(r.dropped)}"
                     f" drop={len(r.dropped)}")
        if r.late_arrivals:  # async aggregation delivered late reports
            line += (f" late={len(r.late_arrivals)}"
                     f" stale={r.mean_staleness:.2f}")
        if r.updates_applied != 1:   # not the plain one-barrier round
            line += f" upd={r.updates_applied}"
        if getattr(engine, "time_mode", "rounds") == "wall_clock":
            # simulated clock, in deadline units (seed format untouched
            # in the default rounds mode)
            line += f" sim={r.sim_time:.2f}(+{r.round_seconds:.2f})"
        self.log(line)


class CheckpointCallback(RoundCallback):
    """Save engine params every ``every`` rounds (0 = final only)."""

    def __init__(self, path: str, every: int = 0):
        self.path = path
        self.every = every

    def _save(self, engine) -> None:
        from repro.checkpointing import save
        os.makedirs(os.path.dirname(os.path.abspath(self.path)) or ".",
                    exist_ok=True)
        save(self.path, engine.params)

    def on_round_end(self, engine, record) -> None:
        if self.every and record.round % self.every == 0:
            self._save(engine)

    def on_train_end(self, engine, result) -> None:
        self._save(engine)


class HistoryWriterCallback(RoundCallback):
    """Dump the round-by-round history as JSON (the format
    ``benchmarks/common.load_fl`` and the fig/table scripts read)."""

    def __init__(self, path: str):
        self.path = path

    def on_train_end(self, engine, result) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(self.path)) or ".",
                    exist_ok=True)
        payload = {
            "method": result.method,
            "summary": result.summary(),
            "history": [dataclasses.asdict(r) for r in result.history],
        }
        with open(self.path, "w") as f:
            json.dump(payload, f, indent=1)

