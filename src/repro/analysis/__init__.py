"""repro.analysis — static analysis + runtime sanitizers for the stack.

Static half: an AST rule engine (``python -m repro.analysis``) with
JAX discipline rules (PRNG key reuse, static-arg abuse, import-time
device work, per-client Python loops) and repo invariants (kernel/ref
twins, benchmark metric specs, exact wire/token accounting), gated by
a committed suppression baseline so legacy findings don't block CI
while new code is held to zero.

Runtime half (``repro.analysis.runtime``): opt-in sanitizer contexts —
``jax.transfer_guard`` wiring and a jit recompile watcher — plus
engine ``RoundCallback``s that pin the steady-state round loop at zero
implicit transfers and zero recompiles after round 1.

Schedule half (``repro.analysis.sched``): the determinism contract for
the event-driven control plane — static SCHED rules (order-sensitive
folds, unordered iteration, untied timestamps, shared RNG), a
happens-before race checker over recorded runs, and the
``SchedulePermuter`` that replays a run under adversarial legal event
permutations (``python -m repro.analysis --sched``).
"""
from __future__ import annotations

from repro.analysis.baseline import DEFAULT_BASELINE, Baseline
from repro.analysis.engine import (Analyzer, ModuleRule, ParsedModule,
                                   ProjectRule, Rule, default_rules,
                                   rule_ids, run_analysis)
from repro.analysis.findings import AnalysisResult, Finding
from repro.analysis.runtime import (RecompileWatchCallback, RecompileWatcher,
                                    TransferGuardCallback, no_transfers)

__all__ = [
    "Analyzer", "AnalysisResult", "Baseline", "DEFAULT_BASELINE",
    "Finding", "ModuleRule", "ParsedModule", "ProjectRule",
    "RecompileWatchCallback", "RecompileWatcher", "Rule",
    "TransferGuardCallback", "default_rules", "no_transfers",
    "rule_ids", "run_analysis",
]
