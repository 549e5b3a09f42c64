"""Benchmark entry point — a thin shim over the ``repro.bench``
harness plus the analysis modules (paper tables/figures, roofline).

    PYTHONPATH=src python -m benchmarks.run [--only table1,fl_engine,...]
                                            [--scale tiny|smoke|full]
                                            [--record] [--check]

Modes:

- default: run the selected analysis modules and registry areas, print
  ``name,us_per_call,derived`` CSV (one emitter, shared with each
  module's standalone ``main()``).
- ``--record``: run the registry areas and (re)write the committed
  ``BENCH_<area>.json`` baselines.
- ``--check``: run the registry areas, diff against the committed
  baselines (direction-aware, per-metric noise tolerance), write the
  fresh snapshots to ``--out`` for artifact upload, and exit non-zero
  on any regression — the CI ratchet.

``--only`` names that match no analysis module, registry area, or
benchmark are an error (exit 2), not a silent no-op.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

from benchmarks import common

#: rows()-protocol modules: analyses over results/, not timed registry
#: benchmarks (they stay outside the ratchet).
ANALYSIS_MODULES = ["table1", "fig2_constraints", "fig3_energy_temp",
                    "fig4_convergence", "roofline"]

#: registry-bearing modules; importing them populates ``repro.bench``.
REGISTRY_MODULES = ["kernel_bench", "fl_engine_bench", "wire_bench"]

#: old ``--only`` spellings for the ported modules keep working.
LEGACY_ALIASES = {"kernel_bench": "kernels", "fl_engine_bench": "fl_engine",
                  "wire_bench": "wire"}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_registry():
    for name in REGISTRY_MODULES:
        __import__(f"benchmarks.{name}")


def select(only):
    """Resolve ``--only`` prefixes to (analysis modules, registry
    areas). Raises SystemExit(2) on a prefix matching nothing."""
    from repro.bench import all_benchmarks, areas

    if not only:
        return list(ANALYSIS_MODULES), areas()
    mods, sel_areas = [], []
    bench_area = {b.name: b.area for b in all_benchmarks()}
    for prefix in only.split(","):
        prefix = LEGACY_ALIASES.get(prefix, prefix)
        hit = False
        for m in ANALYSIS_MODULES:
            if m.startswith(prefix) and m not in mods:
                mods.append(m)
                hit = True
        for a in areas():
            if a.startswith(prefix) and a not in sel_areas:
                sel_areas.append(a)
                hit = True
        for bname, barea in bench_area.items():
            if bname.startswith(prefix) and barea not in sel_areas:
                sel_areas.append(barea)
                hit = True
        if not hit:
            known = ANALYSIS_MODULES + areas() + sorted(bench_area)
            raise SystemExit(
                f"--only {prefix!r} matches no analysis module, benchmark "
                f"area, or benchmark name; known: {', '.join(known)}")
    return mods, sel_areas


def run_analysis(mods) -> int:
    failures = 0
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["rows"])
            common.emit(mod.rows())
        except Exception:
            failures += 1
            print(f"{name}.EXCEPTION,0.0,\"{traceback.format_exc(limit=1)}\"",
                  file=sys.stderr)
    return failures


def check_areas(snapshots, baseline_dir, tol_scale: float = 1.0):
    """Diff fresh area snapshots against committed baselines. Returns
    (reports, ok) — ``ok`` is False on any regression, missing
    ratcheted metric, or absent baseline file."""
    from repro.bench import Snapshot, compare_snapshots, snapshot_filename

    reports, ok = [], True
    for area, fresh in snapshots.items():
        path = os.path.join(baseline_dir, snapshot_filename(area))
        if not os.path.exists(path):
            print(f"[{area}] no baseline at {path} — run "
                  f"`python -m benchmarks.run --record` and commit it",
                  file=sys.stderr)
            ok = False
            continue
        report = compare_snapshots(Snapshot.load(path), fresh,
                                   tol_scale=tol_scale)
        reports.append(report)
        ok = ok and report.ok
    return reports, ok


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes of analysis modules, "
                         "registry areas, or benchmark names")
    ap.add_argument("--scale", default="smoke",
                    choices=("tiny", "smoke", "full"),
                    help="registry preset (committed baselines are smoke)")
    ap.add_argument("--record", action="store_true",
                    help="write BENCH_<area>.json baselines")
    ap.add_argument("--check", action="store_true",
                    help="compare a fresh run against the committed "
                         "baselines; exit non-zero on regressions")
    ap.add_argument("--baseline-dir", default=REPO_ROOT,
                    help="where BENCH_<area>.json baselines live")
    ap.add_argument("--out", default="bench-out",
                    help="--check: directory for the fresh snapshots "
                         "(CI uploads these as artifacts)")
    ap.add_argument("--tol-scale", type=float, default=1.0,
                    help="multiply every noise band")
    args = ap.parse_args(argv)
    if args.record and args.check:
        ap.error("--record and --check are exclusive")
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    load_registry()
    from repro.bench import run_area

    mods, sel_areas = select(args.only)
    log = lambda m: print(m, file=sys.stderr)

    snapshots = {a: run_area(a, scale=args.scale, log=log)
                 for a in sel_areas}
    for snap in snapshots.values():
        common.emit_snapshot(snap)

    if args.record:
        from repro.bench import snapshot_filename
        for area, snap in snapshots.items():
            path = os.path.join(args.baseline_dir, snapshot_filename(area))
            snap.save(path)
            log(f"[bench] wrote {path}")
        sys.exit(0)

    if args.check:
        from repro.bench import snapshot_filename
        os.makedirs(args.out, exist_ok=True)
        for area, snap in snapshots.items():
            snap.save(os.path.join(args.out, snapshot_filename(area)))
        reports, ok = check_areas(snapshots, args.baseline_dir,
                                  tol_scale=args.tol_scale)
        for report in reports:
            print(report.render())
        sys.exit(0 if ok else 1)

    sys.exit(1 if run_analysis(mods) else 0)


if __name__ == "__main__":
    main()
