"""Readings that the limits of ``correct`` are set from. Not part of a
benchmark run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--program] [--variants control,half_batch]

In one process, for each seed:

``--program``    the system itself: the cell's warm-up and one window
                 round through the same session as ``run.py``, then the
                 check's numbers (the lower readings);
``control``      the reference in the precision one step below the
                 configured one, put in the system's place (an upper
                 reading); any other precision kind of the reference
                 module is read the same way;
``half_batch``   the reference with half of every microbatch left out,
                 the mean taken over the rest (a planted fault).

Prints one JSON line per seed and kind. Needs the chip, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None, require_chip=True):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--variants", default="")
    args = ap.parse_args(argv)
    import jax
    from harness import check, device, files, main as run_main
    from harness.session import Session

    cell = files.cell(args.workload)
    if require_chip:
        device.require_accelerator(jax, cell.chips)
    run_main.setup_cache(jax)

    def program(seed):
        t0 = time.perf_counter()
        sess = Session(cell, seed, seconds=0.0).build()
        train = sess.data.train
        sess.run()
        cap, make_w0 = sess.capture, sess.initial_weights
        sess.free()
        ok, _table, values = check.check(cell, seed, train, cap, make_w0(),
                                         rounds=True)
        emit(seed, "program", t0, correct=ok, **values)

    def emit(seed, kind, t0, **values):
        print(json.dumps({"cell": cell.name, "seed": seed, "kind": kind,
                          "seconds": time.perf_counter() - t0, **values}),
              flush=True)

    variants = [v for v in args.variants.split(",") if v]
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.program:
            program(seed)
        if not variants:
            continue
        sess = Session(cell, seed, seconds=0.0).build()
        train, w0 = sess.data.train, sess.initial_weights()
        sess.free()
        t0 = time.perf_counter()
        ref = check.run_reference(cell, seed, train, w0)
        emit(seed, "reference", t0)
        for v in variants:
            t0 = time.perf_counter()
            emit(seed, v, t0, **check.against_reference(cell, seed, train,
                                                        w0, v, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
