"""FL training driver (the paper's experiment), on the composable engine.

    PYTHONPATH=src python -m repro.launch.train --method both \
        --rounds 25 --out results/fl

Writes <out>_<method>.json (round-by-round history) and
<out>_<method>.ckpt (final params) via engine callbacks.
"""
from __future__ import annotations

import argparse
import os

from repro.configs import get_config, get_fl_config
from repro.data import load_corpus
from repro.fl import (CheckpointCallback, FederatedEngine,
                      HistoryWriterCallback, LoggingCallback)
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="charlm-shakespeare")
    ap.add_argument("--method", default="both",
                    help='"cafl", "fedavg", "both", or any strategy name '
                         'the engine resolves (e.g. "fedadam", "cafl+adam")')
    ap.add_argument("--executor", default="sequential",
                    choices=["sequential", "batched"])
    ap.add_argument("--server-opt", default="",
                    help='server optimizer composed onto the method '
                         '("adam" = FedAdam, "momentum" = FedAvgM)')
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--out", default="results/fl")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    setup_compile_cache()

    ds = load_corpus()
    cfg = get_config(args.arch)
    if cfg.vocab_size < ds.vocab_size:
        cfg = cfg.replace(vocab_size=ds.vocab_size)
    fl = get_fl_config().replace(executor=args.executor,
                                 server_opt=args.server_opt)
    if args.rounds:
        fl = fl.replace(rounds=args.rounds)
    if args.seed is not None:
        fl = fl.replace(seed=args.seed)
    model = build(cfg)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".",
                exist_ok=True)

    methods = ["fedavg", "cafl"] if args.method == "both" else [args.method]
    for method in methods:
        path = f"{args.out}_{method}.json"
        callbacks = [HistoryWriterCallback(path),
                     CheckpointCallback(f"{args.out}_{method}.ckpt")]
        if not args.quiet:
            callbacks.append(LoggingCallback())
        engine = FederatedEngine(model, fl, ds, strategy=method,
                                 callbacks=callbacks)
        result = engine.run()
        print(f"[{method}] saved {path}; summary:", result.summary())


if __name__ == "__main__":
    main()
