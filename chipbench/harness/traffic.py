"""The one generator every traffic file goes through.

A traffic file (``traffic/<name>.json``) is data only: the fleet, the
strategy and aggregator, the base knobs, the optimizer, evaluation, the
budgets and dual settings, the duals a run starts from, the corpus, and
how many rounds warm up and how many the check follows. This module
turns it, with the run's seed, into the system's ``FLConfig`` and a
dataset; nothing here is particular to one traffic file.

The corpus (``"kind": "embedded"``) is the system's embedded Shakespeare
text expanded to ``bytes`` characters: fixed text, so the seed moves the
cohorts, the batches and the weights.
"""
from __future__ import annotations

from typing import Dict

FL_KEYS = ("num_clients", "clients_per_round", "k_base", "s_base", "b_base",
           "seq_len", "lr", "optimizer", "weight_decay", "eval_batches",
           "eval_batch_size", "wire_topk", "aggregator", "token_preservation")


def fl_config(t: Dict, seed: int):
    from repro.configs.base import Budgets, DualConfig, FLConfig
    return FLConfig(method=t["strategy"], executor=t["executor"], seed=seed,
                    budgets=Budgets(**t["budgets"]),
                    duals=DualConfig(**t["duals"]),
                    **{k: t[k] for k in FL_KEYS})


def dataset(t: Dict, vocab: int):
    c = t["corpus"]
    if c["kind"] != "embedded":
        raise SystemExit(f"chipbench: unknown corpus kind {c['kind']!r}")
    from repro.data import load_corpus
    ds = load_corpus(target_bytes=c["bytes"])
    if ds.vocab_size > vocab:
        raise SystemExit(f"chipbench: corpus has {ds.vocab_size} symbols, "
                         f"the model {vocab}")
    return ds


def init_duals(t: Dict):
    if not t.get("init_duals"):
        return None
    from repro.core.duals import DualState
    return DualState(lam={k: float(v) for k, v in t["init_duals"].items()})
