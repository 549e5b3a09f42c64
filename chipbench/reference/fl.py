"""Plain reference of one federated run's first rounds (Algorithm 1 of
the CAFL-L paper, as the traffic files configure it).

It imports nothing of the system under test. Everything it needs is
re-derived from the seed and the cell's files, with the semantics the
system documents:

* the fleet: ``num_clients`` contiguous, equal shards of the training
  stream (the last takes the remainder); client ``i`` draws its batches
  from ``np.random.default_rng(seed + 1000 + i)``, each batch ``b``
  uniform start offsets in ``[0, len(shard) - seq - 1)``;
* the cohort: ``np.random.default_rng(seed).choice(N, K, replace=False)``
  once per round, every client always available, no stragglers;
* the knobs: FedAvg's fixed ``(k_base, s_base, b_base, q=0, ga=1)``, or
  CAFL-L's Eq. 5-7 map of the duals with Eq. 8's ``ga = ceil(s_base
  b_base / (s b))``;
* LocalTrain: ``s`` AdamW steps, each over the mean gradient of ``ga``
  microbatches, gradients and updates multiplied by the freezing mask,
  decay on every stored leaf of two or more axes;
* the wire: at ``q > 0`` each leaf's delta is split into blocks of 256,
  scaled by ``absmax / (L - 1)`` with ``L = 2^(bits - 1)``, rounded,
  clipped and scaled back (``bits`` 8 at q=1, 2 at q=2), then masked;
* aggregation: the plain mean of the cohort's deltas, added to the
  server's parameters in float32 and stored back in their own type;
* the duals: the paper's Appendix-A.1 proxies calibrated to Table 1's
  FedAvg row, the cohort's mean usage over the budget, and Eq. 4's
  dead-zone step, clipped to ``[0, lambda_max]``.

The control-plane arithmetic (usage, duals, knobs, wire bytes) is host
float64 in the order the paper's formulas are written, so it is exact
against a system that follows them. The numeric part trains the whole
cohort of a round in one vmapped program.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference.decoder import Precision

BLOCK = 256
BYTES_PER_PARAM = {0: 4.0, 1: 1.0, 2: 0.25}
TABLE1_FEDAVG = {"energy": 4.52e6, "comm": 5.18, "temp": 0.62, "memory": 0.31}
CONSTRAINTS = ("energy", "comm", "memory", "temp")
Q_THRESHOLDS = (0.25, 1.0)
#: wire accounting unit (2**-11 B) and dense per-parameter costs in it
UNIT_BYTES = 2.0 ** -11
DENSE_UNITS = {0: 8192, 1: 2080, 2: 544}


# ---------------------------------------------------------------------------
# fleet, cohort and batches
# ---------------------------------------------------------------------------


def shard_bounds(n_tokens: int, n_clients: int) -> np.ndarray:
    sizes = (np.full(n_clients, 1.0 / n_clients) * n_tokens).astype(int)
    sizes[-1] += n_tokens - sizes.sum()
    assert (sizes >= 1).all(), "fleet larger than the stream"
    return np.concatenate([[0], np.cumsum(sizes)])


class Fleet:
    def __init__(self, train: np.ndarray, n_clients: int, seed: int):
        b = shard_bounds(len(train), n_clients)
        self.shards = [train[b[i]:b[i + 1]] for i in range(n_clients)]
        self.rngs = [np.random.default_rng(seed + 1000 + i)
                     for i in range(n_clients)]

    def shard_size(self, cid: int) -> int:
        return len(self.shards[cid])

    def batch(self, cid: int, b: int, seq: int):
        data = self.shards[cid]
        ix = self.rngs[cid].integers(0, len(data) - seq - 1, size=b)
        toks = np.stack([data[i:i + seq] for i in ix])
        targs = np.stack([data[i + 1:i + seq + 1] for i in ix])
        return toks, targs


def cohort(rng: np.random.Generator, n: int, k: int) -> List[int]:
    if n < k:
        return list(range(n))
    return [int(i) for i in rng.choice(n, size=k, replace=False)]


# ---------------------------------------------------------------------------
# knobs, usage, duals (host float64, the formulas' own order)
# ---------------------------------------------------------------------------


def token_accum(t: Dict, s: int, b: int) -> int:
    return max(1, math.ceil(t["s_base"] * t["b_base"] / (s * b)))


def knobs(t: Dict, lam: Dict[str, float]) -> tuple:
    """-> (k, s, b, q, ga) for the round."""
    if t["strategy"] == "fedavg":
        return (t["k_base"], t["s_base"], t["b_base"], 0, 1)
    d = t["duals"]
    le, lc, lm, lt = (lam["energy"], lam["comm"], lam["memory"],
                      lam["temp"])
    k = max(d["k_min"], t["k_base"]
            - math.floor(d["alpha_k"] * (lc + lm + 0.5 * lt)))
    s = max(d["s_min"], math.floor(t["s_base"]
                                   * (1 - d["beta_s"] * (le + lt))))
    b = max(d["b_min"], math.floor(t["b_base"]
                                   / (1 + d["gamma_b"] * (lt + lm))))
    q = 2 if lc > Q_THRESHOLDS[1] else 1 if lc > Q_THRESHOLDS[0] else 0
    return (k, s, b, q, token_accum(t, s, b))


def count_active(shapes, mask) -> float:
    total = 0.0
    for leaf, m in zip(jax.tree.leaves(shapes), jax.tree.leaves(mask)):
        m_arr = np.asarray(m)
        size = np.prod(leaf.shape)
        if m_arr.ndim == 0:
            total += float(m_arr) * size
        else:
            total += float(np.mean(m_arr)) * size
    return total


def wire_mb(shapes, mask, q: int) -> float:
    units = 0
    for leaf, m in zip(jax.tree.leaves(shapes), jax.tree.leaves(mask)):
        m_arr = np.asarray(m)
        size = int(np.prod(leaf.shape))
        if m_arr.ndim:
            n = int(np.count_nonzero(m_arr)) * (size // m_arr.size)
        else:
            n = size * int(m_arr.item())
        units += n * DENSE_UNITS[q]
    return units * UNIT_BYTES / 1e6


def calibrate(p_total: int, t: Dict) -> Dict[str, float]:
    s, b = t["s_base"], t["b_base"]
    p = float(p_total)
    rem = TABLE1_FEDAVG["temp"] - 0.35
    return {"alpha_e": TABLE1_FEDAVG["energy"] / (p * s * b),
            "kappa_c": TABLE1_FEDAVG["comm"] / (p * BYTES_PER_PARAM[0]),
            "sparsity": 1.0, "alpha_m": 1.0,
            "beta_m": (TABLE1_FEDAVG["memory"] - 0.2) / (p * b),
            "alpha_t": 1.0, "gamma_t": (rem / 2) / s, "delta_t": (rem / 2) / b}


def usage(c: Dict[str, float], active: float, kn: tuple) -> Dict[str, float]:
    _k, s, b, q, _ga = kn
    return {"energy": c["alpha_e"] * active * s * b,
            "comm": c["sparsity"] * active * BYTES_PER_PARAM[q] * c["kappa_c"],
            "memory": c["alpha_m"] * (0.2 + c["beta_m"] * active * b),
            "temp": c["alpha_t"] * (0.35 + c["gamma_t"] * s + c["delta_t"] * b)}


def dual_step(lam: float, ratio: float, d: Dict) -> float:
    x = ratio - 1.0
    step = 0.0 if abs(x) <= d["deadzone"] else x
    lam = lam + d["eta"] * step
    return float(min(max(lam, 0.0), d["lambda_max"]))


# ---------------------------------------------------------------------------
# LocalTrain and the wire, on the device
# ---------------------------------------------------------------------------


def qdq(x, bits: int):
    """Blockwise absmax wire round trip of one leaf, float32."""
    levels = 2 ** (bits - 1) - 1
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    blocks = jnp.pad(flat, (0, (-n) % BLOCK)).reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) * jnp.float32(
        1.0 / levels)
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.rint(blocks / safe), -levels, levels)
    return (codes * scale).reshape(-1)[:n].reshape(x.shape)


def local_train_fn(loss_fn: Callable, cfg: Dict, prec: Precision, t: Dict,
                   ga: int):
    """Jitted LocalTrain over the cohort: (w0, mask, tokens,
    targets) with tokens (C, s, ga, b, S) -> (deltas (C, ...), losses (C,))."""
    lr, wd = t["lr"], t["weight_decay"]
    b1, b2, eps = 0.9, 0.999, 1e-8
    grad = jax.value_and_grad(lambda w, tk, tg: loss_fn(w, tk, tg, cfg, prec))

    def one(w0, mask, tokens, targets):
        w = jax.tree.map(lambda a: a.astype(prec.param), w0)
        zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)

        def step(carry, xs):
            w, mu, nu, n = carry
            tk, tg = xs

            def micro(acc, mb):
                gs, ls = acc
                l, g = grad(w, mb[0], mb[1])
                gs = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gs, g)
                return (gs, ls + l.astype(jnp.float32)), None

            (gs, ls), _ = jax.lax.scan(micro, (zeros, jnp.float32(0)),
                                       (tk, tg))
            g = jax.tree.map(lambda a, m: a / ga * m, gs, mask)
            n = n + 1.0
            mu = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mu, g)
            nu = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                              nu, g)
            bc1, bc2 = 1 - b1 ** n, 1 - b2 ** n

            def new_w(p, m_, v_, m):
                upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                if p.ndim >= 2:
                    upd = upd + wd * p.astype(jnp.float32)
                upd = -lr * upd * m
                return (p.astype(jnp.float32) + upd).astype(p.dtype)

            w = jax.tree.map(new_w, w, mu, nu, mask)
            return (w, mu, nu, n), ls / ga

        (w, _, _, _), losses = jax.lax.scan(
            step, (w, zeros, zeros, jnp.float32(0)), (tokens, targets))
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), w, w0)
        return delta, jnp.mean(losses)

    return jax.jit(jax.vmap(one, in_axes=(None, None, 0, 0)))


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norms, in tree order."""
    return _leaf_norms(tree)


def _ship_fn(q: int):
    """Wire round trip + mask for the cohort's deltas -> their sum and
    each client's per-leaf norms."""
    bits = {1: 8, 2: 2}.get(q)

    def ship(deltas, mask):
        def one(d):
            if bits is not None:
                d = jax.tree.map(lambda l: qdq(l, bits), d)
            return jax.tree.map(lambda l, m: l * m, d, mask)

        shipped = jax.vmap(one)(deltas)
        norms = jax.vmap(_leaf_norms)(shipped)
        return jax.tree.map(lambda l: jnp.sum(l, axis=0), shipped), norms

    return jax.jit(ship)


# ---------------------------------------------------------------------------
# the rounds
# ---------------------------------------------------------------------------


def run(model, cfg: Dict, t: Dict, seed: int, train: np.ndarray, w0,
        rounds: int, prec: Precision, half_batch: bool = False) -> Dict:
    """Follow the first ``rounds`` rounds from the parameters ``w0``.

    ``half_batch`` plants a fault for the check's calibration: every
    microbatch loses its second half of rows, the mean taken over the
    rest.

    -> {"rounds": [per-round record], "next_knobs": tuple,
        "change_norms": per-leaf norms of w_rounds - w0 (float32)}
    """
    n, kpr, seq = t["num_clients"], t["clients_per_round"], t["seq_len"]
    fleet = Fleet(train, n, seed)
    rng = np.random.default_rng(seed)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          w0)
    consts = calibrate(sum(int(np.prod(l.shape))
                           for l in jax.tree.leaves(shapes)), t)
    lam = {c: float(t.get("init_duals", {}).get(c, 0.0))
           for c in CONSTRAINTS}
    budgets = {"energy": t["budgets"]["energy"],
               "comm": t["budgets"]["comm_mb"],
               "memory": t["budgets"]["memory"],
               "temp": t["budgets"]["temp"]}
    w = jax.tree.map(lambda a: a.astype(prec.param), w0)
    trains: Dict[int, Callable] = {}
    ships: Dict[int, Callable] = {}
    out: List[Dict] = []
    for _ in range(rounds):
        ids = cohort(rng, n, kpr)
        kn = knobs(t, lam)
        k, s, b, q, ga = kn
        mask = model.trainable_mask(shapes, cfg, k)
        mask_dev = jax.tree.map(jnp.asarray, mask)
        if ga not in trains:
            trains[ga] = local_train_fn(model.loss, cfg, prec, t, ga)
        if q not in ships:
            ships[q] = _ship_fn(q)
        toks, targs = [], []
        for cid in ids:
            rows = [fleet.batch(cid, b, seq) for _ in range(s * ga)]
            tk = np.stack([r[0] for r in rows]).reshape(s, ga, b, seq)
            tg = np.stack([r[1] for r in rows]).reshape(s, ga, b, seq)
            if half_batch:
                tk, tg = tk[:, :, : b // 2], tg[:, :, : b // 2]
            toks.append(tk)
            targs.append(tg)
        deltas, ls = trains[ga](w, mask_dev, jnp.asarray(np.stack(toks)),
                                jnp.asarray(np.stack(targs)))
        total, nrm = ships[q](deltas, mask_dev)
        del deltas
        ls, nrm = np.asarray(ls), np.asarray(nrm)
        losses = {cid: float(ls[j]) for j, cid in enumerate(ids)}
        norms = {cid: nrm[j] for j, cid in enumerate(ids)}
        mean = jax.tree.map(lambda a: a * jnp.float32(1.0 / len(ids)), total)
        w = jax.tree.map(lambda p, d: (p.astype(jnp.float32) + d
                                       ).astype(p.dtype), w, mean)
        active = count_active(shapes, mask)
        us = [usage(consts, active, kn) for _ in ids]
        if t["strategy"] == "cafl":
            for c in CONSTRAINTS:
                mean_u = sum(u[c] for u in us) / len(us)
                lam[c] = dual_step(lam[c], mean_u / budgets[c], t["duals"])
        out.append({"sampled": ids, "knobs": kn,
                    "losses": losses, "client_norms": norms,
                    "wire_mb": wire_mb(shapes, mask, q),
                    "update_norms": np.asarray(leaf_norms(mean)),
                    "duals": dict(lam)})
    change = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                          - b.astype(jnp.float32), w, w0)
    return {"rounds": out, "next_knobs": knobs(t, lam),
            "change_norms": np.asarray(leaf_norms(change))}


def first_grad_norms(model, cfg: Dict, w0, tokens, targets,
                     prec: Precision) -> np.ndarray:
    """Per-leaf norms of the loss gradient at ``w0`` on one batch: the
    rule that leaves out of the change comparison the leaves whose
    gradient is nought to rounding reads them."""
    g = jax.jit(jax.grad(lambda w, a, b: model.loss(w, a, b, cfg, prec)))(
        w0, tokens, targets)
    return np.asarray(leaf_norms(g))
