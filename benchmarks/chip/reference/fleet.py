"""One synchronous FedCore fleet round in plain jax.numpy.

Semantics (FedCore Alg. 1 with the fleet's padding and sample order):

* a client of m samples is padded to ``m_pad`` = the next power-of-two
  number of batches of B; padded rows weigh nothing;
* every epoch visits all ``m_pad`` slots in a permutation drawn from
  ``(fleet seed, round, client id)``, B at a time, and takes one SGD step
  on the weighted mean loss of each batch;
* a client with coreset budget k = 0 trains E such epochs;
* a straggler (k > 0) computes last-layer gradient features at the
  round-start parameters, takes its k medoids, trains one full epoch and
  then E − 1 full-batch steps on the medoids weighted by cluster size;
* the server takes the mean of the client parameters weighted by m.

The medoids are given (the selection under test) or, where the
reference stands in for the program, chosen by ``kmedoids64.fasterpam``.

Every step runs as one gradient program over a fixed (lanes, B) block of
rows: a step over more than B rows (a coreset step) sums the gradients of
its B-row chunks, and a lane with no step to take gets rows of weight
zero, which leave it unchanged.  So the reference compiles three small
programs (gradient, update, features), whatever the cohort's shapes.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import kmedoids64
from reference import models as refmodels

LANES = 32        # clients per block


def m_padded(m: int, batch: int) -> int:
    n = -(-m // batch)
    return (1 << max(n - 1, 0).bit_length()) * batch


def floor_pow4(b: int) -> int:
    return 1 << (((max(int(b), 1).bit_length() - 1) // 2) * 2)


def coreset_k(m: int, budget: int) -> int:
    """Coreset size of a client with budget b: 0 (full set) when b ≥ m,
    else the largest power of four ≤ b."""
    return 0 if budget >= m else floor_pow4(budget)


def permutations(seed: int, round_seed: int, cid: int, epochs: int,
                 m_pad: int) -> np.ndarray:
    base = np.tile(np.arange(m_pad), (epochs, 1))
    rng = np.random.default_rng(np.random.SeedSequence((seed, round_seed,
                                                        cid)))
    return rng.permuted(base, axis=1).astype(np.int32)


class Reference:
    """The reference's programs for one model and precision."""

    def __init__(self, model, lr: float, epochs: int,
                 precision: Optional[str], batch: int = 32,
                 lanes: int = LANES):
        self.model = model
        self.precision = precision
        self.batch = batch
        self.lanes = lanes
        dt = model.dtype

        def lane_grad(p, x, y, w):
            def weighted_nll(q):
                return jnp.sum(refmodels.nll(model, q, x, y) * w)
            v, g = jax.value_and_grad(weighted_nll)(p)
            return g, v

        def apply(p, g, s):
            def upd(a, b):
                scale = 1.0 / jnp.maximum(s, 1e-9)
                scale = scale.reshape((-1,) + (1,) * (b.ndim - 1))
                return (a - lr * (b * scale)).astype(dt)
            return jax.tree.map(upd, p, g)

        self._grad = jax.jit(jax.vmap(lane_grad))
        self._apply = jax.jit(apply)
        self._feats = jax.jit(jax.vmap(
            lambda p, x, y: refmodels.features(model, p, x, y),
            in_axes=(None, 0, 0)))

    def _ctx(self):
        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)

    def grad(self, ps, x, y, w):
        with self._ctx():
            return self._grad(ps, x, y, w)

    def apply(self, ps, g, s):
        return self._apply(ps, g, s)

    def feats(self, p, x, y):
        with self._ctx():
            return self._feats(p, x, y)


class _Rows:
    """Gathers (lanes, B) blocks of rows from per-client arrays; a row
    index ≥ m (padding) reads row 0 and must carry weight zero."""

    def __init__(self, clients, cids, batch):
        self.x = [np.asarray(clients[c]["x"]) for c in cids]
        self.y = [np.asarray(clients[c]["y"]) for c in cids]
        self.batch = batch

    def block(self, rows: List[Optional[np.ndarray]]):
        xs, ys = [], []
        for i, r in enumerate(rows):
            r = np.zeros(self.batch, np.int64) if r is None else r
            r = np.where(r < len(self.y[i]), r, 0)
            xs.append(self.x[i][r])
            ys.append(self.y[i][r])
        return np.stack(xs), np.stack(ys)


def _chunks(rows: np.ndarray, weights: np.ndarray, b: int):
    """(rows, weights) cut into B-row chunks, the last padded with
    weight zero."""
    n = -(-len(rows) // b)
    pad = n * b - len(rows)
    rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
    weights = np.concatenate([weights, np.zeros(pad, np.float32)])
    return [(rows[i * b:(i + 1) * b], weights[i * b:(i + 1) * b])
            for i in range(n)]


def _features(ref: Reference, p0, rows: _Rows, sizes: List[int]):
    """Each lane's features of its valid rows, at p0."""
    b = ref.batch
    out = [[] for _ in sizes]
    for j in range(max(-(-m // b) for m in sizes)):
        x, y = rows.block([np.arange(j * b, (j + 1) * b) for _ in sizes])
        f = np.asarray(ref.feats(p0, x, y), np.float32)
        for i in range(len(sizes)):
            out[i].append(f[i])
    return [np.concatenate(o)[:m] for o, m in zip(out, sizes)]


def _train(ref: Reference, p0, rows: _Rows, schedules):
    """Run each lane's steps from p0.  ``schedules[i]`` lists lane i's
    steps; a step is a list of (rows, weights) chunks of B rows.
    Returns (params (lanes, ...), final loss per lane)."""
    lanes, b = len(schedules), ref.batch
    ps = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (lanes,)
                                                 + a.shape), p0)
    last = np.full(lanes, np.nan)
    for t in range(max(len(s) for s in schedules)):
        steps = [s[t] if t < len(s) else None for s in schedules]
        g_sum, v_sum = None, np.zeros(lanes)
        s_sum = np.zeros(lanes, np.float32)
        for j in range(max(len(st) for st in steps if st is not None)):
            rows_j, w_j = [], np.zeros((lanes, b), np.float32)
            for i, st in enumerate(steps):
                if st is not None and j < len(st):
                    rows_j.append(st[j][0])
                    w_j[i] = st[j][1]
                else:
                    rows_j.append(None)
            x, y = rows.block(rows_j)
            g, v = ref.grad(ps, x, y, w_j)
            g_sum = g if g_sum is None else jax.tree.map(jnp.add, g_sum, g)
            v_sum += np.asarray(v, np.float64)
            s_sum += w_j.sum(axis=1)
        ps = ref.apply(ps, g_sum, jnp.asarray(s_sum))
        for i, st in enumerate(steps):
            if st is not None:
                last[i] = v_sum[i] / max(float(s_sum[i]), 1e-9)
    return ps, last


def run_round(ref: Reference, params, clients, cids: Sequence[int],
              budgets: Dict[int, int], *, epochs: int, batch: int,
              seed: int, round_seed: int,
              medoids: Optional[Dict[int, np.ndarray]] = None,
              judge: Sequence[int] = (), low: Optional[Reference] = None,
              select_passes: int = 0):
    """One round from ``params``; returns (params, {cid: final loss},
    {cid: medoids used}, [(cid, gain, allowed, k, m, objective, float32
    error, stated-precision error)] for the judged lanes).

    ``medoids`` gives each straggler's selection; where it is None the
    reference selects with ``kmedoids64.fasterpam`` on its own features,
    its contractions in ``select_passes`` (0: float32).  ``judge`` lists
    the stragglers whose selection is judged in float64; ``low`` is the
    reference at the configuration's stated precision, whose features
    enter the judge's allowance."""
    sizes = {c: int(np.asarray(clients[c]["y"]).shape[0]) for c in cids}
    ks = {c: coreset_k(sizes[c], int(budgets[c])) for c in cids}
    judge = set(int(c) for c in judge)
    dt = ref.model.dtype
    p0 = jax.tree.map(lambda a: jnp.asarray(a).astype(dt), params)
    per_epoch = {c: m_padded(sizes[c], batch) // batch for c in cids}

    def n_steps(c):
        if ks[c] == 0:
            return epochs * per_epoch[c]
        return per_epoch[c] + max(epochs - 1, 1)

    order = sorted(cids, key=lambda c: (ks[c] > 0, n_steps(c), c))
    acc, total = None, 0.0
    losses, used, judged = {}, {}, []
    for i0 in range(0, len(order), ref.lanes):
        real = order[i0:i0 + ref.lanes]
        blk = real + [real[-1]] * (ref.lanes - len(real))
        rows = _Rows(clients, blk, batch)
        lane_sizes = [sizes[c] for c in blk]
        meds = {}
        if any(ks[c] for c in real):
            f = _features(ref, p0, rows, lane_sizes)
            f_low = (None if low is None
                     else _features(low, p0, rows, lane_sizes))
        for i, c in enumerate(real):
            if not ks[c]:
                continue
            med = None if medoids is None else medoids.get(c)
            if medoids is None or not _sound(med, ks[c], sizes[c]):
                # a missing or malformed selection fails the judge; the
                # round goes on with one of the reference's own
                if medoids is not None:
                    judged.append((c, float("inf"), 1.0, ks[c], sizes[c],
                                   0.0, 0.0, 0.0))
                    judge.discard(c)
                med = kmedoids64.fasterpam(f[i], ks[c],
                                           passes=select_passes)
            med = np.asarray(med)
            used[c] = med
            x64 = f[i].astype(np.float64)
            meds[i] = (med, kmedoids64.assign(x64, med))
            if c in judge:
                judge.discard(c)
                gain, allowed, obj, e32, elow = kmedoids64.lane_gain(
                    x64, med, None if f_low is None
                    else f_low[i].astype(np.float64), parts=True)
                judged.append((c, gain, allowed, ks[c], sizes[c], obj, e32,
                               elow))
        schedules = []
        for i, c in enumerate(blk):
            if i >= len(real):          # a repeat lane: nothing to train
                schedules.append([])
                continue
            perm = permutations(seed, round_seed, c, epochs,
                                per_epoch[c] * batch)
            n_ep = epochs if ks[c] == 0 else 1
            sched = [[(r, (r < sizes[c]).astype(np.float32))]
                     for e in range(n_ep) for r in perm[e].reshape(-1, batch)]
            if ks[c]:
                med, counts = meds[i]
                sched += [_chunks(med.astype(np.int64),
                                  counts.astype(np.float32), batch)
                          ] * max(epochs - 1, 1)
            schedules.append(sched)
        ps, last = _train(ref, p0, rows, schedules)
        mw = np.array([sizes[c] for c in real] + [0] * (len(blk) - len(real)),
                      np.float32)
        part = jax.tree.map(
            lambda v: jnp.tensordot(jnp.asarray(mw), v.astype(jnp.float32),
                                    axes=(0, 0)), ps)
        acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        total += float(mw.sum())
        for i, c in enumerate(real):
            losses[c] = float(last[i])
    new = jax.tree.map(lambda v: (v / total).astype(dt), acc)
    return new, losses, used, judged


def _sound(med, k: int, m: int) -> bool:
    """k distinct indices of valid rows."""
    if med is None:
        return False
    med = np.asarray(med)
    return (med.shape == (k,) and bool(np.all((med >= 0) & (med < m)))
            and len(np.unique(med)) == k)
