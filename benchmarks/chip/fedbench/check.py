"""The comparison that decides ``correct``.

The run's set-up drives the program's fleet round through its first
``ROUNDS`` rounds from the seed's weights; the reference follows the
same rounds from the same weights and data, taking each straggler's
medoids from the program.  Numbers, each held to its limit in
``limits/<cell>.json``:

* ``loss_gap``: the first round's final local loss of every client,
  program against reference: the median over clients of the relative
  gap.  (The mean over clients, and later rounds, swing from seed to
  seed with the few clients whose local SGD the rounding of the stated
  precision sends elsewhere; ``diagnostics`` keeps them.)
* ``grad_gap``: the server's first update Δ₁ = p₁ − p₀ (FedAvg's
  pseudo-gradient as the server applies it), per leaf: the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf.
* ``change_gap``: the same for p₃ − p₀, the change after three rounds.
* ``select_ratio``: the first round's selections, where program and
  reference start from the same weights: the worst swap gain over its
  allowance among the judged straggler lanes
  (``reference.kmedoids64.lane_gain``); above 1 a lane is no fixed point
  of FasterPAM.
* ``nonfinite``: non-finite losses or parameters in those rounds.

Leaves whose reference update is under a thousandth of the median
leaf's move by round-off alone and are left out of the two norm gaps.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

ROUNDS = 3
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "select_ratio",
           "nonfinite")


def leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(v, np.float64) for v in jax.tree.leaves(tree)]


def norm_gap(prog_delta, ref_delta, keep: Sequence[bool]) -> float:
    pn = [np.linalg.norm(v) for v in leaves(prog_delta)]
    rn = [np.linalg.norm(v) for v in leaves(ref_delta)]
    med = float(np.median(rn))
    gaps = [abs(p - r) / max(r, med, 1e-30)
            for p, r, k in zip(pn, rn, keep) if k]
    return max(gaps) if gaps else 0.0


def tree_sub(a, b):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def judge_sample(cohort, budgets, sizes, n: int, seed: int) -> List[int]:
    """The stragglers whose selection is judged: ``n`` drawn from the
    seed, always with the one of largest (k, m)."""
    from reference.fleet import coreset_k
    strag = [c for c in cohort if coreset_k(int(sizes[c]), budgets[c])]
    if len(strag) <= n:
        return strag
    big = max(strag, key=lambda c: (coreset_k(int(sizes[c]), budgets[c]),
                                    int(sizes[c])))
    rng = np.random.default_rng(np.random.SeedSequence((int(seed) % 2**63,
                                                        7)))
    rest = [c for c in strag if c != big]
    pick = rng.choice(len(rest), n - 1, replace=False)
    return [big] + [rest[i] for i in sorted(pick)]


def reference_rounds(ref, low, p0, pop, fleet: dict, seed: int,
                     medoids: Sequence[Dict], judge: Sequence[int],
                     rounds: int = ROUNDS, select_passes: int = 0):
    """The reference's own rounds from p0.  ``medoids[r]`` is the
    selection under test in round r (None: the reference selects, its
    contractions in ``select_passes``)."""
    from reference.fleet import run_round
    params, out = p0, []
    for r in range(rounds):
        params, losses, used, judged = run_round(
            ref, params, pop.clients, pop.cohort, pop.budgets,
            epochs=fleet["epochs"], batch=fleet["batch_size"], seed=seed,
            round_seed=r, medoids=None if medoids is None else medoids[r],
            judge=judge if r == 0 else (), low=low,
            select_passes=select_passes)
        out.append({"params": params, "losses": losses, "medoids": used,
                    "judged": judged})
    return out


def raw(prog: dict, ref_rounds: list, p0) -> dict:
    """Every reading the numbers are made from, for setting limits: per
    round each client's two final losses, each judged lane's tuple, and
    per leaf the norms of both updates and of their difference."""
    out = {"losses": [], "judged": [], "leaves": {}}
    for r, rr in enumerate(ref_rounds):
        out["losses"].append([[int(c), float(prog["losses"][r][c]),
                               float(v)] for c, v in rr["losses"].items()])
        out["judged"].append([[float(v) for v in j] for j in rr["judged"]])
    for name, r in (("delta1", 0), ("delta3", len(ref_rounds) - 1)):
        pd = leaves(tree_sub(prog["params"][r], p0))
        rd = leaves(tree_sub(ref_rounds[r]["params"], p0))
        out["leaves"][name] = [[float(np.linalg.norm(a)),
                                float(np.linalg.norm(b)),
                                float(np.linalg.norm(a - b))]
                               for a, b in zip(pd, rd)]
    return out


def compare(prog: dict, ref_rounds: list, p0) -> Dict[str, float]:
    """The numbers of ``NUMBERS`` for one run.  ``prog`` holds the
    program's "params" after each round, and per round its "losses"
    ({cid: loss}); ``ref_rounds`` comes from ``reference_rounds``."""
    nonfinite = 0
    for r, rr in enumerate(ref_rounds):
        pl = np.array([prog["losses"][r].get(c, np.nan)
                       for c in rr["losses"]], np.float64)
        nonfinite += int((~np.isfinite(pl)).sum())
        if r == 0:
            rl = np.array(list(rr["losses"].values()), np.float64)
            loss_gap = float(np.median(np.abs(pl - rl)
                                       / np.maximum(np.abs(rl), 1e-30)))
    for p in prog["params"]:
        nonfinite += sum(int((~np.isfinite(v)).sum()) for v in leaves(p))
    g_ref = tree_sub(ref_rounds[0]["params"], p0)
    med = float(np.median([np.linalg.norm(v) for v in leaves(g_ref)]))
    keep = [np.linalg.norm(v) >= 1e-3 * med for v in leaves(g_ref)]
    grad_gap = norm_gap(tree_sub(prog["params"][0], p0), g_ref, keep)
    last = len(ref_rounds) - 1
    change_gap = norm_gap(tree_sub(prog["params"][last], p0),
                          tree_sub(ref_rounds[last]["params"], p0), keep)
    judged = [j for rr in ref_rounds for j in rr["judged"]]
    select_ratio = max((j[1] / max(j[2], 1e-30) for j in judged),
                       default=0.0)
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "select_ratio": select_ratio,
            "nonfinite": float(nonfinite), "judged_lanes": float(len(judged)),
            "diagnostics": diagnostics(prog, ref_rounds, judged)}


def diagnostics(prog: dict, ref_rounds: list, judged: list) -> dict:
    """What a look at a number needs and a limit does not compare: per
    round the two mean losses, the clients whose losses differ most, and
    the judged lanes nearest their allowance."""
    rounds, worst = [], []
    for r, rr in enumerate(ref_rounds):
        cids = list(rr["losses"])
        pl = np.array([prog["losses"][r][c] for c in cids], np.float64)
        rl = np.array([rr["losses"][c] for c in cids], np.float64)
        rel = np.abs(pl - rl) / np.maximum(np.abs(rl), 1e-30)
        rounds.append({"prog_mean": float(pl.mean()),
                       "ref_mean": float(rl.mean()),
                       "median_rel": float(np.median(rel)),
                       "max_rel": float(rel.max())})
        for i in np.argsort(-np.abs(pl - rl))[:3]:
            worst.append([r, int(cids[i]), float(pl[i]), float(rl[i])])
    lanes = sorted(judged, key=lambda j: -j[1] / max(j[2], 1e-30))[:5]
    return {"rounds": rounds, "worst_losses": worst,
            "worst_lanes": [[float(v) for v in j] for j in lanes]}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> List[tuple]:
    """[(name, value, limit, ok)] of every number that has a limit; a
    number without a limit in the cell's file is not compared."""
    out = []
    for name in NUMBERS:
        if name in limits:
            v = numbers[name]
            out.append((name, v, limits[name],
                        bool(np.isfinite(v) and v <= limits[name])))
    return out
