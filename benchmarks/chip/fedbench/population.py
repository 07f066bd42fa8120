"""The cell's client population, cohort, budgets and data.

Sizes, capabilities and the cohort come from the traffic file's
``population_seed``, so every run of a cell sees the same cohort groups;
the run's ``--seed`` draws the sample contents and the initial weights.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (wider than 32 bits included)."""
    import jax
    s = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.key(0), np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(s >> 32))


def lognormal_sizes(n: int, mean: float, std: float, lo: int, hi, rng
                    ) -> np.ndarray:
    """Lognormal client sizes matched to a mean and std, clipped to
    [lo, hi]."""
    mu = np.log(mean ** 2 / np.sqrt(std ** 2 + mean ** 2))
    sigma = np.sqrt(np.log(1 + std ** 2 / mean ** 2))
    sizes = rng.lognormal(mu, sigma, n).astype(int)
    return np.clip(sizes, lo, hi if hi is not None else None)


@dataclasses.dataclass
class Population:
    sizes: np.ndarray             # (n_clients,) samples per client
    cohort: List[int]             # client ids that train every round
    budgets: Dict[int, int]       # cid -> coreset budget (≥ m: full set)
    deadline: float
    clients: list                 # cid -> {"x", "y"} host arrays or None


def build(config, traffic: dict, pop: dict, model: dict, fleet: dict,
          seed: int) -> Population:
    """The population of ``pop`` sizes under ``traffic``; data for the
    cohort only, drawn on the device in one call and split on the host."""
    import jax

    from repro.fed.fleet.batched import nominal_budgets
    from repro.fed.fleet.scenarios import build_scenario
    from repro.fed.simulator import straggler_deadline

    rng = np.random.default_rng(traffic["population_seed"])
    n = pop["n_clients"]
    sizes = lognormal_sizes(n, pop["mean_samples"], pop["std_samples"],
                            pop["min_samples"], pop.get("max_samples"), rng)
    specs, _ = build_scenario(traffic["scenario"], [int(m) for m in sizes],
                              traffic["population_seed"])
    n_cohort = min(int(traffic["cohort"]), n)
    cohort = sorted(int(c) for c in rng.choice(n, n_cohort, replace=False))
    cohort_specs = [specs[c] for c in cohort]
    deadline = straggler_deadline(cohort_specs, fleet["epochs"],
                                  float(traffic["straggler_pct"]))
    budgets = {int(c): int(b) for c, b in nominal_budgets(
        cohort_specs, deadline, fleet["epochs"]).items()}
    csizes = sizes[cohort]
    data = jax.jit(lambda k: config.make_data(k, csizes, model))(
        seed_key(seed))
    data = {k: np.asarray(v) for k, v in data.items()}
    cuts = np.cumsum(csizes)[:-1]
    parts = {k: np.split(v, cuts) for k, v in data.items()}
    clients: list = [None] * n
    for i, cid in enumerate(cohort):
        clients[cid] = {k: parts[k][i] for k in parts}
    return Population(sizes=sizes, cohort=cohort, budgets=budgets,
                      deadline=float(deadline), clients=clients)
