"""The plain reference against the program at a tiny size, and the
float64 judge against a selection made from bfloat16-rounded features."""
import os
import sys
from pathlib import Path

# the benchmark's CPU tests: small shapes on the host CPU, the
# benchmark's own modules and the program on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[3] / "src")]

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import check, harness, population
from fedbench.spec import Cell
from reference import kmedoids64
from reference import models as refmodels
from reference.fleet import Reference


def program_and_reference(cell_name: str, seed: int):
    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         run_fleet_round)

    cell = Cell(cell_name)
    cfg = cell.config
    model, pop_sizes = cfg.TINY["model"], cfg.TINY["population"]
    fleet = dict(cfg.FLEET)
    pop = population.build(cfg, cell.traffic, pop_sizes, model, fleet, seed)
    engine = FleetEngine(cfg.workload(model), FleetConfig(seed=seed, **fleet))
    p0 = cfg.init_params(population.seed_key(seed), model)
    prog = {"params": [], "losses": [], "medoids": []}
    params = p0
    for r in range(check.ROUNDS):
        params, stats = run_fleet_round(engine, params, pop.clients,
                                        pop.cohort, pop.budgets,
                                        round_seed=r)
        prog["params"].append(harness.host(params))
        prog["losses"].append(dict(zip(stats.cids.tolist(),
                                       stats.losses.tolist())))
        prog["medoids"].append(dict(stats.medoids))
    ref = Reference(refmodels.build(cfg.REFERENCE, jnp.float32,
                                    **cfg.reference_sizes(model)),
                    fleet["lr"], fleet["epochs"], "highest")
    judge = check.judge_sample(pop.cohort, pop.budgets, pop.sizes, 4, seed)
    rr = check.reference_rounds(ref, None, p0, pop, fleet, seed,
                                prog["medoids"], judge)
    return prog, rr, p0, pop


@pytest.mark.parametrize("cell", ["shakespeare_lstm.stragglers30"])
def test_reference_follows_the_program(cell):
    prog, rr, p0, pop = program_and_reference(cell, seed=3)
    assert any(k for k in (kmedoids64_k(pop, c) for c in pop.cohort))
    nums = check.compare(prog, rr, harness.host(p0))
    # float32 on both sides of the CPU: rounding only
    assert nums["loss_gap"] < 1e-5
    assert nums["grad_gap"] < 1e-4
    assert nums["change_gap"] < 1e-4
    assert nums["judged_lanes"] > 0
    assert nums["select_ratio"] <= 1.0
    assert nums["nonfinite"] == 0
    # and every round's parameters agree leaf by leaf
    for p, r in zip(prog["params"], rr):
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(r["params"])):
            np.testing.assert_allclose(a, np.asarray(b), atol=1e-5, rtol=1e-4)


def kmedoids64_k(pop, c):
    from reference.fleet import coreset_k
    return coreset_k(int(pop.sizes[c]), pop.budgets[c])


def lanes(n_lanes=6, m=256, f=48, k=64, seed=0):
    """Clustered feature lanes, like last-layer gradient features."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_lanes):
        centers = rng.normal(size=(24, f)).astype(np.float32)
        x = (centers[rng.integers(0, 24, m)]
             + 0.3 * rng.normal(size=(m, f))).astype(np.float32)
        out.append(x)
    return out, k


def test_judge_passes_float32_and_flags_bfloat16_selections():
    xs, k = lanes()
    ratio32, ratio16 = [], []
    for x in xs:
        x64 = x.astype(np.float64)
        med = kmedoids64.fasterpam(x, k, dtype=np.float32)
        gain, allowed, _ = kmedoids64.lane_gain(x64, med)
        ratio32.append(gain / allowed)
        x16 = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                         .astype(jnp.float32))
        med = kmedoids64.fasterpam(x16, k, dtype=np.float32)
        gain, allowed, _ = kmedoids64.lane_gain(x64, med)
        ratio16.append(gain / allowed)
    assert max(ratio32) <= 1.0
    assert max(ratio16) > 1.0


@pytest.mark.parametrize("passes", [1, 3])
def test_rounded_contractions_are_exact_on_bfloat16_values(passes):
    """Rows already in bfloat16 lose nothing in either rounding, nor do
    small whole distances in the swap table's contraction."""
    xs, k = lanes(n_lanes=1, m=64, f=16, k=8)
    x = np.asarray(jnp.asarray(xs[0]).astype(jnp.bfloat16)
                   .astype(jnp.float32))
    np.testing.assert_array_equal(kmedoids64.dists(x, np.float32, passes),
                                  kmedoids64.dists(x, np.float32))
    rng = np.random.default_rng(passes)
    D = rng.integers(1, 50, (64, 64)).astype(np.float64)
    D = np.minimum(D, D.T)
    np.fill_diagonal(D, 0.0)
    med = np.arange(k)
    np.testing.assert_array_equal(kmedoids64.swap_table(D, med, passes)[0],
                                  kmedoids64.swap_table(D, med)[0])


def test_three_passes_round_far_less_than_one():
    xs, _ = lanes(n_lanes=1, m=128, f=48)
    exact = kmedoids64.dists(xs[0], np.float64)
    err = {p: np.abs(kmedoids64.dists(xs[0], np.float32, p) - exact).max()
           for p in (0, 1, 3)}
    assert err[0] < err[3] < err[1] / 20


def test_judge_flags_selections_in_one_bfloat16_pass():
    xs, k = lanes()
    ratios = []
    for x in xs:
        med = kmedoids64.fasterpam(x, k, passes=1)
        gain, allowed, _ = kmedoids64.lane_gain(x.astype(np.float64), med)
        ratios.append(gain / allowed)
    assert max(ratios) > 1.0


def test_judge_allowance_grows_with_the_stated_precision():
    xs, k = lanes(n_lanes=1)
    x64 = xs[0].astype(np.float64)
    med = kmedoids64.fasterpam(xs[0], k)
    _, allowed, obj = kmedoids64.lane_gain(x64, med)
    low = x64 + 1e-3 * np.random.default_rng(1).normal(size=x64.shape)
    _, allowed_low, _ = kmedoids64.lane_gain(x64, med, low)
    assert allowed_low > allowed > kmedoids64.SWAP_RTOL * obj


def test_assign_counts_every_row_once():
    xs, k = lanes(n_lanes=1, m=64, k=8)
    med = kmedoids64.fasterpam(xs[0], k)
    counts = kmedoids64.assign(xs[0], med)
    assert counts.sum() == 64 and (counts >= 1).all()
