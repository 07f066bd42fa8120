"""Fused coreset-selection fast-path tests.

Covers the PR 4 selection pipeline end to end: the Pallas BUILD/Δ-sweep
kernels against their jnp oracles, medoid-index parity against the
``kmedoids_numpy`` oracle over 100+ randomized masked/padded instances
(k = 1, duplicate points, all-valid, mostly-padded lanes), the
legacy-sweep A/B baseline, diagonal-zeroing ownership by the pairwise
wrappers, and the single-dispatch contract of the fused per-group round
program.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import fixed_size_clients
from repro.core.kmedoids import (kmedoids_batched, kmedoids_numpy,
                                 pairwise_sq_dists)
from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                     make_cohort_groups, run_fleet_round)
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# kernels vs jnp oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,m", [(3, 64), (2, 21), (1, 128), (4, 8)])
def test_build_cost_kernel_matches_ref(c, m):
    rng = np.random.default_rng(c * 100 + m)
    D = jnp.asarray(np.abs(rng.normal(size=(c, m, m))).astype(np.float32))
    d_near = jnp.asarray(np.abs(rng.normal(size=(c, m))).astype(np.float32))
    vf = jnp.asarray((rng.random((c, m)) < 0.8).astype(np.float32))
    got = ops.kmedoids_build_cost(D, d_near, vf, use_kernel=True)
    want = ref.kmedoids_build_cost_ref(D, d_near, vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("c,m,k", [(3, 64, 5), (2, 21, 1), (1, 128, 16),
                                   (4, 32, 3)])
def test_delta_sweep_kernel_matches_ref(c, m, k):
    rng = np.random.default_rng(c * 1000 + m + k)
    D = jnp.asarray(np.abs(rng.normal(size=(c, m, m))).astype(np.float32))
    d1 = np.abs(rng.normal(size=(c, m))).astype(np.float32)
    d2 = d1 + np.abs(rng.normal(size=(c, m))).astype(np.float32)  # d1 <= d2
    n_idx = rng.integers(0, k, size=(c, m))
    onehot = np.eye(k, dtype=np.float32)[n_idx]
    vf = (rng.random((c, m)) < 0.8).astype(np.float32)
    args = (D, jnp.asarray(d1), jnp.asarray(d2), jnp.asarray(vf),
            jnp.asarray(onehot))
    A, B = ops.kmedoids_delta_sweep(*args, use_kernel=True)
    A_ref, B_ref = ref.kmedoids_delta_sweep_ref(*args)
    np.testing.assert_allclose(np.asarray(A), np.asarray(A_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(B), np.asarray(B_ref),
                               rtol=1e-5, atol=1e-5)
    assert B.shape == (c, m, k)   # padded lanes sliced off


# ---------------------------------------------------------------------------
# medoid-index parity vs the numpy oracle (the acceptance contract)
# ---------------------------------------------------------------------------

def _oracle_instance(rng, kind, m_pad, k):
    """One masked/padded instance: (D_padded, valid, D_true float32)."""
    if kind == "all_valid":
        m = m_pad
    elif kind == "mostly_padded":
        m = int(rng.integers(max(k, 2), max(k + 1, m_pad // 5)))
    else:
        m = int(rng.integers(max(k, 4), m_pad + 1))
    x = rng.normal(size=(m, 5)).astype(np.float32)
    if kind == "clusters" and m >= 6:
        x[: m // 3] += 4.0
        x[m // 3: 2 * m // 3] -= 4.0
    if kind == "duplicates" and m >= 2 * k:
        x[1::2] = x[::2][: len(x[1::2])]     # exact duplicate points
    D = np.sqrt(np.maximum(
        np.asarray(pairwise_sq_dists(jnp.asarray(x))), 0.0)).astype(
            np.float32)
    Dp = (np.abs(rng.normal(size=(m_pad, m_pad))) * 37).astype(np.float32)
    Dp[:m, :m] = D
    valid = np.arange(m_pad) < m
    return Dp, valid, D


KINDS = ("plain", "clusters", "duplicates", "mostly_padded", "all_valid")


def _canon_medoids(meds, D):
    """Map each medoid to the smallest index at (near-)zero distance from
    it — its duplicate class — sorted.  Duplicate points are
    interchangeable optima; float32 cancellation in ‖a‖²+‖b‖²−2ab can
    leave ~1e-4 between bitwise-equal points after the sqrt, and
    f32-vs-f64 near-ties mid-run may settle on either copy."""
    return sorted(int(np.flatnonzero(D[:, int(j)] < 1e-3).min())
                  for j in np.asarray(meds))


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_medoids_bit_identical_to_numpy_oracle(use_kernel, k):
    """Medoid indices from the fused batched solver (kernel and jnp paths)
    equal the float64 host oracle's on randomized masked instances —
    18 lanes per (k, kernel) combination, 108 instances total across the
    parametrization (the ≥50-instance acceptance bar), solved as three
    18-lane batched calls to also exercise lane independence.  Lanes with
    exact duplicate points compare up to the duplicate class (tied
    optima); every other lane must match index-for-index, bit-identical."""
    m_pad = 32
    rng = np.random.default_rng(1000 + k)
    Ds, valids, trues = [], [], []
    for i in range(18):
        kind = KINDS[i % len(KINDS)]
        Dp, valid, D = _oracle_instance(rng, kind, m_pad, k)
        Ds.append(Dp)
        valids.append(valid)
        trues.append(D)
    res = kmedoids_batched(jnp.asarray(np.stack(Ds)),
                           jnp.asarray(np.stack(valids)), k,
                           max_sweeps=100, use_kernel=use_kernel)
    for c, D in enumerate(trues):
        kind = KINDS[c % len(KINDS)]
        want = kmedoids_numpy(D, k, max_sweeps=100)
        got_meds = np.asarray(res.medoids[c])
        if kind == "duplicates":
            assert _canon_medoids(got_meds, D) == \
                _canon_medoids(want.medoids, D), \
                f"lane {c} kind={kind} k={k} use_kernel={use_kernel}"
            np.testing.assert_allclose(float(res.objective[c]),
                                       float(want.objective), rtol=1e-5)
        else:
            np.testing.assert_array_equal(
                got_meds, np.asarray(want.medoids),
                err_msg=f"lane {c} kind={kind} k={k} "
                        f"use_kernel={use_kernel}")
            np.testing.assert_array_equal(np.asarray(res.weights[c]),
                                          np.asarray(want.weights))
        # weights always partition the m real samples; padding excluded
        m = int(valids[c].sum())
        assert int(np.asarray(res.weights[c]).sum()) == m
        assert (np.asarray(res.assignment[c])[m:] == -1).all()


@pytest.mark.parametrize("use_kernel", [True, False])
def test_equal_cost_ties_break_to_the_lowest_index(use_kernel):
    """Two pairs on a line, 0 1 ... 100 101, k = 2: the first BUILD pick
    ties between 1 and 2, the second between 2 and 3, and no swap
    improves.  Every solver path breaks such ties to the lowest index —
    the f64 oracle by argmin order, the on-device solvers within
    TIE_RTOL — so noise at the level of a reduction-order change (here a
    1e-7 relative nudge towards the other candidate) cannot move a pick.
    """
    from repro.core.kmedoids import kmedoids_batched_from_feats
    x = np.array([[0.0], [1.0], [100.0], [101.0]])
    D = np.abs(x - x.T)
    want = [1, 2]
    assert sorted(np.asarray(kmedoids_numpy(D, 2).medoids)) == want
    nudge = np.ones_like(D)
    nudge[:, [2, 3]] -= 1e-7            # favour columns 2 and 3
    Dn = jnp.asarray((D * nudge)[None].astype(np.float32))
    valid = jnp.ones((1, 4), bool)
    res = kmedoids_batched(Dn, valid, 2, use_kernel=use_kernel)
    assert sorted(np.asarray(res.medoids[0])) == want
    xn = jnp.asarray((x * (1 + 1e-7 * np.arange(4)[:, None]))[None]
                     .astype(np.float32))
    res = kmedoids_batched_from_feats(xn, valid, 2, use_kernel=use_kernel)
    assert sorted(np.asarray(res.medoids[0])) == want


def test_legacy_sweep_is_equivalent_baseline():
    """The pre-fusion minimum/one_hot/einsum chain (the selection
    benchmark's A/B baseline) picks identical medoids to the fused
    Δ-sweep formulation — the clip form is a bitwise case-collapse."""
    rng = np.random.default_rng(7)
    Ds, valids = [], []
    for _ in range(6):
        Dp, valid, _ = _oracle_instance(rng, "plain", 32, 4)
        Ds.append(Dp)
        valids.append(valid)
    D = jnp.asarray(np.stack(Ds))
    v = jnp.asarray(np.stack(valids))
    new = kmedoids_batched(D, v, 4, max_sweeps=100)
    old = kmedoids_batched(D, v, 4, max_sweeps=100, legacy_sweep=True)
    np.testing.assert_array_equal(np.asarray(new.medoids),
                                  np.asarray(old.medoids))
    np.testing.assert_allclose(np.asarray(new.objective),
                               np.asarray(old.objective), rtol=1e-6)


# ---------------------------------------------------------------------------
# diagonal zeroing lives in the pairwise wrappers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_pairwise_wrappers_own_self_diag(use_kernel):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 40, 24))
    out = np.asarray(ops.pairwise_l2_batched(x, squared=True,
                                             use_kernel=use_kernel,
                                             zero_diag=True))
    for c in range(3):
        assert (np.diag(out[c]) == 0.0).all()
    d = np.asarray(pairwise_sq_dists(x[0], use_kernel=use_kernel))
    assert (np.diag(d) == 0.0).all()


# ---------------------------------------------------------------------------
# fused per-group round program: single dispatch
# ---------------------------------------------------------------------------

def _tiny_fleet(n_clients=6, m=40, seed=0):
    # deduped into conftest: same-size mlp clients so one budget maps to
    # exactly one cohort group
    return fixed_size_clients("mlp", n_clients=n_clients, m=m, seed=seed)


def test_fused_group_program_is_single_dispatch():
    """A straggler group's full round (features → distances → k-medoids →
    SGD → gather → coreset epochs) must execute as exactly one jitted
    program invocation — no other engine program may be touched."""
    model, data = _tiny_fleet()
    cfg = FleetConfig(epochs=2, batch_size=8, seed=0)
    engine = FleetEngine(model, cfg)
    params = model.init(jax.random.PRNGKey(0))
    cids = list(range(len(data)))
    budgets = {cid: 9 for cid in cids}           # -> coreset path, k = 4
    groups = make_cohort_groups(data, cids, budgets, cfg, 0)
    assert len(groups) == 1 and groups[0].k == 4
    g = groups[0]

    key = (g.k, jax.tree.structure(g.data))
    program = engine._group_program(g.k, key[1])
    calls = []

    def counting(*args):
        calls.append(1)
        return program(*args)

    engine._group_programs[key] = counting
    # the fused path must not fall back to the pre-fusion stage programs
    engine._feats = engine._feats1 = None
    engine._sgd_step1 = engine._core_step1 = None

    before = engine.dispatch_count
    p, losses, meds = engine.run_group(params, g, batched=True)
    assert len(calls) == 1
    assert engine.dispatch_count - before == 1
    assert meds is not None and meds.shape == (g.n_clients, g.k)
    assert np.isfinite(losses).all()


def test_selection_fused_matches_prefusion_chain_dispatch_counts():
    """select_group_coresets: the 1-dispatch fused program (distance-free)
    and the 3-dispatch pre-fusion baseline chain (materializing) select
    equivalent medoids — equal up to tied-optima classes, scored on one
    shared float64 distance matrix — and the dispatch counts don't
    regress.  (Exact index equality is no longer guaranteed: the two
    paths accumulate distances in different orders, and equal-cost swap
    ties may settle on either optimum.)"""
    model, data = _tiny_fleet(seed=3)
    cfg = FleetConfig(epochs=2, batch_size=8, seed=0)
    engine = FleetEngine(model, cfg)
    params = model.init(jax.random.PRNGKey(1))
    cids = list(range(len(data)))
    groups = make_cohort_groups(data, cids, {c: 20 for c in cids}, cfg, 0)
    g = groups[0]
    assert g.k == 16
    fused, n_fused = engine.select_group_coresets(params, g, fused=True)
    chain, n_chain = engine.select_group_coresets(params, g, fused=False)
    assert (n_fused, n_chain) == (1, 3)
    np.testing.assert_allclose(np.asarray(fused.objective),
                               np.asarray(chain.objective), rtol=1e-6)
    feats = np.asarray(engine._feats(params,
                                     jax.tree.map(jnp.asarray, g.data)),
                       np.float64)
    for c in range(g.n_clients):
        m = int(g.m[c])
        x = feats[c, :m]
        sq = (x * x).sum(-1)
        D64 = np.sqrt(np.maximum(
            sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0))
        np.fill_diagonal(D64, 0.0)

        def obj(meds):
            assert (np.asarray(meds) < m).all()   # never a padded lane
            return D64[:, np.asarray(meds)].min(axis=1).sum()

        fo, co = obj(fused.indices[c]), obj(chain.indices[c])
        np.testing.assert_allclose(fo, co, rtol=1e-5,
                                   err_msg=f"lane {c}: fused and chain "
                                           f"medoids are not cost-tied")
        # both weight vectors partition the same m real samples
        assert int(np.asarray(fused.weights[c]).sum()) == m
        assert int(np.asarray(chain.weights[c]).sum()) == m


def test_round_dispatch_count_is_one_per_group():
    """run_fleet_round on the batched engine issues exactly one top-level
    dispatch per cohort group (the pre-fusion engine issued up to six)."""
    model, data = _tiny_fleet(n_clients=8, seed=5)
    cfg = FleetConfig(epochs=2, batch_size=8, seed=0)
    engine = FleetEngine(model, cfg)
    params = model.init(jax.random.PRNGKey(0))
    cids = list(range(len(data)))
    # half full-set, half coreset -> two groups
    budgets = {c: (40 if c < 4 else 9) for c in cids}
    groups = make_cohort_groups(data, cids, budgets, cfg, 0)
    before = engine.dispatch_count
    run_fleet_round(engine, params, data, cids, budgets, round_seed=0,
                    groups=groups)
    assert engine.dispatch_count - before == len(groups) == 2


def test_use_kernel_tristate_resolution():
    """FleetConfig.use_kernel = None resolves by backend (off on CPU) and
    both forced settings agree with the auto result numerically."""
    assert ops.resolve_use_kernel(None) == (jax.default_backend() == "tpu")
    assert ops.resolve_use_kernel(True) is True
    assert ops.resolve_use_kernel(False) is False
    model, data = _tiny_fleet(seed=11)
    params = model.init(jax.random.PRNGKey(2))
    cids = list(range(len(data)))
    budgets = {c: 9 for c in cids}
    meds = {}
    for uk in (None, True, False):
        cfg = FleetConfig(epochs=2, batch_size=8, seed=0, use_kernel=uk)
        engine = FleetEngine(model, cfg)
        groups = make_cohort_groups(data, cids, budgets, cfg, 0)
        cs, _ = engine.select_group_coresets(params, groups[0], fused=True)
        meds[uk] = np.asarray(cs.indices)
    np.testing.assert_array_equal(meds[None], meds[True])
    np.testing.assert_array_equal(meds[None], meds[False])


def test_fleet_config_replace_keeps_frozen_contract():
    """The benchmark builds kernel-A/B engines via dataclasses.replace —
    keep FleetConfig replace-compatible."""
    cfg = FleetConfig(epochs=3, use_kernel=None)
    on = dataclasses.replace(cfg, use_kernel=True)
    assert on.use_kernel is True and on.epochs == 3
