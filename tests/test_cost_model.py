"""Property + golden tests for the cost-conditioned budget layer.

Three contracts:

  * **Legacy is byte-identical** — ``cost=None`` budgets reproduce the
    exact pre-refactor integer arithmetic (the ×1.0 short-circuit in
    ``WorkloadCostModel``), checked against inline re-implementations of
    the old formulas over a seeded grid.
  * **Budget laws hold for every cost model** — monotone in deadline and
    capability, clipped to [1, m], plan invariants (property tests; run
    under hypothesis when available, otherwise over a seeded random grid
    — the container does not ship hypothesis, so the grid is the CI
    path).
  * **The measured table is sane** — HLO FLOPs per sample for each
    registered workload, pinned within a generous band (XLA flop counts
    drift across versions) plus strict cross-workload ordering, which is
    what budget conditioning actually consumes.
"""
import numpy as np
import pytest

from repro.core.coreset import coreset_budget, needs_coreset
from repro.fed.cost import (FORWARD_FRAC, UNIT_COST, WorkloadCostModel,
                            resolve_cost, workload_cost_model)

try:        # optional: not installed in the CI container
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# case generation: hypothesis when present, seeded grid otherwise
# ---------------------------------------------------------------------------

def _grid_cases(n=2000, seed=0):
    """(m, c, tau, E, kappa) tuples spanning the regimes the formulas see."""
    rng = np.random.default_rng(seed)
    m = rng.integers(1, 400, n)
    c = rng.uniform(0.05, 5.0, n)
    tau = rng.uniform(0.5, 300.0, n)
    E = rng.integers(1, 8, n)
    kappa = rng.choice([1.0, 0.25, 3.7, 91.24, 511.6], n)
    return [(int(m[i]), float(c[i]), float(tau[i]), int(E[i]),
             float(kappa[i])) for i in range(n)]


def for_all_cases(f):
    """Run ``f(m, c, tau, E, kappa)`` for every generated case."""
    if HAVE_HYPOTHESIS:
        @settings(max_examples=500, deadline=None)
        @given(m=st.integers(1, 400), c=st.floats(0.05, 5.0),
               tau=st.floats(0.5, 300.0), E=st.integers(1, 8),
               kappa=st.sampled_from([1.0, 0.25, 3.7, 91.24, 511.6]))
        def run(m, c, tau, E, kappa):
            f(m, c, tau, E, kappa)
        run()
    else:
        for case in _grid_cases():
            f(*case)


# ---------------------------------------------------------------------------
# legacy byte-identity (the seed's formulas, inlined)
# ---------------------------------------------------------------------------

def test_legacy_budget_byte_identical():
    """cost=None reproduces the exact pre-refactor §4.2 arithmetic."""
    def check(m, c, tau, E, _kappa):
        got = coreset_budget(m, c, tau, E)
        want = m if E <= 1 else max(
            1, min(int(np.floor((c * tau - m) / (E - 1))), m))
        assert got == want
        assert needs_coreset(m, c, tau, E) == (E * m > c * tau)
    for_all_cases(check)


def test_legacy_fallback_byte_identical():
    """UNIT_COST.fallback_plan reproduces the seed's §4.4 block."""
    def check(m, c, tau, E, _kappa):
        plan = UNIT_COST.fallback_plan(m, c, tau, E)
        avail = c * tau - FORWARD_FRAC * m
        budget = max(1, min(int(avail // E), m))
        eff = max(1, min(E, int(avail // budget)))
        work = FORWARD_FRAC * m + eff * budget
        assert plan.budget == budget
        assert plan.eff_epochs == eff
        assert plan.work == work
        assert plan.violated == (work > c * tau * (1.0 + 1e-9))
    for_all_cases(check)


def test_nominal_budgets_legacy_unchanged():
    """The fleet driver's vectorized budgets match per-spec coreset_budget
    with and without a cost model."""
    from repro.fed.fleet.batched import nominal_budgets
    from repro.fed.simulator import ClientSpec
    rng = np.random.default_rng(7)
    specs = [ClientSpec(cid=i, m=int(rng.integers(4, 200)),
                        c=float(rng.uniform(0.1, 4.0))) for i in range(64)]
    cm = WorkloadCostModel(name="x", cost_per_sample=3.7, source="manual")
    for cost in (None, cm):
        budgets = nominal_budgets(specs, deadline=40.0, epochs=3, cost=cost)
        r = resolve_cost(cost)
        for s in specs:
            want = (s.m if not r.needs_coreset(s.m, s.c, 40.0, 3)
                    else r.budget(s.m, s.c, 40.0, 3))
            assert budgets[s.cid] == want


# ---------------------------------------------------------------------------
# budget laws for arbitrary cost models
# ---------------------------------------------------------------------------

def test_budget_bounds_and_monotonicity():
    def check(m, c, tau, E, kappa):
        cm = WorkloadCostModel(name="t", cost_per_sample=kappa,
                               source="manual")
        b = cm.budget(m, c, tau, E)
        assert 1 <= b <= m
        # monotone nondecreasing in deadline and in capability
        assert cm.budget(m, c, tau * 1.5, E) >= b
        assert cm.budget(m, c * 1.5, tau, E) >= b
        # more expensive samples never buy a bigger budget
        slow = WorkloadCostModel(name="t2", cost_per_sample=kappa * 2.0,
                                 source="manual")
        assert slow.budget(m, c, tau, E) <= b
    for_all_cases(check)


def test_plan_invariants():
    def check(m, c, tau, E, kappa):
        cm = WorkloadCostModel(name="t", cost_per_sample=kappa,
                               source="manual")
        plan = cm.primary_plan(m, c, tau, E)
        if plan is not None:
            assert not plan.violated
            assert plan.eff_epochs == E
            assert plan.work == m + (E - 1) * plan.budget
            # the primary plan fits inside the deadline by construction
            assert plan.work <= cm.available_samples(c, tau) * (1 + 1e-12)
        fb = cm.fallback_plan(m, c, tau, E)
        assert 1 <= fb.budget <= m
        assert 1 <= fb.eff_epochs <= E
        assert fb.work >= FORWARD_FRAC * m
        if not fb.violated:
            assert cm.work_units(fb.work) <= c * tau * (1.0 + 1e-9)
    for_all_cases(check)


def test_needs_coreset_consistent_with_full_round_time():
    def check(m, c, tau, E, kappa):
        cm = WorkloadCostModel(name="t", cost_per_sample=kappa,
                               source="manual")
        assert cm.needs_coreset(m, c, tau, E) == \
            (cm.full_round_time(m, c, E) > tau)
    for_all_cases(check)


# ---------------------------------------------------------------------------
# resolve_cost + conversions
# ---------------------------------------------------------------------------

def test_resolve_cost():
    assert resolve_cost(None) is UNIT_COST
    cm = WorkloadCostModel(name="x", cost_per_sample=2.0, source="manual")
    assert resolve_cost(cm) is cm
    scalar = resolve_cost(2.5)
    assert scalar.cost_per_sample == 2.5 and scalar.source == "manual"
    with pytest.raises(TypeError):
        resolve_cost("mlp")


def test_unit_conversions():
    cm = WorkloadCostModel(name="x", cost_per_sample=4.0, source="manual")
    # work: samples x kappa; duration: work / capability
    assert cm.work_units(10) == 40.0
    assert cm.duration(10, 2.0) == 20.0
    assert cm.full_round_time(m=10, capability=2.0, epochs=3) == 60.0
    # available samples invert duration: n samples fit in duration(n, c)
    n = cm.available_samples(2.0, 20.0)
    assert np.isclose(cm.duration(n, 2.0), 20.0)
    # the unit model is a passthrough
    assert UNIT_COST.work_units(7) == 7
    assert UNIT_COST.available_samples(3.0, 5.0) == 15.0


# ---------------------------------------------------------------------------
# measured golden table
# ---------------------------------------------------------------------------

# HLO FLOPs per sample for the jitted local-SGD step (batch 8), measured
# on the container's CPU backend.  XLA flop counting drifts across
# versions, hence the wide rtol; the *ordering* below is the strict part.
GOLDEN_FLOPS_PER_SAMPLE = {
    "mlp": 2.66e3,
    "cnn": 8.59e5,
    # a while body is counted once: the LSTM's hoisted matmuls count in
    # full, those left in its time loop for one step
    "charlm": 4.63e5,
    "xlstm": 7.53e5,
    "translm": 1.36e6,
}


@pytest.mark.parametrize("name", sorted(GOLDEN_FLOPS_PER_SAMPLE))
def test_golden_flops_table(name):
    cm = workload_cost_model(name)
    if cm.source != "flops":    # backend without cost_analysis FLOPs
        pytest.skip(f"backend reported no FLOPs (source={cm.source})")
    assert cm.flops_per_sample == pytest.approx(
        GOLDEN_FLOPS_PER_SAMPLE[name], rel=0.5)


def test_measured_cost_ordering():
    """What conditioning consumes: relative cost must rank the workloads
    by arithmetic intensity — every sequence/conv model costs a multiple
    of the flat-feature mlp reference, and the transformer block is the
    most expensive per sample."""
    cms = {n: workload_cost_model(n) for n in GOLDEN_FLOPS_PER_SAMPLE}
    rel = {n: cm.cost_per_sample for n, cm in cms.items()}
    assert rel["mlp"] == pytest.approx(1.0)     # self-normalized reference
    assert min(rel[n] for n in ("cnn", "charlm", "xlstm", "translm")) > 10.0
    assert rel["translm"] > rel["xlstm"] > rel["charlm"]
    # budgets respond: under one deadline the costly workload gets the
    # smaller coreset (deadline sized so mlp fits comfortably while a
    # ~500x-per-sample transformer is pinned at the floor)
    b_cheap = cms["mlp"].budget(50, 1.0, 200.0, 3)
    b_dear = cms["translm"].budget(50, 1.0, 200.0, 3)
    assert b_dear < b_cheap
