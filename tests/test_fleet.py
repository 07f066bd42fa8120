"""Fleet subsystem tests: batched kernels/solvers, cohort grouping,
batched-vs-loop engine parity, adaptive participation, and scenario
determinism through both the sync server and the async event runtime."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coreset import coreset_budget
from repro.core.kmedoids import (kmedoids_batched, kmedoids_jax,
                                 kmedoids_masked, pairwise_sq_dists)
from repro.fed.fleet.batched import (FleetConfig, FleetEngine, _floor_pow4,
                                     _next_pow2, make_cohort_groups,
                                     nominal_budgets, run_fleet,
                                     run_fleet_round)
from repro.fed.fleet.scenarios import SCENARIOS, build_scenario, run_scenario
from repro.fed.fleet.scheduler import (AdaptiveParticipation,
                                       ParticipationConfig)
from repro.fed.fleet.sharded import ShardedFleetEngine, client_mesh
from repro.fed.simulator import (ClientSpec, TraceConfig,
                                 straggler_deadline)
from repro.kernels.ops import pairwise_l2, pairwise_l2_batched


@pytest.fixture(scope="module")
def fleet_fl(fleet_bundles):
    # the deduped mlp bundle from conftest: same data/specs the sharded
    # and conformance suites build from
    b = fleet_bundles(workload="mlp", n_clients=16, seed=3)
    return b.model, b.train, b.test, b.specs


# ---------------------------------------------------------------------------
# batched primitives
# ---------------------------------------------------------------------------

def test_pairwise_l2_batched_matches_unbatched():
    x = np.random.default_rng(0).normal(size=(3, 40, 60)).astype(np.float32)
    xj = jnp.asarray(x)
    for squared in (True, False):
        ref = np.stack([np.asarray(pairwise_l2(xj[c], squared=squared))
                        for c in range(3)])
        got = np.asarray(pairwise_l2_batched(xj, squared=squared,
                                             use_kernel=True))
        np.testing.assert_allclose(got, ref, atol=2e-4)


def test_kmedoids_masked_matches_unpadded():
    rng = np.random.default_rng(1)
    m, m_pad, k = 21, 32, 5
    x = rng.normal(size=(m, 6)).astype(np.float32)
    D = np.sqrt(np.maximum(np.asarray(pairwise_sq_dists(jnp.asarray(x))), 0))
    Dp = rng.normal(size=(m_pad, m_pad)).astype(np.float32) * 50  # garbage
    Dp[:m, :m] = D
    valid = np.arange(m_pad) < m
    ref = kmedoids_jax(jnp.asarray(D), k)
    got = kmedoids_masked(jnp.asarray(Dp), jnp.asarray(valid), k)
    np.testing.assert_array_equal(np.asarray(got.medoids),
                                  np.asarray(ref.medoids))
    np.testing.assert_array_equal(np.asarray(got.weights),
                                  np.asarray(ref.weights))
    np.testing.assert_allclose(float(got.objective), float(ref.objective),
                               rtol=1e-5)
    assert (np.asarray(got.assignment)[m:] == -1).all()


def test_kmedoids_batched_equals_per_lane():
    rng = np.random.default_rng(2)
    C, m_pad, k = 5, 24, 3
    Ds, vs = [], []
    for _ in range(C):
        m = int(rng.integers(6, m_pad + 1))
        x = rng.normal(size=(m, 4)).astype(np.float32)
        D = np.sqrt(np.maximum(
            np.asarray(pairwise_sq_dists(jnp.asarray(x))), 0))
        Dp = np.zeros((m_pad, m_pad), np.float32)
        Dp[:m, :m] = D
        Ds.append(Dp)
        vs.append(np.arange(m_pad) < m)
    Ds, vs = jnp.asarray(np.stack(Ds)), jnp.asarray(np.stack(vs))
    batched = kmedoids_batched(Ds, vs, k)
    for c in range(C):
        lane = kmedoids_masked(Ds[c], vs[c], k)
        np.testing.assert_array_equal(np.asarray(batched.medoids[c]),
                                      np.asarray(lane.medoids))


# ---------------------------------------------------------------------------
# cohort grouping
# ---------------------------------------------------------------------------

def test_pow_helpers():
    assert [_next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert [_floor_pow4(n) for n in (1, 3, 4, 15, 16, 80)] == \
        [1, 1, 4, 4, 16, 64]


def test_floor_pow4_ladder_and_group_keys():
    """Budget quantization is a power-of-FOUR ladder (what the
    make_cohort_groups docstring promises), and group keys quantize member
    budgets with it — never exceeding any member's true budget."""
    # every rung of the ladder up to 4^5
    for e in range(6):
        lo, hi = 4 ** e, 4 ** (e + 1)
        for n in (lo, lo + 1, hi - 1):
            assert _floor_pow4(n) == lo, n
        assert _floor_pow4(hi) == hi
    # pow2-but-not-pow4 values round DOWN to the pow4 below
    assert [_floor_pow4(n) for n in (2, 8, 32, 128)] == [1, 4, 16, 64]

    # group keys: m=24 pads to 32 (next pow2 of 3 batches x B=8); budgets
    # 9 and 20 quantize to the (32, 4)/(32, 16) buckets; b >= m means k=0
    data = [{"x": np.zeros((24, 2), np.float32),
             "y": np.zeros(24, np.int32)} for _ in range(3)]
    cfg = FleetConfig(epochs=1, batch_size=8, seed=0)
    budgets = {0: 9, 1: 20, 2: 24}
    groups = make_cohort_groups(data, [0, 1, 2], budgets, cfg, 0)
    keys = {(g.valid.shape[1], g.k): g.cids.tolist() for g in groups}
    assert keys == {(32, 4): [0], (32, 16): [1], (32, 0): [2]}
    for g in groups:
        for cid in g.cids:
            assert g.k <= budgets[cid] or g.k == 0


def test_cohort_groups_partition_and_pad(fleet_fl):
    _, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=2, batch_size=16, seed=0)
    deadline = straggler_deadline(specs, cfg.epochs, 30.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    cids = list(range(len(specs)))
    groups = make_cohort_groups(train, cids, budgets, cfg, round_seed=1)
    seen = np.concatenate([g.cids for g in groups])
    assert sorted(seen.tolist()) == cids          # exact partition
    for g in groups:
        c, m_pad = g.valid.shape
        assert m_pad % cfg.batch_size == 0
        assert g.perms.shape == (c, cfg.epochs, m_pad)
        for i in range(c):
            # valid prefix mask matches true sizes; perms are permutations
            assert g.valid[i].sum() == g.m[i] <= m_pad
            for e in range(cfg.epochs):
                assert sorted(g.perms[i, e].tolist()) == list(range(m_pad))
            if g.k > 0:   # quantized budget never exceeds the true budget
                assert g.k <= budgets[g.cids[i]]


def test_cohort_groups_rng_independent_of_grouping(fleet_fl):
    _, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=2, batch_size=16, seed=0)
    deadline = straggler_deadline(specs, cfg.epochs, 30.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    full = make_cohort_groups(train, list(range(len(specs))), budgets, cfg, 0)
    solo = make_cohort_groups(train, [5], budgets, cfg, 0)
    g, idx = next((g, list(g.cids).index(5)) for g in full if 5 in g.cids)
    np.testing.assert_array_equal(g.perms[idx], solo[0].perms[0])


# ---------------------------------------------------------------------------
# engine parity + determinism
# ---------------------------------------------------------------------------

def test_batched_engine_matches_per_client_loop(fleet_fl):
    model, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=3, batch_size=16, lr=0.05, seed=0)
    deadline = straggler_deadline(specs, cfg.epochs, 40.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    engine = FleetEngine(model, cfg)
    params = model.init(jax.random.PRNGKey(0))
    cids = list(range(len(specs)))
    pb, sb = run_fleet_round(engine, params, train, cids, budgets,
                             round_seed=0, batched=True)
    pl, sl = run_fleet_round(engine, params, train, cids, budgets,
                             round_seed=0, batched=False)
    assert sb.used_coreset.sum() > 0      # the straggler path is exercised
    for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(pl)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert set(sb.medoids) == set(sl.medoids)
    for cid in sb.medoids:
        np.testing.assert_array_equal(sb.medoids[cid], sl.medoids[cid])
    np.testing.assert_allclose(sb.losses, sl.losses, atol=1e-5)


def test_lone_coreset_client_runs_as_two_lanes(fleet_fl):
    """A one-client coreset group is dispatched with its client repeated
    to two lanes (a one-lane program compiles for minutes on a TPU) and
    returns one lane, equal to the per-client loop; a one-client
    full-set group keeps one lane."""
    model, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=3, batch_size=16, lr=0.05, seed=0)
    deadline = straggler_deadline(specs, cfg.epochs, 40.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    groups = make_cohort_groups(train, list(range(len(specs))), budgets,
                                cfg, round_seed=0)
    engine = FleetEngine(model, cfg)
    params = model.init(jax.random.PRNGKey(0))
    for coreset in (True, False):
        g = next(g for g in groups if (g.k > 0) == coreset)
        lone = dataclasses.replace(
            g, cids=g.cids[:1], data=jax.tree.map(lambda v: v[:1], g.data),
            valid=g.valid[:1], m=g.m[:1], perms=g.perms[:1])
        _, args = engine.stage_group(params, lone)
        assert jax.tree.leaves(args[2])[0].shape[0] == 1 + coreset
        pb, lb, mb = engine.run_group(params, lone, batched=True)
        pl, ll, ml = engine.run_group(params, lone, batched=False)
        assert np.asarray(lb).shape == (1,)
        for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(pl)):
            assert a.shape[0] == 1
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        np.testing.assert_allclose(np.asarray(lb), np.asarray(ll),
                                   atol=1e-5)
        if coreset:
            np.testing.assert_array_equal(np.asarray(mb), np.asarray(ml))


class _ScriptedScheduler:
    """Minimal select/budget/observe/record_round scheduler driving a
    fixed per-round cohort script."""

    def __init__(self, cohorts, specs):
        self.cohorts = list(cohorts)
        self.specs = specs
        self.observed = []
        self._r = 0

    def select(self):
        cohort = self.cohorts[min(self._r, len(self.cohorts) - 1)]
        self._r += 1
        return np.asarray(cohort, np.int64)

    def budget(self, cid, deadline, epochs):
        return self.specs[cid].m    # full-set training for everyone

    def observe(self, cid, work, duration):
        self.observed.append((cid, work, duration))

    def record_round(self, train_loss):
        pass


def test_empty_cohort_round_is_noop(fleet_fl):
    """A scheduler may select an empty cohort (e.g. every candidate
    infeasible): the round must keep the previous params and record zero
    participants instead of crashing on an empty aggregation."""
    model, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=2, batch_size=16, seed=0)
    engine = FleetEngine(model, cfg)
    params = model.init(jax.random.PRNGKey(0))

    # direct round-level check: params pass through bit-identically
    p2, stats = run_fleet_round(engine, params, train, [], {}, round_seed=0)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats.cids.size == 0 and stats.losses.size == 0
    assert stats.medoids == {}

    # driver-level: an empty middle round yields a zero-participant record
    sched = _ScriptedScheduler([[0, 1], [], [0, 1]], specs)
    out = run_fleet(model, train, specs, cfg, rounds=3, scheduler=sched)
    rec = out["history"][1]
    assert rec.n_participants == 0
    assert rec.sim_round_time == 0.0 and rec.client_times == []
    assert np.isnan(rec.train_loss)
    # surrounding rounds still train
    assert out["history"][0].n_participants == 2
    assert out["history"][2].n_participants == 2


def test_fleet_trace_indexed_per_client_dispatch(fleet_fl):
    """The CapabilityTrace is defined per (client, dispatch): a client
    absent for some rounds must draw its *next* trace entry on return,
    exactly as the sync server and async event loop index it — not the
    round number (the old bug)."""
    from repro.fed.simulator import CapabilityTrace

    model, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=2, batch_size=16, seed=0)
    tc = TraceConfig(jitter_std=0.3, slowdown_prob=0.5,
                     slowdown_factor=4.0, seed=7)
    # client 0 participates every round; client 1 skips rounds 1-2
    cohorts = [[0, 1], [0], [0], [0, 1]]
    sched = _ScriptedScheduler(cohorts, specs)
    out = run_fleet(model, train, specs, cfg, rounds=4, scheduler=sched,
                    trace=tc)

    # reference: a fresh trace indexed by per-client dispatch counts —
    # the indexing contract shared with events.py (dispatch_counts) and
    # server.py; same (seed, cid, index) => identical draws everywhere
    ref = CapabilityTrace(tc)
    counts = {cid: 0 for cid in range(len(specs))}
    for r, cohort in enumerate(cohorts):
        rec = out["history"][r]
        assert rec.n_participants == len(cohort)
        expect = []
        for cid in cohort:
            k = counts[cid]
            counts[cid] += 1
            s = specs[cid]
            work = cfg.epochs * s.m      # full-set budgets (see scheduler)
            expect.append(work / ref.capability(s, k) * ref.jitter(s, k))
        # client_times follow cohort-group order; compare as multisets
        np.testing.assert_allclose(sorted(rec.client_times), sorted(expect),
                                   rtol=1e-12)
    # client 1's second appearance (round 3) drew dispatch index 1; the
    # old code indexed by round number and would have drawn entry 3
    s1 = specs[1]
    assert (ref.capability(s1, 1), ref.jitter(s1, 1)) != \
        (ref.capability(s1, 3), ref.jitter(s1, 3))


def test_run_fleet_deterministic_and_trace_sensitive(fleet_fl):
    model, train, test, specs = fleet_fl
    _, trace = build_scenario("flash_crowd", [s.m for s in specs], seed=0)
    cfg = FleetConfig(epochs=2, batch_size=16, seed=0)

    def go():
        return run_fleet(model, train, specs, cfg, rounds=2, trace=trace,
                         test_data=test)
    a, b = go(), go()
    assert [dataclasses.astuple(r) for r in a["history"]] == \
        [dataclasses.astuple(r) for r in b["history"]]
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the trace perturbs durations relative to a no-trace run
    c = run_fleet(model, train, specs, cfg, rounds=2, test_data=test)
    assert a["history"][0].client_times != c["history"][0].client_times


# ---------------------------------------------------------------------------
# sharded engine (single-device mesh; the 4-virtual-device parity run
# lives in test_fleet_sharded.py)
# ---------------------------------------------------------------------------

def test_sharded_engine_matches_batched(fleet_fl):
    """shard_map execution + psum-tree aggregation reproduce the batched
    engine: identical medoids, params within float32 tolerance.  On one
    device this exercises the full sharded code path (placement,
    padding, psum) without cross-device splits."""
    model, train, _, specs = fleet_fl
    cfg = FleetConfig(epochs=3, batch_size=16, lr=0.05, seed=0)
    deadline = straggler_deadline(specs, cfg.epochs, 40.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    params = model.init(jax.random.PRNGKey(0))
    cids = list(range(len(specs)))
    pb, sb = run_fleet_round(FleetEngine(model, cfg), params, train, cids,
                             budgets, round_seed=0, mode="batched")
    eng = ShardedFleetEngine(model, cfg, mesh=client_mesh())
    ps, ss = run_fleet_round(eng, params, train, cids, budgets,
                             round_seed=0, mode="sharded")
    assert sb.used_coreset.sum() > 0
    for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(ps)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert set(sb.medoids) == set(ss.medoids)
    for cid in sb.medoids:
        np.testing.assert_array_equal(sb.medoids[cid], ss.medoids[cid])
    np.testing.assert_allclose(sb.losses, ss.losses, atol=1e-5)


def test_run_fleet_sharded_engine_option(fleet_fl):
    """run_fleet(engine="sharded") matches the batched driver end to end
    (on one device it falls back to the batched path; on a multi-device
    host it runs the mesh engine — either way the history must agree)."""
    model, train, test, specs = fleet_fl
    cfg = FleetConfig(epochs=2, batch_size=16, seed=0)
    a = run_fleet(model, train, specs, cfg, rounds=2, test_data=test,
                  engine="sharded")
    b = run_fleet(model, train, specs, cfg, rounds=2, test_data=test,
                  engine="batched")
    assert a["engine"] == "sharded"
    assert a["engine_mode"] == ("batched" if a["n_devices"] == 1
                                else "sharded")
    for ra, rb in zip(a["history"], b["history"]):
        assert ra.n_participants == rb.n_participants
        np.testing.assert_allclose(ra.train_loss, rb.train_loss, atol=1e-5)
        np.testing.assert_allclose(ra.test_acc, rb.test_acc, atol=1e-5)


# ---------------------------------------------------------------------------
# adaptive participation
# ---------------------------------------------------------------------------

def _specs(caps, m=50):
    return [ClientSpec(cid=i, m=m, c=float(c)) for i, c in enumerate(caps)]


def test_scheduler_selects_fastest_and_explores():
    specs = _specs([1.0, 9.0, 8.0, 0.1, 7.0, 0.2, 0.3, 6.0])
    sched = AdaptiveParticipation(specs, ParticipationConfig(
        min_cohort=4, explore_frac=0.25, seed=0))
    cohort = sched.select()
    assert len(cohort) == 4
    # 3 fastest guaranteed, 1 explored from the rest
    assert {1, 2, 4} <= set(cohort.tolist())
    # dispatch weights: cohort at 1.0, soft exploration tail at explore_frac
    mask = sched.eligible_mask()
    assert (mask == 1.0).sum() == 4 and (mask[[1, 2, 4, 7]] == 1.0).all()
    assert (mask[[0, 3, 5, 6]] == 0.25).all()


def test_scheduler_doubles_on_plateau():
    specs = _specs(np.ones(64))
    sched = AdaptiveParticipation(specs, ParticipationConfig(
        min_cohort=4, growth_factor=2.0, plateau_tol=0.01,
        plateau_patience=1))
    sizes = []
    for _ in range(6):
        sizes.append(sched.cohort_size())
        sched.record_round(1.0)       # never improves => plateau every round
    # round 0 only sets the loss baseline; doubling starts at round 1
    assert sizes == [4, 4, 8, 16, 32, 64]
    sched.record_round(1.0)
    assert sched.cohort_size() == 64  # capped at the fleet size


def test_scheduler_improvement_defers_growth():
    specs = _specs(np.ones(16))
    sched = AdaptiveParticipation(specs, ParticipationConfig(
        min_cohort=4, plateau_tol=0.01, plateau_patience=1))
    loss = 1.0
    for _ in range(4):
        sched.record_round(loss)
        loss *= 0.5                   # strong improvement every round
    assert sched.cohort_size() == 4
    assert sched.growth_log == []


def test_scheduler_observed_capability_reranks_and_rebudgets():
    specs = _specs([2.0, 1.0], m=100)
    sched = AdaptiveParticipation(specs, ParticipationConfig(
        min_cohort=1, explore_frac=0.0, ewma=1.0))
    assert sched.select().tolist() == [0]
    # client 0 turns out to be 20x slower than nominal
    sched.observe(0, work_units=100.0, duration=1000.0)
    assert sched.select().tolist() == [1]
    # budget follows the observed capability, not the spec sheet
    b_nominal = coreset_budget(100, 2.0, deadline=100.0, epochs=3)
    assert b_nominal == 50
    b_observed = sched.budget(0, deadline=100.0, epochs=3)
    assert b_observed < b_nominal
    assert b_observed == coreset_budget(100, 0.1, 100.0, 3)


# ---------------------------------------------------------------------------
# scenarios through both runtimes, from one registry
# ---------------------------------------------------------------------------

SWEPT = ("uniform", "pareto", "flash_crowd", "device_classes")


def test_registry_has_named_regimes():
    assert set(SWEPT) <= set(SCENARIOS)
    assert len(SCENARIOS) >= 5
    sizes = [40] * 200
    for name in SCENARIOS:
        specs, trace = build_scenario(name, sizes, seed=0)
        caps = np.array([s.c for s in specs])
        assert (caps > 0).all()
        assert 0.3 < caps.mean() < 3.0   # mean-≈1 so deadlines compare
        specs2, _ = build_scenario(name, sizes, seed=0)
        assert [s.c for s in specs2] == [s.c for s in specs]


@pytest.mark.parametrize("name", SWEPT)
def test_scenario_sync_deterministic(fleet_fl, name):
    model, train, test, _ = fleet_fl

    def go():
        return run_scenario(name, "sync", model, train, seed=1, rounds=2,
                            clients_per_round=3, epochs=2, batch_size=8)

    def virtual(history):   # drop wall_time — the only real-clock field
        recs = [dataclasses.asdict(r) for r in history]
        for r in recs:
            r.pop("wall_time")
        return recs
    a, b = go(), go()
    assert virtual(a["history"]) == virtual(b["history"])
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("name", SWEPT)
def test_scenario_async_deterministic(fleet_fl, name):
    model, train, test, _ = fleet_fl

    def go():
        return run_scenario(name, "async", model, train, seed=1,
                            max_updates=6, clients_per_round=3,
                            concurrency=3, epochs=2, batch_size=8)
    a, b = go(), go()
    assert "\n".join(a["event_log"]).encode() == \
        "\n".join(b["event_log"]).encode()
    assert a["telemetry"]["makespan"] == b["telemetry"]["makespan"]


def test_scenarios_differ_from_each_other(fleet_fl):
    model, train, _, _ = fleet_fl
    logs = {}
    for name in ("uniform", "flash_crowd"):
        out = run_scenario(name, "async", model, train, seed=1,
                           max_updates=6, concurrency=3, epochs=2,
                           batch_size=8)
        logs[name] = out["event_log"]
    assert logs["uniform"] != logs["flash_crowd"]


def test_async_scheduler_restricts_dispatch(fleet_fl):
    model, train, _, specs = fleet_fl
    # ewma=0 freezes the ranking so the eligible set is constant all run
    sched = AdaptiveParticipation(specs, ParticipationConfig(
        min_cohort=4, explore_frac=0.0, plateau_tol=1.0,
        max_cohort=4, ewma=0.0))
    out = run_scenario("uniform", "async", model, train, seed=1,
                       max_updates=8, concurrency=4, epochs=2,
                       batch_size=8, scheduler=sched)
    eligible = set(np.flatnonzero(sched.eligible_mask()).tolist())
    dispatched = {int(line.split("cid=")[1].split(" ")[0])
                  for line in out["event_log"] if " dispatch " in line}
    assert dispatched <= eligible
    assert (sched._n_obs > 0).sum() > 0


def test_fleet_runtime_via_registry(fleet_fl):
    model, train, test, _ = fleet_fl
    out = run_scenario("device_classes", "fleet", model, train, test,
                       seed=0, rounds=2, epochs=2, batch_size=16)
    assert out["runtime"] == "fleet"
    assert len(out["history"]) == 2
    assert np.isfinite(out["history"][-1].test_acc)
