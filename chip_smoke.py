#!/usr/bin/env python3
"""Drive FedCore's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: fleet round + FedCore-LM
    python chip_smoke.py --chips 4   # sharded fleet engine on four chips

Phases (one process; no phase's failure is caught):

* fleet — the registered ``cnn`` workload (SmallCNN on pseudo-MNIST, the
  paper's MNIST family) at its registered width: a 1024-client fleet under
  the ``device_classes`` scenario, 2 rounds of
  ``run_fleet(engine="batched")`` with the selection kernels compiled
  (Pallas, no interpret mode), selection groups on both sides of the
  ``materialize_below`` cutover, then round 0 again with
  ``use_kernel=False`` as the jnp reference.
* lm — ``repro.launch.train.main`` on the 100m preset with ``--fedcore``.
* sharded (``--chips 4`` only) — one round of the mesh-sharded engine on
  four devices against the same round on one device.

Exits non-zero, and prints no result, when JAX finds no TPU.  The last
line of stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# aggregated-params tolerance of the workload conformance matrix for cnn
# (tests/test_workload_conformance.py PARAMS_ATOL)
PARAMS_ATOL = 2e-4
STRAGGLER_PCT = 30.0
# FasterPAM swap sweeps: enough for every lane of the smoke fleets to
# reach a fixed point (FleetConfig's default of 25 stops large-k lanes
# short), so that each lane's selection can be judged on its own
MAX_SWEEPS = 1000


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _say(msg: str) -> None:
    print(msg, flush=True)


def _cnn_fleet(n_clients: int, mean_samples: float, std_samples: float,
               seed: int):
    """(workload, train clients, test data, specs, trace) of the cnn fleet
    under the ``device_classes`` scenario."""
    from repro.data.partition import train_test_split_clients
    from repro.fed.fleet.scenarios import build_scenario
    from repro.fed.fleet.workloads import client_sizes, get_workload

    wl = get_workload("cnn")
    clients = wl.make_clients(n_clients=n_clients, seed=seed,
                              mean_samples=mean_samples,
                              std_samples=std_samples)
    wl.validate_clients(clients)
    train, test = train_test_split_clients(clients, test_frac=0.1)
    specs, trace = build_scenario("device_classes", client_sizes(train),
                                  seed)
    return wl, train, test, specs, trace


def _round0_groups(wl, train, specs, cfg):
    """Round-0 deadline, budgets and cohort groups, as ``run_fleet``
    derives them (every client participates, nominal budgets)."""
    from repro.fed.cost import resolve_cost
    from repro.fed.fleet.batched import make_cohort_groups, nominal_budgets
    from repro.fed.simulator import straggler_deadline

    cost = resolve_cost(cfg.cost)
    deadline = straggler_deadline(specs, cfg.epochs, STRAGGLER_PCT, cost)
    budgets = nominal_budgets(specs, deadline, cfg.epochs, cost)
    cids = list(range(len(specs)))
    groups = make_cohort_groups(train, cids, budgets, cfg, round_seed=0)
    return cids, budgets, groups


def _max_abs_diff(a, b) -> float:
    import jax
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _dists(x: np.ndarray, dtype) -> np.ndarray:
    """(M, M) distances by the norm expansion the solvers use."""
    x = x.astype(dtype)
    sq = (x * x).sum(-1)
    d = np.sqrt(np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0))
    np.fill_diagonal(d, 0.0)
    return d


def _lane_gain(x: np.ndarray, medoids: np.ndarray):
    """One lane's medoid set against FasterPAM's stopping rule, in
    float64: (best swap gain, allowed gain, objective).

    x holds the lane's valid rows.  The solver stops once no swap gains
    more than SWAP_RTOL of the objective in its own float32 arithmetic,
    so a float32 fixed point may show a float64 gain of up to
    SWAP_RTOL·objective plus the float32 error of its swap table.  That
    error is measured on the lane: the table built from the float32
    norm-expansion distances against the one built from float64
    distances.  The allowance takes it twice, as the kernels' arithmetic
    is held to twice the jnp reference's error."""
    from repro.core.kmedoids import BIG, SWAP_RTOL, swap_deltas_numpy

    delta, obj = swap_deltas_numpy(_dists(x, np.float64), medoids)
    delta32, _ = swap_deltas_numpy(_dists(x, np.float32), medoids)
    free = delta < BIG / 2                  # j is not a medoid
    if not free.any():
        return 0.0, SWAP_RTOL * obj, obj
    err = float(np.abs(delta32 - delta)[free].max())
    gain = max(0.0, -float(delta[free].min()))
    return gain, SWAP_RTOL * obj + 2.0 * err, obj


def _judge_lanes(groups, feats, runs) -> dict:
    """Every coreset lane of ``groups`` in every run of ``runs``
    ({name: {cid: medoid indices}}) must be a float64 fixed point within
    its allowance (``_lane_gain``).  Returns lanes, lanes whose sets agree
    across runs, the worst relative objective gap between runs, the worst
    gain/allowance per run, and the failing (run, cid) pairs."""
    out = {"lanes": 0, "same": 0, "gap": 0.0,
           "ratio": {name: 0.0 for name in runs}, "failed": []}
    for g in groups:
        for i, cid in enumerate(g.cids):
            x = feats[id(g)][i][g.valid[i]]
            sets = {name: np.sort(np.asarray(r[int(cid)]))
                    for name, r in runs.items()}
            judged, objs = {}, []
            for name, meds in sets.items():
                key = meds.tobytes()
                if key not in judged:
                    judged[key] = _lane_gain(x, meds)
                gain, allowed, obj = judged[key]
                objs.append(obj)
                out["ratio"][name] = max(out["ratio"][name],
                                         gain / max(allowed, 1e-30))
                if gain > allowed:
                    out["failed"].append((name, int(cid)))
            out["lanes"] += 1
            out["same"] += len(judged) == 1
            out["gap"] = max(out["gap"], (max(objs) - min(objs))
                             / max(min(objs), 1e-30))
    return out


def _select(cfg, g, feats: np.ndarray, use_kernel: bool) -> dict:
    """{cid: medoids} of the group's selection on ``feats``, through the
    solver the group programs call."""
    import jax

    from repro.core.coreset import build_coreset_batched

    select = jax.jit(lambda f, v: build_coreset_batched(
        f, v, g.k, use_kernel=use_kernel, max_sweeps=cfg.max_sweeps,
        distance_free=cfg.distance_free,
        materialize_below=cfg.materialize_below).indices)
    meds = np.asarray(select(feats, g.valid))
    return {int(c): m for c, m in zip(g.cids, meds)}


def _bf16_control(cfg, groups, feats) -> dict:
    """The lane judge run on selections made from features rounded to
    bfloat16 — what a one-pass bf16 contraction does to its operands — on
    the jnp path.  The judge must flag some of them, or it could not tell
    a float32 selection from a wrong one."""
    import jax.numpy as jnp

    meds = {}
    for g in groups:
        f16 = np.asarray(jnp.asarray(feats[id(g)]).astype(jnp.bfloat16)
                         .astype(jnp.float32))
        meds.update(_select(cfg, g, f16, use_kernel=False))
    return _judge_lanes(groups, feats, {"bf16": meds})


def _kernel_math_errors(x: np.ndarray, valid: np.ndarray, medoids):
    """Max abs error against float64 of the Pallas kernels and of the jnp
    reference, on identical float32 inputs: {name: (kernel, jnp, scale)}.

    x (C, M, F) features with zero padded rows; ``medoids`` (C, k) sets
    the Δ-sweep's nearest / second-nearest inputs."""
    import jax.numpy as jnp

    from repro.kernels import ops

    c, m, _ = x.shape
    vf = valid.astype(np.float32)
    d64 = np.stack([_dists(x[i], np.float64) for i in range(c)])
    dm = np.take_along_axis(d64, medoids[:, None, :], axis=2)   # (C, M, k)
    order = np.argsort(dm, axis=-1)
    d1 = np.take_along_axis(dm, order[..., :1], -1)[..., 0]
    d2 = (np.take_along_axis(dm, order[..., 1:2], -1)[..., 0]
          if medoids.shape[1] > 1 else np.full_like(d1, 1e30))
    onehot = (np.arange(medoids.shape[1]) == order[..., :1]).astype(
        np.float32)
    big = np.full((c, m), 1e30, np.float32)
    want = {
        "pairwise": d64,
        "build_cost": (d64 * vf[..., None]).sum(1),
        "delta_A": ((np.minimum(d64, d1[..., None]) - d1[..., None])
                    * vf[..., None]).sum(1),
        "delta_B": np.einsum("cij,cil->cjl", (np.clip(
            d64, d1[..., None], d2[..., None]) - d1[..., None])
            * vf[..., None], onehot),
    }
    for name in ("build_cost", "delta_A", "delta_B"):
        want[name + "_D"] = want[name]
    xs, vs = jnp.asarray(x), jnp.asarray(vf)
    d1s, d2s = jnp.asarray(d1, jnp.float32), jnp.asarray(d2, jnp.float32)
    out = {}
    for uk in (True, False):
        got = {"pairwise": ops.pairwise_l2_batched(x, use_kernel=uk,
                                                   zero_diag=True),
               "build_cost": ops.kmedoids_build_cost_from_feats(
                   xs, jnp.asarray(big), vs, use_kernel=uk)}
        got["delta_A"], got["delta_B"] = \
            ops.kmedoids_delta_sweep_from_feats(xs, d1s, d2s, vs,
                                                jnp.asarray(onehot),
                                                use_kernel=uk)
        # the D-input pair, on the float64 distances rounded to float32
        d32 = jnp.asarray(d64, jnp.float32)
        got["build_cost_D"] = ops.kmedoids_build_cost(
            d32, jnp.asarray(big), vs, use_kernel=uk)
        got["delta_A_D"], got["delta_B_D"] = ops.kmedoids_delta_sweep(
            d32, d1s, d2s, vs, jnp.asarray(onehot), use_kernel=uk)
        for name, g in got.items():
            g = np.asarray(g, np.float64)
            if name == "pairwise":
                mask = valid[:, :, None] & valid[:, None, :]
            elif name.startswith("delta_B"):
                mask = np.broadcast_to(valid[..., None], g.shape)
            else:
                mask = valid
            err = float(np.abs(g - want[name])[mask].max())
            scale = float(np.abs(want[name])[mask].max())
            out.setdefault(name, [None, None, scale])[0 if uk else 1] = err
    return {k: tuple(v) for k, v in out.items()}


def _round_spans(records):
    """Per round: (wall s, dispatches, compiles) from the recorder's
    ``round`` spans and the group-dispatch spans inside each."""
    spans = [r for r in records if r["kind"] == "span"]
    rounds = sorted((s for s in spans if s["name"] == "round"),
                    key=lambda s: s["attrs"]["round"])
    out = []
    for rs in rounds:
        inner = [s for s in spans
                 if s["name"] in ("local_sgd", "coreset_group")
                 and rs["t0"] <= s["t0"] <= rs["t1"]]
        out.append((rs["dur"], len(inner),
                    sum(bool(s["attrs"].get("compile")) for s in inner)))
    return out


def fleet_phase(n_clients: int = 1024, mean_samples: float = 384.0,
                std_samples: float = 256.0, rounds: int = 2, seed: int = 0,
                materialize_below: int = 256) -> dict:
    """Two fleet rounds of the cnn workload, then round 0 with the
    kernels on and off.  Returns what the checks read."""
    import jax

    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         run_fleet, run_fleet_round)
    from repro.kernels.ops import resolve_use_kernel
    from repro.obs import InMemorySink, Recorder, use_recorder

    t0 = time.perf_counter()
    wl, train, test, specs, trace = _cnn_fleet(n_clients, mean_samples,
                                               std_samples, seed)
    cfg = FleetConfig(seed=seed, materialize_below=materialize_below,
                      max_sweeps=MAX_SWEEPS)
    kernels = resolve_use_kernel(cfg.use_kernel)
    cids, budgets, groups = _round0_groups(wl, train, specs, cfg)
    n_bytes = sum(np.asarray(v).nbytes for c in train
                  for v in jax.tree.leaves(c))
    _say(f"[fleet] cnn: {n_clients} clients, {n_bytes / 1e9:.3f} GB of "
         f"client data, set-up {time.perf_counter() - t0:.1f} s; "
         f"selection kernels {'Pallas' if kernels else 'jnp'}")
    cut = cfg.materialize_below
    for g in groups:
        m_pad = g.valid.shape[1]
        path = ("full-set" if g.k == 0 else
                "distance-free" if m_pad >= cut else "D-input")
        _say(f"[fleet]   group M={m_pad:5d} k={g.k:5d} "
             f"clients={g.n_clients:4d}  {path}")
    sel = [g for g in groups if g.k > 0]
    dfree = [g for g in sel if g.valid.shape[1] >= cut]
    dinput = [g for g in sel if g.valid.shape[1] < cut]
    _check(bool(dfree) and bool(dinput),
           f"selection groups on both sides of materialize_below={cut}")

    # -- the main path: run_fleet, batched engine ---------------------------
    sink = InMemorySink()
    t0 = time.perf_counter()
    with use_recorder(Recorder([sink])):
        out = run_fleet(wl, train, specs, cfg, rounds=rounds, trace=trace,
                        straggler_pct=STRAGGLER_PCT, test_data=test,
                        engine="batched")
    params = jax.block_until_ready(out["params"])
    total = time.perf_counter() - t0
    per_round = _round_spans(sink.records)
    # a round span ends after the eval, whose host read of the accuracy
    # waits for every group program of the round
    for rec, (wall, n_disp, n_comp) in zip(out["history"], per_round):
        _say(f"[fleet] round {rec.round}: wall {wall:.3f} s (to the eval's "
             f"host read), groups {n_disp}, coreset clients "
             f"{rec.n_coreset}, compiles {n_comp}, test acc "
             f"{rec.test_acc:.4f}")
    _say(f"[fleet] run_fleet {rounds} rounds: {total:.3f} s to "
         f"block_until_ready (compilation included)")
    _check(len(out["history"]) == rounds, "every round ran")
    _check(all(r.n_coreset > 0 for r in out["history"]),
           "every round has coreset clients")
    _check(all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(params)), "params are finite")
    _check(all(np.isfinite(r.test_acc) for r in out["history"]),
           "test accuracy is finite")

    # -- the kernels compiled into the group programs -----------------------
    params0 = wl.init(jax.random.PRNGKey(cfg.seed))
    eng_on = FleetEngine(wl, cfg)
    custom_call = {}
    for name, g in (("distance-free", dfree[0]), ("D-input", dinput[0])):
        program, args = eng_on.stage_group(params0, g)
        text = program.lower(*args).compile().as_text()
        custom_call[name] = "tpu_custom_call" in text
        _say(f"[fleet] {name} group program (M={g.valid.shape[1]}, "
             f"k={g.k}): tpu_custom_call "
             f"{'present' if custom_call[name] else 'absent'}")
    if kernels:
        _check(all(custom_call.values()),
               "tpu_custom_call in both selection group programs")

    # -- round 0, kernels on vs the jnp reference ---------------------------
    eng_off = FleetEngine(wl, dataclasses.replace(cfg, use_kernel=False))
    p_on, s_on = run_fleet_round(eng_on, params0, train, cids, budgets,
                                 round_seed=0, groups=groups)
    p_off, s_off = run_fleet_round(eng_off, params0, train, cids, budgets,
                                   round_seed=0, groups=groups)
    dparams = _max_abs_diff(p_on, p_off)
    feats_fn = jax.jit(jax.vmap(wl.grad_features, in_axes=(None, 0)))
    feats = {id(g): np.asarray(feats_fn(params0, jax.tree.map(
        np.asarray, g.data))) * g.valid[..., None] for g in sel}

    # the kernels' arithmetic: on identical float32 inputs, no further
    # from float64 than the jnp reference's, up to twice its error plus
    # the worst-case float32 rounding of an M-term sum (M·2⁻²⁴·scale)
    for name, g in (("distance-free", dfree[0]), ("D-input", dinput[0])):
        meds = np.stack([s_on.medoids[int(c)] for c in g.cids])
        errs = _kernel_math_errors(feats[id(g)], g.valid, meds)
        m_pad = g.valid.shape[1]
        for op, (e_k, e_j, scale) in errs.items():
            _say(f"[fleet] {name} group {op}: max abs error vs f64 "
                 f"kernel {e_k:.3e}, jnp {e_j:.3e} (scale {scale:.3e})")
            _check(e_k <= 2.0 * e_j + m_pad * 2.0 ** -24 * scale,
                   f"{op} kernel as accurate as the jnp reference")

    # the selections, per lane: k-medoids is a local search, and the two
    # paths' float32 rounding can end a lane in different local optima;
    # each path's set must be a fixed point of the swap rule in float64
    judged = _judge_lanes(sel, feats, {"kernel": s_on.medoids,
                                       "jnp": s_off.medoids})
    ctrl_groups = [max(side, key=lambda g: (g.k, g.n_clients))
                   for side in (dfree, dinput)]
    control = _bf16_control(cfg, ctrl_groups, feats)
    _say(f"[fleet] round 0 kernel vs jnp: {judged['same']}/"
         f"{judged['lanes']} medoid sets identical, lane objectives max "
         f"rel diff {judged['gap']:.3e}; worst float64 swap gain / "
         f"allowance kernel {judged['ratio']['kernel']:.3f}, jnp "
         f"{judged['ratio']['jnp']:.3f}, {len(judged['failed'])} lanes "
         f"not at a fixed point; params max abs diff {dparams:.3e} "
         f"(limit {PARAMS_ATOL:.0e})")
    _say(f"[fleet] bf16 control ({control['lanes']} lanes of the largest-k "
         f"group on each side of the cutover): worst swap gain / "
         f"allowance {control['ratio']['bf16']:.3f}, "
         f"{len(control['failed'])} lanes flagged")
    _check(not judged["failed"],
           f"every lane's medoids a float64 fixed point: failed "
           f"{judged['failed']}")
    _check(dparams <= PARAMS_ATOL, "round-0 params, kernel vs jnp")
    return {"kernels": kernels, "custom_call": custom_call,
            "history": out["history"], "per_round": per_round,
            "medoids_identical": judged["same"],
            "selection_lanes": judged["lanes"],
            "objective_rel_diff": judged["gap"], "params_diff": dparams,
            "control_flagged": len(control["failed"])}


def lm_phase(preset: str = "100m", silos: int = 4, rounds: int = 2,
             steps: int = 4) -> dict:
    """FedCore-LM through the training launcher's entry point."""
    from repro.launch import train

    t0 = time.perf_counter()
    out = train.main(["--preset", preset, "--fedcore", "--silos",
                      str(silos), "--rounds", str(rounds), "--steps",
                      str(steps)])
    wall = time.perf_counter() - t0
    hist = out["history"]
    _say(f"[lm] {preset}: {rounds} FedCore rounds over {silos} silos in "
         f"{wall:.3f} s (compilation included)")
    _check(len(hist) == rounds, "every LM round ran")
    _check(all(h["round_time"] <= h["tau"] * 1.001 for h in hist),
           "LM rounds within the deadline")
    _check(all(np.isfinite(h["loss"]) for h in hist), "LM loss is finite")
    return out


def sharded_phase(n_devices: int = 4, n_clients: int = 256,
                  mean_samples: float = 384.0, std_samples: float = 256.0,
                  seed: int = 0) -> dict:
    """Round 0 on the mesh-sharded engine against the batched engine on
    one device: every lane's medoids a float64 fixed point on both
    engines, params within tolerance, group data spread over every
    device, an all-reduce in the compiled program."""
    import jax

    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         run_fleet_round)
    from repro.fed.fleet.sharded import ShardedFleetEngine, client_mesh

    _check(len(jax.devices()) >= n_devices,
           f"{n_devices} devices (found {len(jax.devices())})")
    wl, train, _, specs, _ = _cnn_fleet(n_clients, mean_samples,
                                        std_samples, seed)
    cfg = FleetConfig(seed=seed, max_sweeps=MAX_SWEEPS)
    cids, budgets, groups = _round0_groups(wl, train, specs, cfg)
    params0 = wl.init(jax.random.PRNGKey(cfg.seed))
    mesh = client_mesh(n_devices)
    sharded = ShardedFleetEngine(wl, cfg, mesh=mesh)

    g = max((g for g in groups if g.k > 0), key=lambda g: g.n_clients)
    program, args = sharded.stage_group(params0, g)
    names = ("params", "data", "w", "lane_w", "idx1", "valid", "steps")
    for name, a in zip(names, args):
        leaf = jax.tree.leaves(a)[0]
        _say(f"[sharded] {name}: {leaf.shape} {leaf.sharding}")
    data_leaf = jax.tree.leaves(args[1])[0]
    spread = {s.device for s in data_leaf.addressable_shards}
    _say(f"[sharded] group data (C={g.n_clients}, k={g.k}) on "
         f"{len(spread)} devices: {sorted(d.id for d in spread)}")
    _check(len(spread) == n_devices, "group data spread over every device")
    text = program.lower(*args).compile().as_text()
    all_reduce = "all-reduce" in text
    _say(f"[sharded] compiled group program: all-reduce "
         f"{'present' if all_reduce else 'absent'}")
    _check(all_reduce, "all-reduce in the sharded group program")

    t0 = time.perf_counter()
    p_sh, s_sh = run_fleet_round(sharded, params0, train, cids, budgets,
                                 round_seed=0, groups=groups,
                                 mode="sharded")
    p_sh = jax.block_until_ready(p_sh)
    t_sh = time.perf_counter() - t0
    t0 = time.perf_counter()
    p_b, s_b = run_fleet_round(FleetEngine(wl, cfg), params0, train, cids,
                               budgets, round_seed=0, groups=groups,
                               mode="batched")
    p_b = jax.block_until_ready(p_b)
    t_b = time.perf_counter() - t0
    dparams = _max_abs_diff(p_sh, p_b)
    sel = [g for g in groups if g.k > 0]
    feats_fn = jax.jit(jax.vmap(wl.grad_features, in_axes=(None, 0)))
    feats = {id(g): np.asarray(feats_fn(params0, jax.tree.map(
        np.asarray, g.data))) * g.valid[..., None] for g in sel}
    judged = _judge_lanes(sel, feats, {"sharded": s_sh.medoids,
                                       "batched": s_b.medoids})
    f_diff, f_same, f_lanes = _split_feature_diff(wl, params0, sel, mesh)
    _say(f"[sharded] round 0: sharded {t_sh:.3f} s on {n_devices} "
         f"devices, batched {t_b:.3f} s on one (compilation included); "
         f"{judged['same']}/{judged['lanes']} medoid sets identical, lane "
         f"objectives max rel diff {judged['gap']:.3e}; worst float64 swap "
         f"gain / allowance sharded {judged['ratio']['sharded']:.3f}, "
         f"batched {judged['ratio']['batched']:.3f}, "
         f"{len(judged['failed'])} lanes not at a fixed point; params max "
         f"abs diff {dparams:.3e} (limit {PARAMS_ATOL:.0e})")
    _say(f"[sharded] round-start features of the coreset groups, per "
         f"device vs on one device: {f_same}/{f_lanes} lanes "
         f"bit-identical, max abs diff {f_diff:.3e}")
    _check(set(s_sh.medoids) == set(s_b.medoids), "same coreset clients")
    _check(not judged["failed"],
           f"every lane's medoids a float64 fixed point: failed "
           f"{judged['failed']}")
    _check(dparams <= PARAMS_ATOL, "sharded params within tolerance")
    return {"spread": len(spread), "all_reduce": all_reduce,
            "medoids_identical": judged["same"],
            "selection_lanes": judged["lanes"], "params_diff": dparams,
            "feature_lanes_identical": f_same}


def _split_feature_diff(wl, params, groups, mesh):
    """The coreset groups' round-start gradient features computed as the
    sharded engine lays them out (each device vmaps its own C/n lanes)
    and on one device (one vmap over all C lanes): (max abs diff, lanes
    whose features are bit-identical, lanes)."""
    import jax
    from jax.sharding import PartitionSpec as P

    vm = jax.vmap(wl.grad_features, in_axes=(None, 0))
    one_device = jax.jit(vm)
    per_device = jax.jit(jax.shard_map(
        vm, mesh=mesh, in_specs=(P(), P("clients")),
        out_specs=P("clients")))
    diff, same, lanes = 0.0, 0, 0
    for g in groups:
        c = g.n_clients
        pad = (-c) % mesh.devices.size
        data = jax.tree.map(lambda v: np.concatenate(
            [v, np.repeat(v[-1:], pad, axis=0)]), g.data)
        valid = g.valid[..., None]
        one = np.asarray(one_device(params, data))[:c] * valid
        split = np.asarray(per_device(params, data))[:c] * valid
        diff = max(diff, float(np.abs(one - split).max()))
        same += sum(np.array_equal(one[i], split[i]) for i in range(c))
        lanes += c
    return diff, same, lanes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    from repro.utils.xla_env import enable_compile_cache

    cache = enable_compile_cache()
    _say(f"device: {dev.device_kind}, {len(devices)} device(s); "
         f"compile cache {cache}")
    if args.chips == 4:
        sharded_phase(n_devices=4)
    else:
        report = fleet_phase()
        _check(report["kernels"], "Pallas selection kernels on the TPU")
        # a few small lanes may all pass under bf16; the full-size
        # fleet's largest-k groups must not
        _check(report["control_flagged"] > 0,
               "the lane check flags the bf16 control")
        lm_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
