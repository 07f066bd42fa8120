"""Fleet-scale sweep: batched 1000+-client rounds + scenario matrix.

Two measurements in one harness:

1. **Engine benchmark** — one full federated round over N=1024 clients
   (synthetic logreg workload, device-class-mixture capabilities),
   executed twice from identical seeds: once by the batched fleet engine
   (clients vmapped inside a handful of XLA programs) and once by the
   per-client Python loop reference (same jitted math, one client per
   dispatch).  Results must agree (same medoids, params within
   tolerance); the report is clients/sec, virtual round makespan, and the
   batched-over-loop wall-clock speedup (target: ≥ 5×).

2. **Selection-phase breakdown** — the coreset-selection pipeline
   (features → distance stack → k-medoids) over every straggler group
   of the same cohort, fused single-dispatch program (Δ-sweep fast
   path) vs the pre-fusion 3-dispatch chain, plus a Pallas-kernel
   on/off A-B on the fused path.  Records ``selection_wall_s``,
   dispatches-per-group, and the kernel A/B under
   ``BENCH_fleet.json["selection"]``; gates on fused == baseline
   medoids and ``--min-selection-speedup``.

2b. **Selection memory** (``--selection-memory``) — peak selection RSS
   + wall A/B of the distance-free solver vs the materializing
   (C, M, M) stack at M ∈ {128, 512, 2048}, one fresh subprocess per
   point (VmHWM across the cold solve; XLA's allocator retains warm
   buffers, so reused processes can't see the peak).  The stack path is
   skipped at the top M — its O(C·M²) peak is extrapolated from the
   512 point — and the gates are: distance-free completes M = 2048,
   its measured peak there stays under 25% of the extrapolated stack
   peak, and the small-M throughput ratio holds the
   ``--min-selection-memory-speedup`` keep-green.  Results land under
   ``BENCH_fleet.json["selection"]["memory"]``.

3. **Scenario sweep** — every named heterogeneity regime from
   ``repro.fed.fleet.scenarios`` driven through BOTH the synchronous
   server and the async event runtime at smoke scale, so regressions in
   either path show up as a changed loss/makespan row.

3b. **Workload matrix** — every registered ``FleetWorkload`` (mlp, cnn,
   charlm, xlstm, translm) driven through the batched fleet runtime at smoke
   scale with a per-round history recorded under
   ``BENCH_fleet.json["workloads"]``, plus a batched-vs-loop round-0
   parity gate per workload (the rigorous cross-engine matrix lives in
   ``tests/test_workload_conformance.py``).  ``--workload`` additionally
   selects which workload the engine/selection benchmarks (1) and (2)
   run on — the tracked selection gate stays on the default ``mlp``.

3c. **Cost model** (``--cost-model``) — per-workload measured step costs
   (HLO FLOPs per sample, normalized to mlp) under
   ``BENCH_fleet.json["cost_model"]``, plus the deadline A/B on the most
   expensive workload: cost-conditioned budgets vs the κ-ignorant legacy
   sample-count planner on the same device_classes fleet with the same
   measured durations; gates on violation-rate(cost) ≤
   violation-rate(legacy).  The ``make bench-cost`` keep-green target.

4. **Sharded device sweep** (``--device-sweep 1,2,4``) — the mesh-sharded
   engine (``repro.fed.fleet.sharded``) timed at increasing device
   counts on the same fleet, one subprocess per count (XLA fixes the
   host-platform device count at import, so each point re-execs this
   script with ``--xla_force_host_platform_device_count=N``).  Records
   round throughput per device count plus a sharded-vs-batched parity
   check at the largest mesh.  Wall-clock scaling on CPU is bounded by
   the physical core count — the recorded ``n_cpu_cores`` says how much
   parallelism the host could possibly expose.

Writes ``BENCH_fleet.json`` next to this script (override with --out) so
the perf trajectory is tracked in-repo.

  PYTHONPATH=src python benchmarks/fleet_sweep.py --smoke     # CPU, ~2 min
  PYTHONPATH=src python benchmarks/fleet_sweep.py             # full
  PYTHONPATH=src python benchmarks/fleet_sweep.py --smoke \
      --skip-engine --skip-scenarios --device-sweep 1,2,4     # scaling
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from repro.data.partition import train_test_split_clients
from repro.data.synthetic import synthetic_dataset
from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                     make_cohort_groups, nominal_budgets,
                                     run_fleet_round)
from repro.fed.fleet.scenarios import SCENARIOS, build_scenario, run_scenario
from repro.fed.fleet.workloads import WORKLOADS, client_sizes, get_workload
from repro.fed.simulator import straggler_deadline
from repro.models.small import LogisticRegression
from repro.utils.xla_env import (enable_compile_cache,
                                 forced_host_device_env)

SWEEP_SCENARIOS = ("uniform", "pareto", "diurnal", "flash_crowd",
                   "device_classes")


def _max_param_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _engine_workload(n_clients: int, epochs: int, batch_size: int,
                     seed: int, use_kernel, workload: str = "mlp"):
    """Shared builder for the engine/selection benchmarks: an n-client
    device-class fleet of the chosen ``FleetWorkload`` (default mlp —
    byte-identical to the pre-workload-axis synthetic-logreg fleet), its
    cohort grouping (timed — the round driver runs it once per round
    either way), and the round-start params."""
    wl = get_workload(workload)
    clients = wl.make_clients(n_clients=n_clients, seed=seed,
                              mean_samples=48.0, std_samples=32.0)
    train, _ = train_test_split_clients(clients, test_frac=0.2)
    specs, _ = build_scenario("device_classes", client_sizes(train), seed)
    model = wl
    cfg = FleetConfig(epochs=epochs, batch_size=batch_size, lr=0.05,
                      seed=seed, use_kernel=use_kernel)
    deadline = straggler_deadline(specs, cfg.epochs, 30.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    params = model.init(jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    groups = make_cohort_groups(train, list(range(len(specs))), budgets,
                                cfg, round_seed=0)
    prep_s = time.perf_counter() - t0
    return model, train, specs, cfg, budgets, params, groups, prep_s


def bench_selection(n_clients: int, epochs: int, batch_size: int,
                    seed: int = 0, use_kernel=None, reps: int = 3,
                    workload: str = "mlp", verbose: bool = False) -> Dict:
    """Selection-phase breakdown: fused single-dispatch program vs the
    pre-fusion 3-dispatch chain, plus a Pallas-kernel on/off A-B.

    "Selection" is the straggler path's feature → distance-stack →
    k-medoids pipeline over every coreset group of one cohort round.  The
    fused path runs it as one jitted program per group (Δ-sweep fast
    path); the unfused baseline replays the dispatch chain this PR
    replaced (jitted feature pass, jitted pairwise program, eager
    diagonal fix-up, jitted legacy-sweep solve).  Warm wall clocks are
    min-over-reps; parity requires identical medoid indices — exact
    equality holds because the fused path's distance-free selection
    materializes below the adaptive ``FleetConfig.materialize_below``
    cutover, which these fleet-sized groups (M < 256) always are (the
    streaming solver is only cost-tied, not bit-identical; its parity
    gate is ``tests/test_distance_free.py``).
    """
    from repro.kernels.ops import resolve_use_kernel
    model, _, _, cfg, _, params, groups, _ = _engine_workload(
        n_clients, epochs, batch_size, seed, use_kernel, workload)
    sgroups = [g for g in groups if g.k > 0]
    if not sgroups:
        raise RuntimeError("selection benchmark found no straggler groups")

    def run(engine, fused):
        outs = [engine.select_group_coresets(params, g, fused=fused)[0]
                for g in sgroups]
        jax.block_until_ready([o.indices for o in outs])
        return outs

    def timed(engine, fused, tag):
        t0 = time.perf_counter()
        outs = run(engine, fused)
        dt = time.perf_counter() - t0
        if verbose:
            print(f"  [{'fused' if fused else 'chain'}] {tag:9s} {dt:8.3f}s")
        return outs, dt

    def measure(engine, fused, tag):
        outs, cold = timed(engine, fused, f"{tag}/cold")
        warm = min(timed(engine, fused, f"{tag}/warm{i}")[1]
                   for i in range(reps))
        return outs, cold, warm

    engine = FleetEngine(model, cfg)
    outs_fused, cold_f, warm_f = measure(engine, True, "auto")
    outs_chain, cold_u, warm_u = measure(engine, False, "legacy")
    meds_equal = all(
        np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
        for a, b in zip(outs_fused, outs_chain))

    # kernel A-B on the fused path (forced on = interpret mode off-TPU)
    ab = {}
    for label, uk in (("on", True), ("off", False)):
        eng = FleetEngine(model, dataclasses.replace(cfg, use_kernel=uk))
        _, _, ab[label] = measure(eng, True, f"kernel-{label}")

    return {
        "workload": workload,
        "n_clients": n_clients,
        "epochs": epochs,
        "n_straggler_groups": len(sgroups),
        "n_coreset_clients": int(sum(g.n_clients for g in sgroups)),
        "budgets_k": sorted({g.k for g in sgroups}),
        "use_kernel_mode": {None: "auto", True: "on",
                            False: "off"}[use_kernel],
        "use_kernel_resolved": resolve_use_kernel(use_kernel),
        "selection_wall_s": warm_f,
        "selection_unfused_wall_s": warm_u,
        "selection_cold_wall_s": cold_f,
        "selection_unfused_cold_wall_s": cold_u,
        "selection_speedup": warm_u / warm_f,
        "dispatches_per_group_fused": 1,
        "dispatches_per_group_unfused": 3,
        "kernel_ab": {"fused_kernel_on_wall_s": ab["on"],
                      "fused_kernel_off_wall_s": ab["off"],
                      # > 1 means forcing the kernels on is slower (on CPU
                      # "on" = interpret mode, which is why auto picks off)
                      "on_over_off_wall_ratio": ab["on"] / ab["off"]},
        "parity_medoids_equal": bool(meds_equal),
    }


SELECTION_MEMORY_MS = (128, 512, 2048)


def _vm_hwm_bytes() -> int:
    """Peak resident set (VmHWM) of this process, in bytes (-1 off-Linux)."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmHWM:"):
                    return int(ln.split()[1]) * 1024
    except OSError:
        pass
    return -1


def selection_memory_worker(variant: str, m: int, c: int, f: int, k: int,
                            reps: int) -> Dict:
    """One (variant, M) selection-memory point, run in a fresh process.

    Peak selection memory is only observable *cold*: XLA's host allocator
    retains warm buffers, so a warm re-solve in a reused process shows a
    zero RSS delta.  Each point therefore re-execs this script and
    measures VmHWM across the first solve (baseline read after the input
    stack is resident, so the delta is the solver's working set plus its
    one-time compile).  ``variant``: ``dfree`` is the shipped default
    (``distance_free=True`` with the adaptive materialize-below-256
    cutover), ``stack`` forces the materializing (C, M, M) baseline.
    Prints a RESULT: JSON line for the parent to parse."""
    import jax.numpy as jnp
    from repro.core.coreset import build_coreset_batched

    rng = np.random.default_rng(1234 + m)
    x = rng.normal(size=(c, m, f)).astype(np.float32)
    valid = np.ones((c, m), bool)
    valid[:, m - max(m // 8, 1):] = False   # engine-style padded tail rows
    x[~valid] = 0.0
    feats = jnp.asarray(x)
    vj = jnp.asarray(valid)
    jax.block_until_ready(feats)
    distance_free = variant == "dfree"

    def solve():
        res = build_coreset_batched(feats, vj, k,
                                    distance_free=distance_free,
                                    max_sweeps=4)
        jax.block_until_ready(res.indices)
        return res

    base = _vm_hwm_bytes()
    t0 = time.perf_counter()
    solve()
    cold = time.perf_counter() - t0
    peak = _vm_hwm_bytes()
    warm = None
    for _ in range(reps):
        t0 = time.perf_counter()
        solve()
        dt = time.perf_counter() - t0
        warm = dt if warm is None else min(warm, dt)
    result = {
        "variant": variant, "m": m, "c": c, "f": f, "k": k,
        "completed": True,
        "cold_wall_s": cold,
        "warm_wall_s": warm,
        "baseline_rss_bytes": base,
        "peak_rss_delta_bytes": max(peak - base, 0),
    }
    print("RESULT:" + json.dumps(result))
    return result


def bench_selection_memory(c: int = 16, f: int = 32, k: int = 16,
                           reps: int = 3, ms=SELECTION_MEMORY_MS) -> Dict:
    """Peak selection memory + large-M throughput A/B (distance-free vs
    materializing stack), one fresh subprocess per point.

    The materializing path is measured up to M = 512 and *skipped* at the
    top M — its (C, M, M) working set extrapolates as O(C·M²) from the
    measured 512 point (16x at 2048), which is exactly the wall the
    distance-free path removes; running it would need ~1 GB at the
    default C = 16 and OOM on smaller CI boxes.  Gates (applied by
    ``main``): the distance-free path must *complete* the top M; its
    measured peak there must stay under 25% of the stack path's
    extrapolated peak; and at the smallest M (below the adaptive
    materialize cutover, where both variants run the same program) the
    distance-free warm wall must hold the keep-green ≥1x throughput
    ratio."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    m_max = max(ms)
    points: List[Dict] = []
    for m in sorted(ms):
        for variant in ("stack", "dfree"):
            if variant == "stack" and m >= m_max and len(ms) > 1:
                points.append({
                    "variant": variant, "m": m, "c": c, "f": f, "k": k,
                    "completed": False, "skipped": True,
                    "skip_reason": "O(C*M^2) stack at the top M is the "
                                   "wall being measured; peak is "
                                   "extrapolated from the 512 point",
                })
                print(f"  [stack ] M={m:5d}: skipped (extrapolated)")
                continue
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--selection-memory-worker", "--sm-variant", variant,
                   "--sm-m", str(m), "--sm-clients", str(c),
                   "--sm-f", str(f), "--sm-k", str(k),
                   "--sm-reps", str(reps)]
            proc = subprocess.run(cmd, env=forced_host_device_env(1, repo),
                                  capture_output=True, text=True)
            line = next((ln for ln in proc.stdout.splitlines()
                         if ln.startswith("RESULT:")), None)
            if proc.returncode != 0 or line is None:
                row = {"variant": variant, "m": m, "c": c, "f": f, "k": k,
                       "completed": False, "skipped": False,
                       "error": (proc.stderr or proc.stdout)[-2000:]}
                print(f"  [{variant:6s}] M={m:5d}: FAILED")
            else:
                row = json.loads(line[len("RESULT:"):])
                row["skipped"] = False
                print(f"  [{variant:6s}] M={m:5d}: peak "
                      f"{row['peak_rss_delta_bytes'] / 1e6:8.1f} MB  "
                      f"cold {row['cold_wall_s']:.2f}s  "
                      f"warm {row['warm_wall_s']:.3f}s")
            points.append(row)

    def pick(variant, m):
        return next((p for p in points
                     if p["variant"] == variant and p["m"] == m), None)

    m_small, m_mid = min(ms), sorted(ms)[-2] if len(ms) > 1 else min(ms)
    stack_mid = pick("stack", m_mid)
    dfree_top = pick("dfree", m_max)
    d128, s128 = pick("dfree", m_small), pick("stack", m_small)
    extrapolated = None
    peak_ratio = None
    if stack_mid and stack_mid.get("completed"):
        extrapolated = (stack_mid["peak_rss_delta_bytes"]
                        * (m_max / m_mid) ** 2)
        if dfree_top and dfree_top.get("completed"):
            peak_ratio = dfree_top["peak_rss_delta_bytes"] / extrapolated
    speedup_small = None
    if (d128 and s128 and d128.get("completed") and s128.get("completed")):
        speedup_small = s128["warm_wall_s"] / d128["warm_wall_s"]
    return {
        "points": points,
        "m_values": sorted(ms),
        "dfree_completed_top_m": bool(dfree_top
                                      and dfree_top.get("completed")),
        "top_m": m_max,
        "stack_peak_extrapolated_bytes": extrapolated,
        "dfree_top_peak_bytes": (dfree_top or {}).get(
            "peak_rss_delta_bytes"),
        "peak_ratio_vs_extrapolated_stack": peak_ratio,
        "small_m": m_small,
        "small_m_dfree_speedup": speedup_small,
    }


def bench_engine(n_clients: int, epochs: int, batch_size: int,
                 seed: int = 0, use_kernel=None, workload: str = "mlp",
                 verbose: bool = False) -> Dict:
    """Time one identical 1024-client round through both engines."""
    # identical workload to bench_selection (one shared builder), with the
    # cohort grouping prep reported separately; what's timed here is
    # *engine execution*: every group through run_group + aggregate,
    # exactly what run_fleet_round executes
    model, train, specs, cfg, budgets, params, groups, prep_s = \
        _engine_workload(n_clients, epochs, batch_size, seed, use_kernel,
                         workload)
    engine = FleetEngine(model, cfg)
    cids = list(range(len(specs)))

    def timed(batched: bool, tag: str):
        t0 = time.perf_counter()
        out = run_fleet_round(engine, params, train, cids, budgets,
                              round_seed=0, batched=batched,
                              groups=groups)
        jax.block_until_ready(out[0])
        dt = time.perf_counter() - t0
        if verbose:
            label = "batched" if batched else "loop"
            print(f"  [{label}] {tag:6s} {dt:8.3f}s")
        return out, dt

    # cold passes compile every group program; the comparison is the min
    # over warm reps (wall clocks on shared CI boxes are noisy)
    reps = 3
    (_, _), cold_b = timed(True, "cold")
    warm = [timed(True, f"warm{i}") for i in range(reps)]
    (pb, sb), warm_b = warm[0][0], min(dt for _, dt in warm)

    # telemetry overhead: the same warm batched round with the full
    # observability stack live (spans + metrics + JSONL sink), vs the
    # recording-off wall just measured; the <3% budget is what keeps the
    # recorder always-on-able in production runs
    import tempfile
    from repro.obs import JSONLSink, Recorder, use_recorder
    with tempfile.TemporaryDirectory() as tmp:
        rec = Recorder(sinks=[JSONLSink(os.path.join(tmp, "bench.jsonl"))])
        with use_recorder(rec):
            warm_r = min(timed(True, f"rec-warm{i}")[1]
                         for i in range(reps))
        rec.close()
    rec_overhead_pct = 100.0 * (warm_r - warm_b) / warm_b

    (_, _), cold_l = timed(False, "cold")
    warm = [timed(False, f"warm{i}") for i in range(reps)]
    (pl, sl), warm_l = warm[0][0], min(dt for _, dt in warm)

    diff = _max_param_diff(pb, pl)
    meds_equal = (set(sb.medoids) == set(sl.medoids) and all(
        np.array_equal(sb.medoids[c], sl.medoids[c]) for c in sb.medoids))
    speedup = warm_l / warm_b
    makespan = max(sb.work[i] / specs[c].c
                   for i, c in enumerate(sb.cids))
    return {
        "workload": workload,
        "n_clients": n_clients,
        "epochs": epochs,
        "batch_size": batch_size,
        "n_coreset_clients": int(sb.used_coreset.sum()),
        "group_construction_s": prep_s,
        "n_groups": len(groups),
        "batched_wall_s": warm_b,
        "loop_wall_s": warm_l,
        "batched_cold_wall_s": cold_b,
        "loop_cold_wall_s": cold_l,
        "recording_warm_wall_s": warm_r,
        "recording_overhead_pct": rec_overhead_pct,
        "speedup": speedup,
        "clients_per_sec": n_clients / warm_b,
        "round_makespan_virtual_s": float(makespan),
        "parity_max_param_diff": diff,
        "parity_medoids_equal": bool(meds_equal),
    }


class _LazyFleetClients:
    """Sequence view that synthesizes a client's dataset on first access.

    The 100k-client scale point needs 100k ``ClientSpec`` rows but only
    ever trains the few hundred clients the event loop actually
    dispatches — so data is generated per-cid on ``__getitem__`` (mlp
    schema: flat float32 features + int32 labels) and cached.  Sizes are
    fixed up front so specs and data always agree."""

    def __init__(self, sizes: List[int], n_features: int = 60,
                 n_classes: int = 10, seed: int = 0):
        self.sizes = list(sizes)
        self.n_features = n_features
        self.n_classes = n_classes
        self.seed = seed
        self._cache: Dict[int, Dict[str, np.ndarray]] = {}

    def __len__(self) -> int:
        return len(self.sizes)

    @property
    def n_materialized(self) -> int:
        return len(self._cache)

    def __getitem__(self, cid: int) -> Dict[str, np.ndarray]:
        got = self._cache.get(cid)
        if got is None:
            m = self.sizes[cid]
            rng = np.random.default_rng(
                np.random.SeedSequence((self.seed, int(cid))))
            got = {
                "x": rng.normal(
                    size=(m, self.n_features)).astype(np.float32),
                "y": rng.integers(
                    0, self.n_classes, size=m).astype(np.int32),
            }
            self._cache[cid] = got
        return got


def bench_async_fleet(n_clients: int, epochs: int, batch_size: int,
                      seed: int = 0, use_kernel=None, workload: str = "mlp",
                      flushes: int = 4, reps: int = 2,
                      verbose: bool = False) -> Dict:
    """Throughput of the event-driven async fleet engine at the sync
    engine's reference fleet size.

    The same device-class fleet as ``bench_engine``, driven through
    ``run_async_fleet`` with the whole fleet in flight and K sized so
    ``flushes`` buffer flushes merge every client once — the async
    analogue of one barrier round.  Reported clients/sec is merged
    clients over the min warm wall (a caller-held engine keeps the group
    program cache warm across reps, exactly like the sync benchmark's
    reused engine)."""
    from repro.fed.fleet.async_engine import (AsyncFleetConfig,
                                              run_async_fleet)
    wl = get_workload(workload)
    clients = wl.make_clients(n_clients=n_clients, seed=seed,
                              mean_samples=48.0, std_samples=32.0)
    train, _ = train_test_split_clients(clients, test_frac=0.2)
    specs, trace = build_scenario("device_classes", client_sizes(train),
                                  seed)
    buffer_k = max(1, len(specs) // flushes)
    cfg = AsyncFleetConfig(max_updates=flushes, buffer_k=buffer_k,
                           concurrency=len(specs), epochs=epochs,
                           batch_size=batch_size, lr=0.05, seed=seed,
                           use_kernel=use_kernel, trace=trace)
    eng = FleetEngine(wl, cfg.fleet_config())

    def timed(tag):
        t0 = time.perf_counter()
        out = run_async_fleet(wl, train, specs, cfg, engine="batched",
                              engine_obj=eng)
        jax.block_until_ready(out["params"])
        dt = time.perf_counter() - t0
        if verbose:
            print(f"  [async_fleet] {tag:6s} {dt:8.3f}s")
        return out, dt

    out, cold = timed("cold")
    warm_runs = [timed(f"warm{i}") for i in range(reps)]
    out, warm = warm_runs[0][0], min(dt for _, dt in warm_runs)
    tel = out["telemetry"]
    return {
        "workload": workload,
        "n_clients": len(specs),
        "epochs": epochs,
        "batch_size": batch_size,
        "flushes": int(out["applied"]),
        "buffer_k": buffer_k,
        "cold_wall_s": cold,
        "warm_wall_s": warm,
        "clients_per_sec": tel["n_merged_clients"] / warm,
        "n_merged_clients": tel["n_merged_clients"],
        "n_dispatches": tel["n_dispatches"],
        "n_group_dispatches": tel["n_group_dispatches"],
        "n_partial_flushes": tel["n_partial_flushes"],
        "makespan_virtual_s": tel["makespan"],
        "mean_staleness": tel["mean_staleness"],
        "staleness_hist": np.asarray(tel["staleness_hist"]).tolist(),
        "buffer_occupancy_hist":
            np.asarray(tel["buffer_occupancy_hist"]).tolist(),
        "mean_buffer_occupancy": tel["mean_buffer_occupancy"],
    }


def bench_async_fleet_scale(n_clients: int = 100_000, seed: int = 0,
                            concurrency: int = 256, buffer_k: int = 64,
                            flushes: int = 2, verbose: bool = False) -> Dict:
    """The 100k-client completion point: a fleet of 100k specs through
    the event-driven engine on CPU.  Feasible because (a) dispatch waves
    and the event queue are O(in-flight), not O(fleet), (b) jitted
    dispatches scale with cohort-group shapes per flush, not clients,
    and (c) client data is materialized lazily — only dispatched cids
    ever exist in memory."""
    from repro.fed.fleet.async_engine import (AsyncFleetConfig,
                                              run_async_fleet)
    rng = np.random.default_rng(seed)
    sizes = np.clip(rng.normal(48.0, 32.0, n_clients), 8, None).astype(int)
    specs, trace = build_scenario("device_classes", sizes.tolist(), seed)
    train = _LazyFleetClients(sizes.tolist(), seed=seed)
    cfg = AsyncFleetConfig(max_updates=flushes, buffer_k=buffer_k,
                           concurrency=concurrency, epochs=2, batch_size=8,
                           lr=0.05, seed=seed, trace=trace)
    t0 = time.perf_counter()
    out = run_async_fleet(get_workload("mlp"), train, specs, cfg,
                          engine="batched")
    jax.block_until_ready(out["params"])
    wall = time.perf_counter() - t0
    tel = out["telemetry"]
    row = {
        "n_clients": n_clients,
        "concurrency": concurrency,
        "buffer_k": buffer_k,
        "flushes": int(out["applied"]),
        "wall_s": wall,
        "n_dispatches": tel["n_dispatches"],
        "n_group_dispatches": tel["n_group_dispatches"],
        "n_merged_clients": tel["n_merged_clients"],
        "n_clients_materialized": train.n_materialized,
        "makespan_virtual_s": tel["makespan"],
        "completed": bool(out["applied"] >= 1),
    }
    if verbose:
        print(f"  scale point ({n_clients} clients): {wall:.1f}s wall, "
              f"{row['n_dispatches']} client dispatches -> "
              f"{row['n_group_dispatches']} group programs, "
              f"{row['n_clients_materialized']} of {n_clients} clients "
              f"materialized")
    return row


def _sharded_fleet(n_clients: int, epochs: int, batch_size: int, seed: int):
    """Shared workload builder for the device sweep (worker + parity)."""
    clients = synthetic_dataset(0.5, 0.5, n_clients=n_clients,
                                mean_samples=160.0, std_samples=64.0,
                                seed=seed)
    train, _ = train_test_split_clients(clients, test_frac=0.2)
    sizes = [len(d["y"]) for d in train]
    specs, _ = build_scenario("device_classes", sizes, seed)
    model = LogisticRegression()
    cfg = FleetConfig(epochs=epochs, batch_size=batch_size, lr=0.05,
                      seed=seed)
    deadline = straggler_deadline(specs, cfg.epochs, 30.0)
    budgets = nominal_budgets(specs, deadline, cfg.epochs)
    return model, train, specs, cfg, budgets


def sharded_worker(n_clients: int, epochs: int, batch_size: int, seed: int,
                   parity: bool, reps: int = 5) -> Dict:
    """One device-sweep point: time sharded rounds at this process's
    device count.  Prints a RESULT: JSON line for the parent to parse."""
    from repro.fed.fleet.sharded import ShardedFleetEngine, client_mesh
    model, train, specs, cfg, budgets = _sharded_fleet(
        n_clients, epochs, batch_size, seed)
    params = model.init(jax.random.PRNGKey(seed))
    cids = list(range(len(specs)))
    groups = make_cohort_groups(train, cids, budgets, cfg, round_seed=0)
    engine = ShardedFleetEngine(model, cfg, mesh=client_mesh())

    def timed(eng, mode):
        t0 = time.perf_counter()
        out = run_fleet_round(eng, params, train, cids, budgets,
                              round_seed=0, mode=mode, groups=groups)
        jax.block_until_ready(out[0])
        return out, time.perf_counter() - t0

    (_, _), cold = timed(engine, "sharded")
    warm_runs = [timed(engine, "sharded") for _ in range(reps)]
    (ps, ss), warm = warm_runs[0][0], min(dt for _, dt in warm_runs)
    result = {
        "n_devices": len(jax.devices()),
        "n_clients": n_clients,
        "cold_wall_s": cold,
        "warm_wall_s": warm,
        "clients_per_sec": n_clients / warm,
    }
    if parity:
        eng_b = FleetEngine(model, cfg)
        timed(eng_b, "batched")     # compile
        (pb, sb), _ = timed(eng_b, "batched")
        result["parity_max_param_diff"] = _max_param_diff(ps, pb)
        result["parity_medoids_equal"] = bool(
            set(ss.medoids) == set(sb.medoids) and all(
                np.array_equal(ss.medoids[c], sb.medoids[c])
                for c in sb.medoids))
    print("RESULT:" + json.dumps(result))
    return result


def bench_sharded_scaling(device_counts: List[int], n_clients: int,
                          epochs: int, batch_size: int, seed: int) -> Dict:
    """Run one subprocess per device count; collect throughput + parity."""
    per_count: Dict[str, Dict] = {}
    for nd in device_counts:
        cmd = [sys.executable, os.path.abspath(__file__), "--sharded-worker",
               "--clients", str(n_clients), "--epochs", str(epochs),
               "--batch-size", str(batch_size), "--seed", str(seed)]
        if nd == max(device_counts):
            cmd.append("--parity")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(cmd, env=forced_host_device_env(nd, repo),
                              capture_output=True, text=True)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("RESULT:")), None)
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"device-sweep worker (devices={nd}) failed:\n"
                f"{proc.stdout}\n{proc.stderr}")
        row = json.loads(line[len("RESULT:"):])
        per_count[str(nd)] = row
        print(f"  devices {nd}: warm {row['warm_wall_s']:.3f}s "
              f"({row['clients_per_sec']:.0f} clients/s)")
    lo, hi = str(min(device_counts)), str(max(device_counts))
    speedup = (per_count[hi]["clients_per_sec"]
               / per_count[lo]["clients_per_sec"])
    return {
        "n_cpu_cores": os.cpu_count(),
        "device_counts": device_counts,
        "workload": {"n_clients": n_clients, "epochs": epochs,
                     "batch_size": batch_size, "seed": seed},
        "per_device_count": per_count,
        "throughput_speedup_max_vs_min": speedup,
        "parity_max_param_diff":
            per_count[hi].get("parity_max_param_diff"),
        "parity_medoids_equal": per_count[hi].get("parity_medoids_equal"),
    }


def sweep_workloads(names, rounds: int, epochs: int, n_clients: int = 24,
                    seed: int = 0, verbose: bool = False) -> Dict:
    """Per-workload fleet rounds: every registered ``FleetWorkload``
    through the batched fleet runtime via the scenario registry, with a
    per-round history row and a batched-vs-loop round-0 parity gate
    (identical train loss / test acc to float32 tolerance)."""
    table = {}
    for name in names:
        wl = get_workload(name)
        clients = wl.make_clients(n_clients=n_clients, seed=seed)
        train, test = train_test_split_clients(clients, test_frac=0.2)
        t0 = time.perf_counter()
        out = run_scenario("device_classes", "fleet", clients_data=train,
                           test_data=test, workload=wl, seed=seed,
                           rounds=rounds, epochs=epochs, batch_size=8,
                           fleet_engine="batched")
        wall = time.perf_counter() - t0
        ref = run_scenario("device_classes", "fleet", clients_data=train,
                           test_data=test, workload=wl, seed=seed,
                           rounds=1, epochs=epochs, batch_size=8,
                           fleet_engine="loop")
        h0, r0 = out["history"][0], ref["history"][0]
        parity = (abs(h0.train_loss - r0.train_loss) < 1e-4
                  and abs(h0.test_acc - r0.test_acc) < 1e-4)
        hist = out["history"]
        table[name] = {
            "description": wl.description,
            "n_clients": len(train),
            "batched_wall_s": wall,
            "final_train_loss": float(hist[-1].train_loss),
            "final_test_acc": float(hist[-1].test_acc),
            "n_coreset_total": int(sum(r.n_coreset for r in hist)),
            "parity_loop_round0": bool(parity),
            "rounds": [{
                "round": r.round,
                "train_loss": float(r.train_loss),
                "test_acc": float(r.test_acc),
                "sim_round_time": float(r.sim_round_time),
                "n_coreset": int(r.n_coreset),
            } for r in hist],
        }
        if verbose:
            print(f"  {name:8s} loss={table[name]['final_train_loss']:.3f} "
                  f"acc={table[name]['final_test_acc']:.3f} "
                  f"core={table[name]['n_coreset_total']:3d} "
                  f"wall={wall:6.2f}s "
                  f"parity={'PASS' if parity else 'FAIL'}")
    return table


class _LegacySamplePlanner:
    """κ-ignorant baseline planner: §4.2 budgets that treat the deadline
    as a *sample count* (the pre-cost-model arithmetic), with full
    participation and no adaptation.  Implemented as a scheduler-protocol
    stub so ``run_fleet`` still prices realized durations through the
    true measured cost model while the *budgets* ignore it — the
    controlled A/B the cost-model gate runs."""

    def __init__(self, specs):
        self.specs = specs

    def select(self):
        return np.arange(len(self.specs))

    def budget(self, cid: int, deadline: float, epochs: int) -> int:
        from repro.fed.cost import UNIT_COST
        s = self.specs[cid]
        if not UNIT_COST.needs_coreset(s.m, s.c, deadline, epochs):
            return s.m
        return UNIT_COST.budget(s.m, s.c, deadline, epochs)

    def observe(self, cid, work_units, duration):
        pass

    def record_round(self, train_loss):
        pass


def _violation_rate(out) -> float:
    n_v = sum(r.n_violations for r in out["history"])
    n_p = sum(r.n_participants for r in out["history"])
    return n_v / max(n_p, 1)


def bench_cost_model(gate_workload: str = "translm", n_clients: int = 24,
                     rounds: int = 3, epochs: int = 2, batch_size: int = 8,
                     seed: int = 0, verbose: bool = False) -> Dict:
    """Cost-conditioned budgets: the measured table + the deadline A/B.

    Part 1 measures every registered workload's per-sample step cost
    (HLO FLOPs of the jitted local-SGD step, wall-clock fallback),
    normalized to the mlp reference — the table budget conditioning
    consumes.

    Part 2 is the divergence experiment on ``gate_workload`` under the
    ``device_classes`` mixture: the same fleet, trace, and *measured*
    per-sample durations twice — once with cost-conditioned budgets
    (``FleetConfig.cost``), once with the κ-ignorant legacy sample-count
    planner.  On an expensive workload the legacy planner reads the
    cost-calibrated deadline as ~κ× more samples than truly fit and
    overcommits; the recorded deadline-violation rates are the gate
    (cost ≤ legacy)."""
    from repro.fed.cost import workload_cost_model
    from repro.fed.fleet.batched import run_fleet

    flops_table = {}
    for name in sorted(WORKLOADS):
        cm = workload_cost_model(name)
        flops_table[name] = {
            "cost_per_sample_rel": cm.cost_per_sample,
            "flops_per_sample": cm.flops_per_sample,
            "source": cm.source,
        }
        if verbose:
            print(f"  {name:8s} source={cm.source:9s} "
                  f"rel={cm.cost_per_sample:9.2f} "
                  f"flops/sample={cm.flops_per_sample}")

    wl = get_workload(gate_workload)
    clients = wl.make_clients(n_clients=n_clients, seed=seed)
    train, _ = train_test_split_clients(clients, test_frac=0.2)
    specs, trace = build_scenario("device_classes", client_sizes(train),
                                  seed)
    cm = workload_cost_model(gate_workload)
    cfg = FleetConfig(epochs=epochs, batch_size=batch_size, lr=0.05,
                      seed=seed, cost=cm)

    def run(scheduler):
        t0 = time.perf_counter()
        out = run_fleet(wl, train, specs, cfg, rounds=rounds, trace=trace,
                        straggler_pct=30.0, scheduler=scheduler)
        return out, time.perf_counter() - t0

    out_cost, wall_c = run(None)
    out_legacy, wall_l = run(_LegacySamplePlanner(specs))
    rate_cost = _violation_rate(out_cost)
    rate_legacy = _violation_rate(out_legacy)
    if verbose:
        print(f"  {gate_workload} x device_classes "
              f"(κ={cm.cost_per_sample:.1f}): violation rate "
              f"cost={rate_cost:.3f} vs legacy={rate_legacy:.3f}")
    return {
        "reference": "mlp",
        "per_workload": flops_table,
        "gate": {
            "workload": gate_workload,
            "scenario": "device_classes",
            "n_clients": len(specs),
            "rounds": rounds,
            "epochs": epochs,
            "cost_per_sample_rel": cm.cost_per_sample,
            "deadline_violation_rate_cost": rate_cost,
            "deadline_violation_rate_legacy": rate_legacy,
            "n_coreset_cost": int(sum(r.n_coreset
                                      for r in out_cost["history"])),
            "n_coreset_legacy": int(sum(r.n_coreset
                                        for r in out_legacy["history"])),
            "wall_s_cost": wall_c,
            "wall_s_legacy": wall_l,
        },
    }


def bench_faults(n_clients: int = 20, rounds: int = 3,
                 gate_rounds: int = 12, epochs: int = 1,
                 batch_size: int = 8, seed: int = 0,
                 verbose: bool = False) -> Dict:
    """Fault matrix + the Byzantine robustness gate.

    Part 1 crosses fault profiles with server aggregation rules on the
    mlp fleet workload (short horizon — it checks every cell *runs* and
    records its fault accounting, not asymptotics).

    Part 2 is the gate: under 20% sign-flip Byzantine clients, at least
    one robust aggregator's final eval accuracy must exceed
    weighted-mean's.  Sign-flip only *slows* the mean early on — the
    separation appears once honest clients approach their optimum and
    the Byzantine bias becomes the binding constraint — so the gate runs
    a longer horizon than the matrix."""
    from repro.fed.fleet.batched import run_fleet

    wl = get_workload("mlp")
    clients = wl.make_clients(n_clients=n_clients, seed=seed,
                              mean_samples=60.0, std_samples=40.0)
    train, test = train_test_split_clients(clients, test_frac=0.15)
    specs, _ = build_scenario("uniform", client_sizes(train), seed)

    def run(agg, profile, n_rounds):
        cfg = FleetConfig(epochs=epochs, batch_size=batch_size,
                          seed=seed, aggregator=agg)
        out = run_fleet(wl, train, specs, cfg, rounds=n_rounds,
                        test_data=test, faults=profile)
        hist = out["history"]
        return {
            "final_test_acc": float(hist[-1].test_acc),
            "final_test_loss": float(hist[-1].test_loss),
            "accs": [float(r.test_acc) for r in hist],
            "n_dropped": int(sum(r.n_dropped for r in hist)),
            "n_violations": int(sum(r.n_violations for r in hist)),
        }

    profiles = ("none", "dropout", "churn", "byzantine_signflip")
    aggs = ("weighted_mean", "trimmed_mean", "median", "krum")
    matrix = {}
    for profile in profiles:
        row = {}
        for agg in aggs:
            cell = row[agg] = run(agg, profile, rounds)
            if verbose:
                print(f"  {profile:20s} {agg:14s} "
                      f"acc={cell['final_test_acc']:.3f} "
                      f"dropped={cell['n_dropped']}")
        matrix[profile] = row

    gate_aggs = ("weighted_mean", "trimmed_mean", "norm_clip")
    gate = {agg: run(agg, "byzantine_signflip", gate_rounds)
            for agg in gate_aggs}
    mean_acc = gate["weighted_mean"]["final_test_acc"]
    best_robust = max((a for a in gate_aggs if a != "weighted_mean"),
                      key=lambda a: gate[a]["final_test_acc"])
    margin = gate[best_robust]["final_test_acc"] - mean_acc
    if verbose:
        print(f"  gate ({gate_rounds} rounds, byzantine_signflip): "
              f"{best_robust} {gate[best_robust]['final_test_acc']:.3f} "
              f"vs weighted_mean {mean_acc:.3f} (margin {margin:+.3f})")
    return {
        "workload": "mlp",
        "scenario": "uniform",
        "n_clients": len(specs),
        "rounds": rounds,
        "epochs": epochs,
        "matrix": matrix,
        "gate": {
            "profile": "byzantine_signflip",
            "rounds": gate_rounds,
            "cells": gate,
            "best_robust": best_robust,
            "weighted_mean_acc": mean_acc,
            "best_robust_acc": gate[best_robust]["final_test_acc"],
            "robust_margin": margin,
        },
    }


def sweep_scenarios(n_clients: int, rounds: int, epochs: int,
                    seed: int = 0, verbose: bool = False) -> Dict:
    """Every named scenario through both the sync server and the async
    event runtime, from the one registry."""
    clients = synthetic_dataset(0.5, 0.5, n_clients=n_clients,
                                mean_samples=60.0, std_samples=60.0,
                                seed=seed)
    train, test = train_test_split_clients(clients, test_frac=0.3)
    model = LogisticRegression()
    table = {}
    for name in SWEEP_SCENARIOS:
        row = {"description": SCENARIOS[name].description}
        for runtime in ("sync", "async"):
            out = run_scenario(name, runtime, model, train, test,
                               seed=seed, rounds=rounds,
                               clients_per_round=max(4, n_clients // 6),
                               epochs=epochs, batch_size=8,
                               verbose=verbose)
            hist = out["history"]
            accs = [r.test_acc for r in hist if np.isfinite(r.test_acc)]
            makespan = (out["telemetry"]["makespan"] if runtime == "async"
                        else sum(r.sim_round_time for r in hist))
            row[runtime] = {
                "final_train_loss": float(hist[-1].train_loss),
                "final_test_acc": float(accs[-1]) if accs else float("nan"),
                "makespan_virtual_s": float(makespan),
                "n_coreset": int(sum(r.n_coreset for r in hist)),
            }
            if verbose:
                print(f"  {name:15s} {runtime:6s} "
                      f"acc={row[runtime]['final_test_acc']:.3f} "
                      f"makespan={makespan:9.1f}")
        table[name] = row
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-sized run (the CI/Make target)")
    ap.add_argument("--clients", type=int, default=None,
                    help="engine-benchmark fleet size (default 1024)")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="tri-state Pallas switch for the selection fast "
                         "path: auto = kernels on supported backends, jnp "
                         "fallback otherwise (FleetConfig.use_kernel)")
    ap.add_argument("--workload", choices=tuple(sorted(WORKLOADS)),
                    default="mlp",
                    help="FleetWorkload for the engine/selection "
                         "benchmarks (the tracked selection gate runs on "
                         "the default mlp); the workload matrix section "
                         "always sweeps every registered workload")
    ap.add_argument("--skip-workloads", action="store_true",
                    help="skip the per-workload fleet-rounds matrix")
    ap.add_argument("--cost-model", action="store_true",
                    help="measure per-workload step costs (FLOPs/sample) "
                         "and run the cost-vs-legacy deadline-violation "
                         "A/B on --cost-gate-workload under "
                         "device_classes; gates cost rate <= legacy rate")
    ap.add_argument("--cost-gate-workload", default="translm",
                    choices=tuple(sorted(WORKLOADS)),
                    help="workload for the cost-model divergence gate "
                         "(default translm, the most expensive per "
                         "sample)")
    ap.add_argument("--faults", action="store_true",
                    help="run the fault matrix (dropout / churn / "
                         "Byzantine x aggregation rules) and the "
                         "Byzantine robustness gate: under 20% sign-flip "
                         "clients a robust aggregator must beat "
                         "weighted_mean's final accuracy")
    ap.add_argument("--skip-scenarios", action="store_true")
    ap.add_argument("--skip-engine", action="store_true")
    ap.add_argument("--skip-selection", action="store_true",
                    help="skip the selection-phase breakdown benchmark")
    ap.add_argument("--async-fleet", action="store_true",
                    help="benchmark the event-driven async fleet engine: "
                         "throughput at the reference fleet size vs the "
                         "sync batched round, plus the 100k-client lazy "
                         "completion point")
    ap.add_argument("--min-async-ratio", type=float, default=0.5,
                    help="fail if async_fleet clients/sec falls below this "
                         "fraction of the sync batched engine's (needs the "
                         "engine section in this run or the tracked file)")
    ap.add_argument("--async-scale-clients", type=int, default=100_000,
                    help="fleet size for the async_fleet lazy scale point "
                         "(0 disables it)")
    ap.add_argument("--min-speedup", type=float, default=5.0)
    ap.add_argument("--max-recording-overhead", type=float, default=3.0,
                    help="fail if the full observability stack (spans + "
                         "metrics + JSONL sink) slows the warm batched "
                         "round by more than this percentage")
    ap.add_argument("--min-selection-speedup", type=float, default=1.5,
                    help="fail if the fused selection path is not at least "
                         "this much faster than the pre-fusion dispatch "
                         "chain (1.0 = no-regression keep-green gate)")
    ap.add_argument("--device-sweep", default="",
                    help="comma-separated device counts for the sharded "
                         "engine scaling sweep (e.g. 1,2,4); each count "
                         "runs in a subprocess with XLA's forced "
                         "host-platform device count")
    ap.add_argument("--min-scaling", type=float, default=0.0,
                    help="fail if max-vs-min device throughput gain falls "
                         "below this (0 = record only; CPU wall-clock "
                         "scaling is bounded by physical cores)")
    ap.add_argument("--selection-memory", action="store_true",
                    help="peak selection memory + large-M throughput A/B: "
                         "distance-free vs materializing (C, M, M) stack "
                         "at M in {128, 512, 2048}, one fresh subprocess "
                         "per point (VmHWM across the cold solve); "
                         "results land in BENCH_fleet.json['selection']"
                         "['memory']")
    ap.add_argument("--min-selection-memory-speedup", type=float,
                    default=1.0,
                    help="fail if the distance-free warm wall at the "
                         "smallest memory-sweep M falls below this ratio "
                         "of the stack path's (1.0 = keep-green; below "
                         "the adaptive cutover both variants run the "
                         "same program, so this guards the cutover "
                         "default; 5%% timer-jitter tolerance applied)")
    ap.add_argument("--sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)   # internal: one sweep point
    ap.add_argument("--parity", action="store_true",
                    help=argparse.SUPPRESS)   # worker: also check parity
    ap.add_argument("--selection-memory-worker", action="store_true",
                    help=argparse.SUPPRESS)   # internal: one memory point
    ap.add_argument("--sm-variant", choices=("dfree", "stack"),
                    default="dfree", help=argparse.SUPPRESS)
    ap.add_argument("--sm-m", type=int, default=512,
                    help=argparse.SUPPRESS)
    ap.add_argument("--sm-clients", type=int, default=16,
                    help=argparse.SUPPRESS)
    ap.add_argument("--sm-f", type=int, default=32,
                    help=argparse.SUPPRESS)
    ap.add_argument("--sm-k", type=int, default=16,
                    help=argparse.SUPPRESS)
    ap.add_argument("--sm-reps", type=int, default=3,
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_fleet.json"))
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if (args.selection_memory or args.device_sweep) and backend != "cpu":
        # these sections measure the CPU host in forced-device children;
        # under a parent that holds an accelerator they would run on the
        # CPU without saying so
        ap.error(f"--selection-memory and --device-sweep measure the CPU "
                 f"host in forced-device subprocesses, but this process "
                 f"runs on {backend!r}; run them with JAX_PLATFORMS=cpu")

    if args.sharded_worker or args.selection_memory_worker:
        # workers time a cold first solve and read VmHWM across its
        # compile: a persistent cache warmed by an earlier run would hide
        # that compile, so they keep it off even where
        # JAX_COMPILATION_CACHE_DIR is set
        jax.config.update("jax_enable_compilation_cache", False)
    if args.sharded_worker:
        sharded_worker(args.clients or 512, args.epochs or 3,
                       args.batch_size, args.seed, parity=args.parity)
        return 0
    if args.selection_memory_worker:
        selection_memory_worker(args.sm_variant, args.sm_m,
                                args.sm_clients, args.sm_f, args.sm_k,
                                args.sm_reps)
        return 0

    n_clients = args.clients or 1024
    epochs = args.epochs or (2 if args.smoke else 3)
    use_kernel = {"auto": None, "on": True, "off": False}[args.use_kernel]
    # with the persistent cache on, the parent's *cold_wall_s include
    # compilation only where the cache does not hold the program yet
    enable_compile_cache()
    report = {"mode": "smoke" if args.smoke else "full",
              "backend": backend, "persistent_compile_cache": True}
    ok = True

    if not args.skip_engine:
        print(f"== engine: one {n_clients}-client round "
              f"({args.workload}), batched vs per-client loop")
        eng = bench_engine(n_clients, epochs, args.batch_size,
                           seed=args.seed, use_kernel=use_kernel,
                           workload=args.workload, verbose=True)
        report["engine"] = eng
        print(f"  clients/sec (batched): {eng['clients_per_sec']:10.1f}")
        print(f"  round makespan (virtual): "
              f"{eng['round_makespan_virtual_s']:8.1f}s")
        print(f"  wall: batched {eng['batched_wall_s']:.3f}s  "
              f"loop {eng['loop_wall_s']:.3f}s  "
              f"speedup {eng['speedup']:.1f}x")
        parity = (eng["parity_medoids_equal"]
                  and eng["parity_max_param_diff"] < 1e-4)
        print(f"  [{'PASS' if parity else 'FAIL'}] parity: medoids equal, "
              f"max param diff {eng['parity_max_param_diff']:.2e}")
        fast = eng["speedup"] >= args.min_speedup
        print(f"  [{'PASS' if fast else 'FAIL'}] speedup "
              f"{eng['speedup']:.1f}x >= {args.min_speedup:.1f}x")
        lean = (eng["recording_overhead_pct"]
                <= args.max_recording_overhead)
        print(f"  [{'PASS' if lean else 'FAIL'}] telemetry overhead "
              f"{eng['recording_overhead_pct']:+.2f}% <= "
              f"{args.max_recording_overhead:.1f}% "
              f"(recording {eng['recording_warm_wall_s']:.3f}s vs "
              f"off {eng['batched_wall_s']:.3f}s)")
        ok = ok and parity and fast and lean

    if not args.skip_selection:
        print(f"\n== selection: coreset-selection phase at {n_clients} "
              f"clients, fused single-dispatch vs pre-fusion chain "
              f"(kernels: {args.use_kernel})")
        sel = bench_selection(n_clients, epochs, args.batch_size,
                              seed=args.seed, use_kernel=use_kernel,
                              workload=args.workload,
                              verbose=args.verbose)
        report["selection"] = sel
        print(f"  {sel['n_coreset_clients']} coreset clients in "
              f"{sel['n_straggler_groups']} groups, k in "
              f"{sel['budgets_k']}")
        print(f"  wall: fused {sel['selection_wall_s']:.3f}s "
              f"({sel['dispatches_per_group_fused']} dispatch/group)  "
              f"chain {sel['selection_unfused_wall_s']:.3f}s "
              f"({sel['dispatches_per_group_unfused']} dispatches/group)")
        print(f"  kernel A/B (fused): on "
              f"{sel['kernel_ab']['fused_kernel_on_wall_s']:.3f}s  off "
              f"{sel['kernel_ab']['fused_kernel_off_wall_s']:.3f}s")
        sel_parity = sel["parity_medoids_equal"]
        print(f"  [{'PASS' if sel_parity else 'FAIL'}] parity: fused "
              f"medoids == pre-fusion chain medoids")
        sel_fast = sel["selection_speedup"] >= args.min_selection_speedup
        print(f"  [{'PASS' if sel_fast else 'FAIL'}] selection speedup "
              f"{sel['selection_speedup']:.2f}x >= "
              f"{args.min_selection_speedup:.1f}x")
        ok = ok and sel_parity and sel_fast

    if args.selection_memory:
        print("\n== selection-memory: peak RSS + wall A/B, distance-free "
              "vs materializing (C, M, M) stack (fresh subprocess per "
              "point)")
        mem = bench_selection_memory()
        report.setdefault("selection", {})["memory"] = mem
        completes = mem["dfree_completed_top_m"]
        print(f"  [{'PASS' if completes else 'FAIL'}] distance-free "
              f"completes M={mem['top_m']} (stack path skipped there)")
        ratio = mem["peak_ratio_vs_extrapolated_stack"]
        under = ratio is not None and ratio < 0.25
        if ratio is not None:
            print(f"  [{'PASS' if under else 'FAIL'}] peak at "
                  f"M={mem['top_m']}: "
                  f"{mem['dfree_top_peak_bytes'] / 1e6:.1f} MB = "
                  f"{100.0 * ratio:.1f}% of the stack path's "
                  f"extrapolated "
                  f"{mem['stack_peak_extrapolated_bytes'] / 1e6:.1f} MB "
                  f"(< 25%)")
        else:
            print("  [FAIL] stack baseline point missing — no "
                  "extrapolation")
        sp = mem["small_m_dfree_speedup"]
        floor = args.min_selection_memory_speedup - 0.05
        keep_green = sp is not None and sp >= floor
        print(f"  [{'PASS' if keep_green else 'FAIL'}] M="
              f"{mem['small_m']} throughput: distance-free "
              f"{sp if sp is not None else float('nan'):.2f}x the stack "
              f"path >= {args.min_selection_memory_speedup:.1f}x "
              f"keep-green (5% jitter tolerance)")
        ok = ok and completes and under and keep_green

    if args.async_fleet:
        print(f"\n== async_fleet: event-driven engine at {n_clients} "
              f"clients (micro-batched flushes vs sync batched round)")
        af = bench_async_fleet(n_clients, epochs, args.batch_size,
                               seed=args.seed, use_kernel=use_kernel,
                               workload=args.workload, verbose=True)
        # reference sync throughput: this run's engine section, else the
        # tracked file's (a --skip-engine keep-green run)
        sync_cps = None
        if "engine" in report:
            sync_cps = report["engine"]["clients_per_sec"]
        elif os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    sync_cps = json.load(f)["engine"]["clients_per_sec"]
            except (OSError, json.JSONDecodeError, KeyError):
                sync_cps = None
        af["sync_clients_per_sec_ref"] = sync_cps
        af["async_over_sync_ratio"] = (
            af["clients_per_sec"] / sync_cps if sync_cps else None)
        print(f"  merged {af['n_merged_clients']} clients in "
              f"{af['flushes']} flushes (K={af['buffer_k']}): "
              f"{af['clients_per_sec']:10.1f} clients/sec")
        print(f"  {af['n_dispatches']} client completions -> "
              f"{af['n_group_dispatches']} jitted group dispatches; "
              f"mean staleness {af['mean_staleness']:.2f}, "
              f"mean buffer occupancy {af['mean_buffer_occupancy']:.1f}")
        report["async_fleet"] = af
        if af["async_over_sync_ratio"] is not None:
            near = af["async_over_sync_ratio"] >= args.min_async_ratio
            print(f"  [{'PASS' if near else 'FAIL'}] async/sync throughput "
                  f"{af['async_over_sync_ratio']:.2f}x >= "
                  f"{args.min_async_ratio:.2f}x "
                  f"(sync ref {sync_cps:.1f} clients/sec)")
            ok = ok and near
        else:
            print("  [SKIP] no sync engine reference available for the "
                  "throughput ratio gate")
        if args.async_scale_clients > 0:
            print(f"  scale: {args.async_scale_clients}-client fleet, "
                  f"lazy data, dispatches ~ groups not clients")
            scale = bench_async_fleet_scale(
                args.async_scale_clients, seed=args.seed, verbose=True)
            af["scale"] = scale
            grouped = (scale["n_group_dispatches"]
                       < scale["n_dispatches"])
            done = scale["completed"]
            print(f"  [{'PASS' if done and grouped else 'FAIL'}] "
                  f"{scale['n_clients']}-client sim completed with "
                  f"{scale['n_group_dispatches']} group programs for "
                  f"{scale['n_dispatches']} client dispatches")
            ok = ok and done and grouped

    if not args.skip_workloads:
        wl_rounds = 2 if args.smoke else 4
        names = tuple(sorted(WORKLOADS))
        print(f"\n== workloads: {len(names)} FleetWorkloads x fleet "
              f"runtime ({wl_rounds} rounds, batched + loop parity)")
        report["workloads"] = sweep_workloads(
            names, wl_rounds, epochs=2 if args.smoke else 3,
            seed=args.seed, verbose=True)
        wl_parity = all(row["parity_loop_round0"]
                        for row in report["workloads"].values())
        print(f"  [{'PASS' if wl_parity else 'FAIL'}] batched==loop "
              f"round-0 parity on every workload")
        ok = ok and wl_parity

    if args.cost_model:
        print(f"\n== cost model: measured per-sample step costs + "
              f"deadline-violation A/B ({args.cost_gate_workload} x "
              f"device_classes)")
        cmrep = bench_cost_model(
            gate_workload=args.cost_gate_workload,
            n_clients=24 if args.smoke else 64,
            rounds=3 if args.smoke else 6,
            epochs=2 if args.smoke else 3,
            seed=args.seed, verbose=True)
        report["cost_model"] = cmrep
        g = cmrep["gate"]
        better = (g["deadline_violation_rate_cost"]
                  <= g["deadline_violation_rate_legacy"] + 1e-12)
        print(f"  [{'PASS' if better else 'FAIL'}] cost-conditioned "
              f"violation rate {g['deadline_violation_rate_cost']:.3f} <= "
              f"legacy sample-count rate "
              f"{g['deadline_violation_rate_legacy']:.3f}")
        ok = ok and better

    if args.faults:
        print("\n== faults: dropout / churn / Byzantine x aggregation "
              "rules, plus the sign-flip robustness gate")
        frep = bench_faults(n_clients=20 if args.smoke else 48,
                            rounds=3 if args.smoke else 6,
                            gate_rounds=12 if args.smoke else 20,
                            epochs=1, batch_size=8, seed=args.seed,
                            verbose=True)
        report["faults"] = frep
        g = frep["gate"]
        robust = g["robust_margin"] > 0.0
        print(f"  [{'PASS' if robust else 'FAIL'}] {g['best_robust']} beats "
              f"weighted_mean under {g['profile']}: "
              f"{g['best_robust_acc']:.3f} vs {g['weighted_mean_acc']:.3f} "
              f"(margin {g['robust_margin']:+.3f})")
        ok = ok and robust

    if not args.skip_scenarios:
        sc_clients = 24 if args.smoke else 64
        sc_rounds = 3 if args.smoke else 8
        print(f"\n== scenarios: {len(SWEEP_SCENARIOS)} regimes x "
              f"{{sync, async}} at {sc_clients} clients")
        report["scenarios"] = sweep_scenarios(
            sc_clients, sc_rounds, epochs=2 if args.smoke else 3,
            seed=args.seed, verbose=True)

    if args.device_sweep:
        counts = sorted({int(c) for c in args.device_sweep.split(",")})
        sw_clients = args.clients or (512 if args.smoke else 1024)
        print(f"\n== sharded engine: device sweep {counts} at "
              f"{sw_clients} clients ({os.cpu_count()} physical cores)")
        scaling = bench_sharded_scaling(counts, sw_clients,
                                        args.epochs or 3, args.batch_size,
                                        args.seed)
        report["sharded_scaling"] = scaling
        gain = scaling["throughput_speedup_max_vs_min"]
        parity_ok = (scaling["parity_medoids_equal"] is not False and
                     (scaling["parity_max_param_diff"] or 0.0) < 1e-4)
        print(f"  [{'PASS' if parity_ok else 'FAIL'}] sharded==batched "
              f"parity at {max(counts)} devices "
              f"(max param diff {scaling['parity_max_param_diff']:.2e})")
        print(f"  throughput gain {max(counts)}dev vs {min(counts)}dev: "
              f"{gain:.2f}x (host has {os.cpu_count()} cores)")
        ok = ok and parity_ok
        if args.min_scaling > 0:
            scaled = gain >= args.min_scaling
            print(f"  [{'PASS' if scaled else 'FAIL'}] scaling {gain:.2f}x "
                  f">= {args.min_scaling:.1f}x")
            ok = ok and scaled

    # partial runs (--skip-*) update their sections of the tracked report
    # instead of clobbering the others
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
        except (OSError, json.JSONDecodeError):
            merged = {}
        if args.skip_engine and "mode" in merged:
            # a sections-only run must not relabel the mode that produced
            # the headline engine numbers already in the file
            report.pop("mode", None)
        merged.update(report)
        report = merged
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"\nwrote {args.out}")
    print(f"overall: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
