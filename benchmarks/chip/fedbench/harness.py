"""One run of one cell: set up, measure, check, report.

Set-up builds the cell's population and data, draws the weights on the
device, and drives the program's fleet round through its first
``check.ROUNDS`` rounds from them (round 0 compiles every group program
the window uses, or loads it from the persistent cache).  The window
then runs rounds in a closed loop for ``seconds``: each is
``run_fleet_round`` on a batched ``FleetEngine`` from the previous
round's parameters, ending in ``block_until_ready`` on the aggregated
parameters.  With ``trace`` the window records the program's spans and
counters, and a few more rounds run under the profiler.  Once the
window has closed and the device's peak memory has been read, the
reference follows the set-up's rounds and decides ``correct``.
"""
from __future__ import annotations

import json
import math
import shutil
import sys
import time
from collections import defaultdict
from contextlib import nullcontext
from pathlib import Path
import numpy as np

from fedbench import check, population, xplane
from fedbench.peaks import peaks
from fedbench.spec import BENCH_DIR, Cell, kernel_counters

OUT_DIR = BENCH_DIR / "out"
TRACE_ROUNDS = 2          # rounds under the profiler, after the window
JUDGE_LANES = 32          # first-round straggler lanes judged in float64


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """Seconds JAX spends tracing, lowering and compiling (or loading
    from the persistent cache), from its monitoring events."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_load_s"}

    def __init__(self):
        from jax import monitoring
        self.secs = defaultdict(float)
        self.count = defaultdict(int)
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.secs[self.EVENTS[event]] += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.count["cache_hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.count["cache_requests"] += 1

    def snapshot(self) -> dict:
        return {**{k: round(v, 6) for k, v in self.secs.items()},
                **dict(self.count)}


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX finds platform {devs[0].platform!r}; "
                         f"nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    import jax

    from repro.utils.xla_env import enable_compile_cache
    path = enable_compile_cache()
    # store every program, however quick its compile or small its entry
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def host(tree):
    import jax
    return jax.tree.map(lambda v: np.array(v, copy=True), tree)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, tiny: bool = False, require_tpu: bool = True,
        out_dir: Path = OUT_DIR) -> dict:
    cell = Cell(cell_name)
    cfg = cell.config
    devs = devices(cell.chips, require_tpu)
    cache_dir = enable_cache() if require_tpu else None
    import jax

    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         run_fleet_round)
    from repro.obs import InMemorySink, Recorder, use_recorder

    model = dict(cfg.TINY["model"] if tiny else cfg.MODEL)
    pop_sizes = dict(cfg.TINY["population"] if tiny else cfg.POPULATION)
    fleet = dict(cfg.FLEET)
    fseed = int(seed) % (1 << 63)
    log = CompileLog()
    split = {}

    # -- set-up -----------------------------------------------------------
    t0 = time.perf_counter()
    pop = population.build(cfg, cell.traffic, pop_sizes, model, fleet, seed)
    split["data_build_s"] = time.perf_counter() - t0
    wl = cfg.workload(model)
    wl.validate_clients([pop.clients[c] for c in pop.cohort])
    engine = FleetEngine(wl, FleetConfig(seed=fseed, **fleet))
    t0 = time.perf_counter()
    p0 = jax.jit(lambda k: cfg.init_params(k, model))(
        population.seed_key(seed))
    p0 = jax.block_until_ready(p0)
    split["init_s"] = time.perf_counter() - t0
    before = log.snapshot()
    prog = {"params": [], "losses": [], "medoids": []}
    params = p0
    for r in range(check.ROUNDS):
        t0 = time.perf_counter()
        params, stats = run_fleet_round(engine, params, pop.clients,
                                        pop.cohort, pop.budgets,
                                        round_seed=r)
        params = jax.block_until_ready(params)
        split[f"round{r}_s"] = time.perf_counter() - t0
        prog["params"].append(host(params))
        prog["losses"].append({int(c): float(v) for c, v in
                               zip(stats.cids, stats.losses)})
        prog["medoids"].append({int(c): np.asarray(m)
                                for c, m in stats.medoids.items()})
        if r == 0:
            split["round0_compile"] = {k: v for k, v in
                                       log.snapshot().items()
                                       if v != before.get(k)}
    groups = group_shapes(pop, fleet)
    setup_s = time.perf_counter() - t_start
    say(f"[setup] {cell_name} seed {seed}: setup_s {setup_s:.3f}; "
        + json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in split.items()}))
    say(f"[setup] cohort {len(pop.cohort)} clients, "
        f"{sum(1 for g in groups if g['k'])} coreset groups of "
        f"{len(groups)}; compile cache {cache_dir}")
    for g in groups:
        say(f"[groups] M={g['m_pad']} k={g['k']} clients={g['clients']} "
            f"lanes={g['lanes']}")

    # -- window -----------------------------------------------------------
    sink = InMemorySink()
    rec = Recorder([sink]) if trace else None
    walls, attempted, failed = [], 0, 0
    r = check.ROUNDS
    with use_recorder(rec) if rec is not None else nullcontext():
        compiles0 = _counter(rec, "program_cache.compiles")
        t_win = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            params, stats = run_fleet_round(engine, params, pop.clients,
                                            pop.cohort, pop.budgets,
                                            round_seed=r)
            params = jax.block_until_ready(params)
            t1 = time.perf_counter()
            walls.append(t1 - t0)
            r += 1
            attempted += len(stats.cids)
            failed += int((~np.isfinite(stats.losses) | stats.dropped).sum())
            if t1 - t_win >= seconds:
                break
        window_s = t1 - t_win
        compiles = _counter(rec, "program_cache.compiles") - compiles0
    say(f"[window] {len(walls)} rounds in {window_s:.6f} s; round walls "
        f"min {min(walls):.6f} median {float(np.median(walls)):.6f} "
        f"max {max(walls):.6f} s")

    metrics, device, breakdown = {}, _device(devs), None
    flops = round_flops(cfg, model, pop, fleet)
    if not trace:
        done = attempted - failed
        metrics["client_updates_per_s"] = (done / window_s, "updates/s")
        metrics["round_p90_s"] = (float(np.percentile(walls, 90)), "s")
        metrics["setup_s"] = (setup_s, "s")
    else:
        ctx = {"spans": [x for x in sink.records if x["kind"] == "span"],
               "window_compiles": compiles, "rounds": len(walls),
               "window_s": window_s, "round_flops": flops,
               "groups": groups, "device_kind": device["kind"],
               "peaks": None, "trace": None, "trace_rounds": 0,
               "kernels": kernel_counters()}
        tr, n_traced = traced_rounds(engine, params, pop, r,
                                     out_dir / "trace" / f"{cell_name}-{seed}",
                                     host_ops=not require_tpu)
        ctx["trace"], ctx["trace_rounds"] = tr, n_traced
        ctx["kernel_calls"] = lambda kern: [
            (kern.call_shape(xplane.operand_shapes(name)), dur)
            for name, _, dur in tr.events_matching(kern.MATCH)]
        ctx["peaks"] = peaks(device["kind"]) if require_tpu else None
        for name, reader in cell.readers().items():
            value = reader.read(ctx)
            if value is not None:
                unit = next(m["unit"] for m in cell.per_layer
                            if m["name"] == name)
                metrics[name] = (float(value), unit)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = tr.breakdown()
        say(f"[trace] {n_traced} rounds, window {tr.window_s:.6f} s, busy "
            f"{tr.busy_s:.6f} s")
    device["memory_peak_bytes"] = _peak_bytes(devs)

    # -- correctness ------------------------------------------------------
    del engine, params, stats
    numbers = correctness(cfg, model, pop, fleet, fseed, seed, p0, prog)
    rows = check.verdict(numbers, cell.limits)
    correct = bool(rows) and all(ok for *_, ok in rows) and failed == 0
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checked"] = {name: {"value": float(v), "limit": float(lim)}
                         for name, v, lim, _ in rows}
    result["checked"]["failed_updates"] = {"value": float(failed),
                                           "limit": 0.0}
    write_details(out_dir, cell_name, seed, trace, {
        "setup": split, "setup_s": setup_s, "walls": walls,
        "groups": groups, "numbers": numbers, "result": result,
        "round_flops": flops})
    for name, v, lim, ok in rows:
        say(f"[check] {name} {float(v)!r} limit {float(lim)!r} "
            f"{'ok' if ok else 'FAILED'}")
    say(f"[check] failed_updates {failed} limit 0")
    return result


def _counter(rec, name: str) -> int:
    if rec is None:
        return 0
    return int(rec.metrics.snapshot().get("counters", {}).get(name, 0))


def _device(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(devs) -> int:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:
            pass
    return peak


def group_shapes(pop, fleet) -> list:
    """The cohort groups as the program forms them: (padded M, k) with
    their client and program-lane counts."""
    from reference.fleet import coreset_k, m_padded
    by = defaultdict(int)
    for c in pop.cohort:
        m = int(pop.sizes[c])
        by[(m_padded(m, fleet["batch_size"]),
            coreset_k(m, pop.budgets[c]))] += 1
    return [{"m_pad": m, "k": k, "clients": n,
             "lanes": 2 if (n == 1 and k) else n}
            for (m, k), n in sorted(by.items())]


def round_flops(cfg, model, pop, fleet) -> float:
    """Model operations of one round on real samples: forward+backward
    per sample visit, plus the stragglers' forward feature pass."""
    from reference.fleet import coreset_k
    per = cfg.model_flops(model)
    e = fleet["epochs"]
    total = 0.0
    for c in pop.cohort:
        m = int(pop.sizes[c])
        k = coreset_k(m, pop.budgets[c])
        visits = e * m if k == 0 else m + max(e - 1, 1) * k
        total += visits * per["train"] + (m * per["features"] if k else 0)
    return total


def traced_rounds(engine, params, pop, r0: int, trace_dir: Path,
                  host_ops: bool = False):
    """Rounds under the profiler, each in a ``bench_round`` span, the
    program's spans bridged onto the profiler clock; returns the reduced
    trace and the number of rounds."""
    import jax

    from repro.fed.fleet.batched import run_fleet_round
    from repro.obs import InMemorySink, Recorder, use_recorder

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True, exist_ok=True)
    rec = Recorder([InMemorySink()], annotate=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with use_recorder(rec):
            for r in range(r0, r0 + TRACE_ROUNDS):
                with rec.span("bench_round", round=r):
                    params, _ = run_fleet_round(engine, params, pop.clients,
                                                pop.cohort, pop.budgets,
                                                round_seed=r)
                    params = jax.block_until_ready(params)
    finally:
        jax.profiler.stop_trace()
    planes = xplane.load(xplane.find_xplane(trace_dir))
    reduced = xplane.reduce_planes(planes, host_ops=host_ops)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced, TRACE_ROUNDS


def correctness(cfg, model, pop, fleet, fseed, seed, p0, prog) -> dict:
    import jax.numpy as jnp

    from reference import models as refmodels
    from reference.fleet import Reference

    sizes = cfg.reference_sizes(model)
    ref = Reference(refmodels.build(cfg.REFERENCE, jnp.float32, **sizes),
                    fleet["lr"], fleet["epochs"], "highest")
    low = (Reference(refmodels.build(cfg.REFERENCE, jnp.float32, **sizes),
                     fleet["lr"], fleet["epochs"], None)
           if cfg.PRECISION == "default" else None)
    judge = check.judge_sample(pop.cohort, pop.budgets, pop.sizes,
                               JUDGE_LANES, seed)
    t0 = time.perf_counter()
    rr = check.reference_rounds(ref, low, p0, pop, fleet, fseed,
                                prog["medoids"], judge)
    numbers = check.compare(prog, rr, host(p0))
    numbers["reference_s"] = time.perf_counter() - t0
    say("[check] diagnostics " + json.dumps(numbers["diagnostics"]))
    say(f"[check] reference {check.ROUNDS} rounds in "
        f"{numbers['reference_s']:.3f} s, {int(numbers['judged_lanes'])} "
        f"lanes judged")
    return numbers


def write_details(out_dir: Path, cell: str, seed: int, trace: bool,
                  details: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{cell}.{seed}.{int(trace)}.json"
    path.write_text(json.dumps(details, default=_plain, indent=1))


def _plain(v):
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    return str(v)
