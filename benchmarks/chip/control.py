#!/usr/bin/env python3
"""Read the correctness numbers of a cell over many seeds in one process:
the program's, the lower-precision control's, and planted faults'.

    python3 benchmarks/chip/control.py \\
        --workload shakespeare_lstm.stragglers30 --seeds 11 12 13 \\
        --control-seeds 11 12 13 --fault-seeds 11 12 13

For each seed: the cell's population and weights as a run builds them,
the program's first rounds (the compiled group programs are shared by
all seeds), then the reference's, compared as in a run (``numbers``).
For each control seed, the reference put in the program's place
(``--controls``): computed in bfloat16, with its own FasterPAM selection
on bfloat16 features (``bf16``); or at float32 ``highest`` with its
FasterPAM's contractions rounded to one bfloat16 pass (``select_bf16``)
or three (``select_high``), the precision below the selection's own.
For each fault seed, the program with a planted fault (``--faults``):
every other row of each client weighted zero, so that each minibatch's
mean is taken over half of it (``half_batch``); half of each group's
clients left out of the weighted mean (``half_cohort``); one medoid of
every judged lane replaced by a non-medoid in what the program returns
(``medoid``).
One JSON line per reading on standard output; the limits in
``limits/<cell>.json`` are set from them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def program_rounds(engine, p0, pop, rounds, aggregate=None):
    import jax
    import numpy as np

    from fedbench.harness import host
    from repro.fed.fleet import batched

    saved = batched._aggregate_groups
    if aggregate is not None:
        batched._aggregate_groups = aggregate
    try:
        prog = {"params": [], "losses": [], "medoids": []}
        params = p0
        for r in range(rounds):
            params, stats = batched.run_fleet_round(
                engine, params, pop.clients, pop.cohort, pop.budgets,
                round_seed=r)
            params = jax.block_until_ready(params)
            prog["params"].append(host(params))
            prog["losses"].append({int(c): float(v) for c, v in
                                   zip(stats.cids, stats.losses)})
            prog["medoids"].append({int(c): np.asarray(m)
                                    for c, m in stats.medoids.items()})
    finally:
        batched._aggregate_groups = saved
    return prog


def half_batch_left_out(group_args):
    """``FleetEngine._group_args`` with every other row of each client
    weighted zero: each minibatch's weighted mean is taken over about
    half of its rows."""
    import jax.numpy as jnp

    def half(self, params, group):
        args = list(group_args(self, params, group))
        w = args[3]
        args[3] = w * (jnp.arange(w.shape[1]) % 2 == 0)[None, :]
        return tuple(args)
    return half


def half_left_out(aggregate):
    """``aggregate`` over the first half of each group's clients only."""
    import numpy as np

    def half(partials, fallback):
        cut = []
        for stacked, w in partials:
            w = np.array(w, np.float64)
            w[(len(w) + 1) // 2:] = 0.0
            cut.append((stacked, w))
        return aggregate(cut, fallback)
    return half


FAULTS = ("half_batch", "half_cohort", "medoid")
# control -> (reference in bfloat16, FasterPAM contraction passes)
CONTROLS = {"bf16": (True, 0), "select_bf16": (False, 1),
            "select_high": (False, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS)
    ap.add_argument("--controls", nargs="*", default=list(CONTROLS),
                    choices=list(CONTROLS))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedbench import check, harness, population
    from fedbench.spec import Cell
    from reference import models as refmodels
    from reference.fleet import Reference
    from repro.fed.fleet import batched

    cell = Cell(args.workload)
    cfg = cell.config
    harness.devices(cell.chips, require_tpu=True)
    harness.enable_cache()
    model, pop_sizes = dict(cfg.MODEL), dict(cfg.POPULATION)
    fleet = dict(cfg.FLEET)
    sizes = cfg.reference_sizes(model)
    ref = Reference(refmodels.build(cfg.REFERENCE, jnp.float32, **sizes),
                    fleet["lr"], fleet["epochs"], "highest")
    low = (Reference(refmodels.build(cfg.REFERENCE, jnp.float32, **sizes),
                     fleet["lr"], fleet["epochs"], None)
           if cfg.PRECISION == "default" else None)
    ctl = Reference(refmodels.build(cfg.REFERENCE, jnp.bfloat16, **sizes),
                    fleet["lr"], fleet["epochs"], None)
    out_dir = harness.OUT_DIR / "control" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    engine = None

    def emit(kind, seed, numbers, t0):
        raw = numbers.pop("raw")
        line = {"kind": kind, "seed": seed,
                "seconds": time.perf_counter() - t0, **numbers}
        print(json.dumps(line), flush=True)
        (out_dir / f"{kind}-{seed}.json").write_text(json.dumps(
            {**line, "raw": raw}))

    every = set(args.seeds) | set(args.control_seeds) | set(args.fault_seeds)
    for seed in sorted(every):
        fseed = int(seed) % (1 << 63)
        pop = population.build(cfg, cell.traffic, pop_sizes, model, fleet,
                               seed)
        p0 = jax.jit(lambda k: cfg.init_params(k, model))(
            population.seed_key(seed))
        judge = check.judge_sample(pop.cohort, pop.budgets, pop.sizes,
                                   harness.JUDGE_LANES, seed)
        if engine is None:
            engine = batched.FleetEngine(
                cfg.workload(model), batched.FleetConfig(seed=fseed,
                                                         **fleet))
        engine.cfg = batched.FleetConfig(seed=fseed, **fleet)

        def reference_of(prog):
            rr = check.reference_rounds(ref, low, p0, pop, fleet, fseed,
                                        prog["medoids"], judge)
            return {**check.compare(prog, rr, harness.host(p0)),
                    "raw": check.raw(prog, rr, harness.host(p0))}

        prog = None
        if seed in args.seeds or (seed in args.fault_seeds
                                  and "medoid" in args.faults):
            t0 = time.perf_counter()
            prog = program_rounds(engine, p0, pop, check.ROUNDS)
            if seed in args.seeds:
                emit("program", seed, reference_of(prog), t0)
        if seed in args.fault_seeds:
            if "medoid" in args.faults:
                t0 = time.perf_counter()
                emit("fault_medoid_altered", seed,
                     reference_of(alter_medoids(prog, judge, pop)), t0)
            if "half_cohort" in args.faults:
                t0 = time.perf_counter()
                emit("fault_half_cohort_left_out", seed, reference_of(
                    program_rounds(engine, p0, pop, check.ROUNDS,
                                   aggregate=half_left_out(
                                       batched._aggregate_groups))), t0)
            if "half_batch" in args.faults:
                t0 = time.perf_counter()
                real = batched.FleetEngine._group_args
                batched.FleetEngine._group_args = half_batch_left_out(real)
                try:
                    prog_half = program_rounds(engine, p0, pop, check.ROUNDS)
                finally:
                    batched.FleetEngine._group_args = real
                emit("fault_half_batch_left_out", seed,
                     reference_of(prog_half), t0)
        for name in args.controls if seed in args.control_seeds else ():
            in_bf16, passes = CONTROLS[name]
            t0 = time.perf_counter()
            cr = check.reference_rounds(ctl if in_bf16 else ref, None, p0,
                                        pop, fleet, fseed, None, (),
                                        select_passes=passes)
            prog = {"params": [harness.host(x["params"]) for x in cr],
                    "losses": [x["losses"] for x in cr],
                    "medoids": [x["medoids"] for x in cr]}
            emit(f"control_{name}", seed, reference_of(prog), t0)
    return 0


def alter_medoids(prog, judge, pop):
    """A copy of ``prog`` in which each judged lane's first medoid is
    replaced by the lowest-index valid row that is not a medoid."""
    import numpy as np
    out = {"params": prog["params"], "losses": prog["losses"],
           "medoids": []}
    for meds in prog["medoids"]:
        new = dict(meds)
        for c in judge:
            if c in new:
                med = np.array(new[c])
                free = np.setdiff1d(np.arange(int(pop.sizes[c])), med)
                med[0] = free[0]
                new[c] = med
        out["medoids"].append(new)
    return out


if __name__ == "__main__":
    sys.exit(main())
