#!/usr/bin/env python3
"""Device time of a cell's fleet round by the group programs' phase scopes.

    python3 benchmarks/chip/phase_split.py \\
        --workload shakespeare_lstm.stragglers30 --seed 7

Sets the cell up as a run does (population, weights, the first
``check.ROUNDS`` rounds), traces ``harness.TRACE_ROUNDS`` rounds as a
``--trace 1`` run does, and gives each device operation the outermost
phase scope on its ``op_name`` path: ``grad_features``, ``selection``,
``local_sgd`` or ``coreset_epochs`` (``FleetEngine._make_group_body``).
The TPU trace names an operation by its HLO text without metadata, so
the path comes from the compiled group programs: an event is matched to
their instructions by name, result shape and opcode, and an instruction
that two programs hold under different phases is ``ambiguous``.  A
phase's time is the union of its operations' intervals in the traced
window, so a ``while`` and its body count once; ``unscoped`` is busy
time under no phase (eager aggregation, lane broadcasts, transfers),
and ``unscoped_ops`` the costliest operations there.  One JSON line on
standard output, times in ms per traced round.  One device: with
several, the union runs over all of their operations.

``op_key``, ``scope_table``, ``split``, ``unscoped_ops`` and
``group_texts`` are the library that per-phase readers would call once
the harness hands them ``group_texts(...)`` of its engine; ``main`` only
repeats the harness's set-up to get there meanwhile.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, Optional, Sequence, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench.xplane import clip, covered, union  # noqa: E402

PHASES = ("grad_features", "selection", "local_sgd", "coreset_epochs")
AMBIGUOUS = "ambiguous"
UNMATCHED = "unmatched"       # no instruction of a group program
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_key(text: str) -> Tuple[str, ...]:
    """(name, result shape, opcode) of an HLO instruction's text, or
    (name,) where the text is the bare name (a CPU trace's events)."""
    m = _INSTR.match(text)
    if m is None:
        return (text.strip().lstrip("%"),)
    rest = text[m.end():]
    if rest.startswith("("):            # a tuple: to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return (m.group(1), shape, rest.split("(", 1)[0])


def phase_of(path: str) -> Optional[str]:
    """The outermost phase scope on an ``op_name`` path, or None."""
    return next((p for p in path.split("/") if p in PHASES), None)


def scope_table(texts: Iterable[str]) -> Dict[Tuple[str, ...], object]:
    """Phase (or None, or ``AMBIGUOUS``) of every instruction of the
    compiled programs' texts, keyed by ``op_key`` and by bare name."""
    table: Dict[Tuple[str, ...], object] = {}

    def put(key, phase):
        table[key] = phase if table.get(key, phase) == phase else AMBIGUOUS

    for text in texts:
        for line in text.splitlines():
            if _INSTR.match(line) is None:
                continue
            key = op_key(line)
            m = _OP_NAME.search(line)
            phase = phase_of(m.group(1)) if m else None
            put(key, phase)
            put(key[:1], phase)
    return table


def split(events: Sequence[Tuple[str, float, float]],
          window: Tuple[float, float], table: dict) -> Dict[str, float]:
    """Nanoseconds per phase inside ``window``; ``ambiguous``: under an
    ambiguous instruction and no phase; ``unscoped``: busy under
    neither, of which ``unmatched`` is under operations of no group
    program; ``overlap``: claimed by two phases at once."""
    lo, hi = window
    by = defaultdict(list)
    for name, t0, dur in events:
        by[table.get(op_key(name), UNMATCHED)].append((t0, t0 + dur))

    def span(keys):
        return covered(union(clip([iv for k in keys for iv in by.get(k, [])],
                                  lo, hi)))

    out = {p: span([p]) for p in PHASES}
    scoped = span(PHASES)
    claimed = span(PHASES + (AMBIGUOUS,))
    out[AMBIGUOUS] = claimed - scoped
    out["unscoped"] = span(list(by)) - claimed
    out[UNMATCHED] = span(PHASES + (AMBIGUOUS, UNMATCHED)) - claimed
    out["overlap"] = sum(out[p] for p in PHASES) - scoped
    return out


def unscoped_ops(events, window, table, n: int = 8, width: int = 100):
    """The operations under no phase that took most device time (ns)."""
    lo, hi = window
    total = defaultdict(float)
    for name, t0, dur in events:
        if (table.get(op_key(name), UNMATCHED) in (None, UNMATCHED)
                and t0 < hi and t0 + dur > lo):
            total[name[:width]] += dur
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def group_texts(engine, params, groups) -> list:
    """Compiled texts of the group programs that ``groups`` dispatch, as
    ``scope_table`` reads them."""
    texts = []
    for g in groups:
        program, group_args = engine.stage_group(params, g)
        texts.append(program.lower(*group_args).compile().as_text())
    return texts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="the cell's tiny sizes on any backend (CPU check)")
    args = ap.parse_args(argv)

    import jax

    from fedbench import check, harness, population
    from fedbench.spec import Cell
    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         make_cohort_groups, run_fleet_round)

    cell = Cell(args.workload)
    cfg = cell.config
    devs = harness.devices(cell.chips, require_tpu=not args.tiny)
    if not args.tiny:
        harness.enable_cache()
    model = dict(cfg.TINY["model"] if args.tiny else cfg.MODEL)
    sizes = dict(cfg.TINY["population"] if args.tiny else cfg.POPULATION)
    fleet = dict(cfg.FLEET)
    pop = population.build(cfg, cell.traffic, sizes, model, fleet, args.seed)
    engine = FleetEngine(cfg.workload(model),
                         FleetConfig(seed=int(args.seed) % (1 << 63),
                                     **fleet))
    params = jax.jit(lambda k: cfg.init_params(k, model))(
        population.seed_key(args.seed))
    for r in range(check.ROUNDS):
        params, _ = run_fleet_round(engine, params, pop.clients, pop.cohort,
                                    pop.budgets, round_seed=r)
    params = jax.block_until_ready(params)
    tr, rounds = harness.traced_rounds(
        engine, params, pop, check.ROUNDS,
        harness.OUT_DIR / "trace" / f"phases-{args.workload}-{args.seed}",
        host_ops=args.tiny)
    table = scope_table(group_texts(engine, params, make_cohort_groups(
        pop.clients, pop.cohort, pop.budgets, engine.cfg, check.ROUNDS)))
    ns = split(tr.op_events, tr.window, table)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": devs[0].device_kind, "count": len(devs)},
        "rounds": rounds, "busy_ms": 1e-6 * tr.busy_ns / rounds,
        "window_ms": 1e-6 * (tr.window[1] - tr.window[0]) / rounds,
        "phases_ms": {k: 1e-6 * v / rounds for k, v in ns.items()},
        "share_of_busy": {k: 100.0 * v / tr.busy_ns for k, v in ns.items()},
        "unscoped_ops": [[k, 1e-6 * v / rounds] for k, v in
                         unscoped_ops(tr.op_events, tr.window, table)],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
