"""Observability layer: recorder/schema/sink contracts + determinism.

Four contracts under test:

1. **Schema** — every runtime (sync server, async event engine, fleet
   loop/batched, async fleet) emits one validating record stream:
   canonical ``round``
   events with the same required fields, aligned ``clients`` events,
   well-nested spans (unique sids, child intervals inside parents).
2. **Coverage** — the phase spans (direct children of each ``round``
   span) account for >= 90% of the round's wall time, so the phase
   timeline in ``benchmarks/report.py`` is an honest decomposition.
3. **Determinism** — recording is observational only: runs with the
   recorder on vs off produce byte-identical params and identical
   histories on every runtime (the recorder touches only the monotonic
   clock, never the RNG or numerics).
4. **Dispatch accounting** — ``DispatchTraceIndexer`` pins the PR 3
   per-(client, dispatch) trace-indexing fix shared by all runtimes,
   and the program-cache/dispatch counters agree with engine state.
"""
import dataclasses
import importlib.util
import io
import json
import os

import jax
import numpy as np
import pytest

from repro.data.partition import train_test_split_clients
from repro.fed.fleet.scenarios import run_scenario
from repro.fed.fleet.workloads import get_workload
from repro.fed.simulator import (CapabilityTrace, ClientSpec,
                                 DispatchTraceIndexer, TraceConfig)
from repro.obs import (NULL_RECORDER, ConsoleSink, InMemorySink, JSONLSink,
                       MetricsRegistry, Recorder, get_recorder, read_jsonl,
                       use_recorder, validate_records)
from repro.obs.sinks import ROUND_FORMATS

RUNTIMES = ("sync", "async", "fleet", "async_fleet")


def _report_mod():
    """Import benchmarks/report.py (not a package) by path."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small_fleet():
    wl = get_workload("mlp")
    clients = wl.make_clients(n_clients=8, seed=0)
    train, test = train_test_split_clients(clients, test_frac=0.25)
    return wl, train, test


def _run(runtime, wl, train, test, sinks, **kw):
    rec = Recorder(sinks=list(sinks))
    with use_recorder(rec):
        out = run_scenario("device_classes", runtime, clients_data=train,
                           test_data=test, workload=wl, seed=0, rounds=2,
                           epochs=2, batch_size=8, **kw)
        rec.close()     # flushes the final metrics snapshot
    return out


@pytest.fixture(scope="module")
def recorded_runs(small_fleet):
    """One recorded run per runtime, shared by the schema/coverage/
    report tests."""
    wl, train, test = small_fleet
    runs = {}
    for runtime in RUNTIMES:
        sink = InMemorySink()
        out = _run(runtime, wl, train, test, [sink])
        runs[runtime] = (out, sink.records)
    return runs


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_metrics_registry():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.counter("a").inc(4)
    m.gauge("g").set(2.5)
    h = m.histogram("h")
    for v in (0.5, 3.0, 3.0, 100.0):
        h.observe(v)
    hx = m.histogram("stale", exact=True)
    hx.observe(0)
    hx.observe(0)
    hx.observe(3)
    snap = m.snapshot()
    assert snap["counters"] == {"a": 5}
    assert snap["gauges"] == {"g": 2.5}
    hs = snap["histograms"]["h"]
    assert hs["count"] == 4 and hs["min"] == 0.5 and hs["max"] == 100.0
    assert hs["buckets"]["le_0.5"] == 1
    assert hs["buckets"]["le_4"] == 2      # power-of-2 upper bounds
    assert hs["buckets"]["le_128"] == 1
    assert snap["histograms"]["stale"]["buckets"] == {"0": 2, "3": 1}


def test_null_recorder_is_inert():
    obs = get_recorder()
    assert obs is NULL_RECORDER and not obs.enabled
    obs.event("round", anything=1)
    with obs.span("phase", k=3) as sp:
        sp.attrs["compile"] = True      # writable throwaway
    obs.metrics.counter("x").inc()
    obs.metrics.histogram("y").observe(1.0)
    assert all(not v for v in obs.metrics.snapshot().values())


# ---------------------------------------------------------------------------
# schema + span nesting across every runtime
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime", RUNTIMES)
def test_schema_validates_per_runtime(recorded_runs, runtime):
    _, records = recorded_runs[runtime]
    validate_records(records)           # envelope + nesting invariants

    rounds = [r for r in records
              if r["kind"] == "event" and r["name"] == "round"]
    assert len(rounds) >= 2
    for r in rounds:
        assert r["data"]["runtime"] == runtime
    clients = [r for r in records
               if r["kind"] == "event" and r["name"] == "clients"]
    assert len(clients) == len(rounds)
    for ev in clients:
        d = ev["data"]
        assert len(d["cids"]) == len(d["durations"]) == len(d["violated"])

    runs = [r for r in records if r["kind"] == "run"]
    assert len(runs) == 1 and runs[0]["data"]["runtime"] == runtime
    snaps = [r for r in records if r["kind"] == "metrics"]
    assert len(snaps) == 1              # rec.close() flushed exactly once
    counters = snaps[-1]["data"]["counters"]
    assert counters["dispatches" if runtime != "fleet"
                    else "fleet.dispatches"] > 0
    if runtime == "async_fleet":
        # client dispatches AND the (fewer) jitted group-program dispatches
        assert 0 < counters["fleet.dispatches"] <= counters["dispatches"]


@pytest.mark.parametrize("runtime", RUNTIMES)
def test_phase_spans_cover_round_wall_time(recorded_runs, runtime):
    """Direct children of each round span sum to >= 90% of its wall."""
    _, records = recorded_runs[runtime]
    rpt = _report_mod()
    (run,) = rpt.load_runs(records)
    rows = [r for r in run.phase_rows() if r["phases"]]
    assert rows, "no instrumented round spans"
    for r in rows:
        assert r["coverage"] >= 0.90, (runtime, r)
    assert run.totals()["phase_coverage_mean"] >= 0.90


def test_jsonl_round_trip(small_fleet, tmp_path):
    """The JSONL file is the in-memory stream, json-normalized."""
    wl, train, test = small_fleet
    path = tmp_path / "run.jsonl"
    mem = InMemorySink()
    _run("fleet", wl, train, test, [mem, JSONLSink(str(path))])
    from_disk = read_jsonl(str(path))
    normalized = [json.loads(json.dumps(r)) for r in mem.records]
    assert from_disk == normalized
    validate_records(from_disk)


def test_console_sink_matches_round_events(small_fleet):
    """Satellite (b): the console line is a pure function of the round
    event — same text the runtimes used to print() directly."""
    wl, train, test = small_fleet
    buf = io.StringIO()
    mem = InMemorySink()
    _run("sync", wl, train, test, [mem, ConsoleSink(stream=buf)])
    rounds = [r["data"] for r in mem.records
              if r["kind"] == "event" and r["name"] == "round"]
    expected = [ROUND_FORMATS[d["runtime"]](d) for d in rounds]
    assert buf.getvalue().splitlines() == expected
    assert expected and expected[0].startswith("[fedcore] round ")


def test_report_cli_renders_and_stamps(small_fleet, tmp_path):
    wl, train, test = small_fleet
    log = tmp_path / "fleet.jsonl"
    bench = tmp_path / "BENCH.json"
    _run("fleet", wl, train, test, [JSONLSink(str(log))])
    bench.write_text(json.dumps({"engine": {"speedup": 5.0}}))
    rpt = _report_mod()
    assert rpt.main([str(log), "--bench-out", str(bench)]) == 0
    stamped = json.loads(bench.read_text())
    assert stamped["engine"] == {"speedup": 5.0}       # merged, not clobbered
    (run,) = stamped["observability"]["runs"]
    assert run["meta"]["runtime"] == "fleet"
    assert run["totals"]["rounds"] == 2
    assert run["phase_wall_s"] and run["top_stragglers"]


# ---------------------------------------------------------------------------
# determinism: recording on == recording off, per runtime/engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime,engine", [
    ("sync", None), ("async", None),
    ("fleet", "batched"), ("fleet", "loop"), ("fleet", "sharded"),
    ("async_fleet", "batched"), ("async_fleet", "loop"),
])
def test_recording_preserves_determinism(small_fleet, runtime, engine):
    """Byte-identical params + identical histories with the recorder on
    vs off: recording never touches event ordering, RNG, or numerics."""
    wl, train, test = small_fleet
    kw = {"fleet_engine": engine} if engine else {}
    sink = InMemorySink()

    def go(record):
        if record:
            return _run(runtime, wl, train, test, [sink], **kw)
        return run_scenario("device_classes", runtime, clients_data=train,
                            test_data=test, workload=wl, seed=0, rounds=2,
                            epochs=2, batch_size=8, **kw)

    def hist_rows(out):
        rows = []
        for r in out["history"]:
            d = dataclasses.asdict(r)
            # real wall-clock, nondeterministic between any two runs
            # (recording on or off) — everything else must match exactly
            d.pop("wall_time", None)
            rows.append(d)
        return rows

    on, off = go(True), go(False)
    assert hist_rows(on) == hist_rows(off)
    for a, b in zip(jax.tree.leaves(on["params"]),
                    jax.tree.leaves(off["params"])):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    if runtime in ("async", "async_fleet"):
        assert on["event_log"] == off["event_log"]
    if (runtime, engine) == ("fleet", "batched"):
        # the sweep counts the gather span reads back only when recording
        counts = [r["attrs"] for r in sink.records if r["kind"] == "span"
                  and r["name"] == "gather" and "swap_sweeps" in r["attrs"]]
        assert counts
        for c in counts:
            assert c["swap_sweeps"] >= 1
            assert c["lane_sweeps_run"] >= c["lane_sweeps_improving"] >= 0


def test_stage_span_in_each_dispatch_span(small_fleet):
    """Each group dispatch holds one ``stage`` span, and its ``bytes`` are
    the arguments copied from the host: data, masks and minibatch indices
    at their device dtypes (the parameters are on the device already)."""
    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         make_cohort_groups, run_fleet_round)
    from repro.fed.fleet.workloads import client_num_samples
    wl, train, _ = small_fleet
    cfg = FleetConfig(epochs=2, batch_size=8, seed=0)
    engine = FleetEngine(wl, cfg)
    params = wl.init(jax.random.PRNGKey(0))
    cids = list(range(len(train)))
    budgets = {c: 9 if c % 2 else client_num_samples(train[c]) for c in cids}
    groups = make_cohort_groups(train, cids, budgets, cfg, 0)
    assert {g.k > 0 for g in groups} == {False, True}
    sink = InMemorySink()
    with use_recorder(Recorder([sink])):
        run_fleet_round(engine, params, train, cids, budgets, groups=groups)
    spans = [r for r in sink.records if r["kind"] == "span"]
    dispatch = [s for s in spans
                if s["name"] in ("local_sgd", "coreset_group")]
    assert len(dispatch) == len(groups)

    def device_bytes(v):
        return v.size * np.dtype(jax.dtypes.canonicalize_dtype(
            v.dtype)).itemsize

    for g, d in zip(groups, dispatch):
        (stage,) = [s for s in spans
                    if s["name"] == "stage" and s["parent"] == d["sid"]]
        c, m = g.valid.shape
        want = sum(device_bytes(v) for v in jax.tree.leaves(g.data))
        want += 4 * c * m                                   # loss weights
        if g.k == 0:
            want += 4 * c * cfg.epochs * m                  # E epochs' indices
        else:
            want += c * m + 4 * c * m                        # mask, epoch 0
        assert stage["attrs"]["bytes"] == want


@pytest.mark.parametrize("aggregator", ["weighted_mean", "median"])
def test_aggregate_bytes_counts_one_update_per_client(small_fleet,
                                                      aggregator):
    """``aggregate.bytes`` is the bytes of the parameter stacks entering
    the reduction, group partials or robust stacks alike: one copy of
    the parameters per client of the cohort."""
    from repro.fed.fleet.batched import (FleetConfig, FleetEngine,
                                         run_fleet_round)
    from repro.fed.fleet.workloads import client_num_samples
    wl, train, _ = small_fleet
    engine = FleetEngine(wl, FleetConfig(epochs=2, batch_size=8, seed=0))
    params = wl.init(jax.random.PRNGKey(0))
    cids = list(range(len(train)))
    budgets = {c: 9 if c % 2 else client_num_samples(train[c]) for c in cids}
    rec = Recorder([InMemorySink()])
    with use_recorder(rec):
        run_fleet_round(engine, params, train, cids, budgets,
                        aggregator=aggregator)
    per_client = sum(int(x.nbytes) for x in jax.tree.leaves(params))
    assert (rec.metrics.snapshot()["counters"]["aggregate.bytes"]
            == len(cids) * per_client)


# ---------------------------------------------------------------------------
# dispatch accounting: the shared trace indexer + cache counters
# ---------------------------------------------------------------------------

def test_trace_indexer_pins_per_dispatch_semantics():
    """Satellite (a): the PR 3 fix, now in one shared helper.  The trace
    is indexed by each client's own dispatch ordinal — a client absent
    for some rounds samples entry k on its k-th *dispatch*, never its
    round number — and the no-trace path is bit-exact (capability is
    spec.c, jitter is exactly 1.0)."""
    specs = [ClientSpec(cid=i, m=16, c=1.0 + i) for i in range(3)]
    trace = CapabilityTrace(TraceConfig(jitter_std=0.2, slowdown_prob=0.5,
                                        seed=7))
    ti = DispatchTraceIndexer(len(specs), trace)
    # client 2 participates only in "rounds" 0 and 2; client 0 in all
    ks = {0: [], 2: []}
    for rnd in range(3):
        for cid in (0, 2) if rnd != 1 else (0,):
            ks[cid].append(ti.begin(cid))
    assert ks[0] == [0, 1, 2]
    assert ks[2] == [0, 1]          # dispatch ordinals, not round numbers
    # the indexer is a pure forwarding wrapper around the trace
    s = specs[2]
    assert ti.capability(s, 1) == trace.capability(s, 1)
    assert ti.jitter(s, 1) == trace.jitter(s, 1)
    # traceless: the identity fast path multiplies by exactly 1.0
    ti0 = DispatchTraceIndexer(len(specs), None)
    assert ti0.capability(s, 5) == s.c
    assert ti0.jitter(s, 5) == 1.0
    d = 123.456
    assert d / ti0.capability(s, 0) * ti0.jitter(s, 0) == d / s.c


def test_program_cache_counters(small_fleet):
    """Round 2 reuses round 1's compiled group programs: misses and
    compiles happen once, later rounds are pure cache hits."""
    wl, train, test = small_fleet
    sink = InMemorySink()
    _run("fleet", wl, train, test, [sink])
    snap = [r for r in sink.records if r["kind"] == "metrics"][-1]["data"]
    c = snap["counters"]
    assert c["program_cache.group.miss"] > 0
    assert c["program_cache.group.hit"] >= c["program_cache.group.miss"]
    assert c["program_cache.compiles"] >= c["program_cache.group.miss"]
    assert c["fleet.dispatches"] > 0
    # every dispatch span carries the compile split
    spans = [r for r in sink.records if r["kind"] == "span"
             and r["name"] in ("local_sgd", "coreset_group")]
    assert spans and all("compile" in s["attrs"] for s in spans)
    assert any(s["attrs"]["compile"] for s in spans)
    assert not all(s["attrs"]["compile"] for s in spans)


def test_scoped_recorder_shares_span_state(tmp_path):
    """scoped() sinks see the same span tree (shared sids/nesting) —
    the async runtime relies on this to tee a window into extra sinks."""
    base, extra = InMemorySink(), InMemorySink()
    rec = Recorder(sinks=[base])
    with rec.span("outer"):
        rec.scoped(extra).event("inner_event", x=1)
        with rec.scoped(extra).span("inner"):
            pass
    validate_records(base.records + [r for r in extra.records
                                     if r not in base.records])
    inner = next(r for r in extra.records
                 if r["kind"] == "span" and r["name"] == "inner")
    outer = next(r for r in base.records
                 if r["kind"] == "span" and r["name"] == "outer")
    assert inner["parent"] == outer["sid"]
    assert inner["depth"] == outer["depth"] + 1
