"""The trace reduction: interval arithmetic, gap attribution, and a real
(CPU) profiler trace read back."""
import os
import sys
from pathlib import Path

# the benchmark's CPU tests: small shapes on the host CPU, the
# benchmark's own modules and the program on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[3] / "src")]

import dataclasses

import pytest

from fedbench import xplane


def test_union_merges_overlaps_and_touching():
    got = xplane.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12)])
    assert got == [(0, 4), (5, 7), (10, 12)]


def test_gaps_are_the_complement_inside_the_window():
    merged = xplane.union([(2, 4), (6, 7), (9, 15)])
    assert xplane.gaps(merged, 0, 10) == [(0, 2), (4, 6), (7, 9)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]
    assert xplane.gaps([(0, 10)], 0, 10) == []


def test_busy_plus_idle_is_the_window():
    ivs = [(1, 3), (2, 5), (8, 9), (11, 20)]
    merged = xplane.union(xplane.clip(ivs, 0, 12))
    busy = xplane.covered(merged)
    idle = sum(e - s for s, e in xplane.gaps(merged, 0, 12))
    assert busy == 4 + 1 + 1
    assert busy + idle == 12


def test_gap_goes_to_the_innermost_open_span():
    spans = [("bench_round", 0, 100), ("cohort_build", 10, 30),
             ("aggregate", 60, 90)]
    got = xplane.attribute([(12, 20), (40, 50), (70, 80), (100, 110)],
                           spans)
    assert got == {"cohort_build": 8, "bench_round": 10, "aggregate": 10,
                   "no_span": 10}
    # a gap over a span's end is cut there
    assert xplane.attribute([(80, 95)], spans) == {"aggregate": 10,
                                                   "bench_round": 5}


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def test_reduce_planes_on_a_synthesized_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench_round", 0, 100), Ev("cohort_build", 0, 40),
        Ev("coreset_group", 40, 20), Ev("aggregate", 60, 30),
        Ev("bench_round", 100, 100), Ev("cohort_build", 100, 50)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_body", 40, 50)]),
        Line("XLA Ops", [Ev("fusion.1", 40, 10), Ev("custom-call.2", 45, 20),
                         Ev("fusion.1", 150, 30), Ev("fusion.9", 190, 30)])])
    tr = xplane.reduce_planes([host, dev])
    assert tr.window == (0, 200)
    # busy: [40, 65) + [150, 180) + [190, 200) inside the window
    assert tr.busy_ns == 25 + 30 + 10
    assert tr.window_s == pytest.approx(200e-9)
    # gaps [0, 40) [65, 100) [100, 150) [180, 190)
    assert tr.idle_by_span == {"cohort_build": 40 + 50, "aggregate": 25,
                               "bench_round": 10 + 10}
    assert tr.op_ns["fusion.1"] == 40
    assert [n for n, _ in tr.breakdown()["device_ops"]][0] == "fusion.1"
    assert len(tr.events_matching(r"custom-call")) == 1


def test_reduce_needs_a_window_and_device_ops():
    host = Plane("/host:CPU", [Line("python", [Ev("bench_round", 0, 10)])])
    with pytest.raises(ValueError):
        xplane.reduce_planes([host])
    with pytest.raises(ValueError):
        xplane.reduce_planes([Plane("/device:TPU:0", [
            Line("XLA Ops", [Ev("fusion", 0, 5)])])])


def test_a_recorded_cpu_trace_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench_round"):
                with jax.profiler.TraceAnnotation("cohort_build"):
                    y = x + 1.0
                f(y).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    planes = xplane.load(xplane.find_xplane(tmp_path))
    tr = xplane.reduce_planes(planes, host_ops=True)
    assert tr.n_devices == 1
    assert 0 < tr.busy_ns <= tr.window[1] - tr.window[0]
    assert tr.op_ns
    assert "bench_round" in {n for n, *_ in xplane.host_spans(planes)}
