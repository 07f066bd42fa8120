"""Device time by phase scope (``phase_split.py``) on synthesized
programs and traces, and the readers of the selection-solver and
staging metrics on synthesized spans."""
import json
import os
import sys
from pathlib import Path

# the benchmark's CPU tests: small shapes on the host CPU, the
# benchmark's own modules and the program on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[3] / "src")]

import pytest

from fedbench import xplane
from fedbench.spec import BENCH_DIR, load_module

phase_split = load_module(BENCH_DIR / "phase_split.py")

# two compiled group programs as ``compiled.as_text()`` prints them
PROGRAM_A = """HloModule jit_body, is_scheduled=true
%body.1 (arg: (s32[], f32[2,8])) -> (s32[], f32[2,8]) {
  %dot.2 = f32[2,8]{1,0:T(2,128)} dot(%a, %b), metadata={op_name="jit(body)/local_sgd/while/body/dot_general" stack_frame_id=1}
  ROOT %tuple.3 = (s32[], f32[2,8]{1,0:T(2,128)}) tuple(%x, %dot.2)
}
ENTRY %main (p: f32[2,8]) -> f32[2,8] {
  %while.4 = (s32[], f32[2,8]{1,0:T(2,128)}) while(%t), condition=%c, body=%body.1, metadata={op_name="jit(body)/local_sgd/while"}
  %fusion.5 = f32[2,8]{1,0:T(2,128)} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(body)/grad_features/mul"}
  %custom-call.6 = f32[2,1,8]{2,1,0:T(1,128)} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/selection/jit(_kmedoids_batched_from_feats)/build/pallas_call"}
  %fusion.7 = f32[2,8]{1,0:T(2,128)} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(body)/selection/local_sgd/swap/while"}
  %copy.8 = f32[2,8]{1,0:T(2,128)} copy(%p)
  ROOT %fusion.9 = f32[2,8]{1,0:T(2,128)} fusion(%p), kind=kLoop, calls=%h, metadata={op_name="jit(body)/coreset_epochs/dot_general"}
}
"""
PROGRAM_B = """HloModule jit_body, is_scheduled=true
ENTRY %main (p: f32[2,8]) -> f32[2,8] {
  ROOT %fusion.5 = f32[2,8]{1,0:T(2,128)} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(body)/local_sgd/mul"}
}
"""


def event(line, t0, dur):
    """A TPU trace event: the instruction's text, operands with their
    shapes, and no metadata."""
    text = line.strip().removeprefix("ROOT ").split(", metadata=")[0]
    return (text.replace("(%p)", "(f32[2,8]{1,0:T(2,128)} %p)"), t0, dur)


def lines(program):
    return {ln.split(" = ")[0].split("%")[-1]: ln
            for ln in program.splitlines() if " = " in ln}


def test_op_key_reads_name_shape_and_opcode():
    key = phase_split.op_key(
        "%while.4 = (s32[]{:T(128)}, f32[2,8]{1,0:T(2,128)S(1)}) "
        "while((s32[], f32[2,8]) %t), condition=%c, body=%b")
    assert key == ("while.4", "(s32[]{:T(128)}, f32[2,8]{1,0:T(2,128)S(1)})",
                   "while")
    assert phase_split.op_key("  ROOT %copy.8 = f32[2]{0} copy(%p)") == (
        "copy.8", "f32[2]{0}", "copy")
    assert phase_split.op_key("while.189") == ("while.189",)


def test_scope_table_takes_the_outermost_phase_and_marks_conflicts():
    table = phase_split.scope_table([PROGRAM_A, PROGRAM_B])
    a = lines(PROGRAM_A)
    key = phase_split.op_key
    assert table[key(a["while.4"])] == "local_sgd"
    assert table[key(a["dot.2"])] == "local_sgd"
    assert table[key(a["custom-call.6"])] == "selection"
    assert table[key(a["fusion.7"])] == "selection"      # outermost phase
    assert table[key(a["copy.8"])] is None               # no metadata
    # the same instruction under two phases in two programs
    assert table[key(a["fusion.5"])] == phase_split.AMBIGUOUS
    assert table[("fusion.5",)] == phase_split.AMBIGUOUS


def test_split_counts_a_loop_and_its_body_once():
    table = phase_split.scope_table([PROGRAM_A, PROGRAM_B])
    a = lines(PROGRAM_A)
    events = [
        event(a["while.4"], 0, 30),              # a loop and two body ops
        event(a["dot.2"], 2, 10), event(a["dot.2"], 15, 10),
        event(a["custom-call.6"], 40, 20), event(a["fusion.7"], 60, 10),
        event(a["fusion.5"], 70, 5),             # ambiguous
        event(a["fusion.9"], 110, 25),
        event(a["copy.8"], 150, 5),              # in a program, no phase
        ("%add.1 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)", 170, 5),
        event(a["while.4"], 205, 50),            # past the window's end
    ]
    got = phase_split.split(events, (0, 200), table)
    assert got == {"grad_features": 0, "selection": 30, "local_sgd": 30,
                   "coreset_epochs": 25, "ambiguous": 5, "unscoped": 10,
                   "unmatched": 5, "overlap": 0}
    busy = xplane.covered(xplane.union(xplane.clip(
        [(t0, t0 + d) for _, t0, d in events], 0, 200)))
    assert sum(got[p] for p in phase_split.PHASES) + got["ambiguous"] \
        + got["unscoped"] == busy
    top = phase_split.unscoped_ops(events, (0, 200), table)
    assert [(n.split(" ")[0], d) for n, d in top] == [("%copy.8", 5),
                                                      ("%add.1", 5)]


def test_overlap_is_time_two_phases_claim():
    table = {("a",): "local_sgd", ("b",): "selection"}
    got = phase_split.split([("a", 0, 10), ("b", 5, 10)], (0, 100), table)
    assert got["local_sgd"] == 10 and got["selection"] == 10
    assert got["overlap"] == 5 and got["unscoped"] == 0


def test_a_tiny_run_splits_its_busy_time(capsys):
    assert phase_split.main(["--workload", "shakespeare_lstm.stragglers30",
                             "--seed", str(2**33 + 17), "--tiny"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    ms = out["phases_ms"]
    assert all(ms[p] > 0 for p in phase_split.PHASES)
    assert sum(ms[p] for p in phase_split.PHASES) + ms["ambiguous"] \
        + ms["unscoped"] == pytest.approx(out["busy_ms"] + ms["overlap"])


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


def span(name, dur=0.0, **attrs):
    return {"name": name, "dur": dur, "attrs": attrs}


def test_selection_solver_and_staging_readers():
    spans = [span("gather", swap_sweeps=6, lane_sweeps_run=14,
                  lane_sweeps_improving=7),
             span("gather", swap_sweeps=4, lane_sweeps_run=10,
                  lane_sweeps_improving=5),
             span("gather"),        # a round with no coreset group
             span("stage", 0.002, bytes=10), span("stage", 0.004, bytes=20),
             span("local_sgd", 1.0)]
    ctx = {"spans": spans, "rounds": 4}
    assert reader("swap_sweeps").read(ctx) == pytest.approx(10 / 4)
    assert reader("swap_converged_lane_share").read(ctx) \
        == pytest.approx(100 * (1 - 12 / 24))
    assert reader("staging_ms").read(ctx) == pytest.approx(6.0 / 4)
    # the spans of a program without these instruments
    bare = {"spans": [span("gather"), span("local_sgd", 1.0)], "rounds": 4}
    for name in ("swap_sweeps", "swap_converged_lane_share", "staging_ms"):
        assert reader(name).read(bare) is None
