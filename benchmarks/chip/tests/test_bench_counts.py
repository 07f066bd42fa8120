"""Operation and byte counts of the selection kernels and the client
models, the peaks table, and the readers that turn them into shares."""
import os
import sys
from pathlib import Path

# the benchmark's CPU tests: small shapes on the host CPU, the
# benchmark's own modules and the program on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[3] / "src")]

import pytest

from fedbench import peaks as peaks_mod
from fedbench.spec import BENCH_DIR, kernel_counters, load_module


def kernels():
    return {k.NAME: k for k in kernel_counters()}


def config(name):
    return load_module(BENCH_DIR / "configs" / f"{name}.py")


def test_feature_kernels_by_hand():
    k = kernels()
    c, m, f, kk = 2, 128, 256, 16
    # cross term 2·C·M²·F, epilogue 8 per (i, j)
    assert k["build_cost_from_feats"].flops(c, m, f, kk) == (
        2 * 2 * 128 * 128 * 256 + 8 * 2 * 128 * 128)
    # features once, norms/d_near/mask and the cost row
    assert k["build_cost_from_feats"].bytes_moved(c, m, f, kk) == 4 * (
        2 * 128 * 256 + 4 * 2 * 128)
    assert k["delta_sweep_from_feats"].flops(c, m, f, kk) == (
        2 * 2 * 128 * 128 * 256 + 2 * 2 * 128 * 128 * 16
        + 12 * 2 * 128 * 128)
    assert k["delta_sweep_from_feats"].bytes_moved(c, m, f, kk) == 4 * (
        2 * 128 * 256 + 6 * 2 * 128 + 2 * 2 * 128 * 16)


def test_distance_input_kernels_by_hand():
    k = kernels()
    assert k["build_cost"].flops(1, 64, 0, 4) == 3 * 64 * 64
    assert k["build_cost"].bytes_moved(1, 64, 0, 4) == 4 * (64 * 64 + 3 * 64)
    assert k["delta_sweep"].flops(1, 64, 0, 4) == 2 * 64 * 64 * 4 + 8 * 64 * 64
    assert k["delta_sweep"].bytes_moved(1, 64, 0, 4) == 4 * (
        64 * 64 + 4 * 64 + 2 * 64 * 4)


def test_every_kernel_file_names_a_selection_kernel():
    ks = kernel_counters()
    assert {k.NAME for k in ks} == {"build_cost_from_feats",
                                    "delta_sweep_from_feats", "build_cost",
                                    "delta_sweep", "pairwise_l2_batched"}
    assert all(k.SELECTION and k.MATCH for k in ks)


# operation names as the TPU trace gives them (HLO text, shortened)
D_SWEEP = ("%kmedoids_delta_sweep.7 = (f32[2,1,128]{2,1,0}, f32[2,128,128]"
           "{2,1,0}) custom-call(f32[2,128,128]{2,1,0} %a, f32[2,128,1]{2,1,0}"
           " %b, f32[2,128,1]{2,1,0} %c, f32[2,128,1]{2,1,0} %d, "
           "f32[2,128,128]{2,1,0} %e), custom_call_target=\"tpu_custom_call\"")
F_SWEEP = ("%kmedoids_delta_sweep_from_feats.3 = (f32[13,1,512]{2,1,0}, "
           "f32[13,512,256]{2,1,0}) custom-call(f32[13,512,3200]{2,1,0} %x, "
           "f32[13,512,3200]{2,1,0} %x, f32[13,512,1]{2,1,0} %s, "
           "f32[13,1,512]{2,1,0} %t, f32[13,512,256]{2,1,0} %o), "
           "custom_call_target=\"tpu_custom_call\"")


def test_kernels_find_their_calls_and_shapes():
    import re

    from fedbench import xplane
    k = kernels()
    hits = {n: [t for t in (D_SWEEP, F_SWEEP) if re.search(kk.MATCH, t)]
            for n, kk in k.items()}
    assert hits["delta_sweep"] == [D_SWEEP]
    assert hits["delta_sweep_from_feats"] == [F_SWEEP]
    assert not hits["build_cost"] and not hits["build_cost_from_feats"]
    assert k["delta_sweep"].call_shape(xplane.operand_shapes(D_SWEEP)) == {
        "c": 2, "m": 128, "f": 0, "k": 128}
    assert k["delta_sweep_from_feats"].call_shape(
        xplane.operand_shapes(F_SWEEP)) == {"c": 13, "m": 512, "f": 3200,
                                            "k": 256}


def test_lstm_model_flops_by_hand():
    fl = config("shakespeare_lstm").model_flops()
    per_step = 2 * (8 + 256) * 1024 + 2 * (256 + 256) * 1024
    fwd = 80 * (per_step + 2 * 256 * 80)
    assert fl["train"] == 3 * fwd == 391_249_920
    assert fl["features"] == fwd + 80 * 2 * 256 * 80


def test_round_flops_by_hand():
    """A full-set client visits its m samples E times; a straggler of
    coreset k visits m once, then k for each of the E − 1 coreset
    epochs, and runs its m samples' feature pass."""
    from types import SimpleNamespace

    import numpy as np

    from fedbench import harness
    cfg = config("shakespeare_lstm")
    fl = cfg.model_flops()
    # client 0: budget ≥ m, full set; client 1: budget 70 → k = 64
    pop = SimpleNamespace(cohort=[0, 1], sizes=np.array([100, 300]),
                          budgets={0: 100, 1: 70})
    flops = harness.round_flops(cfg, cfg.MODEL, pop, {"epochs": 2})
    assert flops == (2 * 100 * fl["train"] + (300 + 64) * fl["train"]
                     + 300 * fl["features"])


def test_peaks_table():
    pk = peaks_mod.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_mod.peaks("TPU v9 imaginary")


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


class FakeTrace:
    def __init__(self, events, busy_s, window_s):
        self.events = events
        self.busy_s = busy_s
        self.window_s = window_s

    def events_matching(self, pattern):
        import re
        return [e for e in self.events if re.search(pattern, e[0])]


def test_kernel_and_device_readers():
    pk = peaks_mod.peaks("TPU v5 lite")
    k = kernels()
    evs = [("%kmedoids_build_cost_from_feats.2 = f32[2,1,512]", 0, 2e6),
           (F_SWEEP, 0, 3e6),
           ("%fusion.3 = f32[2,512]", 0, 9e6)]
    tr = FakeTrace(evs, busy_s=0.8, window_s=1.0)
    shape = dict(c=2, m=512, f=3200, k=64)
    ctx = {"trace": tr, "trace_rounds": 2, "peaks": pk,
           "kernels": kernel_counters(),
           "kernel_calls": lambda kern: [
               (shape, d) for _, _, d in tr.events_matching(kern.MATCH)]}
    assert reader("selection_kernel_ms").read(ctx) == pytest.approx(2.5)
    ideal = sum(max(k[n].flops(**shape) / pk["bf16_flops"],
                    k[n].bytes_moved(**shape) / pk["hbm_bytes_per_s"])
                for n in ("build_cost_from_feats", "delta_sweep_from_feats"))
    assert reader("selection_kernel_roofline").read(ctx) == pytest.approx(
        100 * ideal / 5e-3)
    assert reader("device_idle_share").read(ctx) == pytest.approx(20.0)
    mfu = reader("round_mfu").read({"peaks": pk, "window_s": 2.0,
                                    "rounds": 4, "round_flops": 197e12})
    assert mfu == pytest.approx(200.0)
    assert reader("selection_kernel_ms").read(
        {**ctx, "trace": FakeTrace(evs[2:], 0.5, 1.0)}) is None
