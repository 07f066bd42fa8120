"""Roofline analytic-model validation.

The roofline terms come from the analytic cost model (XLA's cost_analysis
counts lax.scan bodies once — see benchmarks/roofline.py).  Here we
cross-validate the analytic FLOPs against cost_analysis on configs where
the undercount cannot occur (single layer => scan trip count 1, naive
attention, no inner scans), and sanity-check the collective parser.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.roofline import (collective_bytes_per_chip, forward_flops,
                                 hbm_bytes, model_flops, roofline)
from repro.configs import get_config
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.models.model import Model


def _measured_flops(cfg, batch, seq):
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }

    def fwd(p, b):
        logits, _, _ = model.forward(p, b, impl="naive")
        return logits

    compiled = jax.jit(fwd).lower(params, batch_abs).compile()
    return compiled.cost_analysis()["flops"]


@pytest.mark.parametrize("d_ff,vocab", [(512, 512), (1024, 2048)])
def test_analytic_flops_match_xla_single_layer(d_ff, vocab):
    cfg = ModelConfig(arch_id="t", family="dense", n_layers=1, d_model=256,
                      n_heads=4, n_kv_heads=4, d_ff=d_ff, vocab_size=vocab)
    batch, seq = 2, 128
    analytic = forward_flops(cfg, batch, seq)
    measured = _measured_flops(cfg, batch, seq)
    # naive attention counts full SxS (analytic uses S/2 causal average);
    # allow the softmax/norm overhead band
    assert 0.5 < measured / analytic < 2.0, (analytic, measured)


def test_train_flops_3x_forward():
    cfg = get_config("yi-9b")
    shape = SHAPES["train_4k"]
    r = roofline("yi-9b", "train_4k", {"data": 16, "model": 16})
    fwd = forward_flops(cfg, shape.global_batch, shape.seq_len)
    assert abs(r["flops"] / fwd - 3.0) < 1e-6


def test_model_flops_6nd():
    cfg = get_config("yi-9b")
    shape = SHAPES["train_4k"]
    mf = model_flops(cfg, shape)
    expected = 6.0 * cfg.active_param_count() * shape.global_batch * \
        shape.seq_len
    assert mf == expected


def test_useful_ratio_below_one_for_attention_archs():
    """Analytic HLO flops >= 6ND because attention quadratic terms are
    extra — the ratio must be in (0, 1.05] for the dense archs."""
    for arch in ("yi-9b", "mistral-large-123b", "command-r-35b"):
        r = roofline(arch, "train_4k", {"data": 16, "model": 16})
        assert 0.5 < r["useful_flops_ratio"] <= 1.05, (arch, r)


def test_decode_memory_bound():
    """Decode at batch 128 with a 32k cache must be memory-dominated on
    v5e for every dense arch (weights+cache >> flops)."""
    for arch in ("yi-9b", "granite-20b", "command-r-35b"):
        r = roofline(arch, "decode_32k", {"data": 16, "model": 16})
        assert r["dominant"] == "memory", (arch, r["dominant"])


def test_window_cuts_attention_flops():
    cfg = get_config("yi-9b")
    full = forward_flops(cfg, 1, 32768)
    cfg_w = cfg.with_(attention_window=4096)
    windowed = forward_flops(cfg_w, 1, 32768)
    assert windowed < full


def test_remat_cuts_memory_term():
    shape = SHAPES["train_4k"]
    cfg = get_config("mistral-large-123b")
    base = hbm_bytes(cfg, shape, 256, remat=False)
    rem = hbm_bytes(cfg, shape, 256, remat=True)
    assert rem < base


def test_collective_model_scales_with_tp():
    cfg = get_config("yi-9b")
    shape = SHAPES["train_4k"]
    c16 = collective_bytes_per_chip(cfg, shape,
                                    {"data": 16, "model": 16})["total"]
    c8 = collective_bytes_per_chip(cfg, shape,
                                   {"data": 32, "model": 8})["total"]
    assert c8 < c16  # less TP + more DP => fewer activation all-reduce bytes


def test_multi_pod_adds_dcn_term():
    cfg = get_config("yi-9b")
    shape = SHAPES["train_4k"]
    c = collective_bytes_per_chip(cfg, shape,
                                  {"pod": 2, "data": 16, "model": 16})
    assert c["dcn"] > 0


def test_collective_parser():
    from repro.launch.dryrun import collective_stats
    hlo = """
  %all-reduce = f32[64,128]{1,0} all-reduce(%dot.1), channel_id=1
  %ag = bf16[32,256]{1,0} all-gather(%p0), dimensions={0}
  %rs.1 = f32[16]{0} reduce-scatter(%x), dimensions={0}
  %other = f32[8,8]{1,0} add(%a, %b)
"""
    stats = collective_stats(hlo)
    assert stats["counts"]["all-reduce"] == 1
    assert stats["bytes_by_op"]["all-reduce"] == 64 * 128 * 4
    assert stats["bytes_by_op"]["all-gather"] == 32 * 256 * 2
    assert stats["bytes_by_op"]["reduce-scatter"] == 16 * 4
    assert stats["total_bytes"] == 64 * 128 * 4 + 32 * 256 * 2 + 16 * 4
