"""CPU rehearsal of ``chip_smoke.py``: its phase functions at tiny sizes.

The script itself refuses to run off a TPU; these tests call its phases
directly on the CPU (jnp selection path, 16 clients of about 40 samples,
the ``tiny`` LM preset) so that a wrong path, argument or check is found
here and not on the chip.  The sharded phase needs four devices and runs
in a child process with four forced host devices.
"""
import importlib.util
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import xla_env

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(n_clients=16, mean_samples=40.0, std_samples=24.0)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_backend_without_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""                     # no result line
    assert "no TPU" in out.err


def test_fleet_phase_tiny(smoke):
    # at M ~ 40 the cutover moves down to 64 so that selection groups sit
    # on both sides of it, as the full-size fleet's do at 256
    r = smoke.fleet_phase(materialize_below=64, **TINY)
    assert r["kernels"] is False             # CPU resolves to jnp
    assert r["custom_call"] == {"distance-free": False, "D-input": False}
    assert len(r["history"]) == 2
    (_, n0, c0), (_, n1, c1) = r["per_round"]
    assert n0 == n1 and c0 > 0 and c1 == 0   # round 1 is warm
    assert r["medoids_identical"] == r["selection_lanes"] > 0
    assert r["params_diff"] == 0.0


def test_lane_check_passes_float32_and_flags_a_bf16_selection(smoke):
    """The per-lane fixed-point check the chip run gates on: float32
    selections pass it, selections from bf16-rounded features do not
    (here 2 of the 10 lanes of a 256-client fleet's M=256, k=64 group)."""
    import numpy as np

    from repro.fed.fleet.batched import FleetConfig

    wl, train, _, specs, _ = smoke._cnn_fleet(256, 384.0, 256.0, seed=0)
    cfg = FleetConfig(seed=0, max_sweeps=smoke.MAX_SWEEPS)
    _, _, groups = smoke._round0_groups(wl, train, specs, cfg)
    (g,) = [g for g in groups if (g.valid.shape[1], g.k) == (256, 64)]
    params = wl.init(jax.random.PRNGKey(0))
    x = jax.vmap(wl.grad_features, in_axes=(None, 0))(params, g.data)
    feats = {id(g): np.asarray(x) * g.valid[..., None]}
    f32 = smoke._select(cfg, g, feats[id(g)], use_kernel=False)
    judged = smoke._judge_lanes([g], feats, {"jnp": f32})
    assert judged["lanes"] == g.n_clients and not judged["failed"]
    assert judged["ratio"]["jnp"] < 1.0
    control = smoke._bf16_control(cfg, [g], feats)
    assert control["failed"] and control["ratio"]["bf16"] > 1.0


def test_fleet_phase_requires_both_sides_of_the_cutover(smoke):
    with pytest.raises(RuntimeError, match="both sides"):
        smoke.fleet_phase(materialize_below=256, **TINY)


def test_lm_phase_tiny(smoke, monkeypatch):
    monkeypatch.setattr(xla_env, "enable_compile_cache", lambda: None)
    out = smoke.lm_phase(preset="tiny")
    assert len(out["history"]) == 2
    assert all(h["round_time"] <= h["tau"] * 1.001 for h in out["history"])


def test_sharded_phase_four_host_devices():
    code = ("import chip_smoke; chip_smoke.sharded_phase(n_devices=4, "
            "n_clients=16, mean_samples=40.0, std_samples=24.0)")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=xla_env.forced_host_device_env(4, str(ROOT)),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "on 4 devices: [0, 1, 2, 3]" in proc.stdout
    assert "all-reduce present" in proc.stdout


def test_compile_cache_dir_is_the_env_or_one_fixed_path(monkeypatch,
                                                        tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(xla_env.CACHE_ENV, str(tmp_path))
    assert xla_env.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX
    monkeypatch.delenv(xla_env.CACHE_ENV)
    try:
        got = xla_env.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_fleet_sweep_refuses_cpu_children_under_an_accelerator(
        monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "fleet_sweep", ROOT / "benchmarks" / "fleet_sweep.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod.jax, "default_backend", lambda: "tpu")
    for flag in (["--selection-memory"], ["--device-sweep", "1,2"]):
        with pytest.raises(SystemExit) as e:
            mod.main(flag)
        assert e.value.code != 0
