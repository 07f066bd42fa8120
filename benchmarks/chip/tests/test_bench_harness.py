"""A whole run of the harness at a tiny size on the CPU (the look for a
chip skipped): the result line, and ``correct`` false under each fault
the timed path can have on one chip."""
import os
import sys
from pathlib import Path

# the benchmark's CPU tests: small shapes on the host CPU, the
# benchmark's own modules and the program on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[3] / "src")]

import json
import shutil
import subprocess

import jax.numpy as jnp

from fedbench import harness
from fedbench.spec import BENCH_DIR, REPO_ROOT

CELL = "shakespeare_lstm.stragglers30"


def run(tmp_path, cell=CELL, trace=False):
    return harness.run(cell, 2**33 + 17, 0.3, trace, t_start=0.0, tiny=True,
                       require_tpu=False, out_dir=tmp_path)


def test_a_sound_run_is_correct(tmp_path):
    res = run(tmp_path)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checked"
    assert res["correct"] is True, res["checked"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"client_updates_per_s", "round_p90_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert {"select_ratio", "change_gap"} <= set(res["checked"])


def test_a_traced_run_reports_per_layer_metrics(tmp_path):
    res = run(tmp_path, cell="shakespeare_lstm.stragglers30", trace=True)
    assert res["correct"] is True, res["checked"]
    m = res["metrics"]
    assert {"cohort_build_ms", "window_compiles",
            "device_idle_share"} <= set(m)
    assert m["window_compiles"]["value"] == 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch):
    from repro.fed.fleet.batched import FleetEngine
    real = FleetEngine._run_group_stacked

    def unchanged(self, params, group):
        p, losses, meds = real(self, params, group)
        return self._broadcast_params(params, losses.shape[0]), losses, meds

    monkeypatch.setattr(FleetEngine, "_run_group_stacked", unchanged)
    res = run(tmp_path)
    assert res["correct"] is False
    assert res["checked"]["change_gap"]["value"] > 0.9


def test_half_of_each_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    from repro.fed.fleet.batched import FleetEngine
    real = FleetEngine._group_args

    def half(self, params, group):
        args = list(real(self, params, group))
        args[3] = args[3] * (jnp.arange(args[3].shape[1]) % 2 == 0)[None, :]
        return tuple(args)

    monkeypatch.setattr(FleetEngine, "_group_args", half)
    assert run(tmp_path)["correct"] is False


def test_half_the_clients_left_out_of_the_mean_is_not_correct(
        tmp_path, monkeypatch):
    import numpy as np

    from repro.fed.fleet import batched
    real = batched._aggregate_groups

    def half(partials, fallback):
        cut = []
        for stacked, w in partials:
            w = np.array(w, np.float64)
            w[(len(w) + 1) // 2:] = 0.0
            cut.append((stacked, w))
        return real(cut, fallback)

    monkeypatch.setattr(batched, "_aggregate_groups", half)
    assert run(tmp_path)["correct"] is False


def test_a_medoid_altered_where_it_is_chosen_is_not_correct(
        tmp_path, monkeypatch):
    from repro.fed.fleet import batched
    real = batched.build_coreset_batched

    def altered(features, valid, budget, **kw):
        cs = real(features, valid, budget, **kw)
        idx = cs.indices
        m = valid.shape[1]
        is_med = (jnp.arange(m)[None, :, None] == idx[:, None, :]).any(-1)
        first_free = jnp.argmax(valid & ~is_med, axis=1).astype(idx.dtype)
        return cs._replace(indices=idx.at[:, 0].set(first_free))

    monkeypatch.setattr(batched, "build_coreset_batched", altered)
    res = run(tmp_path)
    assert res["correct"] is False
    assert res["checked"]["select_ratio"]["value"] > 1.0


def test_every_seed_gets_the_same_cohort_and_budgets():
    """The traffic file's population seed fixes sizes, cohort and
    budgets; the run's seed draws only the data."""
    from fedbench import population
    from fedbench.spec import Cell
    from reference.fleet import coreset_k

    cell = Cell(CELL)
    cfg = cell.config
    pops = [population.build(cfg, cell.traffic, cfg.TINY["population"],
                             cfg.TINY["model"], dict(cfg.FLEET), seed)
            for seed in (3, 2**33 + 17)]
    a, b = pops
    assert (a.sizes == b.sizes).all() and a.cohort == b.cohort
    assert a.budgets == b.budgets and len(a.cohort) == cell.traffic["cohort"]
    assert any((a.clients[c]["x"] != b.clients[c]["x"]).any()
               for c in a.cohort)
    stragglers = [c for c in a.cohort
                  if coreset_k(int(a.sizes[c]), a.budgets[c])]
    assert 0 < len(stragglers) < len(a.cohort)


def command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    out = command(REPO_ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_the_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(REPO_ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((REPO_ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (REPO_ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH_DIR / "limits" / f"{w['name']}.json").is_file()
    for m in bench["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    assert {m["name"] for m in bench["end_to_end"]} == {
        "client_updates_per_s", "round_p90_s", "setup_s"}
