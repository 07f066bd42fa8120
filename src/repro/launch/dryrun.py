"""Multi-pod dry-run: prove the distribution config is coherent without
real hardware (deliverable e).

For every (architecture x input-shape x mesh) combination this lowers and
compiles the real step function — ``train_step`` for train shapes, forward
for prefill, ``serve_step`` (one token against a full-length KV/SSM cache)
for decode shapes — with the production sharding rules, then records:

  * ``compiled.memory_analysis()``  (bytes per device — proves it fits)
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes for the roofline)
  * collective bytes parsed from the optimized HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single \
      --out results/dryrun
Failures here (sharding mismatch, unsupported collective) are bugs in the
system, not in the harness.
"""
import argparse
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

# The production mesh wants 512 virtual host devices (a multi-pod topology
# simulated on CPU).  Forcing them is a process-global XLA setting, so it
# only happens when this module IS the program (``python -m
# repro.launch.dryrun`` executes it as ``__main__``) or on explicit
# opt-in via REPRO_DRYRUN_FORCE_DEVICES=N — importing the module as a
# library must not reconfigure the host's device count as an import-time
# side effect.  XLA reads the flag at backend init (first jax use), so
# setting it here — after the package imports above already pulled in
# jax — is still in time.
if __name__ == "__main__" or os.environ.get("REPRO_DRYRUN_FORCE_DEVICES"):
    from repro.utils.xla_env import force_host_devices_here
    force_host_devices_here(
        int(os.environ.get("REPRO_DRYRUN_FORCE_DEVICES", "512")))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.distributed.sharding import (batch_specs, decode_state_specs,
                                        param_specs)
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim.optimizers import sgd, adam
from repro.utils.tree import tree_add

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Sum output-shape bytes of every collective op in optimized HLO.

    NOTE: ops inside while-loop bodies (layer scans) appear once in the
    text regardless of trip count — these are lower bounds; the roofline
    harness (benchmarks/roofline.py) scales loop-body collectives by the
    known layer counts via its analytic model.
    """
    per_op: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue
        per_op[m.group(2)] += _shape_bytes(m.group(1))
        counts[m.group(2)] += 1
    return {"bytes_by_op": per_op, "counts": counts,
            "total_bytes": sum(per_op.values())}


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_step(model: Model, shape: ShapeConfig, optimizer: str = "sgd"):
    """Returns (step_fn, example_args builder) for the shape kind."""
    cfg = model.cfg
    if shape.kind == "train":
        opt = adam(1e-4) if optimizer == "adam" else sgd(1e-2)

        def train_step(params, opt_state, batch):
            def loss_fn(p):
                loss, _ = model.loss(p, batch)
                return loss

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = tree_add(params, updates)
            return params, opt_state, loss

        return train_step, opt
    if shape.kind == "prefill":
        def prefill_step(params, batch):
            logits, aux, hidden = model.forward(params, batch)
            # serving prefill returns last-position logits
            return logits[:, -1, :]

        return prefill_step, None

    def serve_step(params, state, batch):
        return model.decode_step(params, state, batch["token"], batch["pos"])

    return serve_step, None


def abstract_params(model: Model, dtype=DTYPE):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype), shapes)


def _named(specs_tree, mesh):
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# the dry run
# ---------------------------------------------------------------------------

def dry_run(arch_id: str, shape_name: str, multi_pod: bool = False,
            sharding_mode: str = "tp", optimizer: str = "sgd",
            context_parallel: bool = False, remat: bool = False,
            mesh_split: Optional[tuple] = None,
            verbose: bool = True) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    cfg = get_config(arch_id, shape=shape)
    if remat:
        cfg = cfg.with_(remat=True)
    model = Model(cfg)
    if mesh_split is not None:
        # perf-iteration rebalance: same 256 chips, different (data, model)
        assert mesh_split[0] * mesh_split[1] == 256
        mesh = jax.make_mesh(mesh_split, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    record: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": dict(mesh.shape),
        "sharding": sharding_mode,
        "context_parallel": context_parallel,
        "remat": remat,
        "optimizer": optimizer if shape.kind == "train" else None,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    t0 = time.perf_counter()

    params_abs = abstract_params(model)
    p_specs = param_specs(cfg, params_abs, mesh, mode=sharding_mode)
    in_specs = model.input_specs(shape, dtype=DTYPE)
    b_specs = batch_specs(in_specs, mesh)

    step, opt = build_step(model, shape, optimizer)

    with mesh:
        if shape.kind == "train":
            opt_abs = jax.eval_shape(opt.init, params_abs)
            o_specs = _opt_specs(opt_abs, p_specs)
            jitted = jax.jit(
                step,
                in_shardings=(_named(p_specs, mesh), _named(o_specs, mesh),
                              _named(b_specs, mesh)),
                out_shardings=(_named(p_specs, mesh), _named(o_specs, mesh),
                               NamedSharding(mesh, P())))
            lowered = jitted.lower(params_abs, opt_abs, in_specs)
        elif shape.kind == "prefill":
            from repro.distributed.sharding import _fit
            out_spec = _fit(
                P(tuple(n for n in ("pod", "data") if n in mesh.axis_names),
                  "model"),
                (shape.global_batch, cfg.vocab_size), mesh)
            jitted = jax.jit(
                step,
                in_shardings=(_named(p_specs, mesh), _named(b_specs, mesh)),
                out_shardings=NamedSharding(mesh, out_spec))
            lowered = jitted.lower(params_abs, in_specs)
        else:  # decode
            state_abs = jax.eval_shape(
                lambda p: model.init_decode_state(
                    p, shape.global_batch, shape.seq_len, dtype=DTYPE),
                params_abs)
            s_specs = decode_state_specs(cfg, state_abs, mesh,
                                         context_parallel=context_parallel)
            jitted = jax.jit(
                step,
                in_shardings=(_named(p_specs, mesh), _named(s_specs, mesh),
                              _named(b_specs, mesh)),
                out_shardings=(NamedSharding(mesh, P()),
                               _named(s_specs, mesh)))
            lowered = jitted.lower(params_abs, state_abs, in_specs)

        record["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        record["compile_s"] = round(time.perf_counter() - t1, 2)

    mem = compiled.memory_analysis()
    record["memory"] = _memory_dict(mem)
    cost = compiled.cost_analysis()
    record["cost"] = {k: v for k, v in cost.items()
                      if k in ("flops", "bytes accessed")} if cost else {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    record["collectives"] = collective_stats(hlo)
    record["hlo_lines"] = hlo.count("\n")
    record["ok"] = True
    if verbose:
        n_dev = int(jnp.prod(jnp.asarray(list(mesh.shape.values()))))
        print(f"[dryrun] {arch_id} x {shape_name} x "
              f"{record['mesh']} ({sharding_mode}) OK — "
              f"lower {record['lower_s']}s compile {record['compile_s']}s "
              f"mem/device "
              f"{record['memory'].get('bytes_per_device', 0)/2**30:.2f} GiB "
              f"flops {record['cost'].get('flops', 0):.3e} "
              f"coll {record['collectives']['total_bytes']/2**30:.2f} GiB",
              flush=True)
    return record


def _opt_specs(opt_abs, p_specs):
    """Optimizer-state sharding: momentum-like trees mirror the params."""
    def build(node, spec_node):
        return spec_node

    out = {}
    for k, v in opt_abs.items():
        if k in ("m", "v", "mu"):
            out[k] = p_specs
        else:
            out[k] = jax.tree.map(lambda _: P(), v)
    return out


def _memory_dict(mem) -> Dict[str, float]:
    if mem is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    total = (out.get("argument_size_in_bytes", 0)
             + out.get("output_size_in_bytes", 0)
             + out.get("temp_size_in_bytes", 0)
             - out.get("alias_size_in_bytes", 0))
    out["bytes_per_device"] = total
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=ARCH_IDS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) pair")
    ap.add_argument("--sharding", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--context-parallel", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--mesh-split", default=None,
                    help="perf iteration: 'DATA,MODEL' split of 256 chips "
                         "(e.g. 32,8)")
    ap.add_argument("--out", default=None, help="JSONL output path")
    args = ap.parse_args(argv)
    mesh_split = (tuple(int(x) for x in args.mesh_split.split(","))
                  if args.mesh_split else None)

    pairs = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                pairs.append((a, s, m))

    records = []
    failures = 0
    for arch, shp, mesh_kind in pairs:
        try:
            rec = dry_run(arch, shp, multi_pod=(mesh_kind == "multi"),
                          sharding_mode=args.sharding,
                          optimizer=args.optimizer,
                          context_parallel=args.context_parallel,
                          remat=args.remat, mesh_split=mesh_split)
        except Exception as e:  # a failure here is a bug in the system
            failures += 1
            rec = {"arch": arch, "shape": shp, "mesh": mesh_kind,
                   "ok": False, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[dryrun] {arch} x {shp} x {mesh_kind} FAILED: {e}",
                  flush=True)
        records.append(rec)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    ok = sum(1 for r in records if r.get("ok"))
    print(f"[dryrun] {ok}/{len(records)} combinations compiled",
          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
