"""Mesh-sharded fleet engine: cohort groups data-parallel over devices.

The batched engine (``repro.fed.fleet.batched``) turned a 1000-client
round from a per-client Python loop into a handful of vmapped XLA
programs — but every one of those programs still runs on a single
device.  This module shards the *client axis* of each ``CohortGroup``
across a 1-D device mesh with ``shard_map``: local SGD, gradient-feature
extraction, and masked k-medoids (distance-free by default — the
feature-tiled selection reductions, no per-device (C, M, M) stack; see
``FleetConfig.distance_free``) all execute on ``C / n_devices`` client
lanes per device, and
the round's weighted parameter aggregation happens as a **psum tree**
inside the same program — no per-group host round-trip, no host-side
accumulation loop.

Execution contract (what makes sharding a pure performance choice):

  * the per-client arithmetic is literally the batched engine's —
    ``ShardedFleetEngine`` re-vmaps the raw ``sgd_scan`` / ``core_scan``
    programs a ``FleetEngine`` builds, so each client lane computes the
    same op sequence regardless of which device it lands on.  On the
    CPU, medoid choices are bit-identical to the batched engine and
    aggregated params agree to float32 summation-order tolerance (local
    partial sums + psum vs one host tensordot).  On a TPU they are not
    yet: a group program's selection can depend on how many lanes it
    holds (on one v5e, the same program on 13 lanes and on 4-lane
    chunks picked different medoids in 1 lane of 13), and the
    per-device program holds fewer lanes than the one-device program
    (ROADMAP, "sharded parity on TPU");
  * groups are padded host-side to a multiple of the device count (at
    least two lanes per device in a coreset group) by repeating the last
    client lane with **zero aggregation weight**, so padding can never
    perturb the weighted mean, and padded medoid / loss lanes are sliced
    off before returning;
  * inputs are placed with ``NamedSharding`` over the ``"clients"`` mesh
    axis (the same placement machinery as ``repro.distributed``), and
    the weighted reduction reuses ``weighted_psum_sum`` from
    ``repro.distributed.fedavg_mesh`` — on hardware the psum lowers to a
    tree all-reduce over ICI/DCN, hierarchically when the mesh is
    factored.

``run_fleet(engine="sharded")`` routes here; on a one-device host it
falls back to the batched path (identical numbers, no shard_map
overhead).  ``benchmarks/fleet_sweep.py --device-sweep`` measures the
scaling, using XLA's forced host-platform device count on CPU CI.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.fedavg_mesh import weighted_psum_sum
from repro.fed.fleet.batched import CohortGroup, FleetConfig, FleetEngine

Pytree = Any

CLIENT_AXIS = "clients"


def client_mesh(n_devices: Optional[int] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the client axis (all local devices by default)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (CLIENT_AXIS,))


def _pad_lanes(v: np.ndarray, pad: int) -> np.ndarray:
    """Pad the leading client dim by repeating the last lane."""
    if pad == 0:
        return v
    return np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])


class ShardedFleetEngine(FleetEngine):
    """A ``FleetEngine`` whose group programs run sharded over a mesh.

    ``run_group_sharded`` executes one cohort group data-parallel over
    the ``"clients"`` mesh axis and returns the group's *weighted
    parameter sum* (already psum-reduced and replicated) — the
    server-side mean becomes one divide at the end of the round
    (``combine_group_sums``) — plus the still-sharded per-client stack
    for consumers that need individual updates (robust aggregation,
    Byzantine corruption).  The inherited ``run_group`` (batched / loop)
    still works, which is what the parity tests and the single-device
    fallback rely on.
    """

    def __init__(self, model, cfg: FleetConfig, mesh: Optional[Mesh] = None):
        super().__init__(model, cfg)
        self.mesh = mesh if mesh is not None else client_mesh()
        self.n_devices = int(self.mesh.shape[CLIENT_AXIS])
        # (k, data treedef) -> jitted shard_mapped group program; jit
        # handles shape polymorphism within one entry, and the treedef key
        # makes the cache schema-generic (any pytree-of-arrays workload)
        self._programs: Dict[Tuple[int, Any], Any] = {}

    # -- program construction --------------------------------------------

    def _program(self, k: int, data_treedef):
        return self._cached_program(self._programs, (k, data_treedef),
                                    lambda: self._build_program(k), "group")

    def _build_program(self, k: int):
        """Build the shard_mapped program for groups with budget ``k``.

        The body sees the per-device view (C_local client lanes) and is
        the batched engine's fused group body
        (``FleetEngine._make_group_body`` — one copy of the arithmetic
        across loop/batched/sharded), ending in the cross-device weighted
        psum."""
        mesh = self.mesh
        axes = (CLIENT_AXIS,)
        group_body = self._make_group_body(k)
        broadcast = self._broadcast_params

        if k == 0:
            def body(params, data, w, lane_w, idx):
                c = w.shape[0]
                p, losses, _ = group_body(params, broadcast(params, c),
                                          data, w, idx)
                part, wsum = weighted_psum_sum(lane_w, p, axes)
                return part, wsum, losses, p

            def specs(params):
                shard = P(CLIENT_AXIS)
                shard_tree = jax.tree.map(lambda _: shard, params)
                in_specs = (jax.tree.map(lambda _: P(), params), shard,
                            shard, shard, shard)
                out_specs = (jax.tree.map(lambda _: P(), params), P(),
                             shard, shard_tree)
                return in_specs, out_specs
        else:
            def body(params, data, w, lane_w, idx1, valid, steps):
                c = w.shape[0]
                p, losses, meds = group_body(params, broadcast(params, c),
                                             data, w, valid, idx1, steps)
                part, wsum = weighted_psum_sum(lane_w, p, axes)
                return part, wsum, losses, meds, p

            def specs(params):
                shard = P(CLIENT_AXIS)
                shard_tree = jax.tree.map(lambda _: shard, params)
                in_specs = (jax.tree.map(lambda _: P(), params), shard,
                            shard, shard, shard, shard, shard)
                out_specs = (jax.tree.map(lambda _: P(), params), P(),
                             shard, shard, shard_tree)
                return in_specs, out_specs

        @jax.jit
        def program(params, *args):
            in_specs, out_specs = specs(params)
            fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            return fn(params, *args)

        return program

    # -- group execution --------------------------------------------------

    def _shard_put(self, v: np.ndarray):
        return jax.device_put(
            v, NamedSharding(self.mesh, P(CLIENT_AXIS)))

    def run_group_sharded(self, params: Pytree, group: CohortGroup,
                          weights: np.ndarray
                          ) -> Tuple[Pytree, jnp.ndarray, np.ndarray,
                                     Optional[np.ndarray], Pytree]:
        """Run one group over the mesh; returns (weighted param sum,
        weight total, per-client losses, medoid indices or None,
        per-client param stack) with padding lanes already stripped from
        losses/medoids/stack.  The stack stays sharded and lazy — it is
        only gathered when a robust aggregation rule or fault-corruption
        pass actually consumes it (the weighted-mean path uses the
        psum-reduced sum and never touches it)."""
        c = group.n_clients
        # shard_put never changes the treedef, so the cache is consulted
        # before staging and the dispatch span covers the host-side
        # padding + device placement along with the program call
        program = self._program(group.k, jax.tree.structure(group.data))
        self.count_dispatch()       # same accounting point as batched:
        # one top-level jitted invocation per group, so batched and
        # sharded runs of a cohort report identical dispatch counts
        name = "local_sgd" if group.k == 0 else "coreset_group"

        # outputs stay device-resident (lazy): materializing here would
        # block each group's program before the next one is dispatched,
        # serializing the mesh — the round driver converts after every
        # group has been enqueued
        with self._dispatch_span(name, program, k=group.k, n_clients=c,
                                 sharded=True):
            args = self._sharded_args(params, group, weights)
            if group.k == 0:
                part, wsum, losses, stack = program(*args)
                stack = jax.tree.map(lambda x: x[:c], stack)
                return part, wsum, losses[:c], None, stack
            part, wsum, losses, meds, stack = program(*args)
            stack = jax.tree.map(lambda x: x[:c], stack)
        return part, wsum, losses[:c], meds[:c], stack

    def _sharded_args(self, params: Pytree, group: CohortGroup,
                      weights: np.ndarray) -> Tuple:
        """Mesh-placed arguments of the group's shard_mapped program:
        lanes padded (zero aggregation weight) to a device multiple, at
        least two per device in a coreset group, each per-client array
        split over the ``"clients"`` axis."""
        cfg = self.cfg
        c = group.n_clients
        n = self.n_devices
        lanes = -(-c // n) * n
        if group.k:     # two lanes per device: see FleetEngine._lanes
            lanes = max(lanes, 2 * n)
        pad = lanes - c
        lane_w = np.concatenate([np.asarray(weights, np.float32),
                                 np.zeros(pad, np.float32)])
        data = jax.tree.map(
            lambda v: self._shard_put(_pad_lanes(np.asarray(v), pad)),
            group.data)
        w = self._shard_put(_pad_lanes(group.valid.astype(np.float32), pad))
        lane_w = self._shard_put(lane_w)
        m_pad = group.valid.shape[1]
        t_full = cfg.epochs * (m_pad // cfg.batch_size)
        idx_all = group.perms.reshape(c, t_full, cfg.batch_size)
        if group.k == 0:
            idx = self._shard_put(_pad_lanes(idx_all, pad))
            return params, data, w, lane_w, idx
        idx1 = self._shard_put(
            _pad_lanes(idx_all[:, : m_pad // cfg.batch_size], pad))
        valid = self._shard_put(_pad_lanes(group.valid, pad))
        steps = self._shard_put(
            np.zeros((c + pad, max(cfg.epochs - 1, 1)), np.float32))
        return params, data, w, lane_w, idx1, valid, steps

    def stage_group(self, params: Pytree, group: CohortGroup
                    ) -> Tuple[Any, Tuple]:
        """(shard_mapped program, mesh-placed arguments) that
        ``run_group_sharded`` would dispatch for ``group`` (unit
        aggregation weights)."""
        program = self._program(group.k, jax.tree.structure(group.data))
        return program, self._sharded_args(
            params, group, np.ones(group.n_clients, np.float32))

    def combine_group_sums(self, partials: List[Tuple[Pytree, jnp.ndarray]],
                           fallback: Pytree) -> Pytree:
        """Σ_g (weighted param sum) / Σ_g (weight total), device-resident.

        Groups are visited in the deterministic sorted-key order
        ``make_cohort_groups`` emits, so the reduction is order-stable.
        An empty cohort (or all-zero weights) returns ``fallback`` — the
        same no-op semantics as ``_aggregate_groups``."""
        if not partials:
            return fallback
        acc, total = partials[0]
        for part, wsum in partials[1:]:
            acc = jax.tree.map(jnp.add, acc, part)
            total = total + wsum
        if float(total) <= 0.0:
            return fallback
        return jax.tree.map(lambda x: x / total, acc)
