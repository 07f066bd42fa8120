"""Cross-silo FedAvg as mesh collectives.

In the cross-silo regime (DESIGN.md §5), FL clients are silos living on
mesh rows: silo i's local params occupy the `data` (and `pod`) slices of
the mesh.  Server aggregation w <- (Σ wᵢ·mᵢ)/(Σ mᵢ) is then not an RPC but
a **weighted psum over the client axes** via shard_map — on hardware this
lowers to one all-reduce over ICI within a pod plus one over DCN across
pods (hierarchical FedAvg for free from mesh factorization).

Layout contract: every leaf of ``local_params`` carries a leading silo dim
of size n_silos = Π|client_axes|, sharded over ``client_axes``; ``weights``
is (n_silos,) sharded the same way.  The output drops the silo dim and is
replicated — ready to broadcast into the next round.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def weighted_psum_sum(weights, stacked, axes: Tuple[str, ...]):
    """Weighted sum over a sharded leading client dim, reduced with psum.

    Must be called *inside* a ``shard_map`` body.  ``stacked`` is a pytree
    whose leaves carry a leading local-client dim matching ``weights``
    (local_clients,); the weighted sum over that dim is reduced locally and
    then psum'd over the mesh ``axes`` — on hardware a tree all-reduce over
    ICI/DCN.  Returns ``(summed pytree with the client dim removed,
    total weight)``, both replicated across ``axes``.  Shared by the
    cross-silo FedAvg collective below and the sharded fleet engine
    (``repro.fed.fleet.sharded``), so both aggregate with the same
    order-stable device-resident reduction.
    """
    total_w = jax.lax.psum(jnp.sum(weights), axes)

    def one(leaf):
        wl = weights.astype(leaf.dtype).reshape(
            (-1,) + (1,) * (leaf.ndim - 1))
        return jax.lax.psum(jnp.sum(leaf * wl, axis=0), axes)

    return jax.tree.map(one, stacked), total_w


def fedavg_allreduce(local_params, weights, mesh: Mesh,
                     client_axes: Tuple[str, ...] = ("pod", "data")):
    """Weighted FedAvg across the client mesh axes.

    local_params: pytree; each leaf (n_silos, ...) sharded P(client_axes).
    weights: (n_silos,) aggregation weights (mⁱ, or ones for uniform 1/K).
    Returns the aggregated pytree with the silo dim removed, replicated.
    """
    axes = tuple(a for a in client_axes if a in mesh.axis_names)

    def agg(w, *leaves):
        # each shard sees (silos_per_shard, ...); reduce locally then psum
        summed, total_w = weighted_psum_sum(w, list(leaves), axes)
        return tuple(leaf / total_w for leaf in summed)

    flat, treedef = jax.tree.flatten(local_params)
    in_specs = (P(axes),) + tuple(
        P(*((axes,) + (None,) * (leaf.ndim - 1))) for leaf in flat)
    out_specs = tuple(P(*((None,) * (leaf.ndim - 1))) for leaf in flat)
    fn = jax.shard_map(agg, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = fn(weights, *flat)
    return jax.tree.unflatten(treedef, list(out))
