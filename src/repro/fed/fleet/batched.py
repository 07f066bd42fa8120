"""Batched fleet client engine: 1000+-client rounds without a per-client
Python loop.

The synchronous server (``repro.fed.server``) and the async event runtime
(``repro.fed.events``) both execute clients one at a time in Python — fine
for cohorts of 10, hopeless at fleet scale where a single round touches a
thousand devices.  This module executes an entire cohort as a handful of
XLA programs:

  * clients are padded into **cohort groups** keyed by (padded size M,
    quantized coreset budget k): every client in a group shares static
    shapes, so local SGD, gradient-feature extraction, and masked
    k-medoids all ``vmap`` over the client axis — selection is
    **distance-free** by default (the BUILD/Δ-sweep reductions consume
    the (C, M, F) feature stack via the feature-tiled Pallas kernels; no
    (C, M, M) distance tensor is ever materialized, so per-client M
    scales to the thousands), with ``FleetConfig.distance_free=False``
    keeping the materializing pairwise + D-input solver as the measured
    baseline;
  * per-client randomness (epoch permutations) is drawn host-side from
    ``(seed, round, cid)`` streams, so results are a pure function of the
    seed regardless of grouping or execution order;
  * the same arithmetic runs either vmapped (``engine="batched"``) or as
    the status-quo per-client Python loop (``engine="loop"``): one client
    at a time, one jitted dispatch per mini-batch step — the execution
    model of ``repro.fed.strategies.LocalTrainer`` that the batched
    engine replaces.  Both paths share every op, so they agree to
    numerical tolerance — `benchmarks/fleet_sweep.py` verifies the
    parity and measures the wall-clock gap, which is the whole point.

Local-training semantics (deliberately simpler than
``repro.fed.strategies`` so they batch): each epoch visits all M padded
slots in a seeded per-client permutation, B at a time; padded samples
carry zero loss weight, so a batch's gradient is the weighted mean over
its real samples only.  Straggling clients run Alg. 1: one full-set
epoch from the round-start params (which also yields the gradient
features), k-medoids coreset selection, then E−1 weighted full-batch
epochs on the coreset.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (load_server_meta, load_server_state,
                              save_server_state)
from repro.core.coreset import build_coreset_batched
from repro.fed.aggregators import ROBUST_METHODS, robust_combine
from repro.fed.cost import resolve_cost
from repro.fed.fleet.faults import (FaultTrace, corrupt_stacked,
                                    get_fault_profile)
from repro.fed.fleet.workloads import client_num_samples
from repro.fed.server import RoundRecord, make_eval_fn
from repro.fed.simulator import (CapabilityTrace, ClientSpec,
                                 DispatchTraceIndexer, TraceConfig,
                                 straggler_deadline)
from repro.obs import active_recorder, get_recorder

Pytree = Any


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    epochs: int = 2               # E
    batch_size: int = 32          # B
    lr: float = 0.05
    # tri-state Pallas switch for the selection fast path (distance stacks
    # + fused BUILD/Δ-sweep reductions): None = auto (kernels on supported
    # backends, jnp fallback otherwise); True/False force on/off
    use_kernel: Optional[bool] = None
    # distance-free selection (default on): the group program's k-medoids
    # reductions consume the (C, M, F) feature stack directly and the
    # (C, M, M) distance tensor is never materialized — O(C·M·F) peak
    # selection memory, per-client M in the thousands.  False keeps the
    # materializing pairwise + D-input solver as the A/B baseline
    # (benchmarks/fleet_sweep.py --selection-memory).
    distance_free: bool = True
    # adaptive cutover for distance_free: below this M the (C, M, M)
    # stack is cheap and streaming's recompute FLOPs cost more than the
    # memory saves, so selection materializes anyway (bit-identical to
    # the D-input path).  0 forces streaming at any size.
    materialize_below: int = 256
    max_sweeps: int = 25          # k-medoids swap sweeps
    weight_by_samples: bool = True  # aggregate ∝ mⁱ (fleet cohorts are not
    # sampled ∝ mⁱ, so size weighting is the unbiased choice here)
    seed: int = 0
    # per-sample step cost (repro.fed.cost.WorkloadCostModel; None =
    # legacy samples-cost-1.0): budgets, derived deadlines, and realized
    # durations all price a sample-visit through this model, so a
    # deadline means FLOPs, not raw sample counts.  Group quantization
    # (`_floor_pow4`) is unchanged — cost rescales what a budget *is*,
    # not how budgets map to cohort groups.
    cost: Any = None
    # server combine rule: "weighted_mean" (the FedAvg default) or one of
    # repro.fed.aggregators.ROBUST_METHODS (trimmed_mean / median / krum /
    # multi_krum / norm_clip) — the Byzantine-resilient rules fed by the
    # engines' per-client parameter stacks
    aggregator: str = "weighted_mean"


@dataclasses.dataclass
class CohortGroup:
    """A same-shape slice of a cohort: C clients padded to M samples.

    Arrays stay host-side (numpy): the batched engine moves each group to
    the device as one stack, while the loop reference converts one
    client's slice per dispatch — exactly the transfer pattern each
    execution model would have in production.

    ``data`` is a pytree of stacked (C, M, ...) arrays whose top level is
    a dict of named fields (the workload's schema — e.g. flat features,
    image tensors, or token sequences); everything below may be nested
    arbitrarily.  The engines only touch it through ``jax.tree`` ops, so
    no field name or rank is assumed anywhere."""
    cids: np.ndarray              # (C,) global client ids
    data: Pytree                  # stacked (C, M, ...) padded client data
    valid: np.ndarray             # (C, M) bool — real-sample mask
    m: np.ndarray                 # (C,) true sizes
    k: int                        # coreset budget (0 = full-set training)
    perms: np.ndarray             # (C, E, M) per-epoch sample permutations

    @property
    def n_clients(self) -> int:
        return len(self.cids)


@dataclasses.dataclass
class FleetRoundStats:
    """Per-client outcome of one fleet round, in cohort order.

    Dropped clients *stay in the stats* (their dispatch happened; only
    the update was lost), so trace accounting and scheduler observations
    remain aligned per-(client, dispatch) under fault injection — the
    ``dropped`` mask is what excluded them from aggregation."""
    cids: np.ndarray              # (N,)
    m: np.ndarray                 # (N,)
    budgets: np.ndarray           # (N,) effective budget (m if full-set)
    used_coreset: np.ndarray      # (N,) bool
    work: np.ndarray              # (N,) work units (samples visited)
    losses: np.ndarray            # (N,) final local train loss
    medoids: Dict[int, np.ndarray]  # cid -> (k,) selected sample indices
    dropped: np.ndarray = None    # (N,) bool — update lost mid-round
    corrupted: np.ndarray = None  # (N,) bool — Byzantine update merged


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _floor_pow4(n: int) -> int:
    """Largest power of 4 ≤ n — the coreset-budget quantizer.

    Rounding budgets *down* can never violate a deadline (any k ≤ bⁱ is
    deadline-safe); the coarse ×4 ladder keeps the number of distinct
    (M, k) cohort groups — and hence compiled programs and dispatches —
    small at fleet scale."""
    return 1 << (((max(int(n), 1).bit_length() - 1) // 2) * 2)


def _pad_rows(v: np.ndarray, m_pad: int) -> np.ndarray:
    """Pad axis 0 to ``m_pad`` by repeating the last row (finite values that
    keep feature scales sane; padded rows are masked everywhere)."""
    m = v.shape[0]
    if m == m_pad:
        return v
    return np.concatenate([v, np.repeat(v[-1:], m_pad - m, axis=0)])


def nominal_budgets(specs: Sequence[ClientSpec], deadline: float,
                    epochs: int, cost=None) -> Dict[int, int]:
    """Paper §4.2 budgets from nominal capabilities: bⁱ for clients that
    need a coreset under (τ, E), mⁱ (full set) for the rest.  The shared
    no-scheduler default of the fleet driver, sweep, and tests.  ``cost``
    (a ``repro.fed.cost.WorkloadCostModel`` or per-sample scalar; None =
    legacy) prices each sample-visit."""
    cm = resolve_cost(cost)
    return {s.cid: (cm.budget(s.m, s.c, deadline, epochs)
                    if cm.needs_coreset(s.m, s.c, deadline, epochs)
                    else s.m)
            for s in specs}


def _strip_weights(data: Pytree) -> Pytree:
    """Drop a caller-supplied top-level ``weights`` field (the engines
    derive loss weights from the padding mask)."""
    if isinstance(data, dict) and "weights" in data:
        return {kk: v for kk, v in data.items() if kk != "weights"}
    return data


def make_cohort_groups(clients_data: Sequence[Pytree],
                       cids: Sequence[int], budgets: Dict[int, int],
                       cfg: FleetConfig, round_seed: int = 0
                       ) -> List[CohortGroup]:
    """Bucket a cohort into same-shape groups.

    ``clients_data[cid]`` is any pytree of arrays sharing a leading sample
    axis (dict top level; see ``CohortGroup.data``) — the grouping logic
    is schema-generic.  ``budgets[cid]`` is the client's coreset budget;
    ``budgets[cid] >= m`` means full-set training.  Padded size M is the
    next power-of-two number of batches; coreset budgets are quantized
    down to a power of **four** (``_floor_pow4`` — the coarse ×4 ladder
    keeps the number of distinct compiled group programs small) so a
    group shares one static k (never exceeding any member's deadline
    budget).  Per-client epoch permutations are drawn from
    ``(cfg.seed, round_seed, cid)`` streams: the grouping is a pure
    performance choice and cannot change any client's arithmetic.
    """
    by_key: Dict[Tuple[int, int], List[int]] = {}
    for cid in cids:
        m = client_num_samples(clients_data[cid])
        m_pad = _next_pow2(-(-m // cfg.batch_size)) * cfg.batch_size
        b = int(budgets[cid])
        k = 0 if b >= m else _floor_pow4(b)
        by_key.setdefault((m_pad, k), []).append(cid)

    groups = []
    for (m_pad, k), members in sorted(by_key.items()):
        stacked = jax.tree.map(
            lambda *vs: np.stack([_pad_rows(np.asarray(v), m_pad)
                                  for v in vs]),
            *[_strip_weights(clients_data[cid]) for cid in members])
        ms = np.array([client_num_samples(clients_data[cid])
                       for cid in members])
        valid = np.arange(m_pad)[None, :] < ms[:, None]
        base = np.tile(np.arange(m_pad), (cfg.epochs, 1))
        perms = np.stack([
            np.random.default_rng(
                np.random.SeedSequence((cfg.seed, round_seed, cid))
            ).permuted(base, axis=1)
            for cid in members]).astype(np.int32)
        groups.append(CohortGroup(
            cids=np.array(members), data=stacked,
            valid=valid, m=ms, k=k, perms=perms))
    return groups


class FleetEngine:
    """Holds the jitted cohort programs (compiled once per group shape).

    ``run_group(..., batched=True)`` executes all C clients of a group as
    **one jitted per-group round program**: the straggler path
    (grad features → distance stack → fused-Δ-sweep k-medoids → epoch-0
    SGD → coreset gather → E−1 coreset epochs) compiles into a single
    XLA dispatch with ``donate_argnums`` on the broadcast parameter stack
    and the group data (the pre-fusion engine issued six dispatches per
    group with host round-trips between them).  ``batched=False`` is the
    status-quo per-client Python loop: the same mini-batch steps, feature
    pass, and masked k-medoids solve, but dispatched one client at a time
    with one jitted call per training step — the
    ``LocalTrainer.run_epochs`` execution model.  Identical arithmetic,
    so results match; only the dispatch structure differs.

    ``dispatch_count`` counts top-level jitted program invocations —
    exactly one per group on the fused path, one per jitted step on the
    loop path — through the single ``count_dispatch`` accounting point
    shared with ``ShardedFleetEngine``, so batched and sharded runs of
    the same cohort report identical counts (asserted in the workload
    conformance matrix).  The benchmark's dispatches-per-group breakdown
    and the single-dispatch regression test read it.
    """

    def __init__(self, model, cfg: FleetConfig):
        self.model = model
        self.cfg = cfg
        self.dispatch_count = 0

        def sgd_step(p, data, w, ix):
            """One mini-batch SGD step for one client."""
            batch = dict(jax.tree.map(lambda v: v[ix], data))
            batch["weights"] = w[ix]
            (loss, _), g = jax.value_and_grad(
                model.loss, has_aux=True)(p, batch)
            p = jax.tree.map(lambda a, b: a - cfg.lr * b, p, g)
            return p, loss

        def sgd_scan(params, data, w, idx):
            """One client: scan the step over idx (T, B) batches."""
            def step(p, ix):
                return sgd_step(p, data, w, ix)
            params, losses = jax.lax.scan(step, params, idx)
            return params, losses[-1]

        def core_step(p, cdata, cw):
            """One weighted full-batch epoch on one client's coreset."""
            batch = dict(cdata, weights=cw)
            (loss, _), g = jax.value_and_grad(
                model.loss, has_aux=True)(p, batch)
            p = jax.tree.map(lambda a, b: a - cfg.lr * b, p, g)
            return p, loss

        def core_scan(params, cdata, cw, n_steps_arr):
            """One client: E−1 weighted full-batch epochs on its coreset."""
            def step(p, _):
                return core_step(p, cdata, cw)
            params, losses = jax.lax.scan(step, params, n_steps_arr)
            return params, losses[-1]

        # raw per-client programs — the fused group bodies re-vmap these
        # (and the sharded engine wraps the same bodies in shard_map) so
        # all three execution modes share one copy of the arithmetic
        self._sgd_scan = sgd_scan
        self._core_scan = core_scan
        # fused per-group round programs, compiled per (k, data treedef) —
        # the treedef key is what lets schema-diverse workloads (images,
        # token sequences, nested field trees) share one engine instance
        self._group_programs: Dict[Tuple[int, Any], Any] = {}
        # fused selection-only programs (benchmark A/B + dispatch tests)
        self._select_programs: Dict[Tuple[int, Any], Any] = {}
        # standalone batched feature pass: first stage of the pre-fusion
        # dispatch chain, kept as the selection benchmark's baseline
        self._feats = jax.jit(jax.vmap(
            lambda p, d: model.grad_features(p, d), in_axes=(None, 0)))
        # per-client loop reference programs (one dispatch per step)
        self._sgd_step1 = jax.jit(sgd_step)
        self._core_step1 = jax.jit(core_step)
        self._feats1 = jax.jit(model.grad_features)

    # -- dispatch accounting + program-cache observability ----------------

    def count_dispatch(self, n: int = 1) -> None:
        """THE dispatch accounting point: every top-level jitted program
        invocation on any engine (batched, sharded, loop) goes through
        here, so counts are comparable across execution modes."""
        self.dispatch_count += n
        get_recorder().metrics.counter("fleet.dispatches").inc(n)

    def _cached_program(self, cache: Dict, key, build, kind: str):
        """Program-cache lookup with hit/miss counters per cache kind."""
        fn = cache.get(key)
        if fn is None:
            fn = build()
            cache[key] = fn
            get_recorder().metrics.counter(
                f"program_cache.{kind}.miss").inc()
        else:
            get_recorder().metrics.counter(f"program_cache.{kind}.hit").inc()
        return fn

    @contextmanager
    def _dispatch_span(self, name: str, program, **attrs):
        """Span around one top-level program invocation, stamping whether
        this call compiled (the jit cache grew) so first-call compile
        time is split from steady-state dispatch time in reports."""
        obs = get_recorder()
        if not obs.enabled:
            yield
            return
        size_fn = getattr(program, "_cache_size", None)
        before = size_fn() if callable(size_fn) else -1
        with obs.span(name, **attrs) as sp:
            yield
            if before >= 0:
                grew = size_fn() > before
                sp.attrs["compile"] = grew
                if grew:
                    obs.metrics.counter("program_cache.compiles").inc()
                    if before > 0:
                        obs.metrics.counter("program_cache.recompiles").inc()

    # -- fused group programs ---------------------------------------------

    def _make_group_body(self, k: int):
        """One cohort group's full round as a single traced body.

        ``k == 0``: E epochs of mini-batch SGD.  ``k > 0``: the Alg. 1
        straggler path — features at round-start params, fused coreset
        selection, one full-set epoch, E−1 weighted coreset epochs.
        Signature (k == 0): ``body(params, p0, data, w, idx)``;
        (k > 0): ``body(params, p0, data, w, valid, idx1, steps)``; both
        return ``(params (C, ...), losses (C,), medoid indices or
        None)``.  ``p0`` is the pre-broadcast (C, ...) parameter stack —
        passed in (rather than built inside) so the jitted wrapper can
        donate its buffers to the same-shaped output stack.  The sharded
        engine wraps this exact body in ``shard_map`` (per-device client
        lanes + psum aggregation), which is what keeps the loop / batched
        / sharded parity contract a single copy of the arithmetic.
        """
        cfg = self.cfg
        model = self.model
        vm_sgd = jax.vmap(self._sgd_scan)
        vm_core = jax.vmap(self._core_scan)
        vm_feats = jax.vmap(lambda p, d: model.grad_features(p, d),
                            in_axes=(None, 0))
        vm_gather = jax.vmap(lambda v, ix: v[ix])

        if k == 0:
            def body(params, p0, data, w, idx):
                p, losses = vm_sgd(p0, data, w, idx)
                return p, losses, None
            return body

        def body(params, p0, data, w, valid, idx1, steps):
            feats = vm_feats(params, data)                 # (C, M, F)
            coreset = build_coreset_batched(
                feats, valid, k, use_kernel=cfg.use_kernel,
                max_sweeps=cfg.max_sweeps,
                distance_free=cfg.distance_free,
                materialize_below=cfg.materialize_below)
            p, _ = vm_sgd(p0, data, w, idx1)
            cdata = jax.tree.map(
                lambda v: vm_gather(v, coreset.indices), data)  # (C, k, ...)
            p, losses = vm_core(p, cdata, coreset.weights, steps)
            return p, losses, coreset.indices
        return body

    @staticmethod
    def _donate_argnums() -> Tuple[int, ...]:
        """Donate (p0, data): the broadcast parameter stack is reused for
        the same-shaped output stack and the group data dies with the
        program.  CPU has no donation support (it would only warn), so
        only accelerators opt in."""
        return (1, 2) if jax.default_backend() != "cpu" else ()

    def _group_program(self, k: int, data_treedef):
        def build():
            return jax.jit(self._make_group_body(k),
                           donate_argnums=self._donate_argnums())
        return self._cached_program(self._group_programs, (k, data_treedef),
                                    build, "group")

    def _selection_program(self, k: int, data_treedef):
        """Selection phase only (features → distances → k-medoids) as one
        jitted dispatch — the benchmark's fused measurement unit."""
        def build():
            cfg = self.cfg
            vm_feats = jax.vmap(
                lambda p, d: self.model.grad_features(p, d),
                in_axes=(None, 0))

            def select(params, data, valid):
                feats = vm_feats(params, data)
                return build_coreset_batched(
                    feats, valid, k, use_kernel=cfg.use_kernel,
                    max_sweeps=cfg.max_sweeps,
                    distance_free=cfg.distance_free,
                    materialize_below=cfg.materialize_below)
            return jax.jit(select)
        return self._cached_program(self._select_programs, (k, data_treedef),
                                    build, "select")

    def select_group_coresets(self, params: Pytree, group: CohortGroup,
                              fused: bool = True):
        """Run one straggler group's selection phase; returns
        (``Coreset`` of stacked fields, dispatches issued).

        ``fused=True`` is the fast path: one jitted program (distance-free
        by default — no (C, M, M) intermediate).  ``fused=False`` replays
        the pre-fusion dispatch chain — a jitted feature pass, a jitted
        pairwise program (diagonal fix-up folded in via ``zero_diag``;
        the eager ``D * (1 − eye)`` epilogue it replaced allocated a
        second (C, M, M) tensor), and a jitted legacy-sweep k-medoids
        solve, with the host walking results between them — as the
        selection benchmark's A/B baseline.
        """
        if group.k == 0:
            raise ValueError("group has no selection phase (k == 0)")
        cfg = self.cfg
        data = jax.tree.map(jnp.asarray, group.data)
        valid = jnp.asarray(group.valid)
        obs = get_recorder()
        if fused:
            program = self._selection_program(group.k,
                                              jax.tree.structure(data))
            self.count_dispatch()
            with self._dispatch_span("selection", program, k=group.k,
                                     n_clients=group.n_clients, fused=True):
                coreset = program(params, data, valid)
            return coreset, 1
        from repro.core.coreset import Coreset
        from repro.core.kmedoids import kmedoids_batched
        from repro.kernels.ops import pairwise_l2_batched
        with obs.span("grad_features", k=group.k):
            feats = self._feats(params, data)              # dispatch 1
        with obs.span("distances", k=group.k):
            # zero_diag folds the self-distance fix-up into the jitted
            # pairwise program — the eager `D * (1 - eye)` epilogue it
            # replaces allocated a second (C, M, M) tensor per group
            D = pairwise_l2_batched(feats, squared=False,  # dispatch 2
                                    use_kernel=False, zero_diag=True)
        with obs.span("selection", k=group.k, fused=False):
            res = kmedoids_batched(D, valid, group.k,      # dispatch 3
                                   max_sweeps=cfg.max_sweeps,
                                   use_kernel=False, legacy_sweep=True)
        self.count_dispatch(3)
        return Coreset(indices=res.medoids,
                       weights=res.weights.astype(jnp.float32),
                       objective=res.objective,
                       assignment=res.assignment), 3

    # -- helpers ----------------------------------------------------------

    def _broadcast_params(self, params: Pytree, c: int) -> Pytree:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (c,) + x.shape), params)

    def _batch_indices(self, group: CohortGroup, epochs: slice, lanes
                       ) -> jnp.ndarray:
        """(C, T, B) minibatch index tensor of the program lanes
        (``_lanes``) for the given epoch range."""
        sel = group.perms[lanes, epochs]                   # (C, e, M)
        c, e, m_pad = sel.shape
        b = self.cfg.batch_size
        return jnp.asarray(sel.reshape(c, e * (m_pad // b), b))

    # -- group execution --------------------------------------------------

    def _run_group_stacked(self, params: Pytree, group: CohortGroup
                           ) -> Tuple[Pytree, jnp.ndarray,
                                      Optional[jnp.ndarray]]:
        """Run a group as ONE jitted dispatch; returns (params (C,...),
        losses, medoid indices or None)."""
        # asarray never changes the treedef, so the program cache can be
        # consulted before staging — letting the dispatch span charge the
        # host-side transfers to the phase they belong to
        program = self._group_program(group.k,
                                      jax.tree.structure(group.data))
        self.count_dispatch()
        name = "local_sgd" if group.k == 0 else "coreset_group"
        c = group.n_clients
        with self._dispatch_span(name, program, k=group.k, n_clients=c):
            p, losses, meds = program(*self._group_args(params, group))
            if losses.shape[0] != c:        # a lone client's second lane
                p = jax.tree.map(lambda x: x[:c], p)
                losses = losses[:c]
                meds = None if meds is None else meds[:c]
        return p, losses, meds

    @staticmethod
    def _lanes(group: CohortGroup):
        """Index of the group rows that become program lanes: all of them
        (a view), or a lone coreset client repeated to two.  With one
        lane, vmap leaves the plain SmallCNN gradient over the k-sample
        coreset batch, and the TPU compiler's code generation for it grows
        faster than k: 17 s at k = 256, 82 s at k = 512 for that gradient
        alone, over 700 s for the whole k = 1024 group program (compiled
        for a described v5e).  With two lanes the lane axis stays in the
        convolutions, which compile in seconds.  The extra lane is sliced
        off the outputs."""
        return [0, 0] if group.n_clients == 1 and group.k else slice(None)

    def _group_args(self, params: Pytree, group: CohortGroup) -> Tuple:
        """Device arguments of the group program, one device transfer per
        array.  ``k == 0``: E epochs of minibatch SGD.  ``k > 0``: the
        Alg. 1 straggler path — features at round-start params, fused
        coreset selection, one full-set epoch, E−1 coreset epochs — all
        inside the one program."""
        lanes = self._lanes(group)
        data = jax.tree.map(lambda v: jnp.asarray(v[lanes]), group.data)
        valid = group.valid[lanes]                              # (C, M)
        c = valid.shape[0]
        w = jnp.asarray(valid.astype(np.float32))
        p0 = self._broadcast_params(params, c)
        if group.k == 0:
            idx = self._batch_indices(group, slice(None), lanes)
            return params, p0, data, w, idx
        idx1 = self._batch_indices(group, slice(0, 1), lanes)
        steps = jnp.zeros((c, max(self.cfg.epochs - 1, 1)))
        return params, p0, data, w, jnp.asarray(valid), idx1, steps

    def stage_group(self, params: Pytree, group: CohortGroup
                    ) -> Tuple[Any, Tuple]:
        """(jitted program, device arguments) that ``run_group`` would
        dispatch for ``group`` — for checks that read the compiled
        program (``program.lower(*args).compile().as_text()``) or the
        arguments' placement."""
        program = self._group_program(group.k,
                                      jax.tree.structure(group.data))
        return program, self._group_args(params, group)

    def _run_client_loop(self, params: Pytree, group: CohortGroup, c: int
                         ) -> Tuple[Pytree, float, Optional[np.ndarray]]:
        """Status-quo execution of one client: per-batch jitted dispatches
        (the ``LocalTrainer.run_epochs`` model), identical arithmetic to
        the vmapped lane."""
        cfg = self.cfg
        data = jax.tree.map(lambda v: jnp.asarray(v[c]), group.data)
        w = jnp.asarray(group.valid[c].astype(np.float32))
        m_pad = group.valid.shape[1]
        idx = group.perms[c].reshape(cfg.epochs,
                                     m_pad // cfg.batch_size,
                                     cfg.batch_size)

        def run_epoch(p, e):
            loss = 0.0
            for t in range(idx.shape[1]):
                p, loss = self._sgd_step1(p, data, w, jnp.asarray(idx[e, t]))
            self.count_dispatch(idx.shape[1])   # one jitted call per step
            return p, loss

        if group.k == 0:
            p, loss = params, 0.0
            for e in range(cfg.epochs):
                p, loss = run_epoch(p, e)
            return p, float(loss), None

        feats = self._feats1(params, data)
        self.count_dispatch()
        coreset = build_coreset_batched(
            feats[None], jnp.asarray(group.valid[c:c + 1]), group.k,
            use_kernel=cfg.use_kernel, max_sweeps=cfg.max_sweeps,
            distance_free=cfg.distance_free,
            materialize_below=cfg.materialize_below)
        p, _ = run_epoch(params, 0)
        med = np.asarray(coreset.indices[0])
        mix = jnp.asarray(med)
        cdata = jax.tree.map(lambda v: v[mix], data)
        cw = coreset.weights[0]
        loss = 0.0
        for _ in range(max(cfg.epochs - 1, 1)):
            p, loss = self._core_step1(p, cdata, cw)
        self.count_dispatch(max(cfg.epochs - 1, 1))
        return p, float(loss), med

    def run_group(self, params: Pytree, group: CohortGroup,
                  batched: bool = True) -> Tuple[Pytree, np.ndarray,
                                                 Optional[np.ndarray]]:
        if batched:
            # losses and medoids stay on the device: converting here would
            # wait for this group before the next one is dispatched (the
            # round driver converts in its gather span)
            return self._run_group_stacked(params, group)
        # the per-client Python loop the batched engine replaces
        ps, losses, meds = [], [], []
        name = "local_sgd" if group.k == 0 else "coreset_group"
        with get_recorder().span(name, k=group.k,
                                 n_clients=group.n_clients, mode="loop"):
            for c in range(group.n_clients):
                p, loss, med = self._run_client_loop(params, group, c)
                ps.append(p)
                losses.append(loss)
                if med is not None:
                    meds.append(med)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *ps)
        return (stacked, np.array(losses),
                np.stack(meds) if meds else None)


def weighted_param_sum(stacked: Pytree, weights) -> Pytree:
    """Σ_c w_c · p_c over a (C, ...) parameter stack — the host-side
    analogue of the sharded engine's ``weighted_psum_sum`` (one
    tensordot per leaf, no per-client loop).  The sync round mean and
    the async fleet engine's merge rules are both linear combinations of
    client stacks, so this is the one reduction they share."""
    ws = jnp.asarray(weights, jnp.float32)
    return jax.tree.map(
        lambda x: jnp.tensordot(ws, x.astype(jnp.float32), axes=(0, 0)),
        stacked)


def _aggregate_groups(partials: List[Tuple[Pytree, np.ndarray]],
                      fallback: Pytree) -> Pytree:
    """Weighted mean over all cohort clients: Σ_g Σ_c w·p / Σ w.

    ``partials`` holds per-group (stacked client params, per-client
    weights).  Group-partial sums keep the reduction order independent of
    engine choice (batched and loop produce identical stacks).  An empty
    cohort — or one whose aggregation weights sum to zero — contributes
    nothing: the round is a no-op and ``fallback`` (the round-start
    params) is returned unchanged.
    """
    total = sum(float(w.sum()) for _, w in partials)
    if not partials or total <= 0.0:
        return fallback
    acc = None
    for stacked, w in partials:
        part = weighted_param_sum(stacked, w)
        acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
    return jax.tree.map(lambda x: x / total, acc)


def _cat(parts: List[np.ndarray], dtype) -> np.ndarray:
    return (np.concatenate(parts).astype(dtype) if parts
            else np.zeros(0, dtype))


def run_fleet_round(engine: FleetEngine, params: Pytree,
                    clients_data: Sequence[Pytree],
                    cids: Sequence[int], budgets: Dict[int, int],
                    round_seed: int = 0, batched: bool = True,
                    groups: Optional[List[CohortGroup]] = None,
                    mode: Optional[str] = None,
                    aggregator: str = "weighted_mean",
                    faults: Optional[FaultTrace] = None,
                    dispatch_ordinals: Optional[Dict[int, int]] = None
                    ) -> Tuple[Pytree, FleetRoundStats]:
    """Execute one cohort round; returns (aggregated params, stats).

    ``mode`` selects the execution model: ``"batched"`` (vmapped cohort
    programs), ``"loop"`` (per-client reference), or ``"sharded"``
    (``engine`` must be a ``repro.fed.fleet.sharded.ShardedFleetEngine``;
    groups run data-parallel over the mesh's client axis with a psum-tree
    aggregation).  ``mode=None`` derives batched/loop from the legacy
    ``batched`` flag.  An empty cohort yields the round-start params and
    zero-length stats.  ``groups`` lets callers reuse a prebuilt cohort
    grouping (it is a pure function of (clients_data, cids, budgets, cfg,
    round_seed)).

    ``aggregator`` selects the server combine rule ("weighted_mean" or a
    robust method — the robust rules consume the engines' per-client
    parameter stacks).  ``faults`` injects mid-round dropout (the update
    is computed, then its aggregation weight is zeroed / its lane is
    excluded) and Byzantine corruption of the stack lanes;
    ``dispatch_ordinals`` maps cid → that client's dispatch ordinal for
    the per-(client, dispatch) fault draws (defaults to 0 — drivers pass
    the ``DispatchTraceIndexer`` cursors)."""
    cfg = engine.cfg
    obs = get_recorder()
    if mode is None:
        mode = "batched" if batched else "loop"
    if mode not in ("batched", "loop", "sharded"):
        raise ValueError(f"unknown fleet execution mode {mode!r}")
    if aggregator != "weighted_mean" and aggregator not in ROBUST_METHODS:
        raise ValueError(f"unknown fleet aggregator {aggregator!r} "
                         f"(expected weighted_mean or one of "
                         f"{ROBUST_METHODS})")
    if groups is None:
        with obs.span("cohort_build", n_clients=len(cids)):
            groups = make_cohort_groups(clients_data, cids, budgets, cfg,
                                        round_seed)
    has_dropout = faults is not None and faults.profile.has_dropout
    has_corruption = faults is not None and faults.profile.has_corruption
    # the weighted-mean-of-honest-lanes path never materializes stacks
    # (sharded keeps its psum); robust rules and corruption need them
    needs_stack = aggregator != "weighted_mean" or has_corruption
    ordinals = dispatch_ordinals or {}
    partials = []
    stacks: List[Tuple[Pytree, np.ndarray, np.ndarray]] = []
    all_cids, all_m, all_b, all_core, all_work, all_loss, all_meds = \
        [], [], [], [], [], [], []
    all_drop, all_corrupt = [], []
    medoids: Dict[int, np.ndarray] = {}
    for g in groups:
        w = (g.m.astype(np.float64) if cfg.weight_by_samples
             else np.ones(g.n_clients))
        ords = np.array([ordinals.get(int(c), 0) for c in g.cids], np.int64)
        drop = (np.array([faults.dropped(int(c), int(o))
                          for c, o in zip(g.cids, ords)], bool)
                if has_dropout else np.zeros(g.n_clients, bool))
        w_eff = np.where(drop, 0.0, w)
        if mode == "sharded":
            part, wsum, losses, meds, stack = engine.run_group_sharded(
                params, g, w_eff)
            if not needs_stack:
                partials.append((part, wsum))
        else:
            stack, losses, meds = engine.run_group(
                params, g, batched=(mode == "batched"))
            if not needs_stack:
                partials.append((stack, w_eff))
        corrupt = np.zeros(g.n_clients, bool)
        if needs_stack:
            if has_corruption:
                stack, _ = corrupt_stacked(stack, params, g.cids, ords,
                                           faults)
                corrupt = faults.byzantine[np.asarray(g.cids, np.int64)]
            stacks.append((stack, w_eff, drop))
        all_cids.append(g.cids)
        all_m.append(g.m)
        eff_b = g.m if g.k == 0 else np.full(g.n_clients, g.k)
        all_b.append(eff_b)
        all_core.append(np.full(g.n_clients, g.k > 0))
        work = (cfg.epochs * g.m if g.k == 0
                else g.m + (cfg.epochs - 1) * g.k * np.ones(g.n_clients,
                                                            np.int64))
        all_work.append(work)
        all_loss.append(losses)     # device arrays stay lazy until after
        all_meds.append(meds)       # every group has been dispatched
        all_drop.append(drop)
        all_corrupt.append(corrupt & ~drop)   # a lost update corrupts nothing
    with obs.span("aggregate", n_groups=len(groups),
                  aggregator=aggregator):
        if obs.enabled:             # bytes entering the reduction
            src = partials if not needs_stack else stacks
            obs.metrics.counter("aggregate.bytes").inc(sum(
                int(leaf.nbytes) for entry in src
                for leaf in jax.tree.leaves(entry[0])))
        if needs_stack:
            trees, wlist = [], []
            for stack, w_eff, drop in stacks:
                keep = np.nonzero(~drop)[0]
                if keep.size == 0:
                    continue
                trees.append(jax.tree.map(
                    lambda x: jnp.asarray(x)[keep], stack))
                wlist.append(np.asarray(w_eff, np.float64)[keep])
            if not trees:
                new_params = params
            else:
                stacked_all = (trees[0] if len(trees) == 1 else jax.tree.map(
                    lambda *xs: jnp.concatenate(xs), *trees))
                new_params = robust_combine(stacked_all, aggregator,
                                            weights=np.concatenate(wlist),
                                            base=params)
        elif mode == "sharded":
            new_params = engine.combine_group_sums(partials, fallback=params)
        else:
            new_params = _aggregate_groups(partials, fallback=params)
    with obs.span("gather", n_groups=len(groups)):
        all_loss = [np.asarray(ls) for ls in all_loss]
        for g, meds in zip(groups, all_meds):
            if meds is not None:
                meds = np.asarray(meds)
                for cid, med in zip(g.cids, meds):
                    medoids[int(cid)] = med
    stats = FleetRoundStats(
        cids=_cat(all_cids, np.int64), m=_cat(all_m, np.int64),
        budgets=_cat(all_b, np.int64),
        used_coreset=_cat(all_core, bool),
        work=_cat(all_work, np.float64),
        losses=_cat(all_loss, np.float64), medoids=medoids,
        dropped=_cat(all_drop, bool), corrupted=_cat(all_corrupt, bool))
    return new_params, stats


def run_fleet(model, clients_data: Sequence[Pytree],
              specs: Sequence[ClientSpec], cfg: FleetConfig, rounds: int,
              scheduler=None, trace: Optional[TraceConfig] = None,
              deadline: Optional[float] = None,
              straggler_pct: float = 30.0,
              test_data: Optional[Dict] = None, init_params=None,
              engine: str = "batched", eval_every: int = 1,
              faults=None, checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 0, resume: bool = False,
              verbose: bool = False) -> Dict[str, Any]:
    """Multi-round fleet driver: adaptive cohorts + batched execution.

    ``model`` is anything exposing the FLModel interface — including a
    ``repro.fed.fleet.workloads.FleetWorkload``, which is how the CNN and
    char-LM workloads run here; ``clients_data`` is the matching pytree-
    of-arrays client list (see ``CohortGroup.data``).
    ``engine`` ∈ {"batched", "loop", "sharded"}: the vmapped cohort
    programs, the per-client reference loop, or the mesh-sharded engine
    (``repro.fed.fleet.sharded``) that runs each cohort group
    data-parallel over every available device.  "sharded" silently falls
    back to "batched" on a single-device host — the two are numerically
    interchangeable.  ``scheduler`` (an ``AdaptiveParticipation`` or
    anything with its ``select`` / ``budget`` / ``observe`` /
    ``record_round`` protocol) picks each round's cohort and conditions
    coreset budgets on *observed* capability; without one, every client
    participates with nominal-capability budgets.  ``trace`` perturbs
    per-round realized durations (slowdown episodes + jitter) exactly as
    the async runtime does, which is what gives the scheduler something
    to learn.  The trace is indexed per-(client, dispatch): each client
    carries its own dispatch counter, so a client absent for some rounds
    samples exactly the entries the sync server and async event loop
    would sample for the same dispatch order.

    ``faults`` (a ``repro.fed.fleet.faults`` profile name / FaultProfile
    / None) injects dropout, churn, and Byzantine corruption as seeded
    deterministic axes; ``cfg.aggregator`` picks the (robust) combine
    rule.  Dropped clients stay in the round's trace accounting — their
    dispatch happened, only the update was lost — so fault injection
    never shifts another client's per-(client, dispatch) draws.

    ``checkpoint_dir`` + ``checkpoint_every`` save server state (params,
    round index, scheduler EWMA + RNG state, dispatch cursors, history)
    every N rounds via ``repro.checkpoint``; ``resume=True`` restores
    the latest checkpoint and continues **byte-identically** with the
    uninterrupted run — everything else (capability trace, fault draws,
    cohort grouping) is a pure function of the seed and regenerates.
    """
    if engine not in ("batched", "loop", "sharded"):
        raise ValueError(f"unknown fleet engine {engine!r} "
                         f"(expected batched | loop | sharded)")
    mode = engine
    if engine == "sharded":
        from repro.fed.fleet.sharded import ShardedFleetEngine, client_mesh
        if len(jax.devices()) > 1:
            eng = ShardedFleetEngine(model, cfg, mesh=client_mesh())
        else:       # one device: sharding is pure overhead
            eng, mode = FleetEngine(model, cfg), "batched"
    else:
        eng = FleetEngine(model, cfg)
    params = (init_params if init_params is not None
              else model.init(jax.random.PRNGKey(cfg.seed)))
    cost = resolve_cost(cfg.cost)
    if deadline is None:
        deadline = straggler_deadline(specs, cfg.epochs, straggler_pct,
                                      cost)
    cap_trace = CapabilityTrace(trace) if trace is not None else None
    eval_fn = make_eval_fn(model, test_data, 512) if test_data else None
    # per-client dispatch cursors: the CapabilityTrace is defined per
    # (client, dispatch), exactly like repro.fed.server / repro.fed.events
    tracei = DispatchTraceIndexer(len(specs), cap_trace)
    profile = get_fault_profile(faults)
    ftrace = (FaultTrace(profile, len(specs), seed=cfg.seed)
              if profile is not None and profile.any_faults() else None)
    obs = active_recorder(verbose)
    obs.run_meta(runtime="fleet", engine=mode, requested_engine=engine,
                 n_clients=len(specs), rounds=rounds,
                 deadline=float(deadline), seed=cfg.seed,
                 aggregator=cfg.aggregator,
                 faults=(profile.name if profile is not None else "none"),
                 n_devices=len(jax.devices()))

    history: List[RoundRecord] = []
    cohort_sizes: List[int] = []
    start_round = 0
    if resume and checkpoint_dir is not None:
        ck_params, ck_round = load_server_state(checkpoint_dir, like=params)
        if ck_params is not None and ck_round >= 0:
            meta = load_server_meta(checkpoint_dir) or {}
            params = ck_params
            start_round = ck_round + 1
            history = [RoundRecord(**h) for h in meta.get("history", [])]
            cohort_sizes = [int(c) for c in meta.get("cohort_sizes", [])]
            if "dispatch_counts" in meta:
                tracei.counts[:] = np.asarray(meta["dispatch_counts"],
                                              np.int64)
            if scheduler is not None and meta.get("scheduler") is not None \
                    and hasattr(scheduler, "load_state_dict"):
                scheduler.load_state_dict(meta["scheduler"])
            obs.event("resume", round=start_round,
                      checkpoint_dir=checkpoint_dir)
    for r in range(start_round, rounds):
        t0 = time.perf_counter()
        rspan = obs.span_begin("round", round=r)
        with obs.span("cohort_select", round=r):
            if scheduler is not None:
                cohort = [int(c) for c in scheduler.select()]
            else:
                cohort = list(range(len(specs)))
            if ftrace is not None and ftrace.profile.has_churn:
                mask, joins, leaves = ftrace.churn_step(r)
                cohort = [cid for cid in cohort if mask[cid]]
                if obs.enabled:
                    obs.metrics.counter("faults.churn_joins").inc(joins)
                    obs.metrics.counter("faults.churn_leaves").inc(leaves)
                    obs.metrics.gauge("faults.n_present").set(
                        int(mask.sum()))
                    obs.metrics.gauge("faults.participation_frac").set(
                        len(cohort) / max(len(specs), 1))
            if scheduler is not None:
                budgets = {cid: scheduler.budget(cid, deadline, cfg.epochs)
                           for cid in cohort}
            else:
                budgets = nominal_budgets(specs, deadline, cfg.epochs, cost)
        # fault draws key on each client's dispatch ordinal — snapshot
        # the cursors before trace_account advances them below
        ordinals = {int(c): int(tracei.counts[c]) for c in cohort}
        params, stats = run_fleet_round(eng, params, clients_data, cohort,
                                        budgets, round_seed=r, mode=mode,
                                        aggregator=cfg.aggregator,
                                        faults=ftrace,
                                        dispatch_ordinals=ordinals)
        n_fault_dropped = int(stats.dropped.sum())
        n_corrupted = int(stats.corrupted.sum())
        if obs.enabled and ftrace is not None:
            if n_fault_dropped:
                obs.metrics.counter("faults.dropped_updates").inc(
                    n_fault_dropped)
            if n_corrupted:
                obs.metrics.counter("faults.corrupted_updates").inc(
                    n_corrupted)
        durations = []
        with obs.span("trace_account", round=r):
            for cid, work in zip(stats.cids, stats.work):
                s = specs[cid]
                k = tracei.begin(cid)
                # stats.work counts sample-visits; the cost model prices
                # them into duration seconds and scheduler work units
                dur = cost.duration(work, tracei.capability(s, k))
                dur *= tracei.jitter(s, k)
                durations.append(dur)
                obs.metrics.histogram("client_busy_s").observe(dur)
                if scheduler is not None:
                    scheduler.observe(int(cid), float(cost.work_units(work)),
                                      float(dur))
        train_loss = (float(np.mean(stats.losses)) if stats.losses.size
                      else float("nan"))
        if scheduler is not None:
            scheduler.record_round(train_loss)
        # honest τ accounting (mirrors ClientResult.deadline_violated):
        # a budget clamped to 1 or a slowdown episode can still overrun τ
        n_violations = int(sum(d > deadline * (1.0 + 1e-9)
                               for d in durations))
        obs.metrics.counter("deadline_violations").inc(n_violations)
        rec = RoundRecord(
            round=r,
            sim_round_time=float(np.max(durations)) if durations else 0.0,
            client_times=[float(d) for d in durations],
            n_participants=len(cohort), n_dropped=n_fault_dropped,
            n_coreset=int(stats.used_coreset.sum()), train_loss=train_loss,
            n_violations=n_violations)
        if eval_fn and (r % eval_every == 0 or r == rounds - 1):
            with obs.span("eval", round=r):
                rec.test_acc, rec.test_loss = eval_fn(params)
        history.append(rec)
        cohort_sizes.append(len(cohort))
        obs.span_end(rspan)
        obs.event("round", runtime="fleet", engine=mode,
                  label=f"fleet/{mode}", round=r,
                  n_participants=len(cohort), n_dropped=n_fault_dropped,
                  n_corrupted=n_corrupted,
                  n_coreset=rec.n_coreset, n_violations=n_violations,
                  sim_round_time=float(rec.sim_round_time),
                  wall_time_s=time.perf_counter() - t0,
                  train_loss=float(train_loss),
                  test_acc=float(rec.test_acc),
                  test_loss=float(rec.test_loss))
        obs.event("clients", round=r,
                  cids=[int(c) for c in stats.cids],
                  durations=[float(d) for d in durations],
                  violated=[bool(d > deadline * (1.0 + 1e-9))
                            for d in durations])
        if checkpoint_dir is not None and checkpoint_every > 0 \
                and (r + 1) % checkpoint_every == 0:
            with obs.span("checkpoint", round=r):
                extra = {
                    "kind": "fleet",
                    "history": [dataclasses.asdict(h) for h in history],
                    "cohort_sizes": cohort_sizes,
                    "dispatch_counts": tracei.counts.tolist(),
                }
                if scheduler is not None and hasattr(scheduler,
                                                     "state_dict"):
                    extra["scheduler"] = scheduler.state_dict()
                save_server_state(checkpoint_dir, r, params, extra=extra)

    return {
        "params": params,
        "history": history,
        "deadline": deadline,
        "engine": engine,          # requested
        "engine_mode": mode,       # executed (sharded may fall back)
        "n_devices": len(jax.devices()),
        "cohort_sizes": cohort_sizes,
        "aggregator": cfg.aggregator,
        "faults": profile.name if profile is not None else "none",
        "strategy": "fedcore_fleet",
    }
