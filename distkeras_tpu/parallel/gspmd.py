"""GSPMD engine: windowed async-SGD with compiler-partitioned tensor
parallelism.

The reference has no tensor parallelism at all (its only strategy is the
socket-parameter-server data parallelism of ``distkeras/trainers.py`` /
``distkeras/parameter_servers.py``); SURVEY.md §2 marks TP as the idiomatic
TPU stretch goal "via pjit param sharding".  This module is that goal: a
second engine with the *same* windowed commit semantics as
:class:`~distkeras_tpu.parallel.engine.WindowedEngine`, built the pjit way
instead of the shard_map way —

  * the mesh is 2-D ``(workers, model)``;
  * per-worker state carries its leading ``[num_workers]`` axis sharded over
    ``workers`` (data parallelism), and every large parameter leaf is
    *additionally* sharded over ``model`` (tensor parallelism) via
    ``with_sharding_constraint``;
  * there is no ``shard_map`` and no hand-placed collective for TP: the
    worker dimension is a ``vmap`` with an axis name (so the commit rules'
    ``psum`` still means "sum over workers"), and XLA's SPMD partitioner
    inserts the all-gathers/reduce-scatters implied by the param shardings.

Because partitioning is sharding-propagation rather than hand-written
collectives, any model works unmodified — TP needs no ``seq_axis``-style
model surgery.  The trade: communication placement is the compiler's choice,
so the shard_map engine remains the default for pure data parallelism.

``commit_schedule`` staleness simulation works here too (same per-step
masked-commit body as the shard_map engine).  Not supported: ``seq_shards``
ring attention, which is a hand-placed-collective design by nature — use
``WindowedEngine`` for sequence parallelism.

``fsdp=True`` additionally shards the *center variable* over the workers
axis (ZeRO-3 / gather-at-use: all-gather at the window-boundary pull,
reduce-scatter after the commit psum, both placed by the partitioner) —
the replicated parameter-server copy stops costing ``num_devices x`` HBM.
Composes with ``tp_shards`` (a leaf can shard over both axes) and is a pure
layout change: trajectories equal the data-parallel run within float
tolerance (reduction order may shift under partitioning — tests/test_fsdp.py).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.algorithms.base import UpdateRule
from distkeras_tpu.models.adapter import ModelAdapter
from distkeras_tpu.parallel.engine import (
    VWORKER_AXIS,
    TrainState,
    WindowedEngine,
    plan_workers,
)
from distkeras_tpu.parallel.mesh import WORKER_AXIS

__all__ = ["GSPMDEngine", "TP_AXIS", "default_tp_dim"]

TP_AXIS = "model"


def default_tp_dim(shape, tp_shards: int):
    """The ONE default tensor-parallel placement rule, shared by every
    engine that shards over a model axis (GSPMD default spec, pipeline
    staged-leaf tails): shard the LAST dim of any >=2-D leaf that splits
    evenly and is at least two lanes per shard; return its index or None.
    Any placement is *correct* under GSPMD — this default puts matmul
    output channels (Dense/Conv kernels, embeddings) on the model axis,
    Megatron column-parallel style."""
    if (
        tp_shards > 1
        and len(shape) >= 2
        and shape[-1] % tp_shards == 0
        and shape[-1] >= 2 * tp_shards
    ):
        return len(shape) - 1
    return None


class GSPMDEngine(WindowedEngine):
    """Drop-in engine with data x tensor parallelism over a (workers, model)
    mesh.  Same public surface as :class:`WindowedEngine` (``init_state``,
    ``run_epoch``, ``shard_batches``, ``average_workers``, ...)."""

    _regather_fn = None
    _slice_fn = None

    def __init__(
        self,
        adapter: ModelAdapter,
        loss,
        worker_optimizer,
        rule: UpdateRule,
        num_workers: Optional[int] = None,
        *,
        tp_shards: int = 1,
        fsdp: bool = False,
        spec_fn=None,
        metrics: Sequence = ("accuracy",),
        compute_dtype: Optional[Any] = None,
        sync_model_state: bool = True,
        commit_schedule: Optional[np.ndarray] = None,
        devices: Optional[Sequence] = None,
        remat: bool = False,
        unroll=1,
    ):
        devices = list(devices if devices is not None else jax.devices())
        self.tp_shards = int(tp_shards)
        # ZeRO-3-style center sharding: store the center variable sharded
        # over the *workers* axis instead of replicated (center-rule state is
        # NOT constrained — every shipped rule keeps only a scalar counter
        # there).  The partitioner materialises it with an
        # all-gather at the window-boundary pull and a reduce-scatter after
        # the commit psum — gather-at-use, the idiomatic TPU form of FSDP.
        # Per-worker local state is untouched (each worker's copy is distinct
        # by construction in this algorithm family — there is no redundancy
        # over the workers axis to eliminate there).
        self.fsdp = bool(fsdp)
        # Optional placement override: shape -> PartitionSpec, or None to
        # fall through to the default Megatron-style rule.  This is how
        # expert parallelism rides this engine (models/moe.expert_partition
        # puts the leading [num_experts] axis on the model mesh axis).
        self.spec_fn = spec_fn
        if len(devices) % self.tp_shards:
            raise ValueError(
                f"tp_shards={tp_shards} does not divide device count {len(devices)}"
            )
        worker_devices = len(devices) // self.tp_shards
        self.adapter = adapter
        self.rule = rule
        self.num_workers = num_workers or worker_devices
        # Same tiling policy as the shard_map engine: largest worker-axis
        # size that divides num_workers; extra logical workers ride as
        # leading-dim shards per device.
        worker_devices, virtual = plan_workers(self.num_workers, worker_devices)
        grid = np.array(devices[: worker_devices * self.tp_shards]).reshape(
            worker_devices, self.tp_shards
        )
        self.mesh = Mesh(grid, (WORKER_AXIS, TP_AXIS))
        self.axis = WORKER_AXIS
        self.seq_axis = None
        self.seq_shards = 1
        self.n_dev, self.virtual = worker_devices, virtual
        # The worker dimension is ONE vmap over all logical workers (XLA
        # splits it across the mesh axis by sharding propagation), so the
        # commit rules' psum reduces over just the vmap axis name.
        self.both_axes = (VWORKER_AXIS,)
        self._rep = NamedSharding(self.mesh, P())
        self._shard = NamedSharding(self.mesh, P(WORKER_AXIS))
        self._finish_init(
            loss, worker_optimizer, metrics, compute_dtype,
            sync_model_state, commit_schedule, remat, unroll,
        )

    # ------------------------------------------------------------- shardings
    def _tp_spec(self, shape, path=()) -> P:
        """Shape-based TP placement: shard the last dim of any >=2-D leaf that
        splits evenly across the model axis.  Any placement is *correct* under
        GSPMD (the partitioner inserts whatever collectives the placement
        implies); this default puts matmul output channels — Dense/Conv
        kernels, embeddings — on the model axis, Megatron column-parallel
        style.  ``spec_fn(shape, path)`` overrides leaf placement first;
        ``path`` is the tuple of pytree key names so rules can match specific
        params (a bare-shape rule cannot tell an expert stack from an
        attention-heads kernel that coincidentally leads with num_experts)."""
        if self.spec_fn is not None:
            spec = self.spec_fn(tuple(shape), path)
            if spec is not None:
                for dim, name in zip(shape, spec):
                    on_model = name == TP_AXIS or (
                        isinstance(name, tuple) and TP_AXIS in name
                    )
                    if on_model and dim % self.tp_shards:
                        raise ValueError(
                            f"spec_fn placed the model axis on a dim of size "
                            f"{dim}, not divisible by tp_shards={self.tp_shards} "
                            f"(leaf shape {tuple(shape)}, path {path})"
                        )
                return spec
        # tp_shards == 1: a size-1 model axis is a layout no-op, but naming it
        # would block _center_spec from giving that dim to the workers axis
        # under fsdp — leave every dim free instead.
        dim = default_tp_dim(tuple(shape), self.tp_shards)
        if dim is not None:
            return P(*([None] * dim), TP_AXIS)
        return P()

    @staticmethod
    def _key_names(path) -> tuple:
        return tuple(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path
        )

    def _center_spec(self, shape, path=()) -> P:
        """TP placement plus, under ``fsdp=True``, the workers axis on the
        largest still-free evenly-splitting dim — each device then stores
        ``1/n_dev`` of the center variable.  Leaves with no such dim stay
        replicated (correct either way; sharding is a layout choice)."""
        spec = list(self._tp_spec(shape, path))
        spec += [None] * (len(shape) - len(spec))
        taken = {
            n for entry in spec if entry is not None
            for n in (entry if isinstance(entry, tuple) else (entry,))
        }
        # A custom spec_fn may already have placed the workers axis (e.g. an
        # FSDP-style override); assigning it a second dim would be an invalid
        # PartitionSpec surfacing as an opaque partitioner error.
        if self.fsdp and self.n_dev > 1 and WORKER_AXIS not in taken:
            free = [
                d for d, name in enumerate(spec)
                if name is None and shape[d] % self.n_dev == 0
                and shape[d] >= 2 * self.n_dev
            ]
            if free:
                spec[max(free, key=lambda d: shape[d])] = WORKER_AXIS
        return P(*spec)

    def _constrain_center(self, tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, self._center_spec(x.shape, self._key_names(path)))
            ),
            tree,
        )

    def _constrain_worker(self, tree):
        """Per-worker trees ([num_workers, ...] leaves): workers axis on dim 0
        plus the TP spec of the per-worker shape."""

        def strip_workers(entry):
            # spec_fn may place WORKER_AXIS (FSDP-style override) — valid for
            # center leaves, but per-worker leaves already spend the workers
            # axis on their leading dim, so it must come out of the TP spec
            if entry == WORKER_AXIS:
                return None
            if isinstance(entry, tuple):
                rest = tuple(n for n in entry if n != WORKER_AXIS)
                return rest if rest else None
            return entry

        def one(path, x):
            if x.ndim >= 1 and x.shape[0] == self.num_workers:
                tp = self._tp_spec(x.shape[1:], self._key_names(path))
                spec = P(WORKER_AXIS, *(strip_workers(e) for e in tp))
            else:
                spec = P()
            return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(one, tree)

    # ------------------------------------------------------------------ init
    # state assembly is the base class recipe; this engine only redirects
    # the _constrain_center/_constrain_worker placement hooks (below)

    def _state_shardings(self, build_fn, params, model_state):
        # placement comes from the with_sharding_constraint calls inside
        # _assemble_state; let jit infer the outputs from those
        del build_fn, params, model_state
        return None

    # ------------------------------------------------------------------ epoch
    def _build_epoch_core(self, n_windows: int, window: int, do_commit: bool, xs_ndim: int = 5):
        """Un-jitted one-epoch function; the base class jits it directly
        (``_make_epoch_fn``) or scans it (``run_epochs``)."""
        vmapped = jax.vmap(
            self._window_fn(do_commit, window),
            in_axes=(None, None, 0, 0),
            out_axes=(0, 0, 0, 0, 0, 0) if self._dynamics else (0, 0, 0, 0, 0),
            axis_name=VWORKER_AXIS,
        )

        def epoch_fn(state: TrainState, xs, ys):
            xs = jnp.moveaxis(xs, 1, 0)  # scan over windows
            ys = jnp.moveaxis(ys, 1, 0)
            local = (state.local_params, state.opt_state, state.model_state,
                     state.rule_local, state.rng)

            def window_body(carry, wdata):
                center_params, center_rule, local = carry
                if self._dynamics:
                    centers_p, centers_r, local, loss, mets, dyn = vmapped(
                        center_params, center_rule, local, wdata
                    )
                else:
                    centers_p, centers_r, local, loss, mets = vmapped(
                        center_params, center_rule, local, wdata
                    )
                    dyn = ()
                # psum over the vmap axis makes every worker's center copy
                # identical; collapse the stacked dim and re-pin the TP
                # sharding so the scan carry stays partitioned.  The whole
                # local tuple is re-pinned: opt_state and rule_local carry
                # param-shaped leaves as large as the params themselves, and
                # an unconstrained carry would let the partitioner replicate
                # them across the model axis.
                center_params = self._constrain_center(
                    jax.tree.map(lambda x: x[0], centers_p)
                )
                center_rule = jax.tree.map(lambda x: x[0], centers_r)
                local = self._constrain_worker(local)
                return (center_params, center_rule, local), (loss, mets, dyn)

            # see the shard_map engine: unroll=True propagates to this loop
            (center_params, center_rule, local), (losses, mets, dyn) = lax.scan(
                window_body,
                (state.center_params, state.center_rule, local),
                (xs, ys), unroll=self.unroll is True,
            )
            local_params, opt_state, model_state, rule_local, rng = local
            # losses/mets carry a [n_windows, num_workers] leading block; the
            # mean over workers is a plain reduction (XLA all-reduces it).
            stats = {
                "loss": jnp.mean(losses, axis=1),
                "metrics": jnp.mean(mets, axis=1),
            }
            if self._dynamics:
                # the vmap already spans every logical worker: plain
                # reductions, no psum (the partitioner all-reduces them)
                dyn_global, dyn_worker = self._dyn_reduce(dyn)
                stats["dynamics"] = {**dyn_global, **dyn_worker}
            new_state = TrainState(
                center_params=center_params,
                center_rule=center_rule,
                local_params=local_params,
                opt_state=opt_state,
                model_state=model_state,
                rule_local=rule_local,
                rng=rng,
                epoch=state.epoch + 1,
            )
            return new_state, stats

        return epoch_fn

    def _make_stepwise_epoch_fn(self, n_steps: int, xs_ndim: int = 4):
        """Staleness simulation under TP: the same per-step masked-commit body
        as the shard_map engine, vmapped over all logical workers under jit."""
        vmapped = jax.vmap(
            self._step_fn(),
            in_axes=(None, None, 0, 0, 0, None, 0),
            out_axes=(0, 0, 0, 0, 0, 0) if self._dynamics else (0, 0, 0, 0, 0),
            axis_name=VWORKER_AXIS,
        )
        schedule_arr = jnp.asarray(self.commit_schedule, jnp.int32)

        def epoch_fn(state: TrainState, xs, ys):
            xs = jnp.moveaxis(xs, 1, 0)  # [n_steps, workers, batch, ...]
            ys = jnp.moveaxis(ys, 1, 0)
            local = (state.local_params, state.opt_state, state.model_state,
                     state.rule_local, state.rng)

            def step_body(carry, inp):
                t, batch = inp
                center_params, center_rule, local, since = carry
                if self._dynamics:
                    centers_p, centers_r, local, since, loss, dyn = vmapped(
                        center_params, center_rule, local, since, batch, t,
                        schedule_arr
                    )
                else:
                    centers_p, centers_r, local, since, loss = vmapped(
                        center_params, center_rule, local, since, batch, t,
                        schedule_arr
                    )
                    dyn = ()
                center_params = self._constrain_center(
                    jax.tree.map(lambda x: x[0], centers_p)
                )
                center_rule = jax.tree.map(lambda x: x[0], centers_r)
                local = self._constrain_worker(local)  # see windowed epoch fn
                return (center_params, center_rule, local, since), (loss, dyn)

            since0 = jnp.zeros((self.num_workers,), jnp.int32)
            (center_params, center_rule, local, _), (losses, dyn) = lax.scan(
                step_body,
                (state.center_params, state.center_rule, local, since0),
                (jnp.arange(n_steps), (xs, ys)), unroll=self.unroll,
            )
            local_params, opt_state, model_state, rule_local, rng = local
            new_state = TrainState(
                center_params=center_params,
                center_rule=center_rule,
                local_params=local_params,
                opt_state=opt_state,
                model_state=model_state,
                rule_local=rule_local,
                rng=rng,
                epoch=state.epoch + 1,
            )
            stats = {"loss": jnp.mean(losses, axis=1),
                     "metrics": jnp.zeros((0,))}
            if self._dynamics:
                dyn_global, dyn_worker = self._dyn_reduce(dyn)
                stats["dynamics"] = {**dyn_global, **dyn_worker}
            return new_state, stats

        return jax.jit(epoch_fn, donate_argnums=(0,))

    # ----------------------------------------------------------------- export
    def gather_center(self, state: TrainState):
        """Re-replicate the model-axis-sharded center leaves so every host
        process can ``np.asarray`` them (trainer finalisation, PS attach)."""
        # cached programs: a fresh jit wrapper per call would re-trace on
        # every checkpoint save / finalisation (per-call-closure trap)
        if self._regather_fn is None:
            self._regather_fn = jax.jit(lambda t: t, out_shardings=self._rep)
        with self.mesh:
            return self._regather_fn(state.center_params)

    def worker_slice(self, tree, index: int):
        # index rides along as a traced operand so one compiled program
        # serves every worker slot (a closed-over index would retrace per i)
        if self._slice_fn is None:
            self._slice_fn = jax.jit(
                lambda t, i: jax.tree.map(lambda x: x[i], t),
                out_shardings=self._rep,
            )
        with self.mesh:
            sliced = self._slice_fn(tree, index)
        return jax.tree.map(np.asarray, sliced)

    # --------------------------------------------------------------- sharding
    def _put_batches(self, xs: np.ndarray, ys: np.ndarray):
        # the base class's shard_batches records the spans around this
        sharding = NamedSharding(self.mesh, P(WORKER_AXIS))
        with self.mesh:
            return (
                jax.make_array_from_callback(xs.shape, sharding, lambda idx: xs[idx]),
                jax.make_array_from_callback(ys.shape, sharding, lambda idx: ys[idx]),
            )
