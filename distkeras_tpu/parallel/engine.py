"""The SPMD training engine: windowed local SGD + collective commits.

This module is the TPU-native replacement for the entire runtime half of the
reference — the Spark job (``distkeras/trainers.py :: DistributedTrainer.train``
shipping pickled Workers into executors), the worker training loop
(``distkeras/workers.py :: *.train``), and the socket parameter-server service
loop (``distkeras/parameter_servers.py :: SocketParameterServer.run``).

Design (SURVEY.md §7):
  * a *worker* is a logical training replica.  Workers tile onto hardware as
    ``num_workers = n_devices x virtual_per_device``: the device dimension is
    a ``shard_map`` over the ``workers`` mesh axis, the virtual dimension a
    ``vmap`` with its own collective axis name — the TPU form of the
    reference running more Spark tasks than machines;
  * the parameter-server center variable is *replicated* across the mesh;
  * one epoch is a single jitted program: ``lax.scan`` over commit windows,
    an inner ``lax.scan`` over local optimizer steps, and the rule's
    ``commit`` — a ``psum`` over ``(vmap axis, mesh axis)`` + replicated
    center update — at each window boundary.  The reference's per-window TCP
    pull/commit round-trip becomes one XLA collective over ICI;
  * asynchrony is *modeled*: the staleness-simulation mode gives each worker
    its own commit period (per-step masked commits), reproducing parameter-
    server race semantics deterministically (SURVEY.md §7 "hard parts").

Everything is static-shaped and trace-once; there is no per-step Python.
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import PartitionSpec as P

from distkeras_tpu import sanitizer as sanitizer_mod
from distkeras_tpu import telemetry
from distkeras_tpu.algorithms.base import CommitCtx, UpdateRule
from distkeras_tpu.telemetry import dynamics as dynamics_mod
from distkeras_tpu.models.adapter import ModelAdapter
from distkeras_tpu.ops import get_loss, get_metric, get_optimizer
from distkeras_tpu.parallel.mesh import (
    SEQ_AXIS,
    make_mesh,
    make_mesh_grid,
    replicated_sharding,
    worker_sharding,
)
from distkeras_tpu.utils.compat import shard_map
from distkeras_tpu.utils.pytree import tree_cast, tree_where

__all__ = ["TrainState", "WindowedEngine", "plan_workers",
           "zero_shard_dim", "zero_gather_tree"]

VWORKER_AXIS = "vworkers"


def zero_shard_dim(shape, shards: int) -> int:
    """The ONE ZeRO shard-placement policy: the largest dim of ``shape``
    that splits evenly over ``shards`` with >=2 rows per shard, or -1 to
    stay replicated.  Shared by the seq-axis fsdp (WindowedEngine) and the
    stage-axis fsdp (PipelineEngine) so the two engines — and checkpoints
    resumed across them — can never disagree on where a leaf shards."""
    free = [d for d, s in enumerate(shape)
            if s % shards == 0 and s >= 2 * shards]
    return max(free, key=lambda d: shape[d]) if free else -1


def init_on_mesh(adapter, rng, sample_input, mesh, seq_axis: str):
    """Init a seq-axis-aware model INSIDE the mesh program with the
    sample's sequence (last) axis sharded — ring-attention blocks use
    ``lax.axis_index``/``ppermute`` during their forward pass, so init
    cannot run outside ``shard_map``.  The one recipe both the windowed
    and the pipeline engine's sp paths use."""
    sample = jnp.asarray(sample_input)
    spec = P(*([None] * (sample.ndim - 1)), seq_axis)
    return shard_map(
        lambda smp: adapter.init(rng, smp),
        mesh=mesh, in_specs=(spec,), out_specs=P(), check_vma=False,
    )(sample)


def zero_gather_tree(dims, tree, axis: str):
    """Inside shard_map: materialise full leaves from their ``axis`` shards
    (gather-at-use; ``dims`` is the int-tree ``zero_shard_dim`` produced).
    ``all_gather``'s transpose is ``psum_scatter``, so differentiating
    through this hands each shard its own summed-gradient block."""
    return jax.tree.map(
        lambda d, x: x if d < 0 else lax.all_gather(x, axis, axis=d, tiled=True),
        dims, tree,
    )


def plan_workers(num_workers: int, n_devices: int) -> tuple[int, int]:
    """Tile ``num_workers`` logical workers onto hardware: returns
    ``(devices_used, virtual_per_device)`` with ``d * v == num_workers``,
    maximising the device dimension (collectives over ICI beat vmap serial
    execution whenever chips are available)."""
    d = min(num_workers, n_devices)
    while num_workers % d:
        d -= 1
    return d, num_workers // d


@struct.dataclass
class TrainState:
    """Full training state.  ``center_*`` leaves are replicated over the mesh;
    all other leaves carry a leading ``[num_workers]`` axis sharded over it."""

    center_params: Any
    center_rule: Any
    local_params: Any
    opt_state: Any
    model_state: Any
    rule_local: Any
    rng: jnp.ndarray
    epoch: jnp.ndarray  # replicated scalar


class WindowedEngine:
    """Builds and owns the jitted epoch functions for one (model, rule) pair."""

    # Mesh axes the engine's shard_map programs are *manual* over (hand-
    # placed collectives).  Empty = all axes (jax.shard_map's default).  The
    # pipeline engine under tensor parallelism sets this to (workers, stages)
    # so its third mesh axis stays *auto*: XLA's SPMD partitioner partitions
    # the stage matmuls from the state's model-axis shardings while the
    # ppermute pipeline and commit psums stay hand-written.
    _manual_axes: frozenset = frozenset()
    # seq-axis ZeRO center sharding — off unless __init__ enables it, and
    # class-level defaults keep subclasses with their own __init__ (GSPMD,
    # pipeline) on the replicated-center path.  ``fsdp`` is the public
    # "center is sharded" flag every engine exposes (GSPMD sets its own);
    # ``_fsdp_seq`` is the internal discriminator the SHARED code paths
    # (_window_fn/_step_fn/_center_in_specs) gate on, because GSPMD's fsdp
    # is partitioner-placed over the workers axis and must NOT trigger the
    # hand-placed seq-axis gathers.
    _fsdp_seq: bool = False
    _center_fsdp_dims = None
    _fsdp_regather = None
    _avg_fn = None
    _final_ms_fn = None
    fsdp: bool = False

    def __init__(
        self,
        adapter: ModelAdapter,
        loss,
        worker_optimizer,
        rule: UpdateRule,
        num_workers: Optional[int] = None,
        *,
        metrics: Sequence = ("accuracy",),
        compute_dtype: Optional[Any] = None,
        commit_schedule: Optional[np.ndarray] = None,
        sync_model_state: bool = True,
        mesh=None,
        seq_shards: int = 1,
        fsdp: bool = False,
        remat: bool = False,
        unroll=1,
    ):
        self.adapter = adapter
        self.rule = rule
        self.seq_shards = int(seq_shards)
        # ZeRO-style center sharding over the SEQ axis (fsdp x sp in one
        # mesh): on the (workers, seq) grid the center variable is otherwise
        # replicated seq_shards x — pure redundancy, since the seq axis
        # exists for activations.  With fsdp=True each seq-row device stores
        # 1/seq_shards of every evenly-splitting center leaf; the window
        # commit all-gathers the shards at use and re-slices after (the
        # hand-placed-collective form of the GSPMD engine's gather-at-use
        # fsdp — trajectory-identical to the replicated layout).  fsdp
        # without sequence parallelism is the GSPMD engine's job.
        self._fsdp_seq = bool(fsdp)
        self.fsdp = self._fsdp_seq
        if self._fsdp_seq and self.seq_shards <= 1:
            raise ValueError(
                "fsdp=True on WindowedEngine shards the center over the seq "
                "axis and needs seq_shards>1; for fsdp without sequence "
                "parallelism use the GSPMD engine (trainers route it there)"
            )
        n_devices = jax.device_count() if mesh is None else mesh.devices.size
        if self.seq_shards > 1:
            # combined data x sequence parallelism: 2-D mesh, worker state on
            # axis 0, sequence blocks on axis 1 (requires a seq-axis-aware
            # model, e.g. TransformerClassifier(seq_axis='seq'))
            worker_devices = n_devices // self.seq_shards
            self.num_workers = num_workers or worker_devices
            self.n_dev, self.virtual = plan_workers(self.num_workers, worker_devices)
            self.mesh = make_mesh_grid(self.n_dev, self.seq_shards)
            self.seq_axis = SEQ_AXIS
        else:
            self.num_workers = num_workers or n_devices
            self.n_dev, self.virtual = plan_workers(self.num_workers, n_devices)
            self.mesh = (
                mesh
                if (mesh is not None and mesh.devices.size == self.n_dev)
                else make_mesh(self.n_dev)
            )
            self.seq_axis = None
        self.axis = self.mesh.axis_names[0]
        self.both_axes = (VWORKER_AXIS, self.axis)
        self._rep = replicated_sharding(self.mesh)
        self._shard = worker_sharding(self.mesh)
        self._finish_init(
            loss, worker_optimizer, metrics, compute_dtype,
            sync_model_state, commit_schedule, remat, unroll,
        )

    def _finish_init(
        self, loss, worker_optimizer, metrics, compute_dtype,
        sync_model_state, commit_schedule, remat=False, unroll=1,
    ):
        """Mesh-independent setup shared with subclasses (GSPMDEngine):
        optimizer/loss/metric resolution and commit-schedule validation.
        Requires ``self.adapter`` and ``self.num_workers`` to be set."""
        self.optimizer = get_optimizer(worker_optimizer)
        self.loss_fn = get_loss(loss, from_logits=self.adapter.outputs_logits)
        if getattr(self.adapter, "per_token_labels", False):
            from distkeras_tpu.ops.metrics import per_token_metric_names

            metrics = per_token_metric_names(metrics)
        self.metric_fns = [get_metric(m) for m in metrics]
        self.compute_dtype = compute_dtype
        # Rematerialise the forward pass on the backward (jax.checkpoint):
        # trades FLOPs for activation memory — the HBM lever for deep models
        # (ResNet-scale+) whose per-window activations outgrow the chip.
        self.remat = bool(remat)
        # Unroll factor for the per-step scans (int, or True = full unroll).
        # On TPU a small unroll lets XLA pipeline across steps; on the CPU
        # test mesh full unroll avoids XLA:CPU's pathological compile times
        # for conv bodies inside while-loops (measured: a 4-step scanned
        # CIFARCNN step compiles ~75s as a loop, ~5s fully unrolled).
        self.unroll = unroll
        self.sync_model_state = sync_model_state
        # Per-worker commit periods (staleness simulation).  None => uniform
        # synchronous windows, one collective per window.
        self.commit_schedule = (
            None if commit_schedule is None else np.asarray(commit_schedule, np.int32)
        )
        if self.commit_schedule is not None and len(self.commit_schedule) != self.num_workers:
            raise ValueError(
                f"commit_schedule has {len(self.commit_schedule)} entries for "
                f"{self.num_workers} workers"
            )
        # Training-dynamics stats (telemetry.dynamics).  Resolved ONCE at
        # engine build so the trace-time branches in the window/step bodies
        # are stable for the life of the cached epoch programs; with the
        # flag off not a single extra op is traced — the jitted program is
        # identical to a build without the feature (pinned in
        # tests/test_dynamics.py).
        self._dynamics = dynamics_mod.enabled()
        # Runtime sanitizer (distkeras_tpu.sanitizer), same convention: one
        # cached bool read at build, zero per-dispatch cost when off and
        # byte-identical lowered programs either way (the guards are pure
        # host-side wrappers — pinned in tests/test_sanitizer.py).
        self._sanitize = sanitizer_mod.enabled()
        self._epoch_fns = {}
        #: filled by :meth:`run_epoch_streaming`: source/transfer timing and
        #: the link-bound verdict for the last streamed epoch
        self.last_stream_report = None
        self._link_warned = False

    # ------------------------------------------------------------------ init
    def init_state(self, rng: jax.Array, sample_input) -> TrainState:
        if self.seq_axis is not None:
            params, model_state = init_on_mesh(
                self.adapter, rng, sample_input, self.mesh, self.seq_axis
            )
        else:
            params, model_state = self.adapter.init(rng, sample_input)
        self._record_fsdp_dims(params)

        def _build(params, model_state):
            return self._assemble_state(rng, params, model_state)

        shardings = self._state_shardings(_build, params, model_state)
        with self.mesh:
            return jax.jit(_build, out_shardings=shardings)(params, model_state)

    # ---------------------------------------------- fsdp (seq-axis ZeRO center)
    def _record_fsdp_dims(self, params):
        """Choose, per center leaf, which dim the seq axis shards: the
        largest dim that splits evenly with >=2 rows per shard, or -1 to
        stay replicated (a tree of ints — ``None`` is not a pytree leaf).
        Recorded from the real param shapes at ``init_state`` /
        ``state_from_center``; every later spec/gather/slice reads this one
        table so block-shape recomputation can never pick a different dim."""
        if not self._fsdp_seq:
            return
        self._center_fsdp_dims = jax.tree.map(
            lambda x: zero_shard_dim(np.shape(x), self.seq_shards), params
        )
        if all(d < 0 for d in jax.tree.leaves(self._center_fsdp_dims)):
            # fsdp=True with nothing shardable would silently store the
            # full center replicated — exactly the HBM redundancy the flag
            # exists to remove.  Say so instead of OOMing mysteriously.
            import warnings

            warnings.warn(
                f"fsdp=True: no center leaf has a dim divisible by "
                f"seq_shards={self.seq_shards} (with >=2 rows per shard); "
                "the center stays fully replicated", stacklevel=3,
            )

    def _fsdp_leaf_spec(self, d) -> P:
        return P() if d < 0 else P(*([None] * d), SEQ_AXIS)

    def _fsdp_center_specs(self):
        if self._center_fsdp_dims is None:
            raise RuntimeError(
                "fsdp=True center placement is recorded from the param "
                "shapes; build the state via init_state/state_from_center "
                "before running epochs"
            )
        return jax.tree.map(self._fsdp_leaf_spec, self._center_fsdp_dims)

    def _fsdp_gather(self, tree):
        """Inside shard_map: materialise the full center from its seq-axis
        shards (gather-at-use, the window-commit analogue of ZeRO-3's
        pre-layer all-gather)."""
        if not self._fsdp_seq:
            return tree
        return zero_gather_tree(self._center_fsdp_dims, tree, SEQ_AXIS)

    def _fsdp_shard(self, tree):
        """Inside shard_map: keep only this seq-row's block of the updated
        center (the commit math ran full-size; storage goes back to
        1/seq_shards)."""
        if not self._fsdp_seq:
            return tree
        idx = lax.axis_index(SEQ_AXIS)

        def one(d, x):
            if d < 0:
                return x
            block = x.shape[d] // self.seq_shards
            return lax.dynamic_slice_in_dim(x, idx * block, block, axis=d)

        return jax.tree.map(one, self._center_fsdp_dims, tree)

    def _constrain_center(self, tree):
        """Placement hook for center leaves inside state assembly — identity
        unless seq-axis fsdp is on (then each leaf pins to its recorded
        seq-shard layout); the GSPMD engine overrides it with TP/fsdp
        sharding constraints."""
        if not self._fsdp_seq:
            return tree
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda d, x: lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, self._fsdp_leaf_spec(d))),
            self._center_fsdp_dims, tree,
        )

    def _constrain_worker(self, tree):
        """Placement hook for per-worker ``[num_workers, ...]`` leaves —
        identity here; GSPMD adds workers-axis + TP constraints."""
        return tree

    def _assemble_state(self, rng, params, model_state) -> TrainState:
        """Pure state assembly (jittable): tile per-worker leaves, init the
        optimizer and rule states.  The single recipe for every engine —
        subclasses redirect placement via the ``_constrain_*`` hooks."""
        n = self.num_workers
        params = self._constrain_center(params)
        center_rule = self.rule.init_center_state()
        rule_local = self.rule.init_local_state(params)
        tile = lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t
        )
        local_params = self._constrain_worker(tile(params))
        opt_state = self._constrain_worker(jax.vmap(self.optimizer.init)(local_params))
        rngs = jax.random.split(jax.random.fold_in(rng, 1), n)
        return TrainState(
            center_params=params,
            center_rule=center_rule,
            local_params=local_params,
            opt_state=opt_state,
            model_state=self._constrain_worker(tile(model_state)),
            rule_local=self._constrain_worker(tile(rule_local)),
            rng=rngs,
            epoch=jnp.zeros((), jnp.int32),
        )

    def state_from_center(
        self, rng: jax.Array, center_params, center_rule, model_state, epoch
    ) -> TrainState:
        """Elastic resume: rebuild full training state around a restored
        center variable at THIS engine's worker count (which may differ from
        the count the checkpoint was written at).

        Local replicas adopt the center — the semantics of the reference's
        worker retry, which reconnects to the PS and pulls
        (``distkeras/workers.py``; SURVEY.md §5.3 "a retried worker
        reconnects and keeps training") — optimizer and rule local state
        re-initialise, and the center-side rule state (commit counters) and
        epoch survive.  Exact same-count resume should use the bitwise
        checkpoint restore instead (``CheckpointManager.restore(like=...)``).
        """
        # host trees go straight into the jitted build: jit places the args
        # under their constrained shardings in one transfer (an eager
        # asarray here would first materialise the full center replicated
        # on one device — the spike fsdp exists to avoid)
        self._record_fsdp_dims(center_params)

        def _build(params, ms):
            st = self._assemble_state(rng, params, ms)
            return st.replace(
                center_rule=center_rule,
                epoch=jnp.asarray(epoch, jnp.int32),
            )

        shardings = self._state_shardings(_build, center_params, model_state)
        with self.mesh:
            return jax.jit(_build, out_shardings=shardings)(center_params, model_state)

    def _state_shardings(self, build_fn, params, model_state):
        """out_shardings for the initial state: center leaves replicated,
        per-worker leaves split on the worker axis.  The pipeline engine
        overrides this with per-leaf shardings (stage-stacked leaves shard
        over the stages axis too)."""
        del build_fn, params, model_state
        center = self._rep
        if self._fsdp_seq:
            from jax.sharding import NamedSharding

            center = jax.tree.map(
                lambda d: NamedSharding(self.mesh, self._fsdp_leaf_spec(d)),
                self._center_fsdp_dims,
            )
        return TrainState(
            center_params=center,
            center_rule=self._rep,
            local_params=self._shard,
            opt_state=self._shard,
            model_state=self._shard,
            rule_local=self._shard,
            rng=self._shard,
            epoch=self._rep,
        )

    # ------------------------------------------------------------- local step
    def _local_step(self, carry, batch):
        params, opt_state, model_state, rng = carry
        rng, sub = jax.random.split(rng)
        x, y = batch

        def compute_loss(p, ms):
            if self.compute_dtype is not None:
                p = tree_cast(p, self.compute_dtype)
                x_c = x.astype(self.compute_dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
            else:
                x_c = x
            out, new_ms = self.adapter.apply(p, ms, x_c, training=True, rng=sub)
            out = out.astype(jnp.float32)
            loss = self.loss_fn(out, y) + self.adapter.aux_loss(new_ms)
            mets = (
                jnp.stack([m(out, y) for m in self.metric_fns])
                if self.metric_fns
                else jnp.zeros((0,), jnp.float32)
            )
            return loss, (new_ms, mets)

        if self.remat:
            compute_loss = jax.checkpoint(compute_loss)
        (loss, (model_state, mets)), grads = jax.value_and_grad(compute_loss, has_aux=True)(
            params, model_state
        )
        grads = self._sync_grads(grads)
        if self._dynamics:
            # per-step health leaves ride the scan ys; reduced to per-window
            # scalars in the window body (no per-step collective)
            dstep = {
                "grad_sq": dynamics_mod.tree_sq_norm(grads),
                "grad_nonfinite": dynamics_mod.tree_nonfinite_count(grads),
            }
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if self._dynamics:
            return (params, opt_state, model_state, rng), (loss, mets, dstep)
        return (params, opt_state, model_state, rng), (loss, mets)

    def _sync_grads(self, grads):
        """Cross-model-axis gradient sync hook (worker-axis reduction is the
        commit rules' job, not this one's).

        Sequence parallelism: each shard's backward pass yields seq_shards x
        (its partial gradient): the loss is computed replicated on every shard
        and psum's transpose inside shard_map is itself a psum, so every
        replica's cotangent lands on each shard.  pmean over the axis =
        psum(partials)/shards = the exact total gradient (verified against
        the unsharded model in tests/test_sequence_parallel.py).

        The pipeline engine overrides this with its stage-axis sync
        (:meth:`distkeras_tpu.parallel.pipeline.PipelineEngine._sync_grads`).
        """
        if self.seq_axis is not None:
            grads = jax.tree.map(lambda g: lax.pmean(g, self.seq_axis), grads)
        return grads

    def _local_in_spec(self):
        """shard_map spec (or per-leaf spec tree) for the per-worker ``local``
        5-tuple.  A single ``P(workers)`` prefix here; the pipeline engine
        returns full per-leaf trees (stage-stacked leaves shard over the
        stages axis too)."""
        return P(self.axis)

    def _center_in_specs(self):
        """shard_map specs (or per-leaf spec trees) for
        ``(center_params, center_rule)`` — replicated here (per-leaf
        seq-shard specs under fsdp); the pipeline engine shards
        stage-stacked center leaves over the stages axis."""
        if self._fsdp_seq:
            return self._fsdp_center_specs(), P()
        return P(), P()

    def _make_ctx(self, mask, steps_in_window) -> CommitCtx:
        """Commit context whose psum totals over BOTH the vmap (virtual
        worker) axis and the mesh (device) axis."""
        psum = lambda t: jax.tree.map(lambda v: lax.psum(v, self.both_axes), t)
        return CommitCtx(
            psum=psum,
            mask=jnp.asarray(mask),
            steps_in_window=jnp.asarray(steps_in_window, jnp.float32),
            num_workers=self.num_workers,
        )

    def _sync_model_state(self, ctx: CommitCtx, model_state):
        if not self.sync_model_state or not jax.tree.leaves(model_state):
            return model_state
        mean = jax.tree.map(lambda x: ctx.psum(x) / self.num_workers, model_state)
        return tree_where(ctx.mask, mean, model_state)

    @property
    def _per_token(self) -> bool:
        """Model emits per-token outputs with per-token labels (LMs —
        ``ModelAdapter.per_token_labels``): labels shard over the seq axis
        with the tokens, and per-shard loss/metric values are block-local
        (not replicated) so epoch stats need a seq-axis mean."""
        return bool(self.adapter.per_token_labels)

    def _reduce_seq_stats(self, *stats):
        """Average block-local stats over the seq axis (no-op when outputs
        are already replicated across it — the classifier's psum-pooled
        logits)."""
        if self.seq_axis is not None and self._per_token:
            stats = tuple(lax.pmean(s, self.seq_axis) for s in stats)
        return stats if len(stats) > 1 else stats[0]

    def _data_specs(self, xs_ndim: int):
        """Partition specs for (xs, ys): worker axis leading; for sequence
        parallelism the sequence (last) axis of xs also shards — and so do
        the labels when the model declares them per-token (language models:
        labels mirror the token array, each shard keeps its block's
        targets)."""
        if self.seq_axis is not None:
            xs_spec = P(self.axis, *([None] * (xs_ndim - 2)), self.seq_axis)
            return xs_spec, (xs_spec if self._per_token else P(self.axis))
        return P(self.axis), P(self.axis)

    def _window_fn(self, do_commit: bool, window: int):
        """Build the one-worker window body: inner scan of local steps, then
        commit.  Runs under ``vmap(axis_name=VWORKER_AXIS)`` — inside
        ``shard_map`` here, or under plain jit in the GSPMD engine."""
        rule = self.rule

        def per_worker_window(center_params, center_rule, local, wdata):
            local_params, opt_state, model_state, rule_local, rng = local
            if self._dynamics:
                (local_params, opt_state, model_state, rng), (losses, mets, dstep) = lax.scan(
                    self._local_step, (local_params, opt_state, model_state, rng),
                    wdata, unroll=self.unroll,
                )
            else:
                (local_params, opt_state, model_state, rng), (losses, mets) = lax.scan(
                    self._local_step, (local_params, opt_state, model_state, rng),
                    wdata, unroll=self.unroll,
                )
            dyn = None
            if self._dynamics:
                # pre-commit snapshot: worker<->center drift and the rule's
                # own staleness clocks, measured before the commit rewrites
                # them.  All worker-local scalars — the end-of-epoch psum
                # reduces them with the loss (no extra collective here).
                full_center = self._fsdp_gather(center_params)
                dyn = {
                    "grad_sq": jnp.sum(dstep["grad_sq"]),
                    "nonfinite_grads": jnp.sum(dstep["grad_nonfinite"]),
                    "nonfinite_params": dynamics_mod.tree_nonfinite_count(local_params),
                    "divergence_sq": dynamics_mod.tree_sq_dist(local_params, full_center),
                    "staleness": jnp.asarray(float(window), jnp.float32),
                    "update_sq": jnp.zeros((), jnp.float32),
                }
                dyn.update(rule.dynamics(
                    self._make_ctx(do_commit, float(window)),
                    local_params, full_center, rule_local, center_rule,
                ))
            if do_commit:
                # seq-axis fsdp: the commit is the one place the full center
                # is needed — gather the shards at use, run the rule's math
                # unchanged (so trajectories match the replicated layout
                # exactly), keep only this row's block after
                center_params = (full_center if self._dynamics
                                 else self._fsdp_gather(center_params))
                center_before = center_params
                ctx = self._make_ctx(True, float(window))
                res = rule.commit(ctx, local_params, center_params, rule_local, center_rule)
                local_params, center_params = res.local_params, res.center_params
                rule_local, center_rule = res.local_state, res.center_state
                if self._dynamics:
                    dyn["update_sq"] = dynamics_mod.tree_sq_dist(
                        center_params, center_before)
                center_params = self._fsdp_shard(center_params)
                model_state = self._sync_model_state(ctx, model_state)
            # Window stats stay worker-local here; one psum at the end of the
            # epoch reduces them (a per-window collective in the scan body
            # would serialise every window on the slowest device).
            loss_mean = jnp.mean(losses)
            mets_mean = jnp.mean(mets, axis=0)
            local = (local_params, opt_state, model_state, rule_local, rng)
            if self._dynamics:
                return center_params, center_rule, local, loss_mean, mets_mean, dyn
            return center_params, center_rule, local, loss_mean, mets_mean

        return per_worker_window

    def _dyn_reduce(self, dyn, psum_axis=None):
        """Reduce stacked dynamics leaves ``[T, v]`` (T windows or steps,
        v workers in this trace) to the epoch-stats layout: *global* series
        — grad norm, non-finite counts, center update norm, each ``[T]`` —
        and *per-worker* series (divergence, staleness, rule extras), each
        ``[T, v]``.  ``psum_axis`` totals the global leaves across mesh
        devices (the windowed engine calls inside shard_map); the GSPMD
        engine's vmap already spans every worker and passes None."""
        total = (lambda a: jnp.sum(a, axis=1)) if psum_axis is None else (
            lambda a: lax.psum(jnp.sum(a, axis=1), psum_axis))
        dyn = dict(dyn)
        dyn_global = {
            "grad_norm": jnp.sqrt(total(dyn.pop("grad_sq"))),
            # the committed center is identical across workers (psum'd):
            # any column of the stacked leaf is the global value
            "update_norm": jnp.sqrt(dyn.pop("update_sq")[:, 0]),
            "nonfinite_grads": total(dyn.pop("nonfinite_grads")),
            "nonfinite_params": total(dyn.pop("nonfinite_params")),
        }
        dyn_worker = dict(dyn)
        dyn_worker["divergence"] = jnp.sqrt(dyn_worker.pop("divergence_sq"))
        return dyn_global, dyn_worker

    # ------------------------------------------------------- epoch (windowed)
    def _build_epoch_core(self, n_windows: int, window: int, do_commit: bool, xs_ndim: int = 5):
        """The un-jitted one-epoch function ``(state, xs, ys) -> (state, stats)``.

        ``_make_epoch_fn`` jits it directly; ``_make_multi_epoch_fn`` scans it
        so a whole training run is ONE dispatch (see :meth:`run_epochs`)."""
        vmapped = jax.vmap(
            self._window_fn(do_commit, window),
            in_axes=(None, None, 0, 0),
            out_axes=(0, 0, 0, 0, 0, 0) if self._dynamics else (0, 0, 0, 0, 0),
            axis_name=VWORKER_AXIS,
        )

        def worker_fn(center_params, center_rule, local, xs, ys):
            # block shapes: local leaves [v, ...]; xs [v, n_windows, window, batch, ...]
            xs = jnp.moveaxis(xs, 1, 0)  # scan over windows
            ys = jnp.moveaxis(ys, 1, 0)

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
                # psum over both axes makes every virtual worker's center
                # identical; collapse the vmap dim.
                center_params = jax.tree.map(lambda x: x[0], centers_p)
                center_rule = jax.tree.map(lambda x: x[0], centers_r)
                return (center_params, center_rule, local), (loss, mets, dyn)

            # full unroll propagates to the window loop too (unroll=True is
            # the XLA:CPU compile-time escape hatch; ints stay step-only)
            (center_params, center_rule, local), (losses, mets, dyn) = lax.scan(
                window_body, (center_params, center_rule, local), (xs, ys),
                unroll=self.unroll is True,
            )
            # losses: [n_windows, v]; mets: [n_windows, v, M].  Single
            # end-of-epoch reduction over virtual workers + mesh devices.
            losses = lax.psum(jnp.sum(losses, axis=1), self.axis) / self.num_workers
            mets = lax.psum(jnp.sum(mets, axis=1), self.axis) / self.num_workers
            losses, mets = self._reduce_seq_stats(losses, mets)
            if self._dynamics:
                dyn_global, dyn_worker = self._dyn_reduce(dyn, self.axis)
                return (center_params, center_rule, local, losses, mets,
                        dyn_global, dyn_worker)
            return center_params, center_rule, local, losses, mets

        xs_spec, ys_spec = self._data_specs(xs_ndim)
        center_spec, center_rule_spec = self._center_in_specs()
        local_spec = self._local_in_spec()
        # dynamics outputs: globals replicated (post-psum), per-worker series
        # concatenate over the worker axis — [n_windows, num_workers] global
        dyn_out_specs = (P(), P(None, self.axis)) if self._dynamics else ()
        mapped = shard_map(
            worker_fn,
            mesh=self.mesh,
            in_specs=(center_spec, center_rule_spec, local_spec, xs_spec, ys_spec),
            out_specs=(center_spec, center_rule_spec, local_spec, P(), P())
            + dyn_out_specs,
            check_vma=False,
            **({"axis_names": self._manual_axes} if self._manual_axes else {}),
        )

        def epoch_fn(state: TrainState, xs, ys):
            local = (state.local_params, state.opt_state, state.model_state,
                     state.rule_local, state.rng)
            if self._dynamics:
                (center_params, center_rule, local, losses, mets,
                 dyn_global, dyn_worker) = mapped(
                    state.center_params, state.center_rule, local, xs, ys
                )
            else:
                center_params, center_rule, local, losses, mets = mapped(
                    state.center_params, state.center_rule, local, xs, ys
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
            stats = {"loss": losses, "metrics": mets}
            if self._dynamics:
                stats["dynamics"] = {**dyn_global, **dyn_worker}
            return new_state, stats

        return epoch_fn

    def _make_epoch_fn(self, n_windows: int, window: int, do_commit: bool, xs_ndim: int = 5):
        return jax.jit(
            self._build_epoch_core(n_windows, window, do_commit, xs_ndim),
            donate_argnums=(0,),
        )

    def _make_multi_epoch_fn(
        self, n_windows: int, window: int, do_commit: bool, xs_ndim: int,
        n_epochs: int, shuffle_seed: Optional[int],
    ):
        """N epochs as ONE jitted program: ``lax.scan`` over the epoch core.

        Dispatching per epoch pays a fixed host/runtime cost per call; the
        device executes the epochs back-to-back, so the gap between them is
        pure dispatch (its share of the headline config's epoch is not
        measured on today's code).  Scanning the epoch body amortises that
        cost over the whole run.

        With ``shuffle_seed`` set, each epoch draws a fresh ON-DEVICE global
        permutation of the flattened step stream (workers x windows x window
        x batch), keyed by the epoch counter so the permutation stream
        survives checkpoint/resume.  The reference reshuffles by Spark
        ``shuffle()`` between epochs (SURVEY.md §3.1) — a full cluster
        round-trip; here it is a single on-device gather.  One deliberate
        difference from the host-side reshuffle (``data.epoch_arrays``): the
        permutation acts on the padded stream, so when the dataset does not
        divide workers x batch x window evenly, the *same* wrap-pad
        duplicates recur every epoch (the host path re-draws them).  Pad a
        divisible dataset — or use ``Trainer.train``'s host loop — when that
        bias matters.
        """
        epoch_core = self._build_epoch_core(n_windows, window, do_commit, xs_ndim)

        def multi_fn(state: TrainState, xs, ys):
            def shuffled(epoch_key, xs, ys):
                sample_shape = xs.shape[4:]
                n_total = int(np.prod(xs.shape[:4]))
                perm = jax.random.permutation(epoch_key, n_total)
                xs_s = xs.reshape((n_total,) + sample_shape)[perm].reshape(xs.shape)
                ys_s = ys.reshape((n_total,) + ys.shape[4:])[perm].reshape(ys.shape)
                return xs_s, ys_s

            def body(st, epoch_key):
                if shuffle_seed is not None:
                    xs_e, ys_e = shuffled(epoch_key, xs, ys)
                else:
                    xs_e, ys_e = xs, ys
                st, stats = epoch_core(st, xs_e, ys_e)
                return st, stats

            keys = (
                jax.vmap(lambda e: jax.random.fold_in(jax.random.PRNGKey(shuffle_seed), e))(
                    state.epoch + jnp.arange(n_epochs)
                )
                if shuffle_seed is not None
                else jnp.zeros((n_epochs, 2), jnp.uint32)
            )
            state, stats = lax.scan(body, state, keys)
            # stats leaves are stacked [n_epochs, ...]; flatten the epoch dim
            # into the existing per-window/per-metric leading dim so shapes
            # match ``n_epochs`` sequential run_epoch calls concatenated.
            # (Explicit sizes, not -1: metrics leaves can be zero-size.)
            stats = jax.tree.map(
                lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), stats
            )
            return state, stats

        return jax.jit(multi_fn, donate_argnums=(0,))

    def _step_fn(self):
        """Build the one-worker masked-commit step body (staleness-sim mode).
        Runs under ``vmap(axis_name=VWORKER_AXIS)`` — inside ``shard_map``
        here, or under plain jit in the GSPMD engine."""
        rule = self.rule

        def per_worker_step(center_params, center_rule, local, since, batch, t, my_window):
            local_params, opt_state, model_state, rule_local, rng = local
            if self._dynamics:
                (local_params, opt_state, model_state, rng), (loss, _, dstep) = self._local_step(
                    (local_params, opt_state, model_state, rng), batch
                )
            else:
                (local_params, opt_state, model_state, rng), (loss, _) = self._local_step(
                    (local_params, opt_state, model_state, rng), batch
                )
            since = since + 1
            mask = (t + 1) % my_window == 0
            ctx = self._make_ctx(mask, 1.0)
            ctx = ctx._replace(steps_in_window=since.astype(jnp.float32))
            # seq-axis fsdp: gather-at-use around the masked commit (a
            # masked-off step updates nothing, so gather->slice is identity)
            center_params = self._fsdp_gather(center_params)
            dyn = None
            if self._dynamics:
                # effective staleness is the live counter itself: steps
                # since this worker's last (masked) commit
                dyn = {
                    "grad_sq": dstep["grad_sq"],
                    "nonfinite_grads": dstep["grad_nonfinite"],
                    "nonfinite_params": dynamics_mod.tree_nonfinite_count(local_params),
                    "divergence_sq": dynamics_mod.tree_sq_dist(local_params, center_params),
                    "staleness": since.astype(jnp.float32),
                    "update_sq": jnp.zeros((), jnp.float32),
                }
                dyn.update(rule.dynamics(
                    ctx, local_params, center_params, rule_local, center_rule))
            center_before = center_params
            res = rule.commit(ctx, local_params, center_params, rule_local, center_rule)
            local_params, center_params = res.local_params, res.center_params
            rule_local, center_rule = res.local_state, res.center_state
            if self._dynamics:
                dyn["update_sq"] = dynamics_mod.tree_sq_dist(
                    center_params, center_before)
            center_params = self._fsdp_shard(center_params)
            model_state = self._sync_model_state(ctx, model_state)
            since = jnp.where(mask, 0, since)
            local = (local_params, opt_state, model_state, rule_local, rng)
            if self._dynamics:
                return center_params, center_rule, local, since, loss, dyn
            return center_params, center_rule, local, since, loss

        return per_worker_step

    # ---------------------------------------------- epoch (staleness-sim mode)
    def _make_stepwise_epoch_fn(self, n_steps: int, xs_ndim: int = 4):
        """Per-step masked commits with a per-worker commit period: the
        faithful deterministic model of parameter-server asynchrony."""
        vmapped = jax.vmap(
            self._step_fn(),
            in_axes=(None, None, 0, 0, 0, None, 0),
            out_axes=(0, 0, 0, 0, 0, 0) if self._dynamics else (0, 0, 0, 0, 0),
            axis_name=VWORKER_AXIS,
        )

        def worker_fn(center_params, center_rule, local, xs, ys, schedule):
            # xs: [v, n_steps, batch, ...]
            xs = jnp.moveaxis(xs, 1, 0)
            ys = jnp.moveaxis(ys, 1, 0)
            schedule = schedule.reshape(-1)  # [v]

            def step_body(carry, inp):
                t, batch = inp
                center_params, center_rule, local, since = carry
                if self._dynamics:
                    centers_p, centers_r, local, since, loss, dyn = vmapped(
                        center_params, center_rule, local, since, batch, t, schedule
                    )
                else:
                    centers_p, centers_r, local, since, loss = vmapped(
                        center_params, center_rule, local, since, batch, t, schedule
                    )
                    dyn = ()
                center_params = jax.tree.map(lambda x: x[0], centers_p)
                center_rule = jax.tree.map(lambda x: x[0], centers_r)
                return (center_params, center_rule, local, since), (loss, dyn)

            since0 = jnp.zeros((schedule.shape[0],), jnp.int32)
            (center_params, center_rule, local, _), (losses, dyn) = lax.scan(
                step_body, (center_params, center_rule, local, since0),
                (jnp.arange(n_steps), (xs, ys)), unroll=self.unroll,
            )
            # losses: [n_steps, v] — one end-of-epoch reduction (see the
            # windowed epoch fn for why this is not done per step).
            losses = lax.psum(jnp.sum(losses, axis=1), self.axis) / self.num_workers
            losses = self._reduce_seq_stats(losses)
            if self._dynamics:
                dyn_global, dyn_worker = self._dyn_reduce(dyn, self.axis)
                return (center_params, center_rule, local, losses,
                        dyn_global, dyn_worker)
            return center_params, center_rule, local, losses

        xs_spec, ys_spec = self._data_specs(xs_ndim)
        center_spec, center_rule_spec = self._center_in_specs()
        local_spec = self._local_in_spec()
        dyn_out_specs = (P(), P(None, self.axis)) if self._dynamics else ()
        mapped = shard_map(
            worker_fn,
            mesh=self.mesh,
            in_specs=(center_spec, center_rule_spec, local_spec, xs_spec, ys_spec,
                      P(self.axis)),
            out_specs=(center_spec, center_rule_spec, local_spec, P())
            + dyn_out_specs,
            check_vma=False,
            **({"axis_names": self._manual_axes} if self._manual_axes else {}),
        )

        schedule_arr = jnp.asarray(self.commit_schedule, jnp.int32)

        def epoch_fn(state: TrainState, xs, ys):
            local = (state.local_params, state.opt_state, state.model_state,
                     state.rule_local, state.rng)
            if self._dynamics:
                (center_params, center_rule, local, losses,
                 dyn_global, dyn_worker) = mapped(
                    state.center_params, state.center_rule, local, xs, ys,
                    schedule_arr
                )
            else:
                center_params, center_rule, local, losses = mapped(
                    state.center_params, state.center_rule, local, xs, ys,
                    schedule_arr
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
            stats = {"loss": losses, "metrics": jnp.zeros((0,))}
            if self._dynamics:
                stats["dynamics"] = {**dyn_global, **dyn_worker}
            return new_state, stats

        return jax.jit(epoch_fn, donate_argnums=(0,))

    # ----------------------------------------------------------------- public
    def _enqueue(self, fn, state, xs, ys):
        """Enqueue one donating epoch program.

        With ``DISTKERAS_SANITIZE`` on, the dispatch (including any cache-miss
        trace) runs under the sanitizer's transfer guard — a host sync hidden
        in the hot loop raises in strict mode, naming the enclosing telemetry
        span — and the donated input state is poisoned afterwards so a stale
        read fails on every backend, not just where donation really aliases
        (DK101/DK103's runtime twins)."""
        if not self._sanitize:
            return fn(state, xs, ys)
        from distkeras_tpu.sanitizer import donation, transfer

        with transfer.guard("epoch_dispatch"):
            out = fn(state, xs, ys)
        donation.poison(state, label="epoch state (donate_argnums=0)")
        return out

    def _dispatch(self, fn, state, xs, ys):
        """Dispatch a whole epoch's (or chunk's) program, under the two
        epoch-grain spans that are always recorded and never block:
        ``dispatch`` is this thread's call that enqueues the program (a
        cache-miss trace and compile land here), ``device_epoch`` runs from
        its return until the epoch's losses are ready on the device, and is
        closed by the telemetry package's readiness thread, not here.  Only
        ``stats["loss"]`` goes to that thread: the state is donated by the
        next dispatch."""
        with telemetry.trace.loop_span("dispatch", windows=int(xs.shape[1])):
            new_state, stats = self._enqueue(fn, state, xs, ys)
        telemetry.trace.probe(stats["loss"], "device_epoch",
                              time.perf_counter(), phase="step")
        return new_state, stats

    def _epoch_program(self, xs):
        """The cached jitted program for one epoch of ``xs``'s shape."""
        if self.commit_schedule is not None:
            key = ("step", xs.shape[1], xs.ndim)
            if key not in self._epoch_fns:
                self._epoch_fns[key] = self._make_stepwise_epoch_fn(xs.shape[1], xs.ndim)
        else:
            n_windows, window = xs.shape[1], xs.shape[2]
            do_commit = self.rule.communication_window > 0
            key = ("win", n_windows, window, do_commit, xs.ndim)
            if key not in self._epoch_fns:
                self._epoch_fns[key] = self._make_epoch_fn(n_windows, window, do_commit, xs.ndim)
        return self._epoch_fns[key]

    def run_epoch(self, state: TrainState, xs: jnp.ndarray, ys: jnp.ndarray):
        """Run one epoch.  ``xs``/``ys`` leading dims: [num_workers, n_windows,
        window, batch] (uniform mode) or [num_workers, n_steps, batch]
        (staleness mode).

        The dispatch is asynchronous with telemetry on as with it off: the
        ``dispatch`` and ``device_epoch`` spans (:meth:`_dispatch`) are
        always recorded and wait for nothing on this thread."""
        fn = self._epoch_program(xs)
        with self.mesh:
            return self._dispatch(fn, state, xs, ys)

    def run_epochs(
        self,
        state: TrainState,
        xs: jnp.ndarray,
        ys: jnp.ndarray,
        num_epochs: int,
        *,
        shuffle_seed: Optional[int] = None,
    ):
        """Run ``num_epochs`` epochs over in-memory data as ONE jitted program.

        Equivalent to ``num_epochs`` sequential :meth:`run_epoch` calls
        (bit-identical trajectory when ``shuffle_seed`` is None — asserted in
        tests/test_run_epochs.py) but with a single dispatch, eliminating the
        per-epoch host round-trip; with ``shuffle_seed`` set, epochs reshuffle
        the sample stream on device (see ``_make_multi_epoch_fn``).  Stats
        leaves concatenate along the leading axis exactly like consecutive
        ``run_epoch`` results.  Uniform-window mode only: the staleness
        simulation already scans its whole epoch in one program.
        """
        if self.commit_schedule is not None:
            raise ValueError(
                "run_epochs runs uniform windows; the staleness simulation "
                "dispatches per epoch (run_epoch)"
            )
        num_epochs = int(num_epochs)
        if num_epochs < 1:
            raise ValueError(f"num_epochs must be >= 1, got {num_epochs}")
        n_windows, window = xs.shape[1], xs.shape[2]
        do_commit = self.rule.communication_window > 0
        key = ("multi", n_windows, window, do_commit, xs.ndim, num_epochs, shuffle_seed)
        if key not in self._epoch_fns:
            self._epoch_fns[key] = self._make_multi_epoch_fn(
                n_windows, window, do_commit, xs.ndim, num_epochs, shuffle_seed
            )
        fn = self._epoch_fns[key]
        with self.mesh:
            return self._dispatch(fn, state, xs, ys)

    def clear_program_cache(self, keep_multi: Optional[tuple] = None) -> None:
        """Drop cached compiled epoch programs.

        A live executable that is not the one being measured degrades
        steady-state TPU throughput ~15-20% until collected (measured on
        v5e); a harness that calibrates before it times calls this between
        the two, then ``gc.collect()``.
        ``keep_multi=(num_epochs, shuffle_seed)`` retains a matching
        :meth:`run_epochs` program — the one about to be timed — so a
        calibration that landed on the same rep count is not recompiled.
        State/data buffers are unaffected."""
        if keep_multi is None:
            self._epoch_fns.clear()
            return
        for key in list(self._epoch_fns):
            if not (key[0] == "multi" and key[-2:] == tuple(keep_multi)):
                del self._epoch_fns[key]

    def stream_put(self, block):
        """Cast + shard one streamed window block ``(xs, ys)`` shaped
        ``[num_workers, window, batch, ...]`` onto the mesh — the h2d half
        of the streaming path, factored out so the datapipe
        :class:`~distkeras_tpu.datapipe.PrefetchRing` can run it as its
        device-put stage on the producer thread (h2d then overlaps the next
        gather); :meth:`run_epoch_streaming` recognises blocks that arrive
        already device-resident and skips its own put.

        Float features ship pre-cast to the compute dtype: the first thing
        the local step does with x is cast it (``_local_step``), so casting
        on host instead is value-identical — and on a bandwidth-bound
        host->device link bf16 halves the bytes of the dominant cost.
        """
        xs, ys = block
        cast = self.compute_dtype
        if cast is not None and jnp.issubdtype(xs.dtype, jnp.floating):
            # copy=False: blocks from the fused native gather+cast
            # (data.epoch_window_iter(feature_dtype=...)) arrive already
            # in the compute dtype — don't pay a second host copy
            xs = xs.astype(cast, copy=False)
        xs, ys = xs[:, None], ys[:, None]
        # a window's transfer: a per-window span the switch governs.  The
        # enqueue only, so it feeds no phase: a streaming fit's ``h2d`` phase
        # reads 0.0 (nothing on this path waits for a transfer)
        with telemetry.trace.span("window_h2d",
                                  bytes=int(xs.nbytes) + int(ys.nbytes)):
            return self._put_batches(xs, ys)

    def run_epoch_streaming(self, state: TrainState, window_iter,
                            prefetch: int = 2, strict_link=None,
                            on_window=None):
        """Run one epoch from a host-side iterator of per-window blocks
        ``(xs, ys)`` shaped ``[num_workers, window, batch, ...]`` (see
        :func:`distkeras_tpu.data.epoch_window_iter`).

        The whole-epoch array is never materialised on device: each block is
        device_put as it's consumed, and because dispatch is asynchronous the
        next block's host gather + transfer overlaps the current block's
        compute (double buffering).  Device-resident blocks are bounded at
        ~2x ``prefetch``: up to ``prefetch`` undispatched blocks wait in the
        buffer while up to ``prefetch`` dispatched windows are in flight.
        The per-window program is the n_windows=1 epoch program, so the
        training trajectory is the math of :meth:`run_epoch` exactly
        (asserted bit-for-bit in tests/test_streaming.py).

        **Link guardrail**: overlap only *hides* source latency while the
        source is faster than the device; a link slower than compute makes
        the accelerators idle every window and no prefetch depth can fix
        it.  This method times the
        source pulls it already makes (no extra syncs), and when the
        steady-state unhideable source fraction exceeds 25% it warns once —
        or raises when ``strict_link=True`` (default: the
        ``DISTKERAS_STREAMING_STRICT`` env var).  The measured report is
        kept on ``self.last_stream_report``.

        ``on_window(state, n)`` (optional) fires after window ``n`` (1-based)
        has been dispatched — the trainers' mid-epoch checkpoint hook (model
        state + datapipe block cursor).  ``window_iter.close()``, when it
        exists (generators, the datapipe PrefetchRing), is called on every
        exit path, so an error mid-epoch drains a prefetch ring instead of
        orphaning its thread.
        """
        if self.commit_schedule is not None:
            raise ValueError(
                "streaming runs uniform windows; the staleness simulation "
                "needs the whole epoch in one program (run_epoch)"
            )
        import os
        import time
        import warnings
        from collections import deque

        if strict_link is None:
            strict_link = os.environ.get(
                "DISTKERAS_STREAMING_STRICT", "").lower() not in ("", "0", "false")

        it = iter(window_iter)
        buf = deque()
        stats_list = []
        steps_list = []  # per-window step counts (ragged tail weighting)
        n_windows = 0
        depth = max(1, prefetch)
        # Source/link accounting: time only the pulls the loop already makes
        # (next(it) + host cast + transfer dispatch) — never an added sync.
        # Steady state starts after the first backpressure wait completes:
        # before that, compile + initial prefill dominate and would
        # misattribute one-time costs to the link.
        src_seconds = 0.0
        steady_src = 0.0
        steady_t0 = None

        def pull():
            nonlocal src_seconds, steady_src
            t0 = time.perf_counter()
            block = next(it, None)
            if block is not None:
                if isinstance(block[0], jax.Array):
                    # the datapipe ring's device-put stage already ran
                    # stream_put on its producer thread: the block arrives
                    # sharded [num_workers, 1, window, batch, ...]
                    steps_list.append(int(block[0].shape[2]))
                else:
                    steps_list.append(block[0].shape[1])
                    block = self.stream_put(block)
            dt = time.perf_counter() - t0
            src_seconds += dt
            if steady_t0 is not None:
                steady_src += dt
            return block

        try:
            while True:
                if not buf:
                    block = pull()
                    if block is None:
                        break
                    buf.append(block)
                xs, ys = buf.popleft()
                # async dispatch of the n_windows=1 epoch program, under the
                # per-window span the switch governs (not the epoch-grain
                # ``dispatch``: a window is not an epoch); the wait is recorded
                # at the real sync point, the backpressure wait below
                with telemetry.trace.span("window_dispatch", window=n_windows):
                    fn = self._epoch_program(xs)
                    with self.mesh:
                        state, stats = self._enqueue(fn, state, xs, ys)
                n_windows += 1
                stats_list.append(stats)
                if on_window is not None:
                    on_window(state, n_windows)
                # Backpressure: dispatch is async, so without a sync the host
                # would device_put the whole epoch ahead of the device and defeat
                # the memory bound.  Waiting on the loss of the window dispatched
                # `prefetch` calls ago caps in-flight windows at prefetch (plus
                # up to prefetch buffered undispatched blocks — see docstring).
                if n_windows > depth:
                    with telemetry.trace.span("window_wait", phase="step",
                                              window=n_windows - 1 - depth):
                        jax.block_until_ready(stats_list[n_windows - 1 - depth]["loss"])
                    if steady_t0 is None:
                        steady_t0 = time.perf_counter()
                # Refill AFTER dispatching (first window included): the very
                # first window's compute then hides the rest of the initial
                # prefill's source latency — measured, not assumed, in
                # tests/test_streaming_overlap.py.
                while len(buf) < depth:
                    block = pull()
                    if block is None:
                        break
                    buf.append(block)
        finally:
            close = getattr(window_iter, "close", None)
            if close is not None:
                close()
        if not stats_list:
            raise ValueError("empty window iterator")
        self._report_stream_link(src_seconds, steady_src, steady_t0,
                                 n_windows, strict_link, time.perf_counter())
        # generic over the stats pytree (loss/metrics, plus the dynamics
        # subtree when enabled): concatenate every leaf along the window axis
        stats = jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *stats_list)
        # per-window step counts ride along as a host leaf so the history
        # can weight a ragged tail window by its actual steps (PARITY.md)
        stats = dict(stats)
        stats["window_steps"] = np.asarray(steps_list, np.int64)
        # each window ran as its own "epoch" program (epoch += n_windows);
        # restore whole-epoch semantics (+1).  The input state was donated by
        # the first window's call, so arithmetic uses the live output state.
        state = state.replace(epoch=state.epoch - (n_windows - 1))
        return state, stats

    def _report_stream_link(self, src_seconds, steady_src, steady_t0,
                            n_windows, strict_link, now):
        """Judge the last streamed epoch's source/compute balance.

        Over the steady-state region (first backpressure wait -> epoch end)
        the loop alternates pulling source blocks and waiting on the device;
        source time hidden behind compute shows up as wall time NOT spent in
        pulls, so ``unhideable = steady_src - (steady_wall - steady_src)``
        is the part of the link cost the device actually waited out.  A
        fraction > 0.25 of steady wall time means the link, not the model,
        bounds throughput — warn loudly (once per engine) or raise in
        strict mode.  Short epochs that never hit backpressure measure
        nothing and never trip the guardrail."""
        import warnings

        steady_wall = (now - steady_t0) if steady_t0 is not None else 0.0
        if steady_wall > 0:
            hidden = max(0.0, steady_wall - steady_src)
            unhideable = max(0.0, steady_src - hidden)
            fraction = unhideable / steady_wall
        else:
            unhideable, fraction = 0.0, 0.0
        link_bound = fraction > 0.25
        self.last_stream_report = {
            "windows": n_windows,
            "source_seconds": src_seconds,
            "steady_wall_seconds": steady_wall,
            "steady_source_seconds": steady_src,
            "unhideable_fraction": fraction,
            "link_bound": link_bound,
        }
        if not link_bound:
            return
        msg = (
            f"streaming source is the bottleneck: {fraction:.0%} of "
            f"steady-state wall time ({steady_src:.2f}s of "
            f"{steady_wall:.2f}s over {n_windows} windows) is source/"
            "transfer latency no prefetch depth can hide — the devices are "
            "idling on the link.  Stage the dataset closer (local disk / "
            "in-memory), widen the link, or grow per-window compute "
            "(larger window/batch).  See engine.last_stream_report."
        )
        if strict_link:
            raise RuntimeError(msg)
        if not self._link_warned:
            self._link_warned = True
            warnings.warn(msg, RuntimeWarning, stacklevel=3)

    def average_workers(self, state: TrainState):
        """One-shot synchronous weight average (AveragingTrainer's final step)."""

        # cached program: a fresh jit wrapper per call would re-trace every
        # time (same per-call-closure trap as _fsdp_regather below)
        if self._avg_fn is None:
            def _avg(state):
                mean_p = jax.tree.map(
                    lambda x: jnp.mean(x, axis=0), state.local_params)
                mean_ms = jax.tree.map(
                    lambda x: jnp.mean(x, axis=0), state.model_state)
                return state.replace(center_params=mean_p), mean_ms

            self._avg_fn = jax.jit(_avg, out_shardings=(None, self._rep))
        with self.mesh:
            new_state, mean_ms = self._avg_fn(state)
        return new_state, mean_ms

    def final_model_state(self, state: TrainState):
        """Replicated model state for the returned model (mean of workers)."""
        if self._final_ms_fn is None:
            self._final_ms_fn = jax.jit(
                lambda ms: jax.tree.map(lambda x: jnp.mean(x, axis=0), ms),
                out_shardings=self._rep,
            )
        with self.mesh:
            return self._final_ms_fn(state.model_state)

    def worker_slice(self, tree, index: int):
        """Fetch one worker's slice of per-worker state to host (Ensemble path)."""
        return jax.tree.map(lambda x: np.asarray(x[index]), tree)

    def gather_center(self, state: TrainState):
        """Center params as host-gatherable (replicated) arrays.  Already
        replicated in this engine unless seq-axis fsdp sharded them; the
        GSPMD engine re-replicates its model-axis-sharded leaves here."""
        if self._fsdp_seq:
            # one cached re-replication program — a fresh lambda per call
            # would miss jit's function-object cache and re-trace every
            # checkpoint save (the per-call-closure trap, generate.py doc)
            if self._fsdp_regather is None:
                self._fsdp_regather = jax.jit(lambda t: t, out_shardings=self._rep)
            with self.mesh:
                return self._fsdp_regather(state.center_params)
        return state.center_params

    # --------------------------------------------------------------- sharding
    def shard_batches(self, xs: np.ndarray, ys: np.ndarray):
        """Device-put epoch data: worker axis leading; sequence (last) axis of
        xs also sharded when sequence parallelism is on.

        Returns as soon as the transfer is enqueued, with telemetry on as
        with it off.  Two epoch-grain spans, always recorded: ``h2d`` is this
        thread's enqueue; ``h2d_transfer`` runs from entry until the rows are
        ready on the device and is closed by the telemetry package's
        readiness thread.  ``bytes`` is the count at the boundary."""
        t0 = time.perf_counter()
        nbytes = int(xs.nbytes) + int(ys.nbytes)
        with telemetry.trace.loop_span("h2d", bytes=nbytes):
            out = self._put_batches(xs, ys)
        telemetry.trace.probe(out, "h2d_transfer", t0, phase="h2d",
                              bytes=nbytes)
        return out

    def _put_batches(self, xs: np.ndarray, ys: np.ndarray):
        """Uses ``make_array_from_callback`` so the same code works
        multi-host (each process materialises only its addressable shards —
        the DCN analogue of Spark shipping partitions to executors)."""
        from jax.sharding import NamedSharding

        xs_spec, ys_spec = self._data_specs(xs.ndim)
        with self.mesh:
            return (
                jax.make_array_from_callback(
                    xs.shape, NamedSharding(self.mesh, xs_spec), lambda idx: xs[idx]
                ),
                jax.make_array_from_callback(
                    ys.shape, NamedSharding(self.mesh, ys_spec), lambda idx: ys[idx]
                ),
            )
