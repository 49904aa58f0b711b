"""Trainers — the user-facing API, signature-compatible with the reference.

Reference surface (``distkeras/trainers.py``): construct a trainer around a
compiled Keras model and call ``trainer.train(dataframe)`` to get a trained
model back.  The class family is preserved exactly — ``SingleTrainer``,
``AveragingTrainer``, ``EnsembleTrainer``, ``DistributedTrainer``,
``AsynchronousDistributedTrainer``, ``DOWNPOUR``, ``AEASGD``, ``EAMSGD``,
``ADAG``, ``DynSGD`` — as are the kwargs the notebooks use
(``features_col``, ``label_col``, ``batch_size``, ``num_epoch``,
``communication_window``, ``rho``, ``learning_rate``, ``momentum``,
``num_workers``, ``master_port``, ``parallelism_factor``).

What changed underneath: ``train`` no longer launches a Spark job against a
socket parameter server — it compiles one SPMD program over a TPU mesh
(:mod:`distkeras_tpu.parallel.engine`) where the PS center variable is
replicated on-device and commits are ICI collectives.  Models may be Keras 3
(JAX backend), flax modules, or adapters; Keras models are returned as Keras
models with trained weights, matching the reference contract.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, List, Optional, Sequence

import jax
import numpy as np

from distkeras_tpu import chaos as _chaos
from distkeras_tpu import fleet as _fleet
from distkeras_tpu import sanitizer
from distkeras_tpu import telemetry
from distkeras_tpu import workers as workers_mod
from distkeras_tpu.data import epoch_arrays
from distkeras_tpu.frame import DataFrame
from distkeras_tpu.models.adapter import ModelAdapter, TrainedModel, as_adapter
from distkeras_tpu.parallel.engine import WindowedEngine
from distkeras_tpu.parameter_servers import (
    ADAGParameterServer,
    DeltaParameterServer,
    DynSGDParameterServer,
    ParameterServer,
)

__all__ = [
    "Trainer",
    "SingleTrainer",
    "AveragingTrainer",
    "EnsembleTrainer",
    "DistributedTrainer",
    "AsynchronousDistributedTrainer",
    "DOWNPOUR",
    "AEASGD",
    "EAMSGD",
    "ADAG",
    "DynSGD",
    "AdaptiveDynSGD",
]


def _serving_twin(adapter: ModelAdapter) -> ModelAdapter:
    """The single-device twin of a sequence-parallel adapter (same params).

    A seq_axis-bearing model jit-traces ring-attention collectives and
    cannot run outside its mesh; every trainer return path hands back the
    seq_axis=None twin so the reference contract — ``train(df)`` returns a
    servable model — holds for sp-trained models too.  No-op for adapters
    without a seq axis."""
    module = getattr(adapter, "module", None)
    if module is not None and getattr(module, "seq_axis", None) is not None:
        from distkeras_tpu.models.adapter import FlaxModel

        return FlaxModel(module.clone(seq_axis=None), adapter.outputs_logits)
    if (dataclasses.is_dataclass(adapter)
            and getattr(adapter, "seq_axis", None) is not None):
        # Staged adapters (pp x sp) are dataclasses, not FlaxModel
        # wrappers — same twin rule via replace.  replace() builds a
        # fresh instance, so carry over the non-field checkpoint slot
        # PretrainedStagedLM's init requires.
        twin = dataclasses.replace(adapter, seq_axis=None)
        pretrained = getattr(adapter, "_pretrained", None)
        if pretrained is not None:
            twin._pretrained = pretrained
        return twin
    return adapter


def _epoch_mean(stats, key):
    """Per-epoch mean of ``stats[key]`` over its window axis, weighted by
    per-window step counts when the streaming path recorded a ragged tail
    (``window_steps``, :meth:`WindowedEngine.run_epoch_streaming`).  A
    ragged tail window averages fewer steps than the full windows, so the
    unweighted mean over-weights it; weighting by steps makes the epoch
    mean match the in-memory path's mean over all steps.  Uniform windows
    (and the in-memory path, which records no ``window_steps``) take the
    plain ``np.mean`` branch so existing histories stay bitwise unchanged."""
    values = np.asarray(stats[key])
    weights = stats.get("window_steps") if isinstance(stats, dict) else None
    if (weights is not None and values.ndim >= 1
            and values.shape[0] == len(weights)
            and int(np.min(weights)) != int(np.max(weights))):
        return np.average(values, axis=0, weights=np.asarray(weights))
    return np.mean(values, axis=0) if values.ndim > 1 else np.mean(values)


class Trainer:
    """Base trainer: model + loss + worker optimizer + wall-clock bookkeeping
    (reference parity: ``trainers.py :: Trainer``)."""

    def __init__(
        self,
        keras_model: Any,
        loss: Any = "categorical_crossentropy",
        worker_optimizer: Any = "sgd",
        metrics: Sequence = ("accuracy",),
        features_col: str = "features",
        label_col: str = "label",
        batch_size: int = 32,
        num_epoch: int = 1,
        seed: int = 0,
        compute_dtype: Any = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        resume: bool = False,
        profile_dir: Optional[str] = None,
        seq_shards: int = 1,
        tp_shards: int = 1,
        fsdp: bool = False,
        tensorboard_dir: Optional[str] = None,
        streaming: bool = False,
        remat: bool = False,
        unroll=1,
        dispatch_epochs: int = 1,
        pipeline_stages: int = 1,
        pp_microbatches: Optional[int] = None,
        tp_spec_fn: Optional[Any] = None,
        prefetch: int = 0,
        checkpoint_blocks: int = 0,
    ):
        self.master_model = keras_model
        self.loss = loss
        self.worker_optimizer = worker_optimizer
        self.metrics = tuple(metrics)
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.seed = seed
        if isinstance(compute_dtype, str):
            import jax.numpy as jnp

            compute_dtype = jnp.dtype(compute_dtype)
        self.compute_dtype = compute_dtype
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.resume = resume
        # SURVEY.md §5.1: the reference only wall-clocked training; we add
        # optional per-epoch device tracing viewable in TensorBoard/Perfetto.
        self.profile_dir = profile_dir
        # SURVEY.md §5.5: optional per-epoch loss/metric scalars (TensorBoard
        # event files when a writer is importable, JSONL otherwise).
        self.tensorboard_dir = tensorboard_dir
        # Streaming data path: feed the engine window-sized blocks through a
        # double-buffered iterator instead of materialising whole epochs
        # (identical trajectory; for datasets approaching HBM size).
        self.streaming = bool(streaming)
        # Rematerialise forward activations on the backward pass
        # (jax.checkpoint in both engines): trades FLOPs for HBM — the lever
        # for deep models (ResNet-scale+) whose per-window activations
        # outgrow the chip.  Gradients are mathematically identical; see
        # tests/test_fixes_r3.py (trajectory-equality on ResNet20).
        self.remat = bool(remat)
        # Per-step scan unroll factor (int, or True = full unroll) — see
        # WindowedEngine._finish_init.  Math is unroll-invariant.
        self.unroll = unroll
        # >1: run up to this many epochs per device dispatch
        # (engine.run_epochs) with ON-DEVICE inter-epoch reshuffling,
        # amortising the fixed per-epoch host round-trip (measurement:
        # WindowedEngine._make_multi_epoch_fn).  The reshuffle draws from the
        # device RNG stream, not the host rng, so trajectories legitimately
        # differ from dispatch_epochs=1 (both are uniform permutations).
        # Checkpoint cadence is preserved: chunks never straddle a
        # checkpoint_every boundary.  Incompatible with streaming=True and
        # with staleness schedules (both need per-epoch host involvement).
        self.dispatch_epochs = int(dispatch_epochs)
        if self.dispatch_epochs < 1:
            raise ValueError(
                f"dispatch_epochs must be >= 1, got {dispatch_epochs}"
            )
        # sequence parallelism (ring attention) shards: >1 requires a
        # seq-axis-aware model (models/transformer.py)
        self.seq_shards = int(seq_shards)
        # tensor parallelism shards: >1 selects the GSPMD engine (param
        # leaves sharded over a 'model' mesh axis; any model, unmodified)
        self.tp_shards = int(tp_shards)
        # ZeRO-3-style sharding of the center variable over the workers axis
        # (GSPMD engine; composes with tp_shards) — pure layout change, the
        # replicated parameter-server copy stops costing num_devices x HBM
        self.fsdp = bool(fsdp)
        # pipeline parallelism stages: >1 selects the pipeline engine
        # (microbatch ppermute pipeline over a 'stages' mesh axis; requires a
        # staged adapter, models/staged.StagedTransformer, with num_stages ==
        # pipeline_stages)
        self.pipeline_stages = int(pipeline_stages)
        self.pp_microbatches = pp_microbatches
        # optional GSPMD leaf-placement override, (shape, path) ->
        # PartitionSpec|None — e.g. models.expert_partition for MoE expert
        # sharding over the model axis
        self.tp_spec_fn = tp_spec_fn
        if tp_spec_fn is not None and self.tp_shards <= 1:
            raise ValueError(
                "tp_spec_fn places leaves on the model mesh axis, which only "
                "exists with tp_shards>1 (the GSPMD engine); without it the "
                "override would be silently ignored"
            )
        # >0 with streaming=True: wrap the epoch's block iterator in a
        # datapipe.PrefetchRing of this depth — gathers (and the h2d put)
        # move to a producer thread and overlap device steps.  The block
        # order and payloads are untouched, so the trajectory stays bitwise
        # identical (tests/test_datapipe.py pins it).
        self.prefetch = int(prefetch)
        if self.prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {prefetch}")
        # >0 with streaming + checkpoint_dir: additionally checkpoint every N
        # consumed blocks MID-epoch (model state + datapipe.DataState cursor),
        # so a killed run resumes at the block it died on, not the epoch
        # boundary.  Needs the streaming path — the in-memory path dispatches
        # whole epochs, leaving no block boundary to save at.
        self.checkpoint_blocks = int(checkpoint_blocks)
        if self.checkpoint_blocks < 0:
            raise ValueError(
                f"checkpoint_blocks must be >= 0, got {checkpoint_blocks}"
            )
        if self.checkpoint_blocks and not self.streaming:
            raise ValueError(
                "checkpoint_blocks>0 saves at streaming block boundaries; "
                "set streaming=True (the in-memory path dispatches whole "
                "epochs, so there is no mid-epoch point to save at)"
            )
        self.history: dict = {}
        self.training_time: float = 0.0
        self._t0: Optional[float] = None

    # -- wall-clock bookkeeping (reference parity) --------------------------
    def record_training_start(self) -> None:
        # monotonic clock: wall-clock (time.time) can jump under NTP slew,
        # yielding negative or wildly wrong durations
        self._t0 = time.perf_counter()

    def record_training_stop(self) -> None:
        if self._t0 is None:  # stop without start: no interval to measure
            self.training_time = 0.0
        else:
            self.training_time = time.perf_counter() - self._t0

    def get_training_time(self) -> float:
        return self.training_time

    def get_history(self) -> dict:
        return self.history

    def _effective_worker_optimizer(self):
        """The optimizer spec handed to engines/workers.  Subclasses with an
        algorithm-specific default (EAMSGD) override this instead of mutating
        ``self.worker_optimizer``, so retraining after changing hyperparams
        resolves a fresh spec."""
        return self.worker_optimizer

    # -- internals ----------------------------------------------------------
    def _load_columns(self, dataframe: DataFrame):
        # Integer token features (TextCNN) must stay integral; every other
        # feature column materialises as one float32 matrix.  Dtype is
        # decided from the raw column BEFORE materialising, so the full
        # dataset is copied exactly once per call.
        f_raw = dataframe.column(self.features_col)
        if f_raw.dtype != object and np.issubdtype(f_raw.dtype, np.integer):
            feats = f_raw.astype(np.int32)
        else:
            feats = dataframe.matrix(self.features_col, dtype=np.float32)
        labels_raw = dataframe.column(self.label_col)
        if labels_raw.dtype == object:
            labels = dataframe.matrix(self.label_col, dtype=np.float32)
        elif np.issubdtype(labels_raw.dtype, np.integer):
            labels = labels_raw.astype(np.int32)
        else:
            labels = labels_raw.astype(np.float32)
        return feats, labels

    def _restore_state(self, ckpt, engine, state, elastic: bool, step=None):
        """Resume from ``checkpoint_dir``: bitwise when the checkpoint was
        written at this trainer's worker count; **elastic** otherwise — the
        restored center variable (and its commit counters and epoch) carry
        over, and the new worker set re-pulls it as fresh local replicas,
        which is the reference's worker-retry semantics (a retried Spark
        task reconnects to the PS and pulls — SURVEY.md §5.3).  Beyond
        reference: upstream had no way to continue a run on a different
        cluster size at all."""
        if not elastic:
            return ckpt.restore(like=state, step=step)  # bitwise, single read
        # elastic: only center/rule/epoch read here; the per-worker
        # [N_old, ...] model-state stack never materialises whole — it
        # reduces to its worker mean in budget-bounded partial restores
        # (checkpoint.model_state_worker_mean), the same semantic
        # sync_model_state applies at every commit.  Both reads pin the
        # step resolved in _fit, so a save landing mid-resume cannot mix
        # checkpoints.
        raw = ckpt.restore_center(step, include_model_state=False)
        epoch = int(np.asarray(raw["epoch"]))
        model_state = ckpt.model_state_worker_mean(step)
        return engine.state_from_center(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch),
            raw["center_params"], raw["center_rule"], model_state, epoch,
        )

    def _watchdog_rollback(self, engine, ckpt, state, watchdog):
        """Restore the last checkpoint after a watchdog trip (policy
        ``rollback``): the diverged state is discarded and training
        continues from the restored center/workers — the same
        :meth:`_restore_state` path a crash-resume takes."""
        reason = watchdog.pending_rollback
        # verified: rolling back onto a corrupt checkpoint would trade a
        # diverged run for a crashed one
        step = ckpt.latest_verified() if ckpt is not None else None
        if step is None:
            raise telemetry.dynamics.TrainingDiverged(
                f"{reason} — rollback requested but no checkpoint has been "
                "saved yet"
            )
        state = self._restore_state(ckpt, engine, state, elastic=False, step=step)
        watchdog.rolled_back()
        if telemetry.enabled():
            telemetry.metrics.counter(
                "dynamics_rollbacks_total",
                help="watchdog-triggered checkpoint restores",
            ).inc()
        return state

    def _apply_staleness_bound(self, policy, summary, state):
        """Feed the finished epoch's dynamics summary to the host-side
        staleness policy and swap the rule's ``staleness_bound`` leaf with
        the bound it returns.  The leaf is traced *data* (same float32
        scalar shape), so the swap never retraces the epoch program; rules
        without the leaf (plain DynSGD) pass through untouched."""
        from distkeras_tpu.algorithms.adaptive import BOUND_KEY

        if BOUND_KEY not in state.center_rule:
            return state
        import jax.numpy as jnp

        bound = float(policy.observe(summary))
        rule_state = dict(state.center_rule)
        rule_state[BOUND_KEY] = jnp.asarray(bound, jnp.float32)
        if telemetry.enabled():
            telemetry.metrics.gauge(
                "dynamics_staleness_bound",
                help="adaptive DynSGD staleness bound in force",
            ).set(bound)
        return state.replace(center_rule=rule_state)

    def _elastic_resize(self, build_engine, engine, state, ckpt, epoch, rng,
                        shuffle, new_workers):
        """Mid-run worker-count change at an epoch boundary: drain to a
        boundary checkpoint, gather the center off the old engine, re-plan,
        and rebuild state at ``new_workers`` via the same
        ``state_from_center`` path an elastic *resume* takes — but live, with
        no process restart.  Progress (center params, rule counters, epoch)
        carries over; local replicas re-pull the center, exactly the
        reference's worker-(re)connect semantics."""
        from distkeras_tpu.parallel.engine import plan_workers

        if ckpt is not None:
            # leave a boundary checkpoint first: if the rebuild dies (OOM on
            # a shrunken mesh, say), train_with_recovery resumes from here
            from distkeras_tpu.datapipe import DataState

            ckpt.save_partial(state, epoch, DataState(
                epoch=epoch + 1, block_cursor=0,
                rng_state=(rng.bit_generator.state if shuffle else None)))
            ckpt.wait()
        from distkeras_tpu.checkpoint import worker_mean

        center = jax.tree.map(np.asarray, engine.gather_center(state))
        center_rule = jax.tree.map(np.asarray, state.center_rule)
        # per-worker model state reduces to its worker mean — the same
        # semantic sync_model_state applies at every commit boundary
        model_state = jax.tree.map(
            lambda v: worker_mean(np.asarray(v)), state.model_state)
        devices_used, _ = plan_workers(new_workers, jax.device_count())
        engine.clear_program_cache()
        new_engine = build_engine(new_workers)
        new_state = new_engine.state_from_center(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch + 1),
            center, center_rule, model_state, epoch + 1,
        )
        if telemetry.enabled():
            telemetry.metrics.counter(
                "elastic_resizes_total",
                help="mid-run worker-count rebuilds",
            ).inc()
            telemetry.metrics.gauge(
                "elastic_workers", help="current logical worker count"
            ).set(new_workers)
            telemetry.metrics.gauge(
                "elastic_devices", help="devices the worker axis occupies"
            ).set(devices_used)
        return new_engine, new_state

    def _fit(self, *args, **kwargs):
        """Crash-forensics boundary around :meth:`_fit_inner`.

        Mints/propagates the fleet ``run_id``, starts the live HTTP exporter
        when one is configured, and — on ANY unhandled exception, including
        watchdog halts and strict sanitizer violations — dumps the
        flight-recorder blackbox into the telemetry dir before re-raising.
        With telemetry off this is one cached-bool check per fit.  Switch or
        no switch, the interpreter's collections are timed from the first
        fit on (``trace.watch_gc``: a ``gc`` span in the ring beside the
        epoch's for one that held the host over a millisecond).
        """
        telemetry.trace.watch_gc()
        if telemetry.enabled():
            telemetry.flightdeck.activate()
        try:
            return self._fit_inner(*args, **kwargs)
        except Exception as e:
            if telemetry.enabled():
                telemetry.flightdeck.on_crash(
                    f"{type(self).__name__}._fit: {type(e).__name__}: {e}")
            raise

    def _fit_inner(
        self,
        dataframe: DataFrame,
        rule,
        num_workers: int,
        *,
        shuffle: bool = True,
        average_at_end: bool = False,
        commit_schedule: Optional[np.ndarray] = None,
    ):
        adapter = as_adapter(self.master_model)
        # Local canonicalised copy: per-token models rename accuracy ->
        # token_accuracy so history/TensorBoard keys match the engine's
        # metric names, WITHOUT mutating the user-visible self.metrics the
        # caller constructed the trainer with.
        metrics = self.metrics
        if getattr(adapter, "per_token_labels", False):
            from distkeras_tpu.ops.metrics import per_token_metric_names

            metrics = per_token_metric_names(metrics)
        with telemetry.trace.span("load_columns", phase="data"):
            feats, labels = self._load_columns(dataframe)

        # One engine-construction recipe, parameterised by worker count, so
        # an elastic resize can re-plan and rebuild mid-run with exactly the
        # configuration the original engine was built under.
        def build_engine(n_workers: int):
            if self.pipeline_stages > 1:
                if self.tp_spec_fn is not None:
                    raise ValueError(
                        "tp_spec_fn is a GSPMD-engine override; the pipeline "
                        "engine places the model axis by its staged-leaf shape "
                        "rule"
                    )
                if commit_schedule is not None:
                    raise ValueError(
                        "pipeline_stages>1 is incompatible with commit_schedule "
                        "(the staleness simulation dispatches per step)"
                    )
                if getattr(adapter, "num_stages", None) != self.pipeline_stages:
                    raise ValueError(
                        f"pipeline_stages={self.pipeline_stages} needs a staged "
                        f"adapter with num_stages={self.pipeline_stages} (e.g. "
                        "models.StagedTransformer); got "
                        f"{type(self.master_model).__name__}"
                    )
                from distkeras_tpu.parallel.pipeline import PipelineEngine

                return PipelineEngine(
                    adapter,
                    self.loss,
                    self._effective_worker_optimizer(),
                    rule,
                    n_workers,
                    microbatches=self.pp_microbatches,
                    tp_shards=self.tp_shards,
                    seq_shards=self.seq_shards,
                    fsdp=self.fsdp,
                    metrics=metrics,
                    compute_dtype=self.compute_dtype,
                    remat=self.remat,
                    unroll=self.unroll,
                )
            if self.tp_shards > 1 or (self.fsdp and self.seq_shards == 1):
                if self.seq_shards > 1:
                    raise ValueError(
                        "tp_shards>1 (GSPMD engine) is incompatible with "
                        "seq_shards>1 (ring attention needs the shard_map "
                        "engine); fsdp + seq_shards IS supported — drop tp_shards"
                    )
                from distkeras_tpu.parallel.gspmd import GSPMDEngine

                return GSPMDEngine(
                    adapter,
                    self.loss,
                    self._effective_worker_optimizer(),
                    rule,
                    n_workers,
                    tp_shards=self.tp_shards,
                    fsdp=self.fsdp,
                    spec_fn=self.tp_spec_fn,
                    metrics=metrics,
                    compute_dtype=self.compute_dtype,
                    commit_schedule=commit_schedule,
                    remat=self.remat,
                    unroll=self.unroll,
                )
            return WindowedEngine(
                adapter,
                self.loss,
                self._effective_worker_optimizer(),
                rule,
                n_workers,
                metrics=metrics,
                compute_dtype=self.compute_dtype,
                commit_schedule=commit_schedule,
                seq_shards=self.seq_shards,
                # fsdp x sp: seq-axis ZeRO center sharding in the shard_map
                # engine (fsdp alone routed to the GSPMD engine above)
                fsdp=self.fsdp and self.seq_shards > 1,
                remat=self.remat,
                unroll=self.unroll,
            )

        engine = build_engine(num_workers)
        window = rule.communication_window if rule.communication_window > 0 else None
        rng = np.random.default_rng(self.seed)

        ckpt = None
        start_epoch = 0
        resuming = False
        elastic = False
        if self.checkpoint_dir:
            from distkeras_tpu.checkpoint import CheckpointManager

            ckpt = CheckpointManager(self.checkpoint_dir, every=self.checkpoint_every)
            # resolve the resume step ONCE; every read below pins it, so a
            # concurrent writer (second elastic job, in-flight async save)
            # cannot hand different reads different checkpoints.  Verified
            # resolution: a step whose bytes no longer match its manifest
            # (torn write, bit rot) is quarantined here and resume falls to
            # the newest step that proves out — never loaded, never trusted
            resume_step = ckpt.latest_verified() if self.resume else None
            resuming = resume_step is not None
            elastic = resuming and ckpt.saved_worker_count(resume_step) != engine.num_workers
            if elastic and rule.communication_window <= 0:
                # no-commit rules (Sequential/OneShotAverage) never fold
                # progress into the center mid-training, so an elastic
                # resume would silently restart from initialization with a
                # nonzero epoch counter — refuse loudly instead
                raise ValueError(
                    f"elastic resume (checkpoint at "
                    f"{ckpt.saved_worker_count(resume_step)} workers, trainer at "
                    f"{engine.num_workers}) requires a committing rule; "
                    f"{type(rule).__name__} only produces its result at the "
                    "end of training, so the checkpointed center carries no "
                    "progress to adopt.  Resume with the original "
                    "num_workers instead."
                )

        # Divergence watchdog: armed only when the engine traces dynamics
        # stats (DISTKERAS_DYNAMICS=1 and not the pipeline engine).  All its
        # checks run on host numpy AFTER the epoch's stats land — never
        # inside the step loop (dklint DK107).
        watchdog = None
        if getattr(engine, "_dynamics", False):
            watchdog = telemetry.dynamics.DivergenceWatchdog.from_config()
        if watchdog is not None and watchdog.policy == "rollback":
            if ckpt is None:
                raise ValueError(
                    "watchdog policy 'rollback' needs checkpoint_dir set so "
                    "there is a checkpoint to restore"
                )
            if self.dispatch_epochs > 1:
                raise ValueError(
                    "watchdog policy 'rollback' needs the per-epoch loop; "
                    "dispatch_epochs>1 runs whole chunks per dispatch with no "
                    "epoch boundary to restore at"
                )

        # Elastic membership: poll the fleet's membership epoch at epoch
        # boundaries and resize the worker set mid-run.  Only meaningful for
        # committing rules (progress must live in the center to carry across
        # a rebuild) on the per-epoch loop.
        elastic_ctl = getattr(self, "elastic", None)
        if elastic_ctl is not None and (
            rule.communication_window <= 0
            or commit_schedule is not None
            or self.pipeline_stages > 1
            or self.dispatch_epochs > 1
        ):
            warnings.warn(
                "elastic membership polling disabled: it requires a "
                "committing rule on the per-epoch loop (no commit_schedule, "
                "pipeline_stages=1, dispatch_epochs=1)",
                RuntimeWarning,
            )
            elastic_ctl = None

        # AdaptiveBound staleness policy: applied between epochs by swapping
        # the rule's traced staleness_bound scalar (same dtype/shape, so no
        # retrace).  Needs the dynamics summary the telemetry layer traces.
        staleness_policy = getattr(self, "staleness_policy", None)
        if staleness_policy is not None and not getattr(engine, "_dynamics", False):
            warnings.warn(
                "staleness_policy set but dynamics telemetry is off "
                "(DISTKERAS_DYNAMICS); the bound will not adapt",
                RuntimeWarning,
            )
            staleness_policy = None

        # The elastic path builds its state straight from the partial
        # restore — a fresh init_state would be thrown away (and costs a
        # full-state materialisation).  The pipeline engine still needs
        # init_state first (it probes the staged shapes there), and the
        # bitwise path needs it as the restore template.
        state = None
        if not elastic or self.pipeline_stages > 1:
            state = engine.init_state(
                jax.random.PRNGKey(self.seed), feats[: self.batch_size]
            )
        resume_data = None
        if resuming:
            state = self._restore_state(ckpt, engine, state, elastic, step=resume_step)
            start_epoch = int(np.asarray(state.epoch))
            # data checkpoint sidecar (datapipe.DataState): exact RNG bit
            # state + mid-epoch block cursor.  A sidecar whose epoch doesn't
            # match the restored model epoch (external writer, older layout)
            # is ignored — the legacy fast-forward below still aligns the
            # shuffle stream at epoch granularity.
            resume_data = ckpt.restore_data_state(resume_step)
            if resume_data is not None and int(resume_data.epoch) != start_epoch:
                resume_data = None
            if (resume_data is not None and resume_data.block_cursor
                    and not self.streaming):
                raise ValueError(
                    f"checkpoint at step {resume_step} was saved mid-epoch "
                    f"(block cursor {resume_data.block_cursor}); resuming it "
                    "requires streaming=True — the in-memory path dispatches "
                    "whole epochs and cannot skip consumed blocks"
                )

        # keep the host RNG stream aligned with the epoch counter on resume:
        # exact bit-state restore when a DataState sidecar was saved, else
        # the legacy epoch-granularity fast-forward.  (Chunked dispatch
        # shuffles on device, keyed by state.epoch — its alignment is free
        # and the host stream is never drawn from.)
        if self.dispatch_epochs == 1:
            if resume_data is not None and resume_data.rng_state is not None:
                resume_data.restore_rng(rng)
            else:
                for _ in range(start_epoch):
                    rng.permutation(len(feats))

        scalar_log = None
        if self.tensorboard_dir:
            from distkeras_tpu.utils.tb import ScalarLogger

            scalar_log = ScalarLogger(self.tensorboard_dir)
        # one profiler path: profile_dir (the explicit per-trainer knob) and
        # the env-driven DISTKERAS_PROFILE both build a step-windowed
        # ProfilerHook; profile_dir takes precedence — both would race on one
        # global profiler session.  It captures the second iteration of the
        # loop below (the first includes compilation), or the only one; the
        # capture blocks nothing, so it holds what the device ran meanwhile.
        if self.profile_dir:
            steps = -(-(self.num_epoch - start_epoch) // self.dispatch_epochs)
            prof = telemetry.ProfilerHook(
                self.profile_dir, start_epoch + min(1, steps - 1))
        else:
            prof = telemetry.ProfilerHook.from_env()
        if telemetry.enabled():
            telemetry.install_jax_hooks()

        last_summary: dict = {}

        def _materialise(stats, epoch_idx):
            # the one place the training thread waits for the device: how
            # long it had nothing to do but wait (always recorded)
            with telemetry.trace.loop_span("stats_wait"):
                stats = jax.tree.map(np.asarray, stats)
            dyn = stats.get("dynamics")
            summary = None
            if dyn is not None:
                # gauges first so the scalar-logger bridge below picks up
                # this epoch's values, then the full series into the
                # metrics JSONL
                summary = telemetry.dynamics.summarize(dyn, loss=stats["loss"])
                telemetry.dynamics.record(epoch_idx, dyn, summary)
                last_summary["value"] = summary
            if scalar_log is not None:
                scalars = {"loss": float(_epoch_mean(stats, "loss"))}
                mets = np.asarray(stats["metrics"])
                if mets.size:
                    per_metric = _epoch_mean(stats, "metrics")
                    for i, name in enumerate(metrics):
                        key = name if isinstance(name, str) else getattr(name, "__name__", f"metric_{i}")
                        scalars[key] = float(per_metric[i])
                scalar_log.log(epoch_idx, **scalars)
                if telemetry.enabled():
                    telemetry.metrics.to_scalar_logger(scalar_log, epoch_idx)
            if summary is not None and watchdog is not None:
                # after logging so a halting epoch still reaches the logs;
                # raises TrainingDiverged under the halt policy
                watchdog.observe(epoch_idx, summary)
            return stats

        epoch_stats: List[dict] = []
        self.record_training_start()
        # try/finally so the scalar logger and profiler release their file
        # handles / capture session even when an epoch raises (previously a
        # failed epoch leaked the ScalarLogger's writer)
        try:
            if self.streaming and commit_schedule is not None:
                raise ValueError(
                    "streaming=True is incompatible with commit_schedule: the "
                    "staleness simulation scans the whole epoch in one program"
                )
            if self.dispatch_epochs > 1:
                if self.streaming:
                    raise ValueError(
                        "dispatch_epochs>1 needs the whole epoch on device; "
                        "streaming=True feeds it window by window"
                    )
                if commit_schedule is not None:
                    raise ValueError(
                        "dispatch_epochs>1 is incompatible with commit_schedule "
                        "(the staleness simulation dispatches per epoch)"
                    )
                state, epoch_stats = self._train_chunked(
                    engine, state, feats, labels, num_workers, window, shuffle,
                    ckpt, start_epoch, _materialise, prof,
                )
                # all epochs consumed; the per-epoch loop below runs zero times
                start_epoch = self.num_epoch
            stream_window = window
            if self.streaming and window is None:
                # No-commit trainers (SingleTrainer/Ensemble) have no natural
                # window; stream in fixed blocks with a ragged tail
                # (pad_to_window=False below), so the step count — and therefore
                # the trajectory — matches the in-memory path exactly.  The tail
                # costs one extra compile; forcing divisor-sized blocks instead
                # could degenerate to 1-step dispatches on prime step counts.
                from distkeras_tpu.data import plan_epoch

                steps = plan_epoch(len(feats), num_workers, self.batch_size, 1)[0]
                stream_window = min(steps, 32)
            for epoch in range(start_epoch, self.num_epoch):
                if _chaos.enabled():
                    _chaos.fault("epoch")  # seeded kill entering this epoch
                if prof is not None:
                    prof.on_step(epoch)
                with telemetry.trace.loop_span(
                        "epoch", epoch=epoch, epochs=1):
                    if self.streaming:
                        from distkeras_tpu.data import epoch_window_iter, plan_epoch

                        if window is not None:
                            total_windows = plan_epoch(
                                len(feats), num_workers, self.batch_size, window)[0]
                        else:
                            steps = plan_epoch(
                                len(feats), num_workers, self.batch_size, 1)[0]
                            total_windows = -(-steps // stream_window)
                        start_block = 0
                        if resume_data is not None and epoch == start_epoch:
                            start_block = min(
                                int(resume_data.block_cursor), total_windows)
                        # bit state BEFORE this epoch's shuffle — what a
                        # mid-epoch DataState must carry (the window iterator
                        # is lazy: the shuffle is drawn at its first next())
                        rng_bits = rng.bit_generator.state if shuffle else None
                        blocks = epoch_window_iter(
                            feats, labels, num_workers, self.batch_size, stream_window,
                            rng=rng if shuffle else None,
                            pad_to_window=window is not None,
                            feature_dtype=self.compute_dtype,
                            start_block=start_block,
                        )
                        if self.prefetch > 0:
                            from distkeras_tpu.datapipe import PrefetchRing

                            blocks = PrefetchRing(
                                blocks, depth=self.prefetch,
                                put_fn=engine.stream_put,
                            )
                        if _chaos.enabled():
                            # seeded kill/stall at a block index, downstream
                            # of the prefetch ring so the fault reaches the
                            # consumer directly (host-side only — the jitted
                            # program is untouched)
                            blocks = _chaos.wrap_blocks(blocks)
                        on_window = None
                        if ckpt is not None and self.checkpoint_blocks:
                            from distkeras_tpu.datapipe import DataState

                            def on_window(live_state, done, _epoch=epoch,
                                          _base=start_block, _bits=rng_bits,
                                          _total=total_windows):
                                # ``done`` windows consumed this run; the
                                # live epoch counter reads _epoch + done
                                # (run_epoch_streaming's end-of-epoch fixup
                                # hasn't happened yet), so rewind it to the
                                # epoch being trained.  Skip the final block
                                # — the epoch-boundary save supersedes it.
                                cursor = _base + done
                                if done % self.checkpoint_blocks or cursor >= _total:
                                    return
                                ckpt.save_partial(
                                    live_state.replace(
                                        epoch=live_state.epoch - done),
                                    _epoch,
                                    DataState(epoch=_epoch, block_cursor=cursor,
                                              rng_state=_bits),
                                )

                        run_one = (
                            lambda blocks=blocks, on_window=on_window:
                            engine.run_epoch_streaming(
                                state, blocks, on_window=on_window))
                    else:
                        if window is None:
                            # single window spanning the whole epoch (no commits)
                            from distkeras_tpu.data import plan_epoch

                            steps = plan_epoch(len(feats), num_workers, self.batch_size, 1)[0]
                            xs, ys = epoch_arrays(
                                feats, labels, num_workers, self.batch_size, steps,
                                rng=rng if shuffle else None,
                            )
                        else:
                            xs, ys = epoch_arrays(
                                feats, labels, num_workers, self.batch_size, window,
                                stepwise=commit_schedule is not None,
                                rng=rng if shuffle else None,
                            )
                        xs, ys = engine.shard_batches(xs, ys)
                        run_one = lambda xs=xs, ys=ys: engine.run_epoch(state, xs, ys)
                    state, stats = run_one()
                    ps = getattr(self, "parameter_server", None)
                    if ps is not None:
                        # live PS observability: copy the commit counter off
                        # this epoch's state before the next dispatch donates it
                        ps.track(getattr(state, "center_rule", None))
                    # keep the current epoch's stats as device arrays: dispatch
                    # is async, so the next epoch's host-side batching overlaps
                    # this epoch's device compute.  Materialise the previous
                    # epoch's stats now (its compute is long done) so retention
                    # stays O(1).
                    if epoch_stats and not isinstance(
                            jax.tree.leaves(epoch_stats[-1])[0], np.ndarray):
                        epoch_stats[-1] = _materialise(epoch_stats[-1], epoch - 1)
                    epoch_stats.append(stats)
                    if watchdog is not None:
                        # an armed watchdog trades the one-epoch async
                        # overlap for prompt detection: materialise (and
                        # observe) the epoch that just ran instead of
                        # deferring it to the next iteration
                        epoch_stats[-1] = _materialise(stats, epoch)
                        if watchdog.pending_rollback:
                            state = self._watchdog_rollback(
                                engine, ckpt, state, watchdog)
                            continue  # don't checkpoint the diverged state
                    if ckpt is not None:
                        # epoch-boundary DataState: cursor 0 at the next
                        # epoch, RNG bits as they stand now (= before the
                        # next epoch's shuffle) — resume restores the exact
                        # bit state instead of replaying permutations
                        from distkeras_tpu.datapipe import DataState

                        ckpt.maybe_save(state, epoch, data_state=DataState(
                            epoch=epoch + 1, block_cursor=0,
                            rng_state=(rng.bit_generator.state
                                       if shuffle else None),
                        ))
                    if staleness_policy is not None:
                        # adapt the staleness bound from THIS epoch's summary
                        # (costs the one-epoch async overlap, same trade the
                        # watchdog makes)
                        if epoch_stats and not isinstance(
                                jax.tree.leaves(epoch_stats[-1])[0],
                                np.ndarray):
                            epoch_stats[-1] = _materialise(
                                epoch_stats[-1], epoch)
                        summary = last_summary.get("value")
                        if summary is not None:
                            state = self._apply_staleness_bound(
                                staleness_policy, summary, state)
                    if _fleet.preemption_requested():
                        # SIGTERM arrived: leave a boundary checkpoint for
                        # whoever resumes, then exit loudly instead of dying
                        # mid-step on the follow-up SIGKILL
                        if ckpt is not None:
                            if (epoch + 1) % self.checkpoint_every:
                                from distkeras_tpu.datapipe import DataState

                                ckpt.save_partial(state, epoch, DataState(
                                    epoch=epoch + 1, block_cursor=0,
                                    rng_state=(rng.bit_generator.state
                                               if shuffle else None)))
                            ckpt.wait()
                        raise _fleet.Preempted(
                            f"preempted (SIGTERM); drained to the epoch "
                            f"{epoch + 1} boundary"
                            + (" checkpoint" if ckpt is not None else ""))
                    if elastic_ctl is not None and epoch + 1 < self.num_epoch:
                        desired = elastic_ctl.poll()
                        if desired and desired != num_workers:
                            engine, state = self._elastic_resize(
                                build_engine, engine, state, ckpt, epoch,
                                rng, shuffle, desired)
                            num_workers = desired
                            resume_data = None
            if epoch_stats and not isinstance(
                    jax.tree.leaves(epoch_stats[-1])[0], np.ndarray):
                epoch_stats[-1] = _materialise(epoch_stats[-1], self.num_epoch - 1)
            if ckpt is not None:
                ckpt.wait()  # flush in-flight async saves before declaring done
        finally:
            if prof is not None:
                prof.close()
            if scalar_log is not None:
                scalar_log.close()
        if average_at_end:
            state, _ = engine.average_workers(state)
        # every epoch's losses have been read, so the readiness thread's
        # last spans are a moment away: have the ring whole on return
        telemetry.trace.drain()

        losses_per_epoch = [float(_epoch_mean(s, "loss")) for s in epoch_stats]
        metrics_per_epoch = [
            _epoch_mean(s, "metrics") for s in epoch_stats
            if np.asarray(s["metrics"]).size
        ]
        self.record_training_stop()

        self.history = {"loss": losses_per_epoch, "training_time": self.get_training_time()}
        for i, name in enumerate(metrics):
            if metrics_per_epoch:
                key = name if isinstance(name, str) else getattr(name, "__name__", f"metric_{i}")
                self.history[key] = [float(m[i]) for m in metrics_per_epoch]
        if telemetry.enabled():
            tt = self.get_training_time()
            telemetry.metrics.gauge(
                "training_seconds", help="wall seconds of the last fit"
            ).set(tt)
            if tt > 0 and epoch_stats:
                telemetry.metrics.gauge(
                    "samples_per_sec_per_chip",
                    help="trained samples per second per device (last fit)",
                ).set(len(epoch_stats) * len(feats) / tt
                      / int(engine.mesh.devices.size))
            # one file pair per process under DISTKERAS_TELEMETRY[_DIR]:
            # the Chrome trace (open in Perfetto) and a metrics snapshot
            telemetry.flush()
        if sanitizer.enabled() and not sanitizer.strict():
            # record mode: per-violation warnings fire once per guard kind,
            # so close the fit with the full tally — the operator's cue to
            # re-run strict (or dklint) before this reaches a TPU pod
            recorded = sanitizer.violations()
            if recorded:
                kinds = sorted({k for k, _ in recorded})
                warnings.warn(
                    f"sanitizer recorded {len(recorded)} violation(s) during "
                    f"this fit ({', '.join(kinds)} guard"
                    f"{'s' if len(kinds) > 1 else ''}); see the sanitizer_* "
                    "counters, or run with DISTKERAS_SANITIZE=strict to fail "
                    "at the offending dispatch",
                    RuntimeWarning,
                )
        return engine, state, adapter

    def _train_chunked(
        self, engine, state, feats, labels, num_workers, window,
        shuffle, ckpt, start_epoch, _materialise, prof,
    ):
        """The ``dispatch_epochs>1`` epoch loop: up to ``dispatch_epochs``
        epochs per device dispatch via :meth:`WindowedEngine.run_epochs`,
        reshuffling ON DEVICE between epochs when ``shuffle`` is set.

        Chunks never straddle a ``checkpoint_every`` boundary, so the set of
        checkpointed epochs is identical to the per-epoch loop's.  Returns
        ``(state, epoch_stats)`` with every epoch's stats but the last
        already materialised — the caller's trailing ``_materialise`` call
        finishes the last one, same invariant as the per-epoch loop.
        ``prof`` (a ``ProfilerHook`` or None) counts chunks as its steps.
        """
        from distkeras_tpu.data import plan_epoch

        if window is None:
            steps = plan_epoch(len(feats), num_workers, self.batch_size, 1)[0]
            xs, ys = epoch_arrays(feats, labels, num_workers, self.batch_size, steps)
        else:
            xs, ys = epoch_arrays(feats, labels, num_workers, self.batch_size, window)
        xs, ys = engine.shard_batches(xs, ys)
        shuffle_seed = self.seed if shuffle else None

        def split(stats, chunk):
            """Chunk stats -> per-epoch dicts (leaves keep [n_windows, ...])."""
            out = []
            for e in range(chunk):
                out.append(jax.tree.map(
                    lambda a: a.reshape((chunk, a.shape[0] // chunk) + a.shape[1:])[e],
                    stats,
                ))
            return out

        epoch_stats: List[dict] = []
        epoch = start_epoch
        chunk_idx = 0
        ps = getattr(self, "parameter_server", None)
        while epoch < self.num_epoch:
            chunk = min(self.dispatch_epochs, self.num_epoch - epoch)
            if ckpt is not None:
                chunk = min(chunk, self.checkpoint_every - epoch % self.checkpoint_every)
            if prof is not None:
                prof.on_step(start_epoch + chunk_idx)
            # "epoch" span per chunk dispatch (attrs carry how many epochs it
            # covers), around the same spans as the per-epoch loop's
            with telemetry.trace.loop_span("epoch", epoch=epoch, epochs=chunk):
                state, stats = engine.run_epochs(
                    state, xs, ys, chunk, shuffle_seed=shuffle_seed)
                if ps is not None:
                    # live progress, as in the per-epoch loop: copy the
                    # commit counter off this chunk's state before the next
                    # dispatch donates it
                    ps.track(getattr(state, "center_rule", None))
                # Same O(1)-retention scheme as the per-epoch loop:
                # materialise the previous chunk's stats (long computed)
                # while this chunk's stay device-resident.
                for i, s in enumerate(epoch_stats):
                    if not isinstance(jax.tree.leaves(s)[0], np.ndarray):
                        epoch_stats[i] = _materialise(s, i + start_epoch)
            epoch_stats.extend(split(stats, chunk))
            epoch += chunk
            chunk_idx += 1
            if ckpt is not None:
                ckpt.maybe_save(state, epoch - 1)
        for i, s in enumerate(epoch_stats[:-1]):
            if not isinstance(jax.tree.leaves(s)[0], np.ndarray):
                epoch_stats[i] = _materialise(s, i + start_epoch)
        return state, epoch_stats

    def _finalize(self, engine: WindowedEngine, state, adapter: ModelAdapter, use_center: bool = True):
        """Materialise the trained model in the same type the user passed in."""
        if use_center:
            params = jax.tree.map(np.asarray, engine.gather_center(state))
        else:
            params = engine.worker_slice(state.local_params, 0)
        model_state = jax.tree.map(np.asarray, engine.final_model_state(state))
        adapter = _serving_twin(adapter)
        if hasattr(adapter, "assign"):  # Keras path: mutate + return the Keras model
            return adapter.assign(params, model_state)
        return TrainedModel(adapter, params, model_state, history=self.history)

    def train(self, dataframe: DataFrame, shuffle: bool = False):
        raise NotImplementedError


class SingleTrainer(Trainer):
    """Single-worker baseline (reference parity: ``SingleTrainer`` — coalesce
    to one partition, run a SequentialWorker)."""

    def train(self, dataframe: DataFrame, shuffle: bool = False):
        worker = workers_mod.SequentialWorker(self.worker_optimizer, self.batch_size)
        engine, state, adapter = self._fit(
            dataframe, worker.rule, num_workers=1, shuffle=shuffle
        )
        return self._finalize(engine, state, adapter, use_center=False)


class AveragingTrainer(Trainer):
    """Synchronous one-shot weight averaging (reference parity:
    ``AveragingTrainer.average_models``): N independent replicas, averaged once
    at the end via a single ``pmean`` over the mesh."""

    def __init__(self, *args, num_workers: int = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_workers = num_workers or jax.device_count()

    def train(self, dataframe: DataFrame, shuffle: bool = False):
        worker = workers_mod.AveragingWorker(self.worker_optimizer, self.batch_size)
        engine, state, adapter = self._fit(
            dataframe, worker.rule, self.num_workers, shuffle=shuffle, average_at_end=True
        )
        return self._finalize(engine, state, adapter, use_center=True)


class EnsembleTrainer(Trainer):
    """Train N independent models, return all of them (reference parity:
    ``EnsembleTrainer``)."""

    def __init__(self, *args, num_models: int = 2, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_models = num_models

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> List:
        worker = workers_mod.SequentialWorker(self.worker_optimizer, self.batch_size)
        engine, state, adapter = self._fit(
            dataframe, worker.rule, self.num_models, shuffle=shuffle
        )
        adapter = _serving_twin(adapter)
        if hasattr(adapter, "assign"):
            # Keras in -> Keras models out (reference parity: the reference's
            # EnsembleTrainer returned N deserialised Keras models).  One
            # independent clone per ensemble member, each carrying its own
            # worker's weights — adapter.assign would mutate the single
            # shared wrapped model N times, leaving N handles to the last
            # worker's weights.
            import keras

            from distkeras_tpu.models.keras_adapter import assign_keras_weights

            models = []
            for i in range(self.num_models):
                params_i = engine.worker_slice(state.local_params, i)
                state_i = engine.worker_slice(state.model_state, i)
                clone = keras.models.clone_model(adapter.model)
                if not clone.built:
                    clone.build(adapter.model.input_shape)
                assign_keras_weights(clone, params_i, state_i.get("ntv"))
                models.append(clone)
            return models
        model_state = jax.tree.map(np.asarray, engine.final_model_state(state))
        return [
            TrainedModel(adapter, engine.worker_slice(state.local_params, i),
                         model_state, history=self.history)
            for i in range(self.num_models)
        ]


class DistributedTrainer(Trainer):
    """Parameter-server training base (reference parity: ``DistributedTrainer``).

    Owns the PS lifecycle (`service`/`stop_service` are retained as no-op-ish
    facades over the on-device center variable) and the worker allocation
    hook; subclasses pick the algorithm.
    """

    parameter_server_class = DeltaParameterServer

    def __init__(
        self,
        keras_model: Any,
        loss: Any = "categorical_crossentropy",
        worker_optimizer: Any = "sgd",
        metrics: Sequence = ("accuracy",),
        num_workers: Optional[int] = None,
        batch_size: int = 32,
        features_col: str = "features",
        label_col: str = "label",
        num_epoch: int = 1,
        master_port: int = 5000,
        seed: int = 0,
        compute_dtype: Any = None,
        commit_schedule: Optional[Sequence[int]] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        resume: bool = False,
        profile_dir: Optional[str] = None,
        seq_shards: int = 1,
        tp_shards: int = 1,
        fsdp: bool = False,
        tensorboard_dir: Optional[str] = None,
        streaming: bool = False,
        remat: bool = False,
        unroll=1,
        dispatch_epochs: int = 1,
        pipeline_stages: int = 1,
        pp_microbatches: Optional[int] = None,
        tp_spec_fn: Optional[Any] = None,
        prefetch: int = 0,
        checkpoint_blocks: int = 0,
        elastic: Optional[Any] = None,
        staleness_policy: Optional[Any] = None,
    ):
        super().__init__(
            keras_model, loss, worker_optimizer, metrics,
            features_col, label_col, batch_size, num_epoch, seed, compute_dtype,
            checkpoint_dir, checkpoint_every, resume, profile_dir, seq_shards,
            tp_shards, fsdp, tensorboard_dir, streaming, remat, unroll,
            dispatch_epochs, pipeline_stages, pp_microbatches, tp_spec_fn,
            prefetch, checkpoint_blocks,
        )
        self.num_workers = num_workers or jax.device_count()
        self.master_port = master_port
        #: fleet.ElasticMembership (or any object with ``poll() -> int|None``)
        #: — polled at epoch boundaries to resize the worker set mid-run
        self.elastic = elastic
        #: adaptive.AdaptiveBound (or any ``observe(summary) -> float``) —
        #: retunes an AdaptiveDynSGD rule's staleness bound between epochs
        self.staleness_policy = staleness_policy
        self.parameter_server: Optional[ParameterServer] = None
        # Optional per-worker commit periods: the deterministic staleness
        # simulation (SURVEY.md §7 "asynchrony semantics on SPMD hardware").
        self.commit_schedule = (
            None if commit_schedule is None else np.asarray(commit_schedule, np.int32)
        )

    def allocate_worker(self) -> workers_mod.Worker:
        raise NotImplementedError

    def allocate_parameter_server(self) -> ParameterServer:
        return self.parameter_server_class(self.master_model, self.master_port)

    def service(self) -> None:
        """Reference parity: started the PS thread.  Here the center variable
        is created on-device by the engine; this just builds the facade."""
        self.parameter_server = self.allocate_parameter_server()
        self.parameter_server.start()

    def stop_service(self) -> None:
        if self.parameter_server is not None:
            self.parameter_server.stop()

    @property
    def num_updates(self) -> int:
        return self.parameter_server.num_updates if self.parameter_server else 0

    def train_with_recovery(self, dataframe: DataFrame, shuffle: bool = False,
                            max_retries: int = 2, backoff_base: float = 0.5,
                            backoff_cap: float = 30.0):
        """Failure-tolerant training (SURVEY.md §5.3).

        The reference leaned on Spark task retries (a retried worker
        reconnects to the PS and keeps training); a JAX SPMD program instead
        fails as a unit, so the recovery unit is the epoch: on an exception
        the trainer reloads the latest checkpoint and resumes.  Requires
        ``checkpoint_dir``; each retry restarts from the last completed
        checkpointed epoch (bit-exact — see test_checkpoint).

        Retries are reserved for transient failures: a retry happens only if
        a checkpoint exists to restore from, and never for the same exception
        signature twice in a row — a deterministic bug (shape error, OOM)
        surfaces immediately instead of being re-run ``max_retries`` times.
        Retries back off exponentially (``backoff_base * 2^k`` capped at
        ``backoff_cap``, x0.5–1.0 jitter) so a fleet of recovering workers
        doesn't stampede the shared checkpoint store, and a SIGTERM
        preemption (:class:`distkeras_tpu.fleet.Preempted`) is never
        retried — the boundary checkpoint is on disk and the process is
        meant to exit.
        """
        if not self.checkpoint_dir:
            raise ValueError("train_with_recovery requires checkpoint_dir")
        from distkeras_tpu.checkpoint import committed_steps, latest_step

        _fleet.install_preemption_handler()
        attempts = 0
        last_failure = None
        last_step = None
        while True:
            try:
                return self.train(dataframe, shuffle)
            except _fleet.Preempted:
                raise  # drained to a boundary checkpoint; exit, don't retry
            except Exception as e:  # noqa: BLE001 — re-raised unless retryable
                failure = (type(e), str(e))
                try:
                    step = latest_step(self.checkpoint_dir)
                except Exception:  # noqa: BLE001 — see below
                    # latest_step flushes in-flight async saves, so a save
                    # that failed in the background re-raises HERE — it
                    # must not mask the training error we're handling or
                    # bypass the retry.  Fall back to the committed
                    # directory listing (final step_ names only appear
                    # after commit, so no flush is needed for those).
                    on_disk = committed_steps(self.checkpoint_dir)
                    step = on_disk[-1] if on_disk else None
                if step != last_step:
                    # checkpointed progress since the previous failure: a
                    # repeating signature is a recurring *transient* (e.g.
                    # periodic preemption), not a deterministic bug
                    last_failure = None
                attempts += 1
                if attempts > max_retries or failure == last_failure or step is None:
                    raise
                last_failure = failure
                last_step = step
                self.resume = True  # pick up from the latest checkpoint
                if backoff_base > 0:
                    import random as _random

                    delay = min(backoff_cap,
                                backoff_base * (2 ** (attempts - 1)))
                    time.sleep(delay * (0.5 + 0.5 * _random.random()))

    @property
    def _logical_workers(self) -> int:
        """Logical worker count; AsynchronousDistributedTrainer multiplies by
        ``parallelism_factor`` (the reference's Spark over-partitioning),
        realised here as virtual workers per device."""
        return self.num_workers * getattr(self, "parallelism_factor", 1)

    def train(self, dataframe: DataFrame, shuffle: bool = False):
        worker = self.allocate_worker()
        self.service()
        engine, state, adapter = self._fit(
            dataframe, worker.rule, self._logical_workers, shuffle=shuffle,
            commit_schedule=self.commit_schedule,
        )
        self.parameter_server.attach(
            engine.gather_center(state), jax.tree.map(np.asarray, state.center_rule),
        )
        self.stop_service()
        model = self._finalize(engine, state, adapter, use_center=True)
        self.parameter_server.model = model
        return model


class AsynchronousDistributedTrainer(DistributedTrainer):
    """Reference parity: adds ``parallelism_factor`` (Spark over-partitioning
    so stragglers overlap).  On a synchronous mesh there are no stragglers; the
    knob is kept for API compat and maps onto the staleness simulation."""

    def __init__(self, *args, parallelism_factor: int = 1, **kwargs):
        super().__init__(*args, **kwargs)
        self.parallelism_factor = parallelism_factor


class DOWNPOUR(AsynchronousDistributedTrainer):
    """Downpour SGD (Dean et al. 2012) — windowed delta commits."""

    def __init__(self, *args, communication_window: int = 5, **kwargs):
        super().__init__(*args, **kwargs)
        self.communication_window = communication_window

    def allocate_worker(self):
        return workers_mod.DOWNPOURWorker(
            self.worker_optimizer, self.batch_size, self.features_col,
            self.label_col, self.communication_window,
        )


class AEASGD(AsynchronousDistributedTrainer):
    """Asynchronous Elastic Averaging SGD (Zhang et al. 2015)."""

    def __init__(self, *args, communication_window: int = 32, rho: float = 5.0,
                 learning_rate: float = 0.1, **kwargs):
        super().__init__(*args, **kwargs)
        self.communication_window = communication_window
        self.rho = rho
        self.learning_rate = learning_rate

    def allocate_worker(self):
        return workers_mod.AEASGDWorker(
            self.worker_optimizer, self.batch_size, self.features_col, self.label_col,
            self.communication_window, self.rho, self.learning_rate,
        )


class EAMSGD(AsynchronousDistributedTrainer):
    """Elastic Averaging with (Nesterov) momentum (Zhang et al. 2015)."""

    def __init__(self, *args, communication_window: int = 32, rho: float = 5.0,
                 learning_rate: float = 0.1, momentum: float = 0.9, **kwargs):
        # Default worker_optimizer to None (=> Nesterov momentum SGD via
        # _effective_worker_optimizer) ONLY when the caller didn't pass one —
        # positionally (reference style: EAMSGD(model, loss, "sgd")) or by
        # keyword.  args[2] is worker_optimizer in the Trainer signature.
        if len(args) < 3 and "worker_optimizer" not in kwargs:
            kwargs["worker_optimizer"] = None
        super().__init__(*args, **kwargs)
        self.communication_window = communication_window
        self.rho = rho
        self.learning_rate = learning_rate
        self.momentum = momentum

    def _effective_worker_optimizer(self):
        # default worker optimizer = Nesterov momentum SGD (the reference's
        # explicit velocity update on the local variable), resolved fresh per
        # train() call so changed learning_rate/momentum take effect on retrain
        if self.worker_optimizer is not None:
            return self.worker_optimizer
        return (
            "sgd",
            {"learning_rate": self.learning_rate, "momentum": self.momentum, "nesterov": True},
        )

    def allocate_worker(self):
        return workers_mod.EAMSGDWorker(
            self._effective_worker_optimizer(), self.batch_size, self.features_col,
            self.label_col, self.communication_window, self.rho, self.learning_rate,
            self.momentum,
        )


class ADAG(AsynchronousDistributedTrainer):
    """Accumulated-Gradient Normalisation (Hermans, arXiv:1710.02368)."""

    parameter_server_class = ADAGParameterServer

    def __init__(self, *args, communication_window: int = 12, **kwargs):
        super().__init__(*args, **kwargs)
        self.communication_window = communication_window

    def allocate_worker(self):
        return workers_mod.ADAGWorker(
            self.worker_optimizer, self.batch_size, self.features_col,
            self.label_col, self.communication_window,
        )


class DynSGD(AsynchronousDistributedTrainer):
    """Staleness-aware dynamic-LR SGD (SIGMOD'17 rule)."""

    parameter_server_class = DynSGDParameterServer

    def __init__(self, *args, communication_window: int = 5, **kwargs):
        super().__init__(*args, **kwargs)
        self.communication_window = communication_window

    def allocate_worker(self):
        return workers_mod.DynSGDWorker(
            self.worker_optimizer, self.batch_size, self.features_col,
            self.label_col, self.communication_window,
        )


class AdaptiveDynSGD(DynSGD):
    """DynSGD with an SSP-style staleness bound carried in the center state
    (beyond reference; ABS arXiv:2301.08895 / DynSSP arXiv:1908.11848).

    Pass ``staleness_policy=AdaptiveBound(...)`` to retune the bound online
    between epochs from the dynamics telemetry (needs
    ``DISTKERAS_DYNAMICS=1``); with the default ``inf`` bound and no policy
    the trajectory is bit-for-bit DynSGD."""

    def __init__(self, *args, communication_window: int = 5,
                 initial_bound: float = float("inf"), **kwargs):
        super().__init__(*args, communication_window=communication_window,
                         **kwargs)
        self.initial_bound = initial_bound

    def allocate_worker(self):
        return workers_mod.AdaptiveDynSGDWorker(
            self.worker_optimizer, self.batch_size, self.features_col,
            self.label_col, self.communication_window, self.initial_bound,
        )
