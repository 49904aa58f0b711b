"""Worker script for the multi-host integration test.

Launched as N separate processes by test_multihost.py; each joins the
jax.distributed coordination service (the reference's master host:port
handshake), contributes its faked CPU devices, and trains DOWNPOUR over the
global 8-device mesh — commits ride the cross-process collective path (the
DCN analogue).  ``engine=windowed`` runs the shard_map engine over a 1-D
workers mesh; ``engine=gspmd`` runs the pjit engine over a 2-D
(workers, model) mesh, so tensor-parallel sharding propagation is exercised
across process boundaries too; ``engine=fsdp`` stores the center variable
ZeRO-3-sharded over a workers axis spanning both processes.
"""

import sys


def _elastic(mode: str, process_id: int, num_processes: int,
             ckpt_dir: str) -> None:
    """Datapipe elastic-resume rehearsal (two phases, separate invocations).

    ``elastic_save`` (2 processes): full trainer flow — streaming +
    PrefetchRing + mid-epoch block checkpoints — killed by a simulated
    preemption at block 3 of epoch 1, leaving a partial step with a
    DataState cursor on the shared checkpoint dir.  ``elastic_resume``
    (4 processes): a fresh trainer at a DIFFERENT host topology (same
    8-device global mesh) restores model + DataState, replays the epoch's
    shuffle, skips the consumed blocks, and trains to completion.
    """
    import numpy as np

    import distkeras_tpu as dk
    from distkeras_tpu import checkpoint as ck
    from distkeras_tpu.datapipe import host_shard
    from distkeras_tpu.frame import from_numpy
    from distkeras_tpu.models import MLP, FlaxModel

    # the per-host sharding helper under a REAL multi-process runtime:
    # defaults pick up jax.process_index(), ranges partition the rows
    spans = [host_shard(512, i, num_processes) for i in range(num_processes)]
    assert host_shard(512) == spans[process_id]
    assert spans[0][0] == 0 and spans[-1][1] == 512
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))

    rng = np.random.default_rng(0)  # same data on every process (SPMD)
    x = rng.normal(size=(512, 8)).astype(np.float32)
    y = (x @ rng.normal(size=(8,)) > 0).astype(np.int32)
    onehot = np.eye(2, dtype=np.float32)[y]
    df = from_numpy(x, onehot)

    def trainer(resume):
        return dk.DOWNPOUR(
            FlaxModel(MLP(features=(16,), num_classes=2)),
            loss="categorical_crossentropy",
            worker_optimizer=("sgd", {"learning_rate": 0.1}),
            num_workers=8, batch_size=8, num_epoch=3,
            communication_window=2, seed=3, streaming=True, prefetch=2,
            checkpoint_dir=ckpt_dir, checkpoint_blocks=2, resume=resume,
        )

    if mode == "elastic_save":
        # 4 blocks/epoch; die at block 3 of epoch 1 — after the cursor-2
        # partial save, before the boundary save
        import distkeras_tpu.data as data_mod

        orig_iter = data_mod.epoch_window_iter
        calls = {"n": 0}

        def killing_iter(*a, **kw):
            calls["n"] += 1
            inner = orig_iter(*a, **kw)
            if calls["n"] == 2:
                def gen():
                    for i, blk in enumerate(inner):
                        if i == 3:
                            raise RuntimeError("simulated preemption")
                        yield blk
                return gen()
            return inner

        data_mod.epoch_window_iter = killing_iter
        died = False
        try:
            trainer(resume=False).train(df, shuffle=True)
        except RuntimeError as e:
            assert "preemption" in str(e)
            died = True
        assert died, "simulated preemption did not fire"
        data_mod.epoch_window_iter = orig_iter
        ck.wait_until_finished()  # commit the in-flight partial before exit
        ds = ck.restore_data_state(ckpt_dir)
        assert ds is not None and (ds.epoch, ds.block_cursor) == (1, 2), ds
    else:
        ds = ck.restore_data_state(ckpt_dir)
        assert ds is not None and (ds.epoch, ds.block_cursor) == (1, 2), ds
        t = trainer(resume=True)
        trained = t.train(df, shuffle=True)
        # resumed inside epoch 1: only epochs 1 and 2 ran here
        assert len(t.get_history()["loss"]) == 2, t.get_history()
        assert ck.latest_step(ckpt_dir) == 3
        # boundary saves supersede the mid-epoch cursor: the final sidecar
        # is a cursor-0 one carrying the next epoch's RNG bits
        final = ck.restore_data_state(ckpt_dir)
        assert final is None or int(final.block_cursor) == 0, final
        preds = np.argmax(np.asarray(trained.predict(x)), -1)
        acc = float((preds == y).mean())
        assert acc > 0.8, acc


def main(coordinator: str, num_processes: int, process_id: int,
         engine_kind: str = "windowed", ckpt_dir: str = "") -> None:
    import os

    devices_per_proc = 8 // num_processes
    import jax

    # set before the backend initialises
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", devices_per_proc)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == devices_per_proc

    if engine_kind in ("elastic_save", "elastic_resume"):
        _elastic(engine_kind, process_id, num_processes, ckpt_dir)
        print(f"process {process_id}: ok ({engine_kind})")
        jax.distributed.shutdown()
        return

    import numpy as np

    from distkeras_tpu.algorithms import Downpour
    from distkeras_tpu.models import MLP, FlaxModel

    if engine_kind == "pipeline":
        from distkeras_tpu.models import StagedTransformer
        from distkeras_tpu.parallel.pipeline import PipelineEngine

        num_workers = 4  # (workers=4, stages=2) grid over the 8 devices
        adapter = StagedTransformer(
            vocab_size=50, num_classes=2, dim=16, heads=2,
            num_stages=2, blocks_per_stage=1, max_len=16,
        )
        # Stage-major device order: row-major reshape to (workers=4,
        # stages=2) then places stage 0 on devices 0-3 (process 0) and
        # stage 1 on devices 4-7 (process 1), so EVERY ppermute stage hop
        # crosses the process boundary.  The default id order would put
        # each worker's stage pair inside one process and the pipeline
        # axis would never touch the wire.
        devs = sorted(jax.devices(), key=lambda d: d.id)
        stage_major = [devs[w + s * num_workers]
                       for w in range(num_workers) for s in range(2)]
        engine = PipelineEngine(
            adapter,
            "categorical_crossentropy",
            ("sgd", {"learning_rate": 0.05}),
            Downpour(communication_window=2),
            num_workers=num_workers,
            microbatches=2,
            devices=stage_major,
        )
        stages_of = {d.process_index for d in engine.mesh.devices[0]}
        assert len(stages_of) == num_processes, (
            f"stage axis does not span processes: {stages_of}"
        )
    elif engine_kind == "gspmd":
        from distkeras_tpu.parallel.gspmd import GSPMDEngine

        num_workers = 4  # (workers=4, model=2) grid over the 8 devices
        engine = GSPMDEngine(
            FlaxModel(MLP(features=(16,), num_classes=2)),
            "categorical_crossentropy",
            ("sgd", {"learning_rate": 0.1}),
            Downpour(communication_window=2),
            num_workers=num_workers,
            tp_shards=2,
        )
    elif engine_kind == "fsdp":
        # ZeRO-3 center sharding over a workers axis that SPANS the process
        # boundary: each process stores only its slice of the center
        # variable, and the partitioner's gather-at-pull / scatter-at-commit
        # ride the cross-process (DCN-analogue) wire.
        from distkeras_tpu.parallel.gspmd import GSPMDEngine

        num_workers = 8
        engine = GSPMDEngine(
            FlaxModel(MLP(features=(16,), num_classes=2)),
            "categorical_crossentropy",
            ("sgd", {"learning_rate": 0.1}),
            Downpour(communication_window=2),
            num_workers=num_workers,
            fsdp=True,
        )
    else:  # "windowed" per-epoch dispatch, or "epochs" single-dispatch
        from distkeras_tpu.parallel.engine import WindowedEngine

        num_workers = 8
        engine = WindowedEngine(
            FlaxModel(MLP(features=(16,), num_classes=2)),
            "categorical_crossentropy",
            ("sgd", {"learning_rate": 0.1}),
            Downpour(communication_window=2),
            num_workers=num_workers,
        )

    rng = np.random.default_rng(0)  # same data on every process (SPMD)
    if engine_kind == "pipeline":
        # token-classification data for the staged transformer: the ppermute
        # pipeline hops (and the stage-sharded param residency) cross the
        # process boundary — the DCN analogue of the reference's workers
        # living on different cluster machines
        x = rng.integers(0, 50, size=(512, 16)).astype(np.int32)
        y = ((x == 7).sum(1) > (x == 3).sum(1)).astype(np.int32)
    else:
        x = rng.normal(size=(512, 8)).astype(np.float32)
        y = (x @ rng.normal(size=(8,)) > 0).astype(np.int32)
    onehot = np.eye(2, dtype=np.float32)[y]
    batch = 512 // (num_workers * 2 * 2)
    xs = x.reshape(num_workers, 2, 2, batch, -1)
    ys = onehot.reshape(num_workers, 2, 2, batch, 2)

    state = engine.init_state(jax.random.PRNGKey(0), x[:16])
    if engine_kind == "fsdp":
        # the sharded center must actually span processes: some leaf's
        # shards live on devices owned by different process indices
        spans = any(
            len({d.process_index for d in leaf.sharding.device_set}) > 1
            and not leaf.sharding.is_fully_replicated
            for leaf in jax.tree.leaves(state.center_params)
        )
        assert spans, "no center leaf is sharded across processes"
    xs_d, ys_d = engine.shard_batches(xs, ys)
    if engine_kind == "epochs":
        # the bench harness's timed region — the multi-epoch single-dispatch
        # run_epochs program with on-device reshuffle — compiled and run
        # across processes (pod-day rehearsal: this is the program a real
        # 8x-host sweep times)
        state, stats = engine.run_epochs(state, xs_d, ys_d, 6, shuffle_seed=0)
        losses = list(np.asarray(stats["loss"]).reshape(6, -1).mean(axis=1))
    else:
        losses = []
        for _ in range(6):
            state, stats = engine.run_epoch(state, xs_d, ys_d)
            losses.append(float(np.mean(np.asarray(stats["loss"]))))
    assert losses[-1] < losses[0], losses
    assert int(np.asarray(state.center_rule["num_updates"])) == num_workers * 2 * 6
    print(f"process {process_id}: ok ({engine_kind}), "
          f"losses {losses[0]:.3f}->{losses[-1]:.3f}")
    jax.distributed.shutdown()


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
         sys.argv[4] if len(sys.argv) > 4 else "windowed",
         sys.argv[5] if len(sys.argv) > 5 else "")
