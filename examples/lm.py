"""Causal language modeling across the parallelism axes.

Beyond the reference's classifier-only scope: trains a small causal
transformer LM on a synthetic next-token corpus five ways —

  1. data parallel            (TransformerLM, 4 workers)
  2. + sequence parallelism   (causal ring attention, per-token labels
                               sharded over the seq axis with the tokens)
  3. pipeline parallel        (StagedLM: GPipe-for-LM, 4 workers x 2 stages)
  4. tp + FSDP center         (GSPMD engine: embedding/head center copies
                               sharded over workers AND model axes)
  5. HuggingFace fine-tune    (a transformers FlaxGPT2LMHeadModel through
                               the same trainer — its params are the
                               initial center, as from_pretrained's would be)
  6. GPT-2 on the pipeline    (gpt2_to_staged re-lays the checkpoint into
                               the staged layout; pipeline_stages=2 +
                               fsdp=True stage-shards embed/head; decode
                               through the pipelined executor)

— then greedily generates from the trained model with a carried KV cache
(one jitted prefill + scan program; see distkeras_tpu/models/generate.py).  Runs on a faked
8-device CPU mesh so it works anywhere (delete the two config lines on
real chips).

Run:  python examples/lm.py [--epochs E]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

if os.environ.get("DK_TPU") != "1":  # delete these two lines on real chips
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

VOCAB = 23
SEQ = 16


def corpus(n=512, seed=0):
    """Next token = (token + 1) mod VOCAB, random start per sequence."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, VOCAB, size=(n, 1))
    x = ((start + np.arange(SEQ)) % VOCAB).astype(np.int32)
    return x, ((x + 1) % VOCAB).astype(np.int32)


def generate(model, ctx, steps=6):
    """KV-cached greedy decode (models/generate.py): prefill + scanned
    single-token steps in one jitted program, O(context) per step instead of
    the O(context^2) full recompute — token-identical to it
    (tests/test_generate.py)."""
    from distkeras_tpu.models import greedy_generate

    return greedy_generate(model, ctx, steps)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=12)
    args = parser.parse_args()
    from distkeras_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

    import distkeras_tpu as dk
    from distkeras_tpu.models import FlaxModel, StagedLM, TransformerLM

    x, y = corpus()
    df = dk.from_numpy(x, y)
    common = dict(loss="token_crossentropy", metrics=("token_accuracy",),
                  batch_size=16, num_epoch=args.epochs,
                  communication_window=2)

    def report(tag, trainer):
        trained = trainer.train(df)
        h = trainer.get_history()
        print(f"{tag:32s} loss {h['loss'][0]:.2f}->{h['loss'][-1]:.3f} "
              f"token-acc {h['token_accuracy'][-1]:.3f} "
              f"time {trainer.get_training_time():.1f}s")
        return trained

    report("LM data parallel (4w)", dk.DOWNPOUR(
        FlaxModel(TransformerLM(vocab_size=VOCAB, dim=32, heads=2,
                                num_layers=1, max_len=64)),
        worker_optimizer=("adam", {"learning_rate": 1e-3}),
        num_workers=4, **common))

    report("LM + ring attention (4w x 2seq)", dk.DOWNPOUR(
        FlaxModel(TransformerLM(vocab_size=VOCAB, dim=32, heads=2,
                                num_layers=1, max_len=64, seq_axis="seq")),
        worker_optimizer=("adam", {"learning_rate": 1e-3}),
        num_workers=4, seq_shards=2, **common))

    trained = report("LM pipeline (4w x 2 stages)", dk.DOWNPOUR(
        StagedLM(vocab_size=VOCAB, dim=32, heads=2, num_stages=2,
                 blocks_per_stage=1, max_len=64),
        worker_optimizer=("adam", {"learning_rate": 1e-3}),
        num_workers=4, pipeline_stages=2, **common))

    # FSDP: the LM's embedding + output head dominate its params — with
    # fsdp=True their center copies shard over the workers axis instead of
    # replicating (ZeRO-3 gather-at-use), here composed with 2-way TP
    report("LM + fsdp center (4w x 2mp)", dk.DOWNPOUR(
        FlaxModel(TransformerLM(vocab_size=VOCAB, dim=32, heads=2,
                                num_layers=1, max_len=64)),
        worker_optimizer=("adam", {"learning_rate": 1e-3}),
        num_workers=4, tp_shards=2, fsdp=True, **common))

    # 5. a HuggingFace Flax model through the identical trainer call —
    #    swap the config-initialised model for .from_pretrained(...) to
    #    fine-tune a real checkpoint
    try:
        from transformers import FlaxGPT2LMHeadModel, GPT2Config
    except ImportError:
        print("transformers not installed -- skipping the HF variant")
    else:
        hf = FlaxGPT2LMHeadModel(
            GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=32,
                       n_layer=1, n_head=2, resid_pdrop=0.0,
                       embd_pdrop=0.0, attn_pdrop=0.0),
            seed=0, input_shape=(1, 8))
        report("HF GPT-2 fine-tune (4w)", dk.DOWNPOUR(
            hf, worker_optimizer=("adam", {"learning_rate": 3e-3}),
            num_workers=4, **common))

        # 6. the same checkpoint ONTO THE PIPELINE MESH: gpt2_to_staged
        #    re-lays the weights into the staged layout (logit-identical —
        #    tests/test_hf_staged.py), fsdp=True stage-shards the
        #    vocab-scale embedding/head, and decode runs through the
        #    pipelined executor (one stage's blocks + KV cache per device)
        from distkeras_tpu.models import gpt2_to_staged
        from distkeras_tpu.models.generate import greedy_generate_staged_pipelined

        hf2 = FlaxGPT2LMHeadModel(
            GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=32,
                       n_layer=2, n_head=2, resid_pdrop=0.0,
                       embd_pdrop=0.0, attn_pdrop=0.0),
            seed=0, input_shape=(1, 8))
        staged = gpt2_to_staged(hf2, num_stages=2)
        tuned = report("GPT-2 on pipeline+fsdp (4w x 2st)", dk.DOWNPOUR(
            staged, worker_optimizer=("adam", {"learning_rate": 3e-3}),
            num_workers=4, pipeline_stages=2, fsdp=True, **common))
        pp_ctx = greedy_generate_staged_pipelined(
            staged, tuned.params, x[:1, :8], 6, devices=jax.devices()[:2])
        print("pipelined GPT-2 generation:", pp_ctx[0, 8:])

    ctx = generate(trained, x[:1, :8])
    print("greedy generation:", ctx[0, 8:], "from context ending at", ctx[0, 7])


if __name__ == "__main__":
    main()
