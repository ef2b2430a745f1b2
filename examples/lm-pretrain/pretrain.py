"""Decoder-LM pretraining on the full tony-tpu stack: DataLoader ->
GQA/MoE Transformer -> chunked large-vocab CE -> fit() with checkpointing.

No reference analog (tony-examples are MNIST-era scripts that hand-roll
their input and loops) — this is the "what a modern job script looks like"
example: ~60 lines of configuration, everything else is framework.

Runs standalone (single process) or under a tony-tpu gang; with
tony.application.checkpoint-dir set, a coordinator retry resumes from the
latest checkpoint automatically (fit() reads TONY_CHECKPOINT_DIR).

    python -m tony_tpu.cli.local --conf_file examples/lm-pretrain/job.toml

The model's sizes are arguments; the defaults are a CPU-sized toy. The
386M flagship (what ``chip_smoke.py`` submits to a TPU) is::

    --vocab 32768 --d-model 1024 --n-layers 28 --n-heads 8 --n-kv-heads 8
    --d-ff 4096 --seq-len 2048 --attention pallas --block-q 512
    --block-k 1024 --remat-policy attn_saved --fused-adamw --donate
    --lr 3e-4 --ce-chunk 2048 --global-batch 4

Inside a job it also writes ``$TONY_JOB_DIR/metrics/run.json``: the
devices jax ran on, the logged losses and how many Mosaic kernels the
train step lowered to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # repo root, for standalone runs

import jax
import jax.numpy as jnp
import optax


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--global-batch", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-kv-heads", type=int, default=2)
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--attention", default="blockwise",
                   help="attention backend: reference | blockwise | pallas")
    p.add_argument("--block-q", type=int, default=64,
                   help="attention q-block size")
    p.add_argument("--block-k", type=int, default=0,
                   help="pallas kv-block size (0 = same as --block-q)")
    p.add_argument("--remat-policy", default="",
                   help="rematerialize blocks keeping nothing | dots | "
                        "attn_saved ('' = no remat)")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--ce-chunk", type=int, default=256,
                   help="vocab tile width of the chunked cross-entropy")
    p.add_argument("--donate", action="store_true",
                   help="donate the train state to each step (needed "
                        "where two copies of it do not fit the device)")
    p.add_argument("--examples", type=int, default=0,
                   help="distinct synthetic sequences (0 = one fresh "
                        "batch per step; --global-batch repeats ONE "
                        "batch, which a model can memorize)")
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--moe", action="store_true", help="MoE FFN every 2nd block")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation microbatches: activation "
                        "footprint of ONE micro, optimizer amortized over "
                        "the global batch (the r5 flagship recipe trains "
                        "at micro 4 x accum 16 = batch 64)")
    p.add_argument("--fused-adamw", action="store_true",
                   help="FusedAdamW + compute-dtype carry: the update "
                        "emits the next step's bf16 params (no separate "
                        "cast pass, bf16 grads) — the bench flagship "
                        "optimizer (docs/PERF.md r5)")
    p.add_argument("--text", nargs="+", default=None, metavar="FILE",
                   help="pretrain on these text files (byte-tokenized into "
                        "a packed .bin) instead of synthetic tokens")
    args = p.parse_args()

    from tony_tpu import distributed
    from tony_tpu.data import (ByteTokenizer, DataLoader, PackedTokenSource,
                               SyntheticTokenSource, encode_files_to_bin)
    from tony_tpu.models import Transformer, TransformerConfig, moe_aux_loss
    from tony_tpu.ops import chunked_cross_entropy
    from tony_tpu.parallel import data_parallel_mesh
    from tony_tpu.parallel.sharding import batch_sharding
    from tony_tpu.train import (
        FusedAdamW,
        JsonlMetricsLogger,
        Trainer,
        fit,
    )

    distributed.initialize()  # no-op outside a gang
    mesh = data_parallel_mesh()

    tok = None
    if args.text:
        # raw text -> packed corpus: byte tokenizer keeps this offline
        tok = ByteTokenizer()
        args.vocab = tok.vocab_size
        # job dir is per-job; standalone runs get a run-unique tempdir so
        # concurrent runs on one host never clobber a live memmap —
        # removed at exit so repeated runs don't fill /tmp
        work = os.environ.get("TONY_JOB_DIR")
        if not work:
            import atexit
            import shutil

            work = tempfile.mkdtemp(prefix="lm-pretrain-")
            atexit.register(shutil.rmtree, work, ignore_errors=True)
        corpus = os.path.join(work, f"corpus-{jax.process_index()}.bin")
        n_tok = encode_files_to_bin(args.text, corpus, tok.encode,
                                    eos_id=tok.eos_id)
        print(f"tokenized {len(args.text)} file(s) -> {n_tok} tokens")

    # --fused-adamw is the bf16 recipe end to end: the MODEL computes in
    # bf16 too (compute_dtype alone would be undone by fp32 layer dtypes)
    compute_dtype = jnp.bfloat16 if args.fused_adamw else None
    cfg = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads, n_layers=args.n_layers,
        d_ff=args.d_ff, max_seq_len=args.seq_len,
        dtype=compute_dtype or jnp.float32,
        attention_backend=args.attention,
        attention_block_size=args.block_q, attention_block_k=args.block_k,
        remat=bool(args.remat_policy),
        remat_policy=args.remat_policy or "nothing",
        mesh=mesh,  # the pallas kernel runs per shard on > 1 device
        moe_every=2 if args.moe else 0, moe_num_experts=4, moe_top_k=2)
    model = Transformer(cfg)
    # initialized by one jitted program on the device (a 386M-parameter
    # init run op by op would compile hundreds of tiny programs), then
    # parked on the host: fit() places its own copy, and at flagship
    # scale a second fp32 copy is HBM the saved activations need
    params = jax.device_get(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, args.seq_len), jnp.int32)))

    def apply_fn(p, batch):
        # segment ids (packed real text): documents in one window never
        # attend across their boundaries
        segs = batch.get("segments")
        # hidden + chunked CE: the [B, L, V] logits are never materialized
        if cfg.moe_every:
            hidden, mut = model.apply(p, batch["tokens"], return_hidden=True,
                                      segment_ids=segs, mutable=["losses"])
            aux = moe_aux_loss(mut["losses"])
        else:
            hidden = model.apply(p, batch["tokens"], return_hidden=True,
                                 segment_ids=segs)
            aux = 0.0
        # drop the cross-boundary target after each EOS: the next
        # document's first token is unpredictable noise
        loss_mask = None if segs is None else segs[:, :-1] == segs[:, 1:]
        ce = chunked_cross_entropy(hidden[:, :-1], p["params"]["embedding"],
                                   batch["tokens"][:, 1:],
                                   chunk_size=args.ce_chunk, mask=loss_mask,
                                   compute_dtype=compute_dtype)
        return ce + aux

    if tok is not None:
        source = PackedTokenSource(corpus, seq_len=args.seq_len,
                                   segment_eos_id=tok.eos_id)
    else:
        source = SyntheticTokenSource(
            num_examples=args.examples
            or args.global_batch * max(args.steps, 1),
            seq_len=args.seq_len, vocab_size=args.vocab, seed=0)
    loader = DataLoader(source, global_batch_size=args.global_batch,
                        num_epochs=None, sharding=batch_sharding(mesh))

    optimizer = FusedAdamW(args.lr) if args.fused_adamw \
        else optax.adamw(args.lr)
    trainer = Trainer(mesh=mesh, apply_fn=apply_fn,
                      optimizer=optimizer, donate=args.donate,
                      compute_dtype=compute_dtype,
                      accum_steps=args.accum)
    sinks = []
    # one writer per job: the job dir is shared by the whole gang
    if os.environ.get("TONY_JOB_DIR") and jax.process_index() == 0:
        sinks.append(JsonlMetricsLogger(
            os.path.join(os.environ["TONY_JOB_DIR"], "metrics",
                         "train.jsonl")))
    # total_steps (not num_steps): a coordinator retry resumes and
    # completes the original budget instead of training a fresh one
    result = fit(trainer, params, loader, total_steps=args.steps,
                 checkpoint_every=max(args.steps // 2, 1),
                 log_every=args.log_every, metric_sinks=sinks)
    losses = [h["loss"] for h in result.history if "loss" in h]
    if os.environ.get("TONY_JOB_DIR") and jax.process_index() == 0:
        # provenance beside the metrics: which devices ran the job, and
        # whether its step held compiled Mosaic kernels (a pallas call
        # lowers to a tpu_custom_call; the interpreter lowers to none).
        # Only the pallas backend can put one there, so only it pays
        # for tracing the step a second time.
        kernels = 0
        if args.attention == "pallas":
            step = trainer.compile_step(
                trainer.state_shardings(result.state))
            kernels = step.lower(result.state, next(iter(loader))) \
                .as_text().count("tpu_custom_call")
        dev = jax.devices()
        with open(os.path.join(os.environ["TONY_JOB_DIR"], "metrics",
                               "run.json"), "w") as f:
            json.dump({
                "platform": dev[0].platform,
                "device_kind": dev[0].device_kind,
                "device_count": len(dev),
                "devices": [str(d) for d in dev],
                "tpu_visible_devices": os.environ.get("TPU_VISIBLE_DEVICES"),
                "steps_run": result.steps_run,
                "history": result.history,
                "mosaic_kernels_in_step": kernels,
            }, f)
    print(f"trained {result.steps_run} steps"
          + (f" (resumed from {result.resumed_from})"
             if result.resumed_from else "")
          + (f"; loss {losses[0]:.3f} -> {losses[-1]:.3f}" if losses else ""))
    if losses and not all(jnp.isfinite(jnp.asarray(losses))):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
