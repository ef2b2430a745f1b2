"""``tony-tpu generate`` — batch inference on a local HF checkpoint.

No reference analog (TonY orchestrates training jobs only); this is the
serving face of the framework's model stack: import a GPT-2/Llama/Mistral/
Qwen2 checkpoint directory (``models/hf.py``), run the jitted KV-cache
decode loop (``models/generate.py``), print completions. Fully offline —
the checkpoint and tokenizer are read from disk, nothing is downloaded.

    python -m tony_tpu.cli.generate --model ./my-llama \
        --prompt "Once upon a time" --max-new-tokens 64 \
        --temperature 0.8 --top-p 0.95

Raw-token mode (no tokenizer needed): ``--token-ids 1,2,3``.

Serving mode (``--serve``): the gateway core (``tony_tpu.gateway``
over ``tony_tpu.serve`` replicas) driven as a JSONL loop — one JSON
request per stdin line, one JSON response per finished request,
printed the moment it finishes while stdin is still being read.
Drivable without a TPU (JAX_PLATFORMS=cpu) and without a tokenizer
(token_ids requests). The network front door over the same core is
``python -m tony_tpu.cli.gateway``:

    printf '%s\n' '{"id": "a", "token_ids": [1, 2, 3]}' \
                  '{"id": "b", "prompt": "Hello", "max_new_tokens": 8}' \
        | python -m tony_tpu.cli.generate --model ./my-llama --serve

Request fields: ``token_ids`` or ``prompt``; optional ``id``,
``max_new_tokens``, ``temperature``, ``top_k``, ``seed`` (defaulting to
the CLI flags). Responses stream in FINISH order (short requests do not
wait on long ones — that is the point): ``{"id", "token_ids",
"finish_reason", "text"?}``.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tony-tpu generate",
        description="Generate from a local HF checkpoint on TPU",
    )
    p.add_argument("--model", required=True,
                   help="local checkpoint directory (HF format)")
    p.add_argument("--prompt", action="append", default=[],
                   help="text prompt (repeatable; needs a tokenizer in the "
                        "model dir)")
    p.add_argument("--token-ids", action="append", default=[],
                   help="raw prompt as comma-separated ids (repeatable, "
                        "no tokenizer needed)")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--num-beams", type=int, default=1,
                   help=">1 uses beam search (overrides sampling knobs)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--repetition-penalty", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eos-id", type=int, default=-1,
                   help="stop token (default: model config's eos_token_id)")
    p.add_argument("--kv-int8", action="store_true",
                   help="int8 KV cache: quantize-on-write with "
                        "per-(position, head) scales — halves the decode "
                        "cache HBM traffic (the dominant decode bytes at "
                        "long context). Recommended below ~2k live "
                        "cache tokens per sequence (1.27x e2e measured); "
                        "above that the in-scan VPU lowering favors the "
                        "bf16 cache (docs/PERF.md r5 context rule)")
    p.add_argument("--flash-decode", action="store_true",
                   help="use the pallas flash-decode kernel for "
                        "single-token decode steps (fused online-softmax "
                        "over the KV cache; int8-aware). Measured ~par "
                        "with the default einsum e2e (1.06x at cache "
                        "512, 0.95x with int8 at 3584 — docs/PERF.md "
                        "r5); the clear win case is VMEM-spill regimes "
                        "(very long caches x batch x heads). "
                        "Interpreted — slow — off TPU")
    p.add_argument("--int8", action="store_true",
                   help="serve with int8 weight-only quantization "
                        "(pallas dequant-matmul; half the weight bytes "
                        "per decode step)")
    p.add_argument("--dtype", choices=("fp32", "bf16"), default="fp32",
                   help="parameter storage dtype. Default fp32 keeps "
                        "bit-exact greedy parity with the torch "
                        "reference; pass bf16 for serving — decode is "
                        "bandwidth-bound on parameter bytes, so bf16 "
                        "storage halves per-token traffic (the standard "
                        "accelerator serving precision)")
    p.add_argument("--serve", action="store_true",
                   help="continuous-batching serving loop: JSONL "
                        "requests on stdin -> JSONL responses on stdout "
                        "(see module docstring). Requests multiplex onto "
                        "one resident KV cache; finished slots are "
                        "refilled the same iteration, so mixed-length "
                        "traffic never idles behind the longest sequence")
    p.add_argument("--serve-batch", type=int, default=4,
                   help="cache slots (resident batch size) in --serve "
                        "mode; bounds the KV-cache footprint")
    p.add_argument("--serve-replicas", type=int, default=1,
                   help="data-parallel engine replicas in --serve mode "
                        "(the gateway core drives one scheduler thread "
                        "per replica; the HTTP front door is "
                        "``tony-tpu gateway``)")
    p.add_argument("--prefix-cache-mb", type=float, default=64.0,
                   help="--serve mode: per-replica byte budget for the "
                        "prefix KV-cache store (shared prompt prefixes "
                        "skip the matched part of prefill; exact "
                        "repeats skip it entirely). 0 disables")
    p.add_argument("--speculate-k", type=int, default=0,
                   help="--serve mode: speculative decoding — up to K "
                        "prompt-lookup draft tokens verified per "
                        "batched dispatch (greedy outputs unchanged; "
                        "sampled requests decode normally). 0 disables")
    p.add_argument("--kv-page-size", type=int, default=0,
                   help="--serve mode: tokens per KV-cache page "
                        "(block-paged cache; 0 auto-sizes from "
                        "max_seq_len)")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="--serve mode: KV page-pool size per replica "
                        "(0 auto-sizes: the unpaged-equivalent "
                        "footprint, grown into free HBM on TPU)")
    p.add_argument("--no-paged-kv", action="store_true",
                   help="--serve mode: fixed-shape per-slot cache rows "
                        "instead of the paged pool (A/B escape hatch; "
                        "sliding-window models downgrade automatically)")
    p.add_argument("--compile-cache", default=None,
                   help="persistent XLA compile-cache dir; decode programs "
                        "compile once per (model, length) ever, not once "
                        "per process. Default: JAX_COMPILATION_CACHE_DIR "
                        "when set, else <checkout>/.jax_compile_cache "
                        "('' disables)")
    return p


def load_model(model_dir: str):
    """(Transformer, params, hf_config) from a local checkpoint dir."""
    import transformers

    from tony_tpu.models import (
        from_hf_gemma,
        from_hf_gpt2,
        from_hf_llama,
        from_hf_mixtral,
        from_hf_neox,
        from_hf_phi,
    )

    config = transformers.AutoConfig.from_pretrained(model_dir)
    hf = transformers.AutoModelForCausalLM.from_pretrained(model_dir)
    if config.model_type == "gpt2":
        model, params = from_hf_gpt2(hf)
    elif config.model_type in ("llama", "mistral", "qwen2"):
        model, params = from_hf_llama(hf)
    elif config.model_type == "gemma":
        model, params = from_hf_gemma(hf)
    elif config.model_type == "mixtral":
        model, params = from_hf_mixtral(hf)
    elif config.model_type == "gpt_neox":
        model, params = from_hf_neox(hf)
    elif config.model_type == "phi":
        model, params = from_hf_phi(hf)
    else:
        raise SystemExit(
            f"unsupported model_type {config.model_type!r} "
            "(supported: gpt2, llama, mistral, qwen2, gemma, mixtral, "
            "gpt_neox, phi)")
    return model, params, config


def resolve_paged_kv(args, model, batch_size: int,
                     n_replicas: int = 1) -> dict:
    """``Server(paged=..., kv_page_size=..., kv_pages=...)`` kwargs from
    CLI args — shared with ``cli.gateway``, mirroring the
    ``resolve_prefix_cache_mb`` precedent: the feature defaults ON, so
    the CLIs degrade (stderr note) instead of crashing on model configs
    the engine refuses (sliding-window attention), and ``--kv-pages 0``
    auto-sizes the per-replica pool: the unpaged-equivalent footprint
    (``batch x ceil(max_seq_len / page_size)`` — capacity parity) as
    the floor, grown toward half the HBM the device reports free
    (``memory_stats()`` of the first local device — this process
    already holds the chip and the weights, so no second program is
    asked) SPLIT ACROSS the ``n_replicas`` pools that will coexist and
    capped at 4x the floor — the freed fixed-shape waste is exactly
    what bigger batches grow into. A backend without memory stats
    (the CPU) keeps the floor. Which source sized the pool goes to
    stderr."""
    if getattr(args, "no_paged_kv", False):
        return {"paged": False}
    if model.cfg.sliding_window:
        print("note: paged KV cache disabled (untested over "
              "sliding-window attention)", file=sys.stderr)
        return {"paged": False}
    from tony_tpu.serve.slots import default_page_size, kv_page_nbytes

    cfg = model.cfg
    ps = int(getattr(args, "kv_page_size", 0) or 0) \
        or default_page_size(cfg)
    ps = max(1, min(ps, cfg.max_seq_len))
    pages = int(getattr(args, "kv_pages", 0) or 0)
    source = "--kv-pages"
    if pages <= 0:
        import jax

        pages = base = batch_size * (-(-cfg.max_seq_len // ps))
        source = "capacity-parity floor (device reports no memory stats)"
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            hbm_pages = int(free * 0.5 / max(1, n_replicas)) \
                // kv_page_nbytes(cfg, ps)
            pages = max(base, min(4 * base, hbm_pages))
            source = (f"memory_stats: {free / 2**30:.2f} GiB free, "
                      f"floor {base}, cap {4 * base}")
    print(f"kv pool: {pages} pages x {ps} tokens per replica ({source})",
          file=sys.stderr)
    return {"paged": True, "kv_page_size": ps, "kv_pages": pages}


def resolve_prefix_cache_mb(args, model) -> float:
    """``--prefix-cache-mb``, downgraded to 0 (with a stderr note) for
    model configs the prefix store refuses — the flag defaults ON, so
    the CLIs must degrade instead of crashing on e.g. Mistral's
    sliding-window attention. Shared with ``cli.gateway``."""
    mb = getattr(args, "prefix_cache_mb", 0.0)
    if mb > 0 and model.cfg.sliding_window:
        print("note: prefix cache disabled (untested over "
              "sliding-window attention)", file=sys.stderr)
        return 0.0
    return mb


def _serve_loop(model, params, args, eos) -> int:
    """``--serve``: read JSONL requests from stdin until EOF, stream one
    JSONL response per finished request (finish order, not submit
    order). Token-id requests need no tokenizer; the first ``prompt``
    request lazy-loads one from the model dir.

    Runs over the gateway core (``tony_tpu.gateway``): requests decode
    on ``--serve-replicas`` worker threads WHILE stdin is still being
    read, responses print the moment they finish, and a full admission
    queue blocks the stdin reader (natural pipe backpressure) instead
    of growing without bound."""
    import json
    import threading
    import time

    from tony_tpu.gateway import Gateway, GatewayQueueFull, GenRequest
    from tony_tpu.serve import FaultPlan, Server

    n_replicas = max(1, getattr(args, "serve_replicas", 1))
    prefix_mb = resolve_prefix_cache_mb(args, model)
    paged_kw = resolve_paged_kv(args, model, args.serve_batch,
                                n_replicas=n_replicas)
    # same chaos hook as the gateway CLI: TONY_SERVE_FAULTS arms
    # deterministic per-replica fault injection (serve/faults.py)
    servers = [Server(model, params["params"],
                      batch_size=args.serve_batch, eos_id=eos,
                      prefix_cache_mb=prefix_mb,
                      speculate_k=args.speculate_k,
                      fault_plan=FaultPlan.from_env(replica=i),
                      **paged_kw)
               for i in range(n_replicas)]
    armed = [i for i, s in enumerate(servers) if s.fault_plan is not None]
    if armed:
        # loud, like the gateway CLI: a TONY_SERVE_FAULTS leftover from
        # a chaos run must not silently sabotage a real serve loop
        print(f"fault injection ARMED on replica(s) {armed} via "
              "TONY_SERVE_FAULTS", file=sys.stderr)
    gateway = Gateway(servers,
                      max_queue=max(64, 32 * n_replicas)).start()
    tokenizer = None
    n_bad = 0
    n_shed = 0
    out_lock = threading.Lock()

    def on_event(ticket, event):
        nonlocal n_shed
        if event[0] == "done":
            res = event[1]
            new_ids = res.tokens
            stops = [i for i, t in enumerate(new_ids) if t in eos]
            if stops:  # mirror the batch CLI: trim at the first stop
                new_ids = new_ids[:stops[0]]
            out = {"id": res.id, "token_ids": list(res.prompt) + new_ids,
                   "finish_reason": res.finish_reason}
            if tokenizer is not None:
                out["text"] = tokenizer.decode(out["token_ids"])
            with out_lock:
                print(json.dumps(out), flush=True)
        elif event[0] == "shed":
            n_shed += 1
            print(f"request {ticket.request.id} shed: {event[2]}",
                  file=sys.stderr)

    for lineno, raw in enumerate(sys.stdin, 1):
        raw = raw.strip()
        if not raw:
            continue
        try:
            d = json.loads(raw)
            if not isinstance(d, dict):
                raise ValueError("request must be a JSON object")
            if "token_ids" in d:
                ids = [int(x) for x in d["token_ids"]]
            elif "prompt" in d:
                if tokenizer is None:
                    import transformers

                    tokenizer = transformers.AutoTokenizer.from_pretrained(
                        args.model)
                ids = tokenizer.encode(d["prompt"])
            else:
                raise ValueError("request needs token_ids or prompt")
            req = GenRequest(
                ids,
                int(d.get("max_new_tokens", args.max_new_tokens)),
                temperature=float(d.get("temperature", args.temperature)),
                top_k=int(d.get("top_k", args.top_k)),
                seed=int(d.get("seed", args.seed)),
                id=d.get("id"))
            while True:
                try:
                    gateway.submit(req, on_event=on_event)
                    break
                except GatewayQueueFull:
                    time.sleep(0.01)  # pipe backpressure, not rejection
        except Exception as e:  # noqa: BLE001 — a malformed
            # line (bad JSON, wrong shapes, a prompt with no tokenizer
            # in the model dir, an oversized prompt) must not kill the
            # stream and strand every queued request: report, skip
            print(f"request line {lineno} rejected: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            n_bad += 1
    gateway.drain()
    return 0 if n_bad == 0 and n_shed == 0 else 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if not args.serve and not args.prompt and not args.token_ids:
        print("need --prompt or --token-ids", file=sys.stderr)
        return 2

    if args.compile_cache != "":
        from tony_tpu.utils import compilecache

        compilecache.enable(args.compile_cache)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import generate

    model, params, config = load_model(args.model)
    if args.dtype == "bf16" and args.int8:
        print("note: --int8 supplies its own storage format; "
              "--dtype bf16 is ignored", file=sys.stderr)
    if args.dtype == "bf16" and not args.int8:
        # cast ONCE at load: flax would otherwise re-read fp32 kernels
        # from HBM every decode step and cast per-use. Inspect x.dtype
        # directly — np.asarray would pull every leaf to host first.
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params)
    if args.int8:
        from tony_tpu.models.quantize import quantize_cli

        model, params = quantize_cli(model, params)
    if args.kv_int8 or args.flash_decode:
        import dataclasses

        from tony_tpu.models import Transformer

        model = Transformer(dataclasses.replace(
            model.cfg,
            kv_cache_quant=args.kv_int8,
            decode_attention="flash" if args.flash_decode
            else model.cfg.decode_attention))

    tokenizer = None
    if args.prompt:
        import transformers

        tokenizer = transformers.AutoTokenizer.from_pretrained(args.model)
    prompts = [tokenizer.encode(t) for t in args.prompt]
    prompts += [[int(i) for i in ids.split(",")] for ids in args.token_ids]

    from tony_tpu.models.generate import normalize_eos_ids

    # HF configs may ship a LIST of eos ids (Llama-3 instruct:
    # [128001, 128009]); the decode loops stop on ANY of them
    eos = normalize_eos_ids(args.eos_id) or \
        normalize_eos_ids(getattr(config, "eos_token_id", None))

    if args.serve:
        if args.int8:
            print("--serve does not support --int8 weight quantization "
                  "yet", file=sys.stderr)
            return 2
        if args.top_p < 1.0:
            print("warning: --top-p is not applied in --serve mode "
                  "(per-slot sampling supports temperature/top-k); "
                  "ignoring", file=sys.stderr)
        return _serve_loop(model, params, args, eos)

    from tony_tpu.models import beam_search

    if args.num_beams > 1 and args.repetition_penalty != 1.0:
        print("warning: --repetition-penalty is not applied under "
              "beam search; ignoring", file=sys.stderr)
    # GREEDY same-length prompts decode as one batch (no padding, so
    # absolute positions agree and greedy rows are independent) — one
    # compiled program and one KV-cache pass serve up to 32 prompts;
    # distinct lengths still compile once each. Sampled decode stays
    # per-prompt so a (prompt, --seed) pair reproduces the same text
    # regardless of what else is in the invocation; beam search's batch
    # dim is the beam.
    outputs: dict[int, list[int]] = {}
    if args.num_beams > 1:  # beam search's batch dim IS the beam
        for pos, ids in enumerate(prompts):
            out = beam_search(model, params["params"],
                              jnp.asarray([ids], jnp.int32),
                              max_new_tokens=args.max_new_tokens,
                              num_beams=args.num_beams, eos_id=eos)
            outputs[pos] = np.asarray(out)[0].tolist()
    else:
        batchable = args.temperature == 0.0
        by_len: dict[int, list[int]] = {}
        for pos, ids in enumerate(prompts):
            by_len.setdefault(len(ids) if batchable else pos, []).append(pos)
        max_group = 32  # bounds the batched KV-cache footprint
        for whole in by_len.values():
            for start in range(0, len(whole), max_group):
                group = whole[start:start + max_group]
                prompt_arr = jnp.asarray(
                    [prompts[pos] for pos in group], jnp.int32)
                out = generate(model, params["params"], prompt_arr,
                               max_new_tokens=args.max_new_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k,
                               top_p=args.top_p, eos_id=eos,
                               repetition_penalty=args.repetition_penalty,
                               rng=jax.random.PRNGKey(args.seed))
                for row, pos in enumerate(group):
                    outputs[pos] = np.asarray(out)[row].tolist()
    for pos, ids in enumerate(prompts):  # print in input order
        new_ids = outputs[pos]
        stops = [i for i, t in enumerate(new_ids) if t in eos]
        if stops:
            new_ids = new_ids[:stops[0]]
        if tokenizer is not None:
            print(tokenizer.decode(ids + new_ids))
        else:
            print(",".join(str(i) for i in ids + new_ids))
    return 0


if __name__ == "__main__":
    sys.exit(main())
