"""Local generation CLI: run a model end to end on this host's device.

The reference's only generation entry points are network clients
(petals/send_message.py, models/qwen3/client/client.py); this tool is the
single-process counterpart the swarm doesn't need but every user wants —
load a preset (random-init or HF cache weights), generate from a prompt,
and pick the engine:

  --engine plain        core.generate.Engine (fused-scan decode)
  --engine batched      core.batch.BatchedEngine (N prompts, one batched
                        decode step per token across all of them)
  --engine speculative  core.speculative.SpeculativeEngine (--draft-model
                        proposes, the target verifies; greedy is
                        token-exact, temperature>0 distribution-exact)

Composable knobs shared with the serving path: --quant int8|w8a8|
int8-kernel (ops.quant), --kv-dtype float8_e4m3fn, --attn {auto,flash,
flash_interpret,xla}, sampling (--temperature/--top-k/--top-p/--min-p),
--seed.

Examples:
  python -m inferd_tpu.tools.generate --model tiny --random-init \
      --prompt-ids 3,7,11 --max-new-tokens 16
  python -m inferd_tpu.tools.generate --model qwen3-0.6b --prompt "hi" \
      --engine speculative --draft-model qwen3-0.6b --draft-layers 8
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="generate", description=__doc__)
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--random-init", action="store_true",
                    help="random weights (zero-egress environments)")
    ap.add_argument("--prompt", default="", help="text prompt (needs a tokenizer)")
    ap.add_argument("--prompt-ids", default="",
                    help="comma-separated token ids (tokenizer-free)")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--engine", default="plain",
                    choices=["plain", "batched", "speculative"])
    ap.add_argument("--lanes", type=int, default=4, help="batched: lanes")
    ap.add_argument("--chunk", type=int, default=1,
                    help="batched: fused decode steps per dispatch")
    ap.add_argument("--lora", default="",
                    help="peft LoRA adapter dir merged into the weights")
    ap.add_argument("--draft-model", default="",
                    help="speculative: draft preset (default: target)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="speculative: truncate the draft to this many layers")
    ap.add_argument("--spec-k", type=int, default=4, help="speculative: draft length")
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "w8a8", "int8-kernel", "int4"])
    ap.add_argument("--kv-dtype", default="model", choices=["model", "float8_e4m3fn"])
    ap.add_argument("--attn", default="auto",
                    choices=["auto", "flash", "flash_interpret", "xla"])
    ap.add_argument("--temperature", type=float, default=0.6)
    ap.add_argument("--top-k", type=int, default=20)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min-p filtering: drop tokens below min_p * max-prob")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed; with --random-init also the weights' "
                    "seed (the same weights `split_model --random-init "
                    "--seed` writes)")
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    ap.add_argument("--pin-prefix-ids", default="",
                    help="plain engine: comma-separated token ids pinned as "
                    "a prefix-cache snapshot before generating (prompts "
                    "starting with these ids skip re-prefilling them)")
    ap.add_argument("--max-pins", type=int, default=4,
                    help="plain engine: LRU cap on pinned prefix snapshots "
                    "(each pin holds a KV snapshot — prefix-cache pressure "
                    "is a capacity decision)")
    return ap


def _load_params(cfg, random_init: bool, seed: int):
    import jax

    from inferd_tpu.models import qwen3

    if random_init:
        return qwen3.init_params(cfg, jax.random.PRNGKey(seed))
    from inferd_tpu.models.loader import load_params

    return load_params(cfg)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from inferd_tpu.utils.platform import (
        enable_compile_cache, force_platform, require_platform,
    )

    force_platform(args.device)
    cache_stats = enable_compile_cache()
    facts = require_platform(args.device)

    from inferd_tpu.config import SamplingConfig, get_config
    from inferd_tpu.ops import quant as quantlib

    cfg = get_config(args.model)
    if args.kv_dtype != "model":
        cfg = dataclasses.replace(cfg, kv_dtype=args.kv_dtype)
    if args.attn != "auto":
        cfg = dataclasses.replace(cfg, attn_impl=args.attn)
    sampling = SamplingConfig(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        min_p=args.min_p
    )

    params = _load_params(cfg, args.random_init, seed=args.seed)
    if args.lora:
        from inferd_tpu.ops import lora as loralib

        params = loralib.merge_adapter(params, loralib.load_adapter(cfg, args.lora))
    params = quantlib.apply_quant_mode(
        args.quant, params, tie_word_embeddings=cfg.tie_word_embeddings
    )

    tokenizer = None
    if args.prompt_ids:
        prompt_ids = [int(t) for t in args.prompt_ids.split(",")]
        eos = None
    elif args.prompt:
        from inferd_tpu.config import HF_REPOS
        from inferd_tpu.core.tokenizer import Tokenizer

        tokenizer = Tokenizer(HF_REPOS.get(cfg.name, cfg.name))
        prompt_ids = tokenizer.apply_chat_template(
            [{"role": "user", "content": args.prompt}], add_generation_prompt=True
        )
        eos = tokenizer.eos_token_id
    else:
        print("need --prompt or --prompt-ids", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    acceptance = None
    if args.engine == "plain":
        from inferd_tpu.core.generate import Engine

        eng = Engine(cfg, params, max_len=args.max_len, sampling_cfg=sampling,
                     max_pins=args.max_pins)
        if args.pin_prefix_ids:
            eng.pin_prefix([int(t) for t in args.pin_prefix_ids.split(",")])
        out = eng.generate(
            prompt_ids, args.max_new_tokens, eos_token_id=eos, seed=args.seed,
            chunk=args.chunk,
        )
    elif args.engine == "batched":
        from inferd_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(
            cfg, params, lanes=args.lanes, max_len=args.max_len,
            sampling_cfg=sampling,
        )
        out = eng.generate_all(
            [prompt_ids], args.max_new_tokens, eos_token_id=eos,
            seed=args.seed, chunk=args.chunk,
        )[0]
    else:  # speculative
        from inferd_tpu.core.speculative import SpeculativeEngine, self_draft

        if args.draft_layers and not args.draft_model and not args.random_init:
            # layer-truncated SELF-draft (shared recipe with the node's
            # speculative /generate): no second checkpoint read
            dcfg, draft_params = self_draft(cfg, params, args.draft_layers)
        else:
            dcfg = get_config(args.draft_model or args.model)
            if args.draft_layers:
                dcfg = dcfg.with_layers(args.draft_layers)
            draft_params = _load_params(dcfg, args.random_init, seed=args.seed + 1)
        eng = SpeculativeEngine(
            cfg, params, dcfg, draft_params, k=args.spec_k,
            max_len=args.max_len, sampling_cfg=sampling,
        )
        out, acceptance = eng.generate(
            prompt_ids, args.max_new_tokens, eos_token_id=eos, seed=args.seed
        )
    dt = time.perf_counter() - t0

    if tokenizer is not None:
        print(tokenizer.decode(out))
    else:
        print("generated ids:", out)
    rate = len(out) / dt if dt > 0 else 0.0
    extra = f", draft acceptance {acceptance:.2f}" if acceptance is not None else ""
    print(
        f"[{len(out)} tokens in {dt:.2f}s = {rate:.1f} tok/s{extra}; "
        f"device {facts['platform']} {facts['device_kind']!r} "
        f"x{facts['device_count']}; compile cache {cache_stats.as_dict()}]",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
