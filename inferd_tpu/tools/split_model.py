"""Offline model splitter: full checkpoint -> per-stage checkpoints.

Capability parity with /root/reference/split_model.py:76-108 (read the stage
table, slice the decoder, save one weight blob per node), redesigned:
per-STAGE (not per-node) msgpack checkpoints so stage replicas and live
migration share one file (fixes SURVEY B2), safe dense encoding (no pickle),
and `--random-init` for zero-egress environments.

Usage:
  python -m inferd_tpu.tools.split_model --manifest cluster.yaml --out parts/
  python -m inferd_tpu.tools.split_model --model qwen3-0.6b --stages 2 \
      --out parts/ --random-init
"""

from __future__ import annotations

import argparse

import jax

from inferd_tpu.config import get_config
from inferd_tpu.models import qwen3
from inferd_tpu.models.loader import load_params
from inferd_tpu.parallel.stages import Manifest, split_and_save


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--manifest", help="cluster topology yaml (model + stage table)")
    ap.add_argument("--model", help="model preset name (used with --stages)")
    ap.add_argument("--stages", type=int, default=2, help="even split into N stages")
    ap.add_argument("--out", required=True, help="output directory for stage checkpoints")
    ap.add_argument("--weights", help="safetensors dir / HF repo (default: model preset)")
    ap.add_argument(
        "--random-init", action="store_true",
        help="random weights (offline benchmarking without a checkpoint)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--device", default="cpu", choices=["auto", "cpu", "tpu"],
        help="platform for the split computation (host-side tool: cpu default)",
    )
    args = ap.parse_args(argv)

    from inferd_tpu.utils.platform import force_platform

    force_platform(args.device)

    if args.manifest:
        manifest = Manifest.from_yaml(args.manifest)
    elif args.model:
        manifest = Manifest.even_split(args.model, args.stages)
    else:
        ap.error("need --manifest or --model")

    cfg = manifest.config
    if args.random_init:
        params = qwen3.init_params(cfg, jax.random.PRNGKey(args.seed))
    else:
        params = load_params(cfg, args.weights)

    paths = split_and_save(params, cfg, manifest, args.out)
    for p in paths:
        print(p)


if __name__ == "__main__":
    main()
