"""Training CLI: run the mesh-parallel train step over a token corpus.

The user-facing front of parallel.train (the reference has no training
story, SURVEY §2): pick a model and a mesh plan, point at a .npy token
array (or --synthetic), and it runs warmup/decay Adam with grad clipping,
periodic checkpointing, and resume — the full loop the library pieces
already implement, behind one command:

  python -m inferd_tpu.tools.train --model tiny --synthetic --steps 20 \\
      --mesh dp=2,pp=2,tp=2 --optimizer adam --checkpoint-dir ckpts/

Training meshes accept all five axes (dp/pp/sp/tp/ep) — serving
(run_node --mesh) accepts all but dp (sp serves long-context prefill
there since round 5). Multi-chip plans run on
whatever jax.devices() exposes; the virtual CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8) works for dry runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--random-init", action="store_true",
                    help="random weights (no checkpoint on disk needed)")
    ap.add_argument("--data", default="",
                    help=".npy 1-D token array to train on")
    ap.add_argument("--synthetic", action="store_true",
                    help="random token stream (smoke runs; zero-egress hosts)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mb", type=int, default=2, help="microbatches (pp schedule)")
    ap.add_argument("--batch", type=int, default=4, help="sequences per microbatch")
    ap.add_argument("--seq", type=int, default=128, help="sequence length")
    ap.add_argument("--mesh", default="",
                    help="training mesh plan, e.g. 'dp=2,pp=2,tp=2' (all five "
                    "axes allowed; default single device)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", choices=["sgd", "adam"], default="adam")
    ap.add_argument("--grad-clip-norm", type=float, default=1.0)
    ap.add_argument("--warmup-steps", type=int, default=0)
    ap.add_argument("--decay-steps", type=int, default=0)
    ap.add_argument("--moe-aux-coef", type=float, default=0.0,
                    help="router load-balancing loss coefficient (MoE only)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="save/resume directory (parallel.checkpoint)")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3, help="snapshots retained")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest snapshot in --checkpoint-dir")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    return ap


def parse_train_mesh(value: str):
    """'dp=2,pp=2' -> MeshPlan; '' -> all-ones (single device)."""
    from inferd_tpu.parallel.mesh import AXES, MeshPlan

    sizes = {}
    for part in value.split(","):
        if not part.strip():
            continue
        axis, _, n = part.strip().partition("=")
        if axis not in AXES or not n.isdigit():
            raise ValueError(f"bad mesh spec {part!r}; want e.g. 'dp=2,pp=2'")
        sizes[axis] = int(n)
    return MeshPlan(**sizes)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from inferd_tpu.utils.platform import force_platform

    force_platform(args.device)

    import jax
    import numpy as np

    from inferd_tpu import data as datalib
    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel import checkpoint as ckptlib
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.train import make_train_step

    cfg = get_config(args.model)
    plan = parse_train_mesh(args.mesh)
    n_dev = len(jax.devices())
    if plan.num_devices > n_dev:
        print(
            f"mesh plan {args.mesh!r} needs {plan.num_devices} devices, "
            f"have {n_dev}",
            file=sys.stderr,
        )
        return 2
    mesh = meshlib.make_mesh(plan)

    if args.synthetic:
        tokens = datalib.synthetic_tokens(
            cfg.vocab_size, n_tokens=max(65536, 4 * args.seq), seed=args.seed
        )
    elif args.data:
        tokens = args.data
    else:
        print("need --data FILE.npy or --synthetic", file=sys.stderr)
        return 2
    ds = datalib.TokenDataset(tokens, args.seq)

    if args.random_init:
        params = qwen3.init_params(cfg, jax.random.PRNGKey(args.seed))
    else:
        from inferd_tpu.models.loader import load_params

        params = load_params(cfg)

    step_fn = make_train_step(
        cfg, mesh, plan,
        learning_rate=args.lr,
        optimizer=args.optimizer,
        grad_clip_norm=args.grad_clip_norm,
        warmup_steps=args.warmup_steps,
        decay_steps=args.decay_steps,
        moe_aux_coef=args.moe_aux_coef,
    )
    state = step_fn.init_state(params)
    start = 0
    if args.resume and args.checkpoint_dir:
        latest = ckptlib.latest_step(args.checkpoint_dir)
        if latest is not None:
            state, meta = ckptlib.restore(
                args.checkpoint_dir, target=state
            )
            start = int(meta["step"])
            print(f"resumed from step {start}", file=sys.stderr)

    losses = []
    t0 = time.perf_counter()
    # skip (not reseed) so a resumed run consumes the identical batch
    # sequence an uninterrupted run would have — crash-equivalent repro
    gen = ds.batches(args.mb, args.batch, seed=args.seed, skip=start)
    for i in range(start, args.steps):
        tokens_b, targets_b = next(gen)
        state, loss = step_fn(state, tokens_b, targets_b)
        losses.append(float(loss))
        if args.log_every and (i + 1) % args.log_every == 0:
            rate = (i + 1 - start) * args.mb * args.batch * args.seq / (
                time.perf_counter() - t0
            )
            print(
                f"step {i + 1}/{args.steps} loss {losses[-1]:.4f} "
                f"({rate:.0f} tok/s)",
                file=sys.stderr,
            )
        if (
            args.checkpoint_dir
            and args.save_every
            and (i + 1) % args.save_every == 0
        ):
            ckptlib.save(
                args.checkpoint_dir, state, i + 1,
                meta={"model": cfg.name}, keep=args.keep,
            )
    if args.checkpoint_dir and start < args.steps:
        # guard: a resume past --steps runs zero steps and must not write
        # a snapshot mislabeled with an earlier step than its state
        ckptlib.save(
            args.checkpoint_dir, state, args.steps,
            meta={"model": cfg.name}, keep=args.keep,
        )
    print(json.dumps({
        "model": cfg.name,
        "steps": args.steps,
        "final_loss": losses[-1] if losses else None,
        "first_loss": losses[0] if losses else None,
        "mesh": args.mesh or "1-device",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
