"""perf CLI.

    python -m inferd_tpu.perf report --preset qwen3-0.6b [--chip v5e]
        [--ctx N] [--batch B] [--artifact BENCH.jsonl]
    python -m inferd_tpu.perf check --artifact BENCH.jsonl
        [--prior OLD.jsonl] [--chip v5e] [--json]

`report` and `check` are pure host-side arithmetic — they run on a
CPU-only box without initializing any JAX backend beyond importing
jax.numpy for dtype sizes.

Exit codes: `check` exits 1 when any ERROR-severity finding exists
(warnings never fail the gate); everything else exits 0 on success.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional


def cmd_report(args) -> int:
    from inferd_tpu.config import get_config
    from inferd_tpu.perf import gate as gatelib
    from inferd_tpu.perf import roofline as rl

    cfg = get_config(args.preset)
    chip = rl.get_chip(args.chip)
    print(rl.format_report(cfg, chip, ctx=args.ctx, batch=args.batch))
    artifact = args.artifact or gatelib.DEFAULT_ARTIFACT
    if artifact and os.path.exists(artifact):
        rows = []
        for name, res in gatelib.load_artifact(artifact):
            parsed = gatelib.parse_decode_metric(str(res.get("metric", "")))
            if parsed is None or parsed[0].name != cfg.name:
                continue
            derived = gatelib.model_frac(res, chip)
            if derived is None:
                continue
            rec = res.get("hbm_roofline_frac")
            rows.append(
                f"  {name}: measured {res['value']} tok/s on "
                f"{res.get('device')} -> model roofline frac {derived:.3f}"
                + (f" (artifact recorded {rec})" if rec is not None else "")
            )
        if rows:
            print(f"\nre-derivation against {os.path.basename(artifact)}:")
            print("\n".join(rows))
    return 0


def cmd_check(args) -> int:
    from inferd_tpu.perf import gate as gatelib

    findings, ok = gatelib.gate(args.artifact, args.prior, args.chip)
    if args.stats:
        # node /stats snapshot (JSON file): span-recording overhead vs
        # compute — warning-severity, so it never flips `ok`
        with open(args.stats) as f:
            findings = findings + gatelib.check_span_overhead(json.load(f))
    if args.json:
        print(json.dumps({
            "artifact": args.artifact,
            "prior": args.prior,
            "ok": ok,
            "findings": [vars(f) for f in findings],
        }))
    else:
        for f in findings:
            print(f.line())
        n_err = sum(f.severity == "error" for f in findings)
        n_warn = len(findings) - n_err
        print(
            f"perf gate: {'PASS' if ok else 'FAIL'} "
            f"({n_err} errors, {n_warn} warnings) on {args.artifact}"
        )
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m inferd_tpu.perf")
    sub = ap.add_subparsers(dest="cmd", required=True)

    rp = sub.add_parser("report", help="analytic roofline table for a preset")
    rp.add_argument("--preset", required=True)
    rp.add_argument("--chip", default="v5e")
    rp.add_argument("--ctx", type=int, default=0)
    rp.add_argument("--batch", type=int, default=1)
    rp.add_argument(
        "--artifact", default="",
        help="BENCH artifact to re-derive decode-leg fractions against "
        "(default: the committed round-5 battery when present)",
    )
    rp.set_defaults(fn=cmd_report)

    ck = sub.add_parser("check", help="perf regression gate over an artifact")
    ck.add_argument("--artifact", required=True)
    ck.add_argument("--prior", default=None,
                    help="prior artifact for the regression check")
    ck.add_argument("--chip", default="v5e")
    ck.add_argument("--json", action="store_true")
    ck.add_argument(
        "--stats", default=None,
        help="node /stats snapshot (JSON) to audit span-recording "
        "overhead against stage compute (warning only)",
    )
    ck.set_defaults(fn=cmd_check)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
