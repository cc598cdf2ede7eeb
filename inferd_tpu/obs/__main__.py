"""obs CLI.

    python -m inferd_tpu.obs merge SPANS... [--out traces.json]
        [--chrome trace.json] [--json] [--check]
    python -m inferd_tpu.obs health [--check] [--rules rules.json]
        [--json] SCRAPE...
    python -m inferd_tpu.obs postmortem TRACE_ID PATHS... [--json]
        [--out report.json] [--rules rules.json]
    python -m inferd_tpu.obs fleet [--check] [--json] PATHS...

`merge` consumes per-node span JSONL files (or directories of them — the
node's --trace-dir output, or /spans endpoint dumps), corrects clock
skew, and prints one line per reconstructed trace: wall time, TTFT,
per-token latency, per-stage breakdown, and whether the span tree nests
cleanly. `--out` writes the full timelines JSON; `--chrome` writes a
chrome://tracing / Perfetto-loadable trace of every span.

`merge --check` is the CI smoke: exit 1 unless at least one trace
merges, the span trees nest with zero violations, and no input line was
skipped — run in run.sh step 0c over the committed fixture
(tests/data/spans) and gated in tier-1 via tests/test_obs.py.

`health` evaluates the SLO rules (obs.health DEFAULT_RULES, or --rules)
offline over a committed scrape: `*.json` files are /stats-shaped
snapshots, `*.events.jsonl` files are event journals. `--check` exits 1
on a `failing` verdict or when zero rules could be evaluated — run.sh
step 0d runs it over tests/data/health.

`postmortem` joins one trace's merged timeline, the event journals, and
the metrics snapshots into a single incident report (obs.postmortem) —
per-stage breakdowns, interleaved fleet events, firing SLO rules, and
the first divergent hop.

`fleet` renders the fleet SLI report (obs.fleet) offline from collector
artifacts: `*.ndjson` fleet-sample files (tools/collector --history)
and/or raw `*.history.json` per-node dumps (the node's --trace-dir
output / GET /metrics/history), which assemble into one fresh sample.
`--check` is the CI smoke: exit 1 unless at least one sample exists,
carries the schema fields, and resolved at least one real SLI series —
run.sh step 0e runs it over the committed tests/data/fleet fixture.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def cmd_merge(args) -> int:
    from inferd_tpu.obs import export, merge

    result = merge.merge_paths(args.paths)
    traces = result["traces"]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(
                {k: v for k, v in result.items() if k != "spans"}, f, indent=1
            )
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(export.chrome_trace(result["spans"]), f)

    n_viol = sum(len(t["nest_violations"]) for t in traces)
    if args.json:
        print(json.dumps(
            {k: v for k, v in result.items() if k != "spans"}
        ))
    else:
        for t in traces:
            ttft = f"{t['ttft_ms']:.1f}" if t["ttft_ms"] is not None else "-"
            ptok = (
                f"{t['per_token_ms']:.1f}"
                if t["per_token_ms"] is not None else "-"
            )
            print(
                f"trace {t['trace']}: root {t['root']['name']}@"
                f"{t['root']['service']} wall {t['wall_ms']:.1f} ms "
                f"ttft {ttft} ms tok {t['tokens']} per-tok {ptok} ms "
                f"spans {t['spans']} services {len(t['services'])} "
                f"nest_violations {len(t['nest_violations'])}"
            )
            for stage, row in t["stages"].items():
                parts = " ".join(
                    f"{k}={v}" for k, v in sorted(row.items()) if k != "hops"
                )
                print(f"  stage {stage}: hops={row['hops']} {parts}")
        hops = result.get("hops")
        if hops:
            print(
                f"hop latency: p50 {hops['p50_ms']} ms "
                f"p99 {hops['p99_ms']} ms over {hops['count']} hops"
            )
        if result["skipped_lines"]:
            print(f"skipped {result['skipped_lines']} unparseable line(s)")
        if result["clamped_spans"]:
            print(
                f"clamped {result['clamped_spans']} negative-duration "
                "span(s) to zero (legacy pre-epoch-anchor recorder)"
            )

    if args.check:
        ok = bool(traces) and n_viol == 0 and result["skipped_lines"] == 0
        print(
            f"obs merge check: {'OK' if ok else 'FAIL'} "
            f"({len(traces)} traces, "
            f"{sum(t['spans'] for t in traces)} spans, "
            f"{n_viol} nest violations, "
            f"{result['skipped_lines']} skipped lines)"
        )
        return 0 if ok else 1
    return 0


def cmd_health(args) -> int:
    from inferd_tpu.obs import health as healthlib

    loaded = healthlib.load_scrape(args.paths)
    rules = loaded["rules"] or list(healthlib.DEFAULT_RULES)
    if args.rules:
        rules = healthlib.load_rules(args.rules)
    events = loaded["events"]
    histories = loaded.get("histories")
    # offline scrape: evaluate event AND burn rules at the artifacts' own
    # clock (rate windows must cover the committed data, not wall-clock)
    stamps = [ev["ts"] for ev in events or []]
    stamps += [
        h["ts"] for h in histories or []
        if isinstance(h.get("ts"), (int, float))
    ]
    now = max(stamps, default=None)
    verdict = healthlib.evaluate(
        rules, loaded["snapshot"], events=events, now=now,
        histories=histories,
    )
    if args.json:
        print(json.dumps(verdict))
    else:
        print(healthlib.format_verdict(verdict))
    if args.check:
        ok = verdict["status"] != "failing" and verdict["evaluated"] > 0
        print(
            f"obs health check: {'OK' if ok else 'FAIL'} "
            f"(status {verdict['status']}, "
            f"{verdict['evaluated']} rules evaluated, "
            f"{len(verdict['firing'])} firing)"
        )
        return 0 if ok else 1
    return 0


def cmd_postmortem(args) -> int:
    from inferd_tpu.obs import health as healthlib
    from inferd_tpu.obs import postmortem as pmlib

    rules = healthlib.load_rules(args.rules) if args.rules else None
    try:
        report = pmlib.build_report(args.trace_id, args.paths, rules=rules)
    except ValueError as e:
        print(f"postmortem: {e}", file=sys.stderr)
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    if args.json:
        print(json.dumps(report))
    else:
        print(pmlib.format_report(report))
    return 0


def cmd_fleet(args) -> int:
    from inferd_tpu.obs import fleet as fleetlib

    samples = fleetlib.load_samples(args.paths)
    if args.json:
        print(json.dumps(samples[-1] if samples else None))
    else:
        print(fleetlib.format_report(samples))
    if args.check:
        problems = fleetlib.check_samples(samples)
        ok = not problems
        print(
            f"obs fleet check: {'OK' if ok else 'FAIL'} "
            f"({len(samples)} sample(s)"
            + (f"; {'; '.join(problems)}" if problems else "")
            + ")"
        )
        return 0 if ok else 1
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m inferd_tpu.obs")
    sub = ap.add_subparsers(dest="cmd", required=True)

    mg = sub.add_parser(
        "merge", help="merge per-node span JSONL into per-trace timelines"
    )
    mg.add_argument(
        "paths", nargs="+",
        help="span .jsonl files or directories containing them",
    )
    mg.add_argument("--out", default="", help="write full timelines JSON here")
    mg.add_argument(
        "--chrome", default="",
        help="write a chrome://tracing / Perfetto trace of every span",
    )
    mg.add_argument("--json", action="store_true", help="machine output")
    mg.add_argument(
        "--check", action="store_true",
        help="CI smoke: exit 1 unless traces merge cleanly",
    )
    mg.set_defaults(fn=cmd_merge)

    hl = sub.add_parser(
        "health", help="evaluate SLO rules over an offline scrape"
    )
    hl.add_argument(
        "paths", nargs="+",
        help="scrape inputs: *.json /stats snapshots, *.events.jsonl "
        "journals, rules.json overrides (or directories of them)",
    )
    hl.add_argument(
        "--rules", default="", help="JSON rules file (overrides defaults)"
    )
    hl.add_argument("--json", action="store_true", help="machine output")
    hl.add_argument(
        "--check", action="store_true",
        help="CI smoke: exit 1 on a failing verdict or zero evaluated rules",
    )
    hl.set_defaults(fn=cmd_health)

    pm = sub.add_parser(
        "postmortem",
        help="assemble one trace's incident report from JSONL artifacts",
    )
    pm.add_argument("trace_id", help="the trace to reconstruct")
    pm.add_argument(
        "paths", nargs="+",
        help="span/event/metrics .jsonl files or directories (the "
        "--trace-dir output)",
    )
    pm.add_argument(
        "--rules", default="",
        help="JSON rules file (default: obs.health POSTMORTEM_RULES)",
    )
    pm.add_argument("--json", action="store_true", help="machine output")
    pm.add_argument("--out", default="", help="write the report JSON here")
    pm.set_defaults(fn=cmd_postmortem)

    fl = sub.add_parser(
        "fleet", help="render fleet SLIs from collector artifacts"
    )
    fl.add_argument(
        "paths", nargs="+",
        help="fleet-sample *.ndjson files and/or per-node *.history.json "
        "dumps (or directories of them)",
    )
    fl.add_argument("--json", action="store_true", help="machine output")
    fl.add_argument(
        "--check", action="store_true",
        help="CI smoke: exit 1 unless a valid sample with real SLI "
        "series exists",
    )
    fl.set_defaults(fn=cmd_fleet)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
