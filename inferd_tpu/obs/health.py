"""SLO rule engine: declarative health rules over metrics + events.

PR 3/4 gave every node gauges, histograms, and gossiped summaries —
but nothing EVALUATES them: /health reported a handful of identity
fields and "is it bad?" was a human squinting at a dashboard. This
module makes health a computation:

  * a rule is one comparison over a named signal, written as a string —
    `"queue.depth < 16"`, `"hbm.frac < 0.95"`, `"trace.dropped == 0"`,
    `"hop.relay_ms.p99_ms < 2000"`, `"event:session.rescue/min < 30"` —
    with a severity (`degraded` or `failing`);
  * `burn:` rules are MULTI-WINDOW BURN-RATE SLOs (the Google-SRE
    workbook pattern): `"burn:availability[5m,1h] > 14"` fires when the
    error-budget burn rate exceeds 14x in BOTH the 5-minute and 1-hour
    trailing windows (short window = fast detection, long window = no
    flapping), evaluated from the local windowed tsdb (obs.tsdb).
    NOTE the inverted convention: a burn rule states the ALERT
    condition (burn > threshold), matching how burn-rate alerts are
    written everywhere, while metric/event rules state the HEALTHY
    condition. SLI names resolve via BURN_SLIS (bad counter / total
    counter / default objective; override the objective inline:
    `burn:availability@99.5[5m,1h] > 14`);
  * signals resolve against a node /stats-shaped snapshot (gauges first,
    then counters, then `histogram.field` paths into the summaries),
    against the event journal (`event:TYPE` = buffered count,
    `event:TYPE/min` = trailing-minute rate), and against gossiped peer
    records (`peer:FIELD` — fires when ANY peer breaches, so one node
    can flag fleet-wide trouble);
  * a signal that doesn't exist SKIPS its rule (a CPU node has no
    hbm.frac; skipping is not passing and not firing — the verdict
    reports how many rules actually evaluated);
  * the verdict is `ok` (nothing firing), `degraded` (only
    degraded-severity rules firing), or `failing` (any failing-severity
    rule firing), plus the firing rules with their observed values.

Served live from the node's enriched /health, gossiped as a `health`
column for the dashboard, and runnable offline over committed artifacts:
`python -m inferd_tpu.obs health --check tests/data/health` (run.sh
step 0d). Pure host-side Python — no jax, no sockets.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from inferd_tpu.obs import trace as tracelib

log = logging.getLogger(__name__)

SEVERITIES = ("degraded", "failing")

_OPS: Dict[str, Callable[[float, float], bool]] = {
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
}

_RULE_RE = re.compile(
    r"^\s*(?P<signal>[A-Za-z_][\w.:/@,\[\]-]*)\s*"
    r"(?P<op><=|>=|==|!=|<|>)\s*"
    r"(?P<threshold>[-+]?\d+(?:\.\d+)?)\s*$"
)

# burn:<sli>[@objective][w_short,w_long] — e.g. "burn:availability[5m,1h]"
# or "burn:availability@99.5[5m,1h]"
_BURN_RE = re.compile(
    r"^(?P<sli>[A-Za-z_][\w.-]*)"
    r"(?:@(?P<objective>\d+(?:\.\d+)?))?"
    r"\[(?P<windows>[^\]]+)\]$"
)

_WINDOW_UNITS = {"s": 1.0, "m": 60.0, "h": 3600.0}

def parse_window(text: str) -> float:
    """'5m' / '1h' / '90s' -> seconds."""
    m = re.match(r"^\s*(\d+(?:\.\d+)?)([smh])\s*$", text)
    if not m:
        raise ValueError(
            f"bad burn window {text!r}: want e.g. '5m', '1h', '30s'"
        )
    return float(m.group(1)) * _WINDOW_UNITS[m.group(2)]


#: Burn-rate SLI catalog: name -> (bad counter, total counter, default
#: objective %). Burn rate = (bad/total) / (1 - objective/100): 1.0 means
#: exactly consuming the error budget; 14 means 14x too fast (the
#: Google-SRE fast-burn page threshold for a 5m/1h pair).
BURN_SLIS: Dict[str, Tuple[str, str, float]] = {
    # user-visible request availability: server-error /generate
    # responses over /generate traffic. Deliberately the generate.*
    # family, NOT the node-wide errors/forward.requests counters: those
    # count canary probe traffic (a failing probe 500s like any other
    # request, and its self-driven hops bump forward.requests), so a
    # broken chain probed on an idle fleet would page "user availability
    # burn" out of purely synthetic load — exactly what canary isolation
    # promises cannot happen.
    "availability": ("generate.errors", "generate.requests", 99.9),
    # synthetic canary probe availability (obs.canary)
    "canary": ("canary.fail", "canary.probes", 99.0),
}


@dataclasses.dataclass(frozen=True)
class BurnSignal:
    """Parsed `burn:` signal: SLI counters + objective + window pair."""

    sli: str
    bad: str
    total: str
    objective: float
    windows: Tuple[float, ...]

    @staticmethod
    def parse(signal: str) -> "BurnSignal":
        m = _BURN_RE.match(signal)
        if not m:
            raise ValueError(
                f"bad burn signal {signal!r}: want "
                "'<sli>[5m,1h]' or '<sli>@99.5[5m,1h]' "
                f"with sli one of {sorted(BURN_SLIS)}"
            )
        sli = m.group("sli")
        if sli not in BURN_SLIS:
            raise ValueError(
                f"unknown burn SLI {sli!r}: want one of {sorted(BURN_SLIS)}"
            )
        bad, total, default_obj = BURN_SLIS[sli]
        obj = float(m.group("objective") or default_obj)
        if not 0.0 < obj < 100.0:
            raise ValueError(f"burn objective {obj} out of range (0, 100)")
        windows = tuple(
            parse_window(w) for w in m.group("windows").split(",") if w.strip()
        )
        if not 1 <= len(windows) <= 2:
            raise ValueError(
                f"burn signal {signal!r}: want one or two windows, "
                "e.g. [5m,1h]"
            )
        return BurnSignal(sli, bad, total, obj, windows)


@dataclasses.dataclass(frozen=True)
class Rule:
    """One SLO rule: `signal op threshold` fires when the comparison is
    VIOLATED (rules state the healthy condition, like an assert)."""

    signal: str
    op: str
    threshold: float
    severity: str = "degraded"

    @property
    def expr(self) -> str:
        return f"{self.signal} {self.op} {self.threshold:g}"

    @staticmethod
    def parse(expr: str, severity: str = "degraded") -> "Rule":
        m = _RULE_RE.match(expr)
        if not m:
            raise ValueError(
                f"bad SLO rule {expr!r}: want '<signal> <op> <number>', "
                "e.g. 'queue.depth < 16' or 'event:session.rescue/min < 30'"
            )
        if severity not in SEVERITIES:
            raise ValueError(
                f"bad severity {severity!r}: want one of {SEVERITIES}"
            )
        signal = m.group("signal")
        if signal.startswith("burn:"):
            BurnSignal.parse(signal[len("burn:"):])  # validate at parse time
        return Rule(
            signal, m.group("op"), float(m.group("threshold")),
            severity,
        )


#: Live-node defaults (evaluated by /health and gossiped): rate-based
#: event rules, so one historical incident doesn't fire forever.
#: Thresholds leave headroom for a SINGLE benign event (rate_over's 30 s
#: reach floor means one event reads at most 2/min) — except oom, where
#: any occurrence deliberately flips the node failing for the next
#: window (a device OOM is never benign on a serving node).
DEFAULT_RULES: Tuple[Rule, ...] = (
    Rule.parse("hbm.frac < 0.95", severity="failing"),
    Rule.parse("trace.dropped == 0"),
    Rule.parse("queue.depth < 16"),
    Rule.parse("hop.relay_ms.p99_ms < 2000"),
    Rule.parse("event:session.rescue/min < 30"),
    # rescue GIVE-UPS: the fleet stopped acting on KV-less chunks and
    # clients are paying full restarts. A sustained rate means either a
    # stage lost every holder AND standby (capacity incident) or the
    # session-location gossip is broken. Its quieter sibling above fires
    # on rescue VOLUME; this one fires when rescues stop working.
    Rule.parse("event:session.rescue_failed/min < 30"),
    # standby promotions degrading to restarts (crash-tolerant sessions,
    # docs/SERVING.md "Failover & durability"): the replicated prefix
    # failed validation at import — replication is shipping bytes that
    # can't promote, i.e. paying RAM + wire for nothing. Zero on nodes
    # without --standby-repl (the event never fires there).
    Rule.parse("event:standby.stale/min < 30"),
    Rule.parse("event:peer.dead/min < 10"),
    Rule.parse("event:executor.warmup_failed/min < 3", severity="failing"),
    Rule.parse("event:kv.overflow/min < 10"),
    # prefix-cache thrash watch (memory plane, ISSUE 13): sustained
    # prefix-index evictions mean every admission's registration evicts
    # some other prompt's blocks before reuse — the pool is too small
    # for the working set (or pins are missing) and the shared-prefix
    # win silently degrades to cold prefills. 240/min = every ~250 ms;
    # ordinary churn ages out far slower. Degraded, not failing:
    # correctness is untouched, only the capacity win.
    Rule.parse("event:prefix.evict/min < 240"),
    Rule.parse("event:oom/min < 1", severity="failing"),
    # fleet memory-capacity watch over the gossiped `kvfree` fraction
    # (runtime/node: paged block-pool blocks_free/num_blocks — the same
    # watermark the admission shed and control.autoscale act on): ANY
    # peer under 2% free is effectively shedding every new session.
    # Dense replicas don't gossip the key and don't vote; a fleet with
    # no paged nodes SKIPS the rule.
    Rule.parse("peer:kvfree > 0.02"),
    # multi-window burn-rate SLOs (Google-SRE workbook pages): the fast
    # pair catches a cliff in minutes, the slow pair a steady leak in
    # hours; both must agree before firing, so a single bad minute
    # doesn't flap the verdict. Evaluated from windowed tsdb histories —
    # skipped (not green) on nodes/scrapes without one.
    Rule.parse("burn:availability[5m,1h] > 14", severity="failing"),
    Rule.parse("burn:availability[30m,4h] > 3"),
    Rule.parse("burn:canary[5m,1h] > 14", severity="failing"),
)

#: Postmortem defaults (evaluated over ONE trace's window): count-based
#: — inside an incident window, a single peer.dead IS the story.
POSTMORTEM_RULES: Tuple[Rule, ...] = (
    Rule.parse("event:peer.dead == 0", severity="failing"),
    Rule.parse("event:session.rescue == 0"),
    Rule.parse("event:oom == 0", severity="failing"),
    Rule.parse("event:kv.overflow == 0"),
    Rule.parse("event:executor.warmup_failed == 0"),
    Rule.parse("event:relay.coalesced_fallback == 0"),
    Rule.parse("trace.dropped == 0"),
    Rule.parse("hbm.frac < 0.95", severity="failing"),
)


# ------------------------------------------------------------- resolution


def _resolve_metric(snapshot: Dict[str, Any], path: str) -> Optional[float]:
    """Signal lookup over a /stats-shaped snapshot: gauges, counters,
    then `<histogram name>.<summary field>` (the summary dicts
    utils.metrics.Histogram.summary emits)."""
    for section in ("gauges", "counters"):
        val = (snapshot.get(section) or {}).get(path)
        if isinstance(val, (int, float)):
            return float(val)
    hists = snapshot.get("histograms") or {}
    if "." in path:
        hname, _, field = path.rpartition(".")
        row = hists.get(hname)
        if isinstance(row, dict) and isinstance(row.get(field), (int, float)):
            return float(row[field])
    return None


def _resolve_event(
    signal: str,
    events: Sequence[Dict[str, Any]],
    now: Optional[float],
    window_s: float,
) -> Optional[float]:
    """`event:TYPE` = count over the provided events; `event:TYPE/min` =
    trailing-window rate per minute (events.rate_over — the ONE
    estimator, reach-clamped so a young node's burst reads as a burst).
    Events are whatever the caller scoped (the live ring for /health,
    one trace's window for postmortem); None (skip) only when no event
    list was provided at all — an empty list means "journal says nothing
    happened" = 0."""
    from inferd_tpu.obs import events as eventslib

    if events is None:
        return None
    etype, per_min = signal, False
    if signal.endswith("/min"):
        etype, per_min = signal[: -len("/min")], True
    if not per_min:
        return float(sum(1 for ev in events if ev.get("type") == etype))
    ref = now if now is not None else tracelib.now()
    return eventslib.rate_over(events, etype, ref, window_s)


def _resolve_burn(
    signal: str,
    histories: Optional[Sequence[Dict[str, Any]]],
    now: Optional[float],
) -> Optional[List[float]]:
    """Per-window burn rates for a `burn:` signal over windowed tsdb
    histories (obs.tsdb — one per node, merged by summed deltas), or
    None (skip) when no history carries the SLI's TOTAL counter: a fleet
    that never served a request has no availability to burn. Zero
    traffic inside a window reads as zero burn, not as a skip — the
    series exists, nothing is being burned. Burn is a ratio of
    SAME-WINDOW SUMS (bad/total), never of per-series rates: a bad
    counter born at the first failure would otherwise read reach-clamped
    (amplified) against its long-lived total."""
    from inferd_tpu.obs import tsdb as tsdblib

    if not histories:
        return None
    burn = BurnSignal.parse(signal)
    budget = 1.0 - burn.objective / 100.0
    out: List[float] = []
    for w in burn.windows:
        total = tsdblib.merge_trailing_sum(histories, burn.total, w, now)
        if total is None:
            return None
        bad = tsdblib.merge_trailing_sum(histories, burn.bad, w, now) or 0.0
        out.append((bad / total / budget) if total > 0 else 0.0)
    return out


def burn_gauges(
    histories: Optional[Sequence[Dict[str, Any]]],
    now: Optional[float] = None,
    window_s: float = 300.0,
) -> Dict[str, float]:
    """Current short-window burn rate per BURN_SLIS entry, as `burn.<sli>`
    gauge values for /metrics — the continuously observable face of the
    burn-rate rules (the rules themselves gate on BOTH windows; this is
    the fast one, for dashboards and ad-hoc scrapes). SLIs whose total
    counter doesn't exist in any history are omitted."""
    from inferd_tpu.obs import tsdb as tsdblib

    out: Dict[str, float] = {}
    for sli, (bad, total, objective) in sorted(BURN_SLIS.items()):
        t = tsdblib.merge_trailing_sum(histories or [], total, window_s, now)
        if t is None:
            continue
        b = tsdblib.merge_trailing_sum(
            histories or [], bad, window_s, now
        ) or 0.0
        budget = 1.0 - objective / 100.0
        out[f"burn.{sli}"] = round((b / t / budget) if t > 0 else 0.0, 4)
    return out


def evaluate_rule(
    rule: Rule,
    snapshot: Dict[str, Any],
    events: Optional[Sequence[Dict[str, Any]]] = None,
    peers: Optional[Dict[str, Dict[str, Any]]] = None,
    now: Optional[float] = None,
    window_s: float = 60.0,
    histories: Optional[Sequence[Dict[str, Any]]] = None,
) -> Tuple[Optional[bool], Optional[float], Optional[str]]:
    """(fired, observed value, offending peer) — fired is None when the
    signal can't be resolved (rule skipped)."""
    sig = rule.signal
    if sig.startswith("burn:"):
        burns = _resolve_burn(sig[len("burn:"):], histories, now)
        if burns is None:
            return None, None, None
        # INVERTED convention (see module docstring): a burn rule states
        # the ALERT condition and fires when it holds in EVERY window
        # (short window = fast detection, long window = no flapping). The
        # observed value is the LIMITING window's burn — the one closest
        # to not firing.
        fired = all(_OPS[rule.op](b, rule.threshold) for b in burns)
        limiting = min(burns) if rule.op in (">", ">=") else max(burns)
        return fired, limiting, None
    if sig.startswith("event:"):
        val = _resolve_event(sig[len("event:"):], events, now, window_s)
        if val is None:
            return None, None, None
        return (not _OPS[rule.op](val, rule.threshold)), val, None
    if sig.startswith("peer:"):
        if not peers:
            # no peers to judge (None OR a single-replica swarm's empty
            # map): SKIP — "no data" must not report as "passing"
            return None, None, None
        field = sig[len("peer:"):]
        worst: Optional[Tuple[float, str]] = None

        def badness(v: float) -> float:
            # "worst" is direction-aware: for a lower-bound healthy
            # condition (`kvfree > 0.02`) the worst violator is the
            # SMALLEST value (the tightest pool), for an upper bound
            # (`hop_p99_ms < 100`) the largest; magnitude only for
            # equality rules. Max-abs alone named the least-critical
            # breacher of a `>` rule.
            if rule.op in (">", ">="):
                return rule.threshold - v
            if rule.op in ("<", "<="):
                return v - rule.threshold
            return abs(v)

        judged = False
        for nid, rec in peers.items():
            v = rec.get(field)
            if not isinstance(v, (int, float)):
                continue
            judged = True
            if not _OPS[rule.op](float(v), rule.threshold):
                if worst is None or badness(float(v)) > badness(worst[0]):
                    worst = (float(v), nid)
        if not judged:
            return None, None, None  # peers exist but none carry the field
        if worst is not None:
            return True, worst[0], worst[1]
        return False, None, None
    val = _resolve_metric(snapshot, sig)
    if val is None:
        return None, None, None
    return (not _OPS[rule.op](val, rule.threshold)), val, None


def evaluate(
    rules: Sequence[Rule],
    snapshot: Dict[str, Any],
    events: Optional[Sequence[Dict[str, Any]]] = None,
    peers: Optional[Dict[str, Dict[str, Any]]] = None,
    now: Optional[float] = None,
    window_s: float = 60.0,
    histories: Optional[Sequence[Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """Verdict over a snapshot: {"status": ok|degraded|failing,
    "firing": [...], "evaluated": N, "skipped": N}. `histories` are
    windowed tsdb history objects (live: the node's own; offline: every
    committed *.history.json) feeding the `burn:` rules."""
    firing: List[Dict[str, Any]] = []
    evaluated = skipped = 0
    for rule in rules:
        fired, val, peer = evaluate_rule(
            rule, snapshot, events=events, peers=peers, now=now,
            window_s=window_s, histories=histories,
        )
        if fired is None:
            skipped += 1
            continue
        evaluated += 1
        if fired:
            row: Dict[str, Any] = {
                "rule": rule.expr,
                "severity": rule.severity,
                "value": round(val, 6) if val is not None else None,
            }
            if peer is not None:
                row["peer"] = peer
            firing.append(row)
    if any(f["severity"] == "failing" for f in firing):
        status = "failing"
    elif firing:
        status = "degraded"
    else:
        status = "ok"
    return {
        "status": status,
        "firing": firing,
        "evaluated": evaluated,
        "skipped": skipped,
    }


# ---------------------------------------------------------------- loading


def load_rules(path: str) -> List[Rule]:
    """Rules from a JSON file: ["expr", ...] or
    [{"rule": "expr", "severity": "failing"}, ...]."""
    with open(path) as f:
        raw = json.load(f)
    if not isinstance(raw, list):
        raise ValueError(f"{path}: want a JSON list of rules")
    out: List[Rule] = []
    for item in raw:
        if isinstance(item, str):
            out.append(Rule.parse(item))
        elif isinstance(item, dict) and isinstance(item.get("rule"), str):
            out.append(
                Rule.parse(item["rule"], item.get("severity", "degraded"))
            )
        else:
            raise ValueError(f"{path}: bad rule entry {item!r}")
    return out


def load_scrape(paths: Sequence[str]) -> Dict[str, Any]:
    """Assemble an offline health input from files/directories:
    `*.json` (not rules.json) = /stats-shaped snapshot (multiple merge
    shallowly, later files win per section key), `*.events.jsonl` =
    journal lines, `*.history.json` = windowed tsdb histories (the
    /metrics/history dumps feeding `burn:` rules), `rules.json` = rule
    overrides."""
    from inferd_tpu.obs import events as eventslib
    from inferd_tpu.obs import tsdb as tsdblib

    snap_files: List[str] = []
    history_files: List[str] = []
    rules_path: Optional[str] = None
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    full = os.path.join(root, f)
                    if f == "rules.json":
                        rules_path = full
                    elif f.endswith(".history.json"):
                        history_files.append(full)
                    elif f.endswith(".json"):
                        snap_files.append(full)
        elif p.endswith("rules.json"):
            rules_path = p
        elif p.endswith(".history.json"):
            history_files.append(p)
        elif p.endswith(".json"):
            snap_files.append(p)
    histories: List[Dict[str, Any]] = []
    for path in history_files:
        try:
            histories.append(tsdblib.load_history_file(path))
        except (ValueError, OSError) as e:
            # degrade-don't-crash, like every other artifact loader: a
            # node killed mid-dump leaves a truncated history — skip it
            # rather than take down the whole verdict
            log.warning("skipping invalid history %s: %s", path, e)
    snapshot: Dict[str, Any] = {}
    for path in snap_files:
        with open(path) as f:
            obj = json.load(f)
        if not isinstance(obj, dict):
            raise ValueError(f"{path}: scrape is not a JSON object")
        for section, vals in obj.items():
            if isinstance(vals, dict):
                snapshot.setdefault(section, {}).update(vals)
            else:
                snapshot[section] = vals
    # events must be None (not []) when the scrape includes NO journal
    # files at all: event rules then SKIP instead of evaluating to a
    # green zero against data that was never collected — the distinction
    # `--check`'s evaluated>0 guard depends on
    has_journals = bool(eventslib.iter_event_files(paths))
    return {
        "snapshot": snapshot,
        "events": eventslib.load_events(paths) if has_journals else None,
        "rules": load_rules(rules_path) if rules_path else None,
        # None (not []) when no history was committed: burn rules must
        # SKIP, mirroring the events-vs-None distinction above
        "histories": histories or None,
    }


def format_verdict(verdict: Dict[str, Any]) -> str:
    lines = [
        f"health: {verdict['status'].upper()} "
        f"({len(verdict['firing'])} firing, {verdict['evaluated']} evaluated, "
        f"{verdict['skipped']} skipped)"
    ]
    for f in verdict["firing"]:
        peer = f" (peer {f['peer']})" if "peer" in f else ""
        lines.append(
            f"  {f['severity'].upper():9} {f['rule']}  "
            f"observed {f['value']}{peer}"
        )
    return "\n".join(lines)
