"""Perf regression gate over committed BENCH_*.json(l) artifacts.

Three checks, each grounded in a round-5 failure mode:

  * ORDERING — a decode leg's steady (differenced) rate must be >= its
    e2e rate: steady removes fixed dispatch overhead, so in per-token ms
    steady <= e2e MUST hold; round 5 shipped a leg with e2e 119 > steady
    78 stamped `steady_timing_valid: true` (VERDICT weak #5). Legs
    produced by the round-6 interleaved-paired methodology (they carry
    `steady_spread_pt`) get a hard ERROR on inversion — the methodology
    guarantees the ordering, so a violation means the harness broke.
    Legacy legs (no spread field) can't retroactively satisfy a guarantee
    their methodology never made: they get a WARNING, which is how the
    gate passes the committed round-5 artifacts while still flagging the
    known inversion.
  * REGRESSION — against a prior artifact: a leg whose roofline fraction
    (or value, when no fraction exists on either side) dropped >= 20% is
    an ERROR. This is the check that makes "win or retire" (VERDICT item
    9) enforceable in CI once two artifacts exist.
  * PHYSICS — a leg claiming more than ~100% of the analytic roofline
    (perf/roofline) is measuring wrong or modeling wrong: ERROR. A
    recorded `hbm_roofline_frac` that drifts >25% from the model's
    re-derivation is a WARNING (bench.py's historical byte accounting
    billed quantized models for the full bf16 embed table; the model does
    not — docs/PERF.md).

`check_artifact` is pure (list of findings in); the CLI (__main__) wires
it to files and exit codes. Run in CI against the committed round-5
artifacts via tests/test_perf.py and run.sh (advisory step).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from inferd_tpu.perf import roofline as rl

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_ARTIFACT = os.path.join(_REPO, "bench_artifacts", "BENCH_tpu_r05.jsonl")

ORDER_TOL = 0.02  # 2% slack: float rounding must not flip the ordering check
FRAC_REGRESSION = 0.20  # >= 20% roofline-fraction drop fails the gate
FRAC_IMPOSSIBLE = 1.02  # claiming > 102% of the roofline is a measurement bug
FRAC_DRIFT_WARN = 0.25  # recorded frac vs model re-derivation
OVERLOAD_GOODPUT_FLOOR = 0.70  # chaos goodput must keep >= 70% of fault-free
HEDGE_EXTRA_CAP = 0.05  # hedged relays may add at most 5% load
HEDGE_BURST = 2  # RatioBudget's burst floor: fired <= cap*primary + burst


@dataclasses.dataclass(frozen=True)
class Finding:
    severity: str  # "error" | "warning"
    leg: str
    check: str  # "ordering" | "regression" | "physics" | "artifact"
    message: str

    def line(self) -> str:
        return f"{self.severity.upper():7} [{self.check}] {self.leg}: {self.message}"


Leg = Tuple[str, Dict[str, Any]]  # (leg name, bench result dict)


def load_artifact(path: str) -> List[Leg]:
    """Legs from a battery .jsonl (one {"leg", "result"} object per line)
    or a single-JSON default-bench artifact (one {"metric", ...} object).
    Lines that never produced a result dict surface as a `_failed` marker
    leg so the gate can warn instead of silently skipping them."""
    legs: List[Leg] = []
    with open(path) as f:
        text = f.read()
    lines = [ln for ln in text.splitlines() if ln.strip()]
    for i, ln in enumerate(lines):
        try:
            obj = json.loads(ln)
        except ValueError as e:
            # a battery killed mid-append leaves a truncated final line;
            # the intact legs must still be gate-checkable
            legs.append((f"line{i + 1}", {"_failed": f"unparseable line: {e}"}))
            continue
        if not isinstance(obj, dict):
            raise ValueError(f"{path}:{i + 1}: not a JSON object")
        if "result" in obj or "leg" in obj:
            name = str(obj.get("leg", f"line{i + 1}"))
            res = obj.get("result")
            if isinstance(res, dict):
                legs.append((name, res))
            else:
                legs.append((name, {"_failed": obj.get("error", "no result")}))
        elif "metric" in obj:
            legs.append((str(obj["metric"]), obj))
        else:
            raise ValueError(
                f"{path}:{i + 1}: neither a battery line nor a bench result"
            )
    return legs


_DECODE_RE = re.compile(
    r"^(?P<preset>.+?)_decode_tok_per_s_bs1"
    r"(?:_ctx(?P<ctx>\d+))?"
    r"(?:_kv-(?P<kv>[A-Za-z0-9_]+?))?"
    r"(?:_(?P<quant>int8|w8a8|int8-kernel|int4))?$"
)


def parse_decode_metric(metric: str):
    """(ModelConfig, quant, kv_dtype, ctx) for a decode-leg metric name,
    or None when the metric isn't a decode leg / names no known preset."""
    from inferd_tpu.config import PRESETS

    m = _DECODE_RE.match(metric)
    if not m:
        return None
    want = m.group("preset")
    cfg = next(
        (c for n, c in PRESETS.items() if n.replace("-", "_") == want), None
    )
    if cfg is None:
        return None
    return (
        cfg,
        m.group("quant") or "none",
        m.group("kv") or "model",
        int(m.group("ctx") or 0),
    )


def model_frac(result: Dict[str, Any], chip: rl.ChipSpec) -> Optional[float]:
    """Re-derive a decode leg's roofline fraction from the analytic model,
    or None when the metric isn't decode-shaped / value is missing."""
    parsed = parse_decode_metric(str(result.get("metric", "")))
    if parsed is None or not isinstance(result.get("value"), (int, float)):
        return None
    cfg, quant, kv, ctx = parsed
    cost = rl.decode_step_cost(cfg, quant=quant, kv_dtype=kv, ctx=ctx)
    return rl.roofline_frac(float(result["value"]), cost, chip)


def _comparable(res: Dict[str, Any], pres: Dict[str, Any]):
    """((kind, cur, prior) | None) for the regression check.

    Recorded roofline fractions are only comparable when both legs were
    produced by the same byte-accounting generation (the round-6 bench
    rewrote the accounting together with the timing-methodology fields —
    an r05 int8 frac of 0.06 and an r06 frac of 0.039 describe the SAME
    measured tok/s). Cross-generation pairs fall back to the raw value:
    the legs already matched on metric, so model/ctx/quant cancel and the
    value is the same-denominator quantity."""
    # kernels legs regress on the worst kernel-vs-xla structural bytes
    # ratio (dimensionless by construction — roofline HBM traffic, not
    # wall clock, so a CPU-proxy artifact gates any host); a pair missing
    # it on either side SKIPS rather than falling through to raw value
    kr = str(res.get("metric", "")).endswith("kernels_min_bytes_ratio")
    ck, pk = res.get("min_kernel_vs_xla"), pres.get("min_kernel_vs_xla")
    if isinstance(ck, (int, float)) and isinstance(pk, (int, float)):
        return "min_kernel_vs_xla", float(ck), float(pk)
    if kr:
        return None
    # swarm-mixed (paged KV) legs regress on the PAGED/DENSE ratio —
    # dimensionless and machine-portable, exactly like the multistep
    # K-speedup below; a pair missing it on either side SKIPS rather than
    # falling through to raw tok/s (cross-host false fail)
    mixed = str(res.get("metric", "")).endswith("_swarm_mixed_tok_per_s")
    cm, pm = res.get("paged_vs_dense"), pres.get("paged_vs_dense")
    if isinstance(cm, (int, float)) and isinstance(pm, (int, float)):
        return "paged_vs_dense", float(cm), float(pm)
    if mixed:
        return None
    # cache-affinity legs regress on the routing-on HIT RATE (0..1,
    # dimensionless, machine-portable — an on/off RATIO is unbounded
    # because the rotated baseline legitimately bottoms out at zero
    # hits); a pair missing it on either side SKIPS rather than falling
    # through to raw tokens
    ca = str(res.get("metric", "")).endswith("_cache_affinity_saved_tokens")
    cr, pr = res.get("hit_frac_prior"), pres.get("hit_frac_prior")
    if isinstance(cr, (int, float)) and isinstance(pr, (int, float)):
        return "hit_frac_prior", float(cr), float(pr)
    if ca:
        return None
    # lora-tenants legs regress on the CO-BATCH/SERIAL aggregate ratio
    # (dimensionless, machine-portable — raw tok/s would false-fail on a
    # slower host); a pair missing it on either side SKIPS rather than
    # falling through to raw tok/s
    lt = str(res.get("metric", "")).endswith("_lora_tenants_tok_per_s")
    clt, plt = res.get("cobatch_vs_serial"), pres.get("cobatch_vs_serial")
    if isinstance(clt, (int, float)) and isinstance(plt, (int, float)):
        return "cobatch_vs_serial", float(clt), float(plt)
    if lt:
        return None
    # failover legs regress on the RECOVERY GAIN (restart-recovery over
    # promotion-recovery, dimensionless) — raw recovery ms would
    # false-fail on a slower host, and "value" here is LOWER-is-better
    # so the generic fallback must never see it
    fo = str(res.get("metric", "")).endswith("_failover_recovery_ms")
    cfo, pfo = res.get("recovery_gain"), pres.get("recovery_gain")
    if isinstance(cfo, (int, float)) and isinstance(pfo, (int, float)):
        return "recovery_gain", float(cfo), float(pfo)
    if fo:
        return None
    # overload legs regress on the chaos/fault-free GOODPUT ratio — the
    # same dimensionless-prior pattern; raw tok/s would false-fail on a
    # slower host
    ov = str(res.get("metric", "")).endswith("_overload_goodput_tok_per_s")
    cg, pg = res.get("goodput_ratio"), pres.get("goodput_ratio")
    if isinstance(cg, (int, float)) and isinstance(pg, (int, float)):
        return "goodput_ratio", float(cg), float(pg)
    if ov:
        return None
    # multi-step decode legs regress on the K-SPEEDUP ratio: it is
    # dimensionless (machine-portable — a CPU-proxy artifact committed on
    # one box gates a run on another), and it IS this leg's claim: the
    # fused K-step loop must keep beating per-token dispatch by the
    # committed margin. Raw tok/s would false-fail on any slower host.
    cs, ps = res.get("speedup_best_vs_k1"), pres.get("speedup_best_vs_k1")
    if isinstance(cs, (int, float)) and isinstance(ps, (int, float)):
        return "speedup_best_vs_k1", float(cs), float(ps)
    if "per_k" in res or "per_k" in pres:
        # a multistep pair missing the ratio on either side (e.g. a sweep
        # that skipped K=1) must NOT fall through to raw tok/s — that is
        # exactly the cross-host false-fail the ratio exists to prevent
        return None
    same_gen = ("timing_methodology" in res) == ("timing_methodology" in pres)
    cf, pf = res.get("hbm_roofline_frac"), pres.get("hbm_roofline_frac")
    if (
        same_gen and isinstance(cf, (int, float))
        and isinstance(pf, (int, float))
    ):
        return "hbm_roofline_frac", float(cf), float(pf)
    cv, pv = res.get("value"), pres.get("value")
    if (
        isinstance(cv, (int, float)) and isinstance(pv, (int, float))
        and res.get("unit") == pres.get("unit")
    ):
        return f"value ({res.get('unit', '?')})", float(cv), float(pv)
    return None


def check_artifact(
    legs: List[Leg],
    prior: Optional[List[Leg]] = None,
    chip: rl.ChipSpec = rl.CHIP_SPECS["v5e"],
) -> List[Finding]:
    out: List[Finding] = []
    prior_map = {name: res for name, res in (prior or [])}
    for name, res in legs:
        if "_failed" in res:
            out.append(Finding(
                "warning", name, "artifact",
                f"leg produced no result: {res['_failed']}",
            ))
            continue
        if res.get("error"):
            # an errored leg is normally advisory (the box may just lack
            # the hardware), but a leg that measured token_exact=False is
            # a CORRECTNESS regression — the multistep ordering gate is
            # documented HARD and must not pass a divergent K-step stream
            sev = "error" if res.get("token_exact") is False else "warning"
            out.append(Finding(
                sev, name, "artifact", f"leg errored: {res['error']}"
            ))
            continue

        # -- ordering: steady rate must be >= e2e rate ---------------------
        v, e2e = res.get("value"), res.get("e2e_tok_per_s")
        if (
            isinstance(v, (int, float)) and isinstance(e2e, (int, float))
            and res.get("steady_timing_valid")
        ):
            if v < e2e * (1 - ORDER_TOL):
                new_method = (
                    "steady_spread_pt" in res or "timing_methodology" in res
                )
                out.append(Finding(
                    "error" if new_method else "warning", name, "ordering",
                    f"steady {v} tok/s < e2e {e2e} tok/s inside a leg "
                    f"stamped steady_timing_valid "
                    + ("— the interleaved-paired methodology guarantees "
                       "this ordering; the harness is broken"
                       if new_method else
                       "(legacy pre-round-6 differencing; advisory)"),
                ))

        # -- ordering: multi-step fused decode must beat per-token dispatch
        # (the decode_multistep leg's whole claim: K tokens per dispatch
        # amortize host-loop overhead, so SOME K>1 must be at least as
        # fast as K=1 — a regression here means the fused inner loop costs
        # more than the dispatches it removes)
        per_k = res.get("per_k")
        if isinstance(per_k, dict):
            base = per_k.get("1", per_k.get(1))
            multi = {
                str(kk): vv for kk, vv in per_k.items()
                if str(kk) != "1" and isinstance(vv, (int, float))
            }
            if isinstance(base, (int, float)) and base > 0 and multi:
                best_k, best = max(multi.items(), key=lambda it: it[1])
                if best < base * (1 - ORDER_TOL):
                    out.append(Finding(
                        "error", name, "ordering",
                        f"multi-step decode best K={best_k} {best} tok/s < "
                        f"K=1 {base} tok/s — the fused K-step inner loop "
                        "regressed below per-token dispatch",
                    ))
                for kk, vv in sorted(multi.items()):
                    if vv < base * (1 - ORDER_TOL):
                        out.append(Finding(
                            "warning", name, "ordering",
                            f"K={kk} {vv} tok/s below K=1 {base} tok/s",
                        ))

        # -- correctness: a leg that measured token_exact=False is a hard
        # regression wherever it appears — a fast divergent stream is not
        # a result (the errored-leg path above already enforces this for
        # legs that died; this covers legs that "succeeded" divergent)
        if res.get("token_exact") is False:
            out.append(Finding(
                "error", name, "artifact",
                "leg measured token_exact=false — the optimized path "
                "diverged from its reference stream",
            ))

        # -- kernel-vs-xla ordering (HARD — the round-19 kernels leg's
        # whole claim: each Pallas decode kernel must move NO MORE HBM
        # bytes than the XLA sibling it replaces; a ratio under 1 means
        # the "optimized" path reads more than the gather/rematerialize
        # it was built to retire). Every graded sub-ratio is checked, not
        # just the min — a new kernel must not hide behind an old win.
        if str(res.get("metric", "")).endswith("kernels_min_bytes_ratio"):
            for fld in ("paged_vs_xla", "quant_int8_vs_xla",
                        "quant_int4_vs_xla", "lora_vs_xla"):
                rv = res.get(fld)
                if rv is None:
                    out.append(Finding(
                        "warning", name, "ordering",
                        f"kernels leg missing {fld} — a graded kernel "
                        "ratio silently dropped out of the artifact",
                    ))
                elif (
                    isinstance(rv, (int, float))
                    and rv < 1.0 * (1 - ORDER_TOL)
                ):
                    out.append(Finding(
                        "error", name, "ordering",
                        f"{fld} = {rv} < 1 — the Pallas kernel moves "
                        "MORE bytes than the XLA sibling it replaces",
                    ))

        # -- ordering: paged aggregate must be >= dense on the same
        # cluster (the swarm-mixed leg's whole claim: block-pool
        # allocation + shared-prefix skip + chunked prefill must WIN on a
        # mixed-length shared-prefix churn workload, not just not-lose)
        dense = res.get("dense_tok_per_s")
        if (
            str(res.get("metric", "")).endswith("_swarm_mixed_tok_per_s")
            and isinstance(v, (int, float))
            and isinstance(dense, (int, float))
            and v < dense * (1 - ORDER_TOL)
        ):
            out.append(Finding(
                "error", name, "ordering",
                f"paged aggregate {v} tok/s < dense {dense} tok/s on the "
                "same cluster — the block pool is costing more than its "
                "prefix-dedupe saves",
            ))

        # -- overload containment invariants (HARD — the leg's whole
        # claim is that deadlines/budgets/cooldowns/hedges CONTAIN a
        # sick replica instead of letting it convert the chain's work
        # into waste; docs/SERVING.md "Overload & reliability")
        if str(res.get("metric", "")).endswith("_overload_goodput_tok_per_s"):
            gr = res.get("goodput_ratio")
            if (
                isinstance(gr, (int, float))
                and gr < OVERLOAD_GOODPUT_FLOOR * (1 - ORDER_TOL)
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"chaos goodput ratio {gr} below the "
                    f"{OVERLOAD_GOODPUT_FLOOR} floor — the containment "
                    "plane is letting one sick replica eat the chain",
                ))
            hung = res.get("hung_requests")
            if isinstance(hung, (int, float)) and hung > 0:
                out.append(Finding(
                    "error", name, "ordering",
                    f"{int(hung)} request(s) ran past their deadline — "
                    "deadline propagation failed to bound them",
                ))
            hf = res.get("hedge_extra_frac")
            fired = res.get("hedge_fired")
            # the RatioBudget admits `cap*primary + burst` hedges, so a
            # SHORT leg that only used its burst floor can legitimately
            # read above the cap as a fraction — exempt exactly that
            # (fired <= burst); a leg not reporting hedge_fired gets the
            # strict fractional check
            burst_only = isinstance(fired, (int, float)) and fired <= HEDGE_BURST
            if (
                isinstance(hf, (int, float))
                and hf > HEDGE_EXTRA_CAP * (1 + ORDER_TOL)
                and not burst_only
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"hedge extra load {hf} exceeds the "
                    f"{HEDGE_EXTRA_CAP} budget cap",
                ))

        # -- crash-failover invariants (HARD — the leg's whole claim is
        # that standby promotion beats the full-restart baseline while
        # re-prefilling no more than the replication lag; docs/SERVING.md
        # "Failover & durability")
        if str(res.get("metric", "")).endswith("_failover_recovery_ms"):
            gain = res.get("recovery_gain")
            if (
                isinstance(gain, (int, float))
                and gain <= 1.0 * (1 + ORDER_TOL)
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"recovery gain {gain} <= 1 — standby promotion "
                    "failed to beat the full-restart baseline",
                ))
            promos = res.get("promotions")
            if isinstance(promos, (int, float)) and promos < 1:
                out.append(Finding(
                    "error", name, "ordering",
                    "replication-on kill produced ZERO standby "
                    "promotions — the failover never exercised the "
                    "replication plane",
                ))
            ro = res.get("restarts_on")
            if isinstance(ro, (int, float)) and ro > 0:
                out.append(Finding(
                    "error", name, "ordering",
                    f"replication-on recovery fell back to {int(ro)} "
                    "full client restart(s) — promotion must continue "
                    "the session, not restart it",
                ))
            ron = res.get("re_prefilled_on")
            roff = res.get("re_prefilled_off")
            if (
                isinstance(ron, (int, float))
                and isinstance(roff, (int, float)) and ron >= roff
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"promotion re-prefilled {int(ron)} tokens vs "
                    f"{int(roff)} for the restart baseline — the "
                    "replicated prefix saved nothing",
                ))
            cap = res.get("re_prefill_cap")
            if (
                isinstance(ron, (int, float))
                and isinstance(cap, (int, float)) and ron > cap
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"promotion re-prefilled {int(ron)} tokens, past "
                    f"the replication-lag bound {int(cap)} — the RPO "
                    "is not bounded",
                ))

        # -- ordering: digest routing must strictly increase the fleet's
        # prefill-tokens-avoided vs the round-robin baseline on the same
        # mixed-churn cluster (the cache-affinity leg's whole claim:
        # gossiped prefix digests steer sessions to the replica already
        # holding their blocks — equal-or-worse means the bonus is not
        # steering, or the digest is stale/garbage)
        if str(res.get("metric", "")).endswith("_cache_affinity_saved_tokens"):
            s_on = res.get("saved_tokens_on")
            s_off = res.get("saved_tokens_off")
            if (
                isinstance(s_on, (int, float))
                and isinstance(s_off, (int, float))
                and s_on <= s_off
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"digest routing saved {s_on} prefill tokens vs "
                    f"{s_off} without — cache-affinity routing failed to "
                    "increase fleet prefill-tokens-avoided",
                ))

        # -- multi-tenant LoRA invariants (HARD — the leg's whole claim:
        # heterogeneous-adapter sessions CO-BATCH into single gathered
        # dispatches, strictly beating per-tenant serial on the same
        # cluster, with every tenant token-exact vs its merged solo
        # reference; docs/SERVING.md "Multi-tenant adapters". The
        # token_exact hard-fail is the generic check above.)
        if str(res.get("metric", "")).endswith("_lora_tenants_tok_per_s"):
            ser_l = res.get("serial_tok_per_s")
            if (
                isinstance(v, (int, float))
                and isinstance(ser_l, (int, float))
                and v <= ser_l * (1 + ORDER_TOL)
            ):
                out.append(Finding(
                    "error", name, "ordering",
                    f"co-batched multi-adapter aggregate {v} tok/s does "
                    f"not strictly beat per-tenant serial {ser_l} tok/s "
                    "on the same cluster — the gathered apply is costing "
                    "more than co-batching saves",
                ))
            loads = res.get("adapter_loads")
            if isinstance(loads, (int, float)) and loads < 1:
                out.append(Finding(
                    "error", name, "ordering",
                    "zero adapter hot-loads recorded — the leg never "
                    "exercised the registry",
                ))
            ds = res.get("distinct_streams")
            if isinstance(ds, (int, float)) and ds < 2:
                out.append(Finding(
                    "error", name, "ordering",
                    f"only {int(ds)} distinct tenant stream(s) — the "
                    "adapters are not discriminating, so token-exactness "
                    "proves nothing",
                ))

        # -- ordering: swarm aggregate must be >= the serial baseline ------
        # (stage-level continuous batching's own invariant: the concurrent
        # side co-batches onto the same device the serial side used one
        # session at a time, so a concurrent aggregate BELOW serial means
        # the window/coalescing machinery is costing more than it saves)
        ser = res.get("serial_tok_per_s")
        if (
            str(res.get("metric", "")).endswith("_swarm_agg_tok_per_s")
            and isinstance(v, (int, float))
            and isinstance(ser, (int, float))
            and v < ser * (1 - ORDER_TOL)
        ):
            out.append(Finding(
                "error", name, "ordering",
                f"swarm aggregate {v} tok/s < serial baseline {ser} tok/s "
                "— co-batching regressed below one-session-at-a-time",
            ))

        # -- physics: recorded + re-derived roofline fraction --------------
        rec = res.get("hbm_roofline_frac")
        if isinstance(rec, (int, float)) and rec > FRAC_IMPOSSIBLE:
            out.append(Finding(
                "error", name, "physics",
                f"recorded hbm_roofline_frac {rec} exceeds the roofline",
            ))
        if res.get("device") == "tpu":
            # a round-6 leg records the chip its fraction was computed
            # against; re-derive against THAT chip, not the CLI default —
            # a v5p artifact checked at v5e's ceiling would false-fail
            leg_chip = rl.CHIP_SPECS.get(str(res.get("roofline_chip")), chip)
            derived = model_frac(res, leg_chip)
            if derived is not None:
                if derived > FRAC_IMPOSSIBLE:
                    out.append(Finding(
                        "error", name, "physics",
                        f"measured {res['value']} tok/s is "
                        f"{derived:.2f}x the {leg_chip.key} analytic ceiling",
                    ))
                if (
                    isinstance(rec, (int, float)) and rec > 0
                    and abs(derived - rec) / rec > FRAC_DRIFT_WARN
                ):
                    out.append(Finding(
                        "warning", name, "physics",
                        f"recorded frac {rec} vs model re-derivation "
                        f"{derived:.3f} (>25% drift — byte-accounting "
                        "divergence, see docs/PERF.md)",
                    ))

        # -- regression vs prior artifact ----------------------------------
        if name in prior_map:
            pres = prior_map[name]
            cmp = (
                _comparable(res, pres)
                if res.get("metric") == pres.get("metric") else None
            )
            if cmp is not None and cmp[2] > 0:
                kind, cur_v, prev_v = cmp
                drop = 1.0 - cur_v / prev_v
                if drop >= FRAC_REGRESSION:
                    out.append(Finding(
                        "error", name, "regression",
                        f"{kind} regressed {drop * 100:.1f}% "
                        f"({prev_v} -> {cur_v})",
                    ))
    return out


SPAN_OVERHEAD_FRAC = 0.01  # span recording must stay under 1% of compute


def check_span_overhead(stats: Dict[str, Any]) -> List[Finding]:
    """Findings over a node /stats snapshot: warn when cumulative
    span-recording cost (the obs.trace ring's `trace.overhead_ms` gauge)
    — or any of its always-on siblings: the event journal's
    `events.overhead_ms`, the windowed tsdb's `tsdb.overhead_ms`
    sampling cost, the canary prober's `canary.overhead_ms` bookkeeping,
    the lock-order sanitizer's `lockwatch.overhead_ms` checking cost
    — exceeds 1% of cumulative stage compute (stage.compute_ms histogram
    mean x count). The whole telemetry plane is only defensible while
    this holds — a warning here means a sampling rate or attr payload
    grew past the Dapper budget and needs a diet, not that the
    instrumentation is wrong."""
    gauges = stats.get("gauges") or {}
    counters = stats.get("counters") or {}
    h = (stats.get("histograms") or {}).get("stage.compute_ms") or {}
    count, mean = h.get("count"), h.get("mean_ms")
    if (
        not isinstance(count, (int, float))
        or not isinstance(mean, (int, float))
        or count <= 0
    ):
        return []
    compute_ms = float(mean) * float(count)
    if compute_ms <= 0:
        return []
    out: List[Finding] = []
    for gauge, label, hint in (
        ("trace.overhead_ms", "span-recording", "trim span attrs or rate"),
        ("events.overhead_ms", "event-journal",
         "trim event attrs or emit sites"),
        ("tsdb.overhead_ms", "tsdb-sampling",
         "lengthen the tick or shrink the level ladder"),
        ("canary.overhead_ms", "canary-probing",
         "lengthen --canary-interval"),
        ("lockwatch.overhead_ms", "lock-order-sanitizer",
         "watch fewer locks or disable INFERD_LOCKWATCH in production"),
    ):
        ov = gauges.get(gauge, counters.get(gauge))
        if not isinstance(ov, (int, float)):
            continue
        if float(ov) > SPAN_OVERHEAD_FRAC * compute_ms:
            out.append(Finding(
                "warning", "node", "overhead",
                f"{label} overhead {float(ov):.2f} ms exceeds "
                f"{SPAN_OVERHEAD_FRAC:.0%} of cumulative stage.compute_ms "
                f"{compute_ms:.1f} ms — {hint}",
            ))
    return out


def gate(
    artifact_path: str,
    prior_path: Optional[str] = None,
    chip_key: str = "v5e",
) -> Tuple[List[Finding], bool]:
    """(findings, ok). ok = zero error-severity findings."""
    legs = load_artifact(artifact_path)
    prior = load_artifact(prior_path) if prior_path else None
    findings = check_artifact(legs, prior, rl.get_chip(chip_key))
    ok = not any(f.severity == "error" for f in findings)
    return findings, ok
