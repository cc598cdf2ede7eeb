"""Memory-plane observability (ISSUE 13): prefix-cache telemetry export,
gossiped prefix digests, and the bounded cache-affinity routing bonus.

The contract under test, end to end: BlockPool counters flow into
/metrics + windowed series + fleet SLIs; each paged replica gossips a
size-bounded `pfx` digest of its hot prefix index; entry routers score
prompts against the digests and grant a BONUS that composes with — and
can never outweigh — the admission watermark, draining exclusion, and
outlier penalty (the acceptance pin: a shedding or draining digest
holder LOSES the ranked pick to a cache-cold healthy peer)."""

import asyncio
import json
import os

import numpy as np
import pytest

from inferd_tpu.config import PRESETS
from inferd_tpu.control import dstar as dstarlib
from inferd_tpu.control import path_finder as pflib
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core.cache import BlockPool
from inferd_tpu.obs import canary as canarylib
from inferd_tpu.obs import devtel as devtellib
from inferd_tpu.obs import events as eventslib
from inferd_tpu.obs import export as obs_export
from inferd_tpu.obs import fleet as fleetlib
from inferd_tpu.obs import health as healthlib
from inferd_tpu.obs import tsdb as tsdblib
from inferd_tpu.utils.metrics import Metrics

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)

TINY = PRESETS["tiny"]
FLEET_FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fleet")
SIM_DATA = os.path.join(os.path.dirname(__file__), "data", "sim")

PROMPT = list(range(100))


def _digest_for(ids, bs=32):
    return prefixlib.make_digest(prefixlib.block_keys(ids, bs), bs)


# ---------------------------------------------------------------------------
# core.prefix: digest + probe
# ---------------------------------------------------------------------------


def test_digest_and_probe_depth():
    probe = prefixlib.AffinityProbe(PROMPT)
    full = _digest_for(PROMPT)
    assert full["bs"] == 32 and len(full["k"]) == len(PROMPT) // 32
    assert probe.depth_frac({"pfx": full}) == 1.0
    # a shallower holder scores a proportional fraction
    one = {"pfx": {"bs": 32, "k": full["k"][:1]}}
    assert probe.depth_frac(one) == pytest.approx(1 / 3)
    # chained keys: the DEEPEST match names the coverage even when
    # shallower keys are missing from the digest
    deep_only = {"pfx": {"bs": 32, "k": full["k"][-1:]}}
    assert probe.depth_frac(deep_only) == 1.0
    # a different prompt's digest never matches (chained identity)
    other = _digest_for([7] + PROMPT[1:])
    assert probe.depth_frac({"pfx": other}) == 0.0


def test_probe_rederives_per_block_size_and_tolerates_garbage():
    probe = prefixlib.AffinityProbe(PROMPT)
    d16 = _digest_for(PROMPT, bs=16)
    assert probe.depth_frac({"pfx": d16}) == 1.0  # re-keyed at bs=16
    # memoized per block size: the second call reuses the chain
    assert probe.keys_for(16) is probe.keys_for(16)
    for garbage in (
        {}, {"pfx": None}, {"pfx": []}, {"pfx": {"bs": 0, "k": ["x"]}},
        {"pfx": {"bs": "?", "k": ["x"]}}, {"pfx": {"bs": 16, "k": []}},
        {"pfx": {"bs": 16, "k": [1, 2]}}, {"pfx": {"bs": 16}},
    ):
        assert probe.depth_frac(garbage) == 0.0
    # prompts shorter than one block have no digestible identity
    assert prefixlib.AffinityProbe([1, 2]).depth_frac({"pfx": d16}) == 0.0


def test_make_digest_is_size_bounded():
    ids = list(range(32 * (prefixlib.DIGEST_MAX_KEYS + 40)))
    d = _digest_for(ids)
    assert len(d["k"]) == prefixlib.DIGEST_MAX_KEYS
    assert all(len(k) == 2 * prefixlib.DIGEST_KEY_BYTES for k in d["k"])


# ---------------------------------------------------------------------------
# BlockPool: digest selection + eviction ages
# ---------------------------------------------------------------------------


def _pool(**kw):
    kw.setdefault("lanes", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 16)
    return BlockPool(TINY, TINY.num_layers, **kw)


def test_digest_keys_pinned_first_then_mru():
    pool = _pool(lanes=3, max_len=96, num_blocks=64)
    a = prefixlib.block_keys(list(range(32)), 16)
    b = prefixlib.block_keys(list(range(100, 132)), 16)
    c = prefixlib.block_keys(list(range(200, 232)), 16)
    for lane, keys in enumerate((a, b, c)):
        pool.ensure(lane, 32, owner=f"lane{lane}")
        pool.register_prefix(lane, keys)
    pool.pin(b)
    out = pool.digest_keys()
    assert out[:2] == b  # pinned entries lead
    assert set(out) == set(a + b + c)
    # MRU next: touch `a` (a hit), then cap the budget at 4 — the two
    # pinned keys plus the two most-recently-touched (a's)
    pool.release_lane(0)
    pool.map_prefix(0, a)
    capped = pool.digest_keys(limit=4)
    assert capped[:2] == b and set(capped[2:]) == set(a)


def test_eviction_age_hook_and_counters():
    clock = [100.0]
    pool = _pool(lanes=2, max_len=64, num_blocks=9, clock=lambda: clock[0])
    evicted = []
    pool.on_evict = lambda key, age_s: evicted.append((key, age_s))
    keys = prefixlib.block_keys(list(range(32)), 16)
    pool.ensure(0, 32, owner="s0")
    pool.register_prefix(0, keys)
    pool.release_lane(0)  # index alone holds the 2 blocks now
    clock[0] = 130.0
    # 8 usable blocks, 2 held by the index: a 7-block demand forces
    # evictions of the idle entries, stamped with their LRU age
    pool.ensure(0, 64, owner="s1")
    pool.ensure(1, 48, owner="s2")
    assert pool.prefix_evictions >= 1 and evicted
    assert all(age == pytest.approx(30.0) for _k, age in evicted)
    assert [k for k, _ in evicted] == keys[: len(evicted)]
    # a raising hook must never break allocation
    pool2 = _pool(lanes=2, max_len=64, num_blocks=9)
    pool2.on_evict = lambda *_a: (_ for _ in ()).throw(RuntimeError("x"))
    pool2.ensure(0, 32, owner="s0")
    pool2.register_prefix(0, prefixlib.block_keys(list(range(32)), 16))
    pool2.release_lane(0)
    pool2.ensure(0, 64, owner="s1")
    pool2.ensure(1, 48, owner="s2")  # would exhaust without eviction
    assert pool2.prefix_evictions >= 1


# ---------------------------------------------------------------------------
# routers: the bounded bonus and its composition contract
# ---------------------------------------------------------------------------


def _hot(**kw):
    return {"load": 1, "cap": 8, "pfx": _digest_for(PROMPT), **kw}


def _cold(**kw):
    return {"load": 1, "cap": 8, **kw}


def test_ranked_pick_prefers_digest_holder_at_equal_load():
    probe = prefixlib.AffinityProbe(PROMPT)
    nid, _ = pflib.min_load_node(
        {"cold": _cold(), "hot": _hot()}, affinity=probe
    )
    assert nid == "hot"
    # without a probe the ordering is the classic min-load (unchanged)
    ranked = pflib.ranked_nodes({"cold": _cold(), "hot": _hot()})
    assert [n for n, _ in ranked] == ["cold", "hot"]  # tie -> sort order


def test_bonus_is_bounded_by_half_a_capacity():
    """The bonus moves a pick only within CACHE_AFFINITY_BONUS load-ratio
    units: a full-depth holder more than 0.5 capacities busier loses."""
    probe = prefixlib.AffinityProbe(PROMPT)
    barely = _hot(load=4)  # +0.375 ratio vs cold: inside the bonus
    nid, _ = pflib.min_load_node(
        {"cold": _cold(load=1), "busy_hot": barely}, affinity=probe
    )
    assert nid == "busy_hot"
    over = _hot(load=6)  # +0.625 ratio: beyond the bonus
    nid, _ = pflib.min_load_node(
        {"cold": _cold(load=1), "busy_hot": over}, affinity=probe
    )
    assert nid == "cold"


def test_cache_hit_never_outweighs_overload():
    """ACCEPTANCE: an admission-shedding or draining replica loses the
    ranked pick to a cache-cold healthy peer, whatever its digest says."""
    probe = prefixlib.AffinityProbe(PROMPT)
    for unhealthy in (
        _hot(shed=1),                     # explicit watermark flag
        _hot(kvfree=0.01),                # old peer, kvfree floor
        _hot(draining=1),                 # drain = exclusion
        _hot(outlier=1),                  # outlier penalty >> bonus
    ):
        nid, _ = pflib.min_load_node(
            {"cold": _cold(), "sick_hot": unhealthy}, affinity=probe
        )
        assert nid == "cold", unhealthy
    # healthy kvfree above the floor still earns the bonus
    nid, _ = pflib.min_load_node(
        {"cold": _cold(), "hot": _hot(kvfree=0.5)}, affinity=probe
    )
    assert nid == "hot"


def test_node_cost_bonus_penalties_and_positivity():
    probe = prefixlib.AffinityProbe(PROMPT)
    base = dstarlib.node_cost(_cold())
    assert dstarlib.node_cost(_cold(), affinity=probe) == base
    bonus = base - dstarlib.node_cost(_hot(), affinity=probe)
    assert bonus == pytest.approx(canarylib.CACHE_AFFINITY_BONUS)
    # strict positivity survives the discount (D*-Lite admissibility)
    assert dstarlib.node_cost({"load": 0, "cap": 8, **_hot()},
                              affinity=probe) > 0
    # shed -> penalty instead of bonus; draining still exclusion-grade
    assert dstarlib.node_cost(_hot(shed=1), affinity=probe) == (
        pytest.approx(base + canarylib.ADMISSION_PENALTY)
    )
    assert dstarlib.node_cost(_hot(draining=1), affinity=probe) >= 1e6
    # no affinity argument -> byte-for-byte the PR 12 cost model
    assert dstarlib.node_cost(_hot(shed=1)) == base


class _StubDHT:
    def __init__(self, snapshot):
        self.snapshot = snapshot

    def get_all(self, _n):
        return {s: dict(m) for s, m in self.snapshot.items()}

    def get_stage(self, s):
        return dict(self.snapshot.get(s, {}))


def test_find_best_chain_affinity_rerank_entry_stage_only():
    probe = prefixlib.AffinityProbe(PROMPT)
    hot_inner = dict(_hot(), host="h3", port=4)  # inner stage holder: ignored
    snapshot = {
        0: {"a": dict(_cold(), host="h1", port=1),
            "b": dict(_hot(), host="h2", port=2)},
        1: {"c": dict(_cold(), host="h3", port=3), "d": hot_inner},
    }
    pf = pflib.PathFinder(_StubDHT(snapshot), 2)
    plain = pf.find_best_chain(0)
    assert plain[0][0] == "a"  # tie -> planner's pick, no probe
    routed = pf.find_best_chain(0, affinity=probe)
    assert routed[0][0] == "b"  # entry re-ranked to the digest holder
    # inner stage unaffected by the probe (token-keyed caches live at
    # the entry): same cost -> planner's original inner pick stands
    assert routed[1][0] == plain[1][0]
    # a shedding entry holder loses the re-rank too
    snapshot[0]["b"]["shed"] = 1
    pf2 = pflib.PathFinder(_StubDHT(snapshot), 2)
    assert pf2.find_best_chain(0, affinity=probe)[0][0] == "a"


# ---------------------------------------------------------------------------
# devtel: the PR-8 gap fix — counters/gauges actually exported
# ---------------------------------------------------------------------------


class _PagedStub:
    prefill_tokens = 40

    def block_stats(self):
        return {
            "blocks_free": 10, "blocks_used": 21, "cow_shared": 3,
            "cow_splits": 2, "prefix_entries": 6, "prefix_hit_tokens": 160,
            "prefix_evictions": 4, "pins_resident": 1,
        }


def test_devtel_exports_prefix_series(monkeypatch):
    m = Metrics()
    devtellib.refresh_gauges(m, _PagedStub())
    snap = m.snapshot()
    assert snap["gauges"]["kv.prefix_entries"] == 6.0
    assert snap["counters"]["kv.prefix_hit_tokens"] == 160.0
    assert snap["counters"]["kv.prefix_evictions"] == 4.0
    assert snap["counters"]["kv.cow_splits"] == 2.0
    assert snap["counters"]["kv.prefill_tokens"] == 40.0
    # the exposition stays valid with the new series
    assert obs_export.validate_exposition(obs_export.prometheus_text(m)) == []
    # kill switch: byte-identical /metrics (the PR 5 contract holds for
    # every new series)
    m2 = Metrics()
    monkeypatch.setenv("INFERD_EVENTS", "0")
    before = obs_export.prometheus_text(m2)
    devtellib.refresh_gauges(m2, _PagedStub())
    assert obs_export.prometheus_text(m2) == before


def test_devtel_dense_executor_contributes_nothing():
    m = Metrics()
    devtellib.refresh_gauges(m, object())
    snap = m.snapshot()
    assert not any(k.startswith("kv.prefix") for k in snap["gauges"])
    assert not any(k.startswith("kv.") for k in snap["counters"])


def test_set_counter_reset_rebaselines_in_tsdb():
    """An executor swap's younger pool reads as a Prometheus counter
    reset: the windowed tsdb re-baselines instead of freezing."""
    m = Metrics()
    clock = [1000.0]
    t = tsdblib.Tsdb(m, clock=lambda: clock[0])
    t.sample()
    clock[0] += 1
    m.set_counter("kv.prefix_hit_tokens", 100.0)
    t.sample()
    clock[0] += 1
    m.set_counter("kv.prefix_hit_tokens", 5.0)  # swap: younger pool
    t.sample()
    clock[0] += 1
    m.set_counter("kv.prefix_hit_tokens", 25.0)
    t.sample()
    total = tsdblib.trailing_sum(t.history(), "kv.prefix_hit_tokens", 60.0)
    assert total == pytest.approx(120.0)  # 100 + reset(0) + 20


# ---------------------------------------------------------------------------
# windowed series -> fleet SLIs -> committed fixture
# ---------------------------------------------------------------------------


def _paged_history(service="n0", stage=0, hit_per_tick=80.0,
                   prefill_per_tick=20.0, ticks=120):
    m = Metrics()
    clock = [1700000000.0]
    t = tsdblib.Tsdb(m, service=service,
                     meta={"stage": stage, "num_stages": 1},
                     clock=lambda: clock[0])
    t.sample()
    for i in range(ticks):
        clock[0] += 1.0
        m.set_counter("kv.prefix_hit_tokens", (i + 1) * hit_per_tick)
        m.set_counter("kv.prefill_tokens", (i + 1) * prefill_per_tick)
        m.inc("stage.tokens", 5)
        t.sample()
    return t.history()


def test_fleet_cache_slis_merge_sums_not_ratios():
    # node A: 80/20 per tick, node B: 0/100 — the fleet hit rate is the
    # ratio of merged sums (80/200 = 0.4), NOT the mean of per-node
    # ratios (0.4 vs (0.8 + 0.0)/2 = 0.4 ... distinguish with asymmetry)
    ha = _paged_history("a", hit_per_tick=80.0, prefill_per_tick=20.0)
    hb = _paged_history("b", hit_per_tick=0.0, prefill_per_tick=100.0)
    s = fleetlib.fleet_sample([ha, hb])
    assert s["fleet"]["cache_hit_frac"] == pytest.approx(80 / 200, abs=0.02)
    assert s["fleet"]["prefill_saved_per_s"] == pytest.approx(80.0, rel=0.1)
    # dense fleets resolve None, never zero
    dense = fleetlib.fleet_sample([_burnless_dense_history()])
    assert dense["fleet"]["cache_hit_frac"] is None
    assert dense["fleet"]["prefill_saved_per_s"] is None
    # the report renders the cache line
    assert "cache: prefill-saved/s" in fleetlib.format_report([s])


def _burnless_dense_history():
    m = Metrics()
    clock = [1700000000.0]
    t = tsdblib.Tsdb(m, service="dense", meta={"stage": 0, "num_stages": 1},
                     clock=lambda: clock[0])
    t.sample()
    clock[0] += 1
    m.inc("stage.tokens", 5)
    t.sample()
    return t.history()


def test_committed_fleet_fixture_resolves_cache_slis(capsys):
    """run.sh 0e coverage: the committed fixture now carries a paged
    replica history (node2) and `obs fleet --check` resolves the cache
    SLIs from it."""
    from inferd_tpu.obs.__main__ import main as obs_main

    assert obs_main(["fleet", "--check", FLEET_FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "cache: prefill-saved/s" in out
    assert "hit-rate 80.0%" in out
    hs = [
        tsdblib.load_history_file(
            os.path.join(FLEET_FIXTURE, f"node{i}.history.json")
        )
        for i in range(3)
    ]
    s = fleetlib.fleet_sample(hs)
    assert s["fleet"]["cache_hit_frac"] == pytest.approx(0.8, abs=0.01)
    assert s["fleet"]["prefill_saved_per_s"] > 0


# ---------------------------------------------------------------------------
# health rules
# ---------------------------------------------------------------------------


def test_prefix_evict_thrash_rule_and_peer_cachehit():
    rule = next(
        r for r in healthlib.DEFAULT_RULES
        if r.signal == "event:prefix.evict/min"
    )
    now = 1000.0
    calm = [{"type": "prefix.evict", "ts": now - i} for i in range(30)]
    fired, val, _ = healthlib.evaluate_rule(rule, {}, events=calm, now=now)
    assert fired is False
    storm = [
        {"type": "prefix.evict", "ts": now - i * 0.1} for i in range(300)
    ]
    fired, val, _ = healthlib.evaluate_rule(rule, {}, events=storm, now=now)
    assert fired is True and val >= 240
    # the gossiped cachehit field is peer:-rule addressable; the worst
    # offender under a lower-bound rule is the SMALLEST value
    r = healthlib.Rule.parse("peer:cachehit > 0.1")
    fired, val, peer = healthlib.evaluate_rule(
        r, {}, peers={
            "a": {"cachehit": 0.9}, "b": {"cachehit": 0.05},
            "c": {"cachehit": 0.02}, "old": {},
        },
    )
    assert fired is True and peer == "c" and val == 0.02


# ---------------------------------------------------------------------------
# collector / dashboard: mixed-version rendering
# ---------------------------------------------------------------------------


def test_collector_cachehit_column_and_old_peer_blanks():
    from inferd_tpu.tools.collector import stage_rows

    swarm = {
        0: {
            "n0": {"load": 1, "cap": 4, "cachehit": 0.9,
                   "pfx": _digest_for(PROMPT)},
            "n1": {"load": 1, "cap": 4, "cachehit": 0.5},
            "old": {"load": 1, "cap": 4},  # pre-digest peer
        },
        1: {"inner": {"load": 0, "cap": 4}},
    }
    rows = {r["stage"]: r for r in stage_rows(swarm, ts=1.0)}
    assert rows[0]["cachehit"] == 70.0  # median of 0.9/0.5, as a %
    assert rows[1]["cachehit"] == ""    # no paged replica: blank


def test_dashboard_cache_cell_blank_for_old_peers():
    from inferd_tpu.tools.dashboard import render_table

    swarm = {0: {
        "new": {"name": "n", "load": 0, "cap": 1, "cachehit": 0.42},
        "old": {"name": "o", "load": 0, "cap": 1},
    }}
    text = render_table(swarm, ts=0.0)
    assert "cache%" in text
    new_line = next(ln for ln in text.splitlines() if " new " in ln)
    old_line = next(ln for ln in text.splitlines() if " old " in ln)
    assert "42%" in new_line
    assert "42%" not in old_line


# ---------------------------------------------------------------------------
# mixed-version gossip compat (the PR 7 test_dht pattern)
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_mixed_version_gossip_digest_keys():
    """The new `pfx`/`shed`/`cachehit` keys pass bit-true through peers
    that predate them, and old records gain nothing."""
    from inferd_tpu.control.dht import SwarmDHT

    def mk(node_id, port, bootstrap=None):
        return SwarmDHT(node_id, port, bootstrap=bootstrap or [], ttl_s=5.0,
                        gossip_period_s=0.05, host="127.0.0.1")

    new = mk("new", PORTS.gossip(1))
    old = mk("old", PORTS.gossip(2), bootstrap=[("127.0.0.1", PORTS.gossip(1))])
    obs = mk("obs", PORTS.gossip(3), bootstrap=[("127.0.0.1", PORTS.gossip(1))])
    await new.start(); await old.start(); await obs.start()
    try:
        digest = _digest_for(PROMPT)
        new.announce({
            "stage": 0, "load": 1, "cap": 4,
            "pfx": digest, "shed": 1, "cachehit": 0.73,
        })
        old.announce({"stage": 0, "load": 0, "cap": 4})  # pre-digest record
        for _ in range(100):
            if len(obs.get_stage(0)) == 2:
                break
            await asyncio.sleep(0.05)
        stage = obs.get_stage(0)
        assert len(stage) == 2, "gossip did not converge"
        assert stage["new"]["pfx"] == digest  # bit-true through the store
        assert stage["new"]["shed"] == 1
        assert stage["new"]["cachehit"] == 0.73
        for key in ("pfx", "shed", "cachehit"):
            assert key not in stage["old"]
        # an OBSERVER'S router scores the relayed digest directly
        probe = prefixlib.AffinityProbe(PROMPT)
        assert probe.depth_frac(stage["new"]) == 1.0
        assert probe.depth_frac(stage["old"]) == 0.0
    finally:
        await new.stop(); await old.stop(); await obs.stop()


# ---------------------------------------------------------------------------
# perf gate: the round-13 invariants
# ---------------------------------------------------------------------------


def _ca_leg(**kw):
    leg = {
        "metric": "tiny_cache_affinity_saved_tokens", "value": 1000,
        "unit": "tokens", "hit_frac_prior": 0.7,
        "saved_tokens_on": 1000, "saved_tokens_off": 100,
        "token_exact": True,
    }
    leg.update(kw)
    return leg


def test_gate_cache_affinity_ordering_invariant():
    from inferd_tpu.perf import gate as gatelib

    ok = gatelib.check_artifact([("ca", _ca_leg())])
    assert not [f for f in ok if f.severity == "error"]
    bad = gatelib.check_artifact(
        [("ca", _ca_leg(saved_tokens_on=90, value=90))]
    )
    assert any(
        f.severity == "error" and "prefill-tokens-avoided" in f.message
        for f in bad
    )


def test_gate_cache_affinity_prior_regression_and_skip():
    from inferd_tpu.perf import gate as gatelib

    prior = [("ca", _ca_leg(hit_frac_prior=0.7))]
    fresh = [("ca", _ca_leg(hit_frac_prior=0.5))]  # 28.6% drop
    found = gatelib.check_artifact(fresh, prior)
    assert any(
        f.check == "regression" and "hit_frac_prior" in f.message
        for f in found
    )
    # a pair missing the ratio on either side SKIPS (no raw-token
    # fallback — exactly the cross-host false-fail the ratio prevents)
    legless = [("ca", {k: v for k, v in _ca_leg().items()
                       if k != "hit_frac_prior"})]
    assert not gatelib.check_artifact(legless, prior)


def test_committed_cache_artifact_passes_gate():
    from inferd_tpu.perf import gate as gatelib

    path = os.path.join(
        os.path.dirname(__file__), "..", "bench_artifacts",
        "BENCH_cache_cpu_r13.json",
    )
    findings, ok = gatelib.gate(path, prior_path=path)
    assert ok, [f.line() for f in findings]
    legs = dict(gatelib.load_artifact(path))
    leg = legs["tiny_cache_affinity_saved_tokens"]
    # the committed evidence: strictly more prefill avoided with digest
    # routing on, token-exact both sides
    assert leg["saved_tokens_on"] > leg["saved_tokens_off"]
    assert leg["token_exact"] is True
    assert 0 < leg["hit_frac_prior"] <= 1


# ---------------------------------------------------------------------------
# executors: digest surface + tokens_saved + evict event
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def batch_exec():
    import jax

    from inferd_tpu.models import qwen3
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    # 10 blocks (9 usable): tight enough that the third distinct prompt
    # family's registration must evict the first's idle index entries
    return BatchedExecutor(
        TINY, params, lanes=2, max_len=128, block_size=16, kv_blocks=10,
    )


def test_executor_digest_tokens_saved_and_evict_event(batch_exec):
    ex = batch_exec
    events = []
    ex.on_event = lambda etype, **attrs: events.append((etype, attrs))
    prompt = [list(range(2, 50))]
    r1 = ex.process("s1", {"tokens": prompt, "start_pos": 0, "real_len": 48})
    assert "tokens_saved" not in r1  # cold prefill: key omitted
    ex.end_session("s1")
    d = ex.prefix_digest()
    assert d is not None and d["bs"] == 16 and d["k"]
    probe = prefixlib.AffinityProbe(prompt[0])
    assert probe.depth_frac({"pfx": d}) > 0.5
    # a second session with the same prompt maps the cached prefix:
    # tokens_saved stamped, prefix.hit journaled
    r2 = ex.process("s2", {"tokens": prompt, "start_pos": 0, "real_len": 48})
    assert r2["tokens_saved"] == 32  # 2 full 16-token blocks (last
    # block covering the final token always computes)
    assert np.allclose(r1["logits"], r2["logits"], atol=2e-5)
    assert any(e == "prefix.hit" for e, _ in events)
    ex.end_session("s2")
    # crowd the pool until the index must evict: prefix.evict carries age
    big = [list(range(60, 120))]
    ex.process("s3", {"tokens": big, "start_pos": 0, "real_len": 60})
    ex.end_session("s3")
    big2 = [list(range(200, 260))]
    ex.process("s4", {"tokens": big2, "start_pos": 0, "real_len": 60})
    ex.end_session("s4")
    evicts = [a for e, a in events if e == "prefix.evict"]
    assert evicts and all("age_ms" in a and a["age_ms"] >= 0 for a in evicts)


def test_stage_executor_prefix_digest_inner_stage_is_none():
    """Inner pipeline stages never see tokens: their digest is None so
    the `pfx` key stays out of gossip (no token-keyed identity to
    advertise)."""
    import jax

    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 2)
    spec = list(manifest.stage_specs())[1]  # the non-entry stage
    sp = extract_stage_params(params, TINY, spec)
    ex = BatchedStageExecutor(
        TINY, spec, sp, lanes=2, max_len=64, block_size=16,
    )
    assert ex.prefix_digest() is None


# ---------------------------------------------------------------------------
# sim: the 1000-node rehearsal (slow lane; fast fixtures ride the
# test_sim parametrization automatically)
# ---------------------------------------------------------------------------


def test_cache_affinity_fixtures_exist_and_diverge():
    with open(os.path.join(SIM_DATA, "cache_affinity.json")) as f:
        on = json.load(f)
    with open(os.path.join(SIM_DATA, "cache_affinity_off.json")) as f:
        off = json.load(f)
    gates_on = {tuple(g[:2]): g[2] for g in on["gates"]}
    gates_off = {tuple(g[:2]): g[2] for g in off["gates"]}
    # the committed pair IS the routing-prefers-holders proof: the on
    # floor sits strictly above the off ceiling
    assert gates_on[("cache.hit_frac", ">=")] > gates_off[
        ("cache.hit_frac", "<=")
    ]


@pytest.mark.slow
def test_cache_affinity_1000_fixture_replays():
    """ROADMAP 2c acceptance: digest-affinity routing rehearsed at 1000
    nodes — fleet hit rate well above chance placement, admission
    watermark never starved, byte-identical trace."""
    from inferd_tpu.sim.scenario import check_fixture

    path = os.path.join(SIM_DATA, "cache_affinity_1000.json")
    ok, failures, metrics = check_fixture(path)
    assert ok, (failures, metrics.get("cache"), metrics.get("sessions"))
