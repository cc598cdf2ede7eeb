"""Multi-tenant LoRA serving (ISSUE 15): the batched unmerged apply, the
AdapterRegistry's hot-load/evict lifecycle, adapter-affinity routing, the
`ada` gossip field's mixed-version compat, and the kill-switch parity
contract (--adapters absent => byte-identical surfaces)."""

import asyncio
import dataclasses
import json
import os

import numpy as np
import pytest

from inferd_tpu.config import TINY
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.ops import lora as loralib
from inferd_tpu.runtime.adapters import (
    ADA_GOSSIP_MAX, AdapterAffinity, AdapterCapacityError, AdapterRegistry,
    combine_affinity, parse_adapter_dirs,
)

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)

SIM_DATA = os.path.join(os.path.dirname(__file__), "data", "sim")

PROMPT = [3, 17, 42, 9, 5, 8, 2, 11]


def _mk_layers(cfg, seed, r=4, targets=None, scale_sd=0.25):
    g = np.random.default_rng(seed)
    h, q = cfg.hidden_size, cfg.q_dim
    kv, inter = cfg.kv_dim, cfg.intermediate_size
    dims = {
        "q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv),
        "o_proj": (q, h), "gate_proj": (h, inter), "up_proj": (h, inter),
        "down_proj": (inter, h),
    }
    if targets is not None:
        dims = {k: v for k, v in dims.items() if k in targets}
    return {
        name: (
            g.normal(0, scale_sd, (cfg.num_layers, din, r)).astype(np.float32),
            g.normal(0, scale_sd, (cfg.num_layers, r, dout)).astype(np.float32),
        )
        for name, (din, dout) in dims.items()
    }


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    """Three synthetic peft tenant dirs (mixed ranks + target subsets)."""
    root = tmp_path_factory.mktemp("adapters")
    dirs = []
    specs = [
        ("ten0", 0, 4, None),
        ("ten1", 1, 2, ("q_proj", "gate_proj")),  # narrower rank + subset
        ("ten2", 2, 4, ("v_proj", "down_proj")),
    ]
    for name, seed, r, targets in specs:
        p = str(root / name)
        loralib.save_adapter(
            p, _mk_layers(TINY, 100 + seed, r=r, targets=targets),
            alpha=8, r=r,
        )
        dirs.append(p)
    return dirs


@pytest.fixture(scope="module")
def base_params():
    import jax

    from inferd_tpu.models import qwen3

    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


def _greedy_stream(ex, sid, prompt, steps, adapter=None):
    payload = {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)}
    if adapter is not None:
        payload["adapter"] = adapter
    out = ex.process(sid, payload)
    toks = [int(np.argmax(out["logits"][0]))]
    pos = len(prompt)
    for _ in range(steps - 1):
        o = ex.process(sid, {
            "tokens": [[toks[-1]]], "start_pos": pos, "real_len": 1,
        })
        toks.append(int(np.argmax(o["logits"][0])))
        pos += 1
    return toks


def _merged_ref(base_params, adir, prompt, steps):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    merged = loralib.merge_adapter(
        base_params, loralib.load_adapter(TINY, adir)
    )
    ex = BatchedExecutor(TINY, merged, lanes=1, max_len=64)
    return _greedy_stream(ex, "ref", prompt, steps)


# ---------------------------------------------------------------------------
# tentpole: batched unmerged apply == merged solo, per tenant, co-batched
# ---------------------------------------------------------------------------


def test_batched_executor_multi_adapter_token_exact(catalog, base_params):
    """Three sessions with THREE different adapters (mixed ranks/targets)
    plus a base-adapter session co-resident on one BatchedExecutor: every
    stream token-exact vs its merged (or base) solo reference."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    reg = AdapterRegistry(TINY, catalog)
    ex = BatchedExecutor(TINY, base_params, lanes=4, max_len=64,
                         adapters=reg)
    streams = {}
    for t, adir in enumerate(catalog):
        name = os.path.basename(adir)
        streams[name] = _greedy_stream(
            ex, f"s{t}", PROMPT, 6, adapter=name
        )
    streams["base"] = _greedy_stream(ex, "sb", PROMPT, 6)
    for t, adir in enumerate(catalog):
        name = os.path.basename(adir)
        assert streams[name] == _merged_ref(base_params, adir, PROMPT, 6), name
    from inferd_tpu.runtime.batch_executor import BatchedExecutor as BE

    base_ref = _greedy_stream(
        BE(TINY, base_params, lanes=1, max_len=64), "r", PROMPT, 6
    )
    assert streams["base"] == base_ref
    # the adapters discriminate (token-exactness would be vacuous if not)
    assert len({tuple(s) for s in streams.values()}) >= 2


def test_stage_executor_adapters_paged_token_exact(catalog, base_params):
    """The stage-batch executor flavor, over PAGED KV: the salted prefix
    chain keeps tenants' shared-prompt KV apart while the gathered apply
    stays token-exact vs merged references."""
    from inferd_tpu.parallel.stages import Manifest
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    spec = list(Manifest.even_split("tiny", 1).stage_specs())[0]
    reg = AdapterRegistry(TINY, catalog)
    ex = BatchedStageExecutor(
        TINY, spec, base_params, lanes=3, max_len=64, block_size=8,
        adapters=reg,
    )
    name0 = os.path.basename(catalog[0])
    name1 = os.path.basename(catalog[1])
    s0 = _greedy_stream(ex, "a0", PROMPT, 5, adapter=name0)
    s1 = _greedy_stream(ex, "a1", PROMPT, 5, adapter=name1)
    assert s0 == _merged_ref(base_params, catalog[0], PROMPT, 5)
    assert s1 == _merged_ref(base_params, catalog[1], PROMPT, 5)
    # same prompt, different adapters: the salted chains must never have
    # shared prefix blocks across the two tenants
    k0 = prefixlib.block_keys(PROMPT, 8, salt=name0)
    k1 = prefixlib.block_keys(PROMPT, 8, salt=name1)
    assert not set(k0) & set(k1)


def test_prefix_salt_kill_switch_and_scoping():
    """No salt => byte-identical chains (the kill-switch contract);
    salted chains differ from unsalted and from each other."""
    plain = prefixlib.block_keys(PROMPT, 4)
    assert plain == prefixlib.block_keys(PROMPT, 4, salt=None)
    assert plain == prefixlib.block_keys(PROMPT, 4, salt="")
    a = prefixlib.block_keys(PROMPT, 4, salt="ten0")
    b = prefixlib.block_keys(PROMPT, 4, salt="ten1")
    assert not set(plain) & set(a) and not set(a) & set(b)


# ---------------------------------------------------------------------------
# registry lifecycle: hot-load, refcounted eviction, pins, errors
# ---------------------------------------------------------------------------


def test_registry_refcount_lru_evict_and_events(catalog):
    reg = AdapterRegistry(TINY, catalog, slots=3)  # 2 non-base slots
    events = []
    reg.on_event = lambda e, **a: events.append((e, a))
    s0 = reg.acquire("ten0")
    s1 = reg.acquire("ten1")
    assert s0 != s1 and 0 not in (s0, s1)
    # both held: a third tenant cannot claim a slot
    with pytest.raises(AdapterCapacityError):
        reg.acquire("ten2")
    reg.release("ten0")
    s2 = reg.acquire("ten2")  # evicts idle ten0, reuses its slot
    assert s2 == s0
    names = [e for e, _ in events]
    assert names.count("adapter.load") == 3
    evicts = [a for e, a in events if e == "adapter.evict"]
    assert len(evicts) == 1 and evicts[0]["name"] == "ten0"
    assert evicts[0]["claimant"] == "ten2" and evicts[0]["idle_s"] >= 0
    st = reg.stats()
    assert st["loads"] == 3 and st["evictions"] == 1 and st["resident"] == 2
    assert reg.resident_names() == ["ten1", "ten2"]


def test_registry_pin_blocks_eviction_and_unknown_name(catalog):
    reg = AdapterRegistry(TINY, catalog, slots=2)  # ONE non-base slot
    reg.pin("ten0")
    with pytest.raises(AdapterCapacityError):
        reg.acquire("ten1")  # the only slot is pinned
    reg.unpin("ten0")
    reg.acquire("ten1")  # now evicts the unpinned idle ten0
    with pytest.raises(ValueError, match="unknown adapter"):
        reg.acquire("nope")


def test_registry_rejects_moe_and_sliding_window(catalog):
    moe = dataclasses.replace(
        TINY, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32
    )
    with pytest.raises(ValueError, match="MoE"):
        AdapterRegistry(moe, catalog)
    sw = dataclasses.replace(TINY, sliding_window=8)
    with pytest.raises(ValueError, match="sliding-window"):
        AdapterRegistry(sw, catalog)


def test_parse_adapter_dirs_collision():
    assert parse_adapter_dirs("/a/x,/b/y") == {"x": "/a/x", "y": "/b/y"}
    with pytest.raises(ValueError, match="collide"):
        parse_adapter_dirs("/a/x,/b/x")


def test_unknown_adapter_typed_and_slots_validation(catalog):
    """A name outside the catalog raises the TYPED UnknownAdapterError
    (the node maps it to a non-retryable 409 `unknown_adapter`, never the
    restart-loop `session_state`), and unservable --adapter-slots values
    raise loudly instead of silently substituting the default."""
    from inferd_tpu.runtime.adapters import UnknownAdapterError

    reg = AdapterRegistry(TINY, catalog)
    with pytest.raises(UnknownAdapterError, match="unknown adapter"):
        reg.acquire("nope")
    # must stay a ValueError so pre-existing broad handlers still catch
    assert issubclass(UnknownAdapterError, ValueError)
    for bad in (1, -3):
        with pytest.raises(ValueError, match="unservable"):
            AdapterRegistry(TINY, catalog, slots=bad)
    assert AdapterRegistry(TINY, catalog, slots=0).slots == len(catalog) + 1


def test_ads_all_base_window_routes_to_no_adapter_graph(catalog, base_params):
    """A dispatch whose lanes all ride slot 0 ships ads=None (the
    already-compiled no-adapter graph) even once pools are resident —
    base-only traffic must not pay zero-math adapter gathers forever."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    reg = AdapterRegistry(TINY, catalog)
    ex = BatchedExecutor(TINY, base_params, lanes=2, max_len=64,
                         adapters=reg)
    slot = reg.acquire("ten0")  # pools become resident
    try:
        assert ex._ads([0, 0]) is None
        mixed = ex._ads([0, slot])
        assert mixed is not None and "ids" in mixed
    finally:
        reg.release("ten0")


def test_executor_rejects_adapter_without_registry(base_params):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(TINY, base_params, lanes=2, max_len=64)
    with pytest.raises(ValueError, match="no adapter registry"):
        ex.process("s", {
            "tokens": [PROMPT], "start_pos": 0, "real_len": len(PROMPT),
            "adapter": "ten0",
        })


def test_executor_capacity_error_releases_reference(catalog, base_params):
    """An admission that dies AFTER acquire must give the reference
    back — otherwise the slot can never be evicted again."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    reg = AdapterRegistry(TINY, catalog)
    ex = BatchedExecutor(TINY, base_params, lanes=2, max_len=16,
                         adapters=reg)
    with pytest.raises(BufferError):  # prompt exceeds max_len
        ex.process("s", {
            "tokens": [list(range(2, 40))], "start_pos": 0, "real_len": 38,
            "adapter": "ten0",
        })
    assert reg._refs == {}  # no leaked reference


# ---------------------------------------------------------------------------
# satellites: exclusive modes + slice bounds
# ---------------------------------------------------------------------------


def test_exclusive_modes_loud():
    loralib.check_exclusive_modes("", "")  # neither: fine
    loralib.check_exclusive_modes("/a", None)
    loralib.check_exclusive_modes(None, "/a,/b")
    with pytest.raises(ValueError, match="mutually exclusive"):
        loralib.check_exclusive_modes("/a", "/b,/c", owner="node0")


def test_slice_adapter_bounds_raise_with_stage_identity():
    ad = {
        "layers": {"q_proj": (np.zeros((2, 8, 4)), np.zeros((2, 4, 8)))},
        "scale": 2.0,
    }
    with pytest.raises(ValueError, match="stage 3.*no-op"):
        loralib.slice_adapter(ad, 1, 1, owner="node0 stage 3")
    with pytest.raises(ValueError, match="inverted|no-op"):
        loralib.slice_adapter(ad, 2, 1)
    with pytest.raises(ValueError, match="runs past the adapter's 2"):
        loralib.slice_adapter(ad, 0, 3, owner="node0 stage 1")
    ok = loralib.slice_adapter(ad, 0, 2)
    assert ok["layers"]["q_proj"][0].shape[0] == 2


# ---------------------------------------------------------------------------
# routing: AdapterAffinity through the real routers
# ---------------------------------------------------------------------------


def test_adapter_affinity_scoring_and_combination():
    aff = AdapterAffinity("ten0")
    assert aff.depth_frac({"ada": ["ten1", "ten0"]}) == 1.0
    assert aff.depth_frac({"ada": ["ten1"]}) == 0.0
    assert aff.depth_frac({}) == 0.0
    assert aff.depth_frac({"ada": "garbage"}) == 0.0
    combo = combine_affinity(AdapterAffinity("x"), AdapterAffinity("ten0"))
    assert combo.depth_frac({"ada": ["ten0"]}) == 1.0  # max composition
    assert combine_affinity(None, None) is None
    assert combine_affinity(aff, None) is aff


def test_routers_prefer_adapter_holder_but_health_dominates():
    from inferd_tpu.control.dstar import node_cost
    from inferd_tpu.control.path_finder import min_load_node, ranked_nodes

    aff = AdapterAffinity("ten0")
    stage = {
        "holder": {"load": 2, "cap": 8, "ada": ["ten0"]},
        "cold": {"load": 1, "cap": 8},
    }
    nid, _ = min_load_node(stage, affinity=aff)
    assert nid == "holder"  # bonus outweighs the small load gap
    # shedding holder: penalized, the cold healthy replica wins
    shed = {
        "holder": {"load": 2, "cap": 8, "ada": ["ten0"], "shed": 1},
        "cold": {"load": 1, "cap": 8},
    }
    assert min_load_node(shed, affinity=aff)[0] == "cold"
    # outlier holder: the penalty (4x the max bonus) dominates
    sick = {
        "holder": {"load": 0, "cap": 8, "ada": ["ten0"], "outlier": 1},
        "cold": {"load": 1, "cap": 8},
    }
    assert ranked_nodes(sick, affinity=aff)[0][0] == "cold"
    # draining holder: no bonus and excluded while others serve
    drain = {
        "holder": {"load": 0, "cap": 8, "ada": ["ten0"], "draining": 1},
        "cold": {"load": 1, "cap": 8},
    }
    assert min_load_node(drain, affinity=aff)[0] == "cold"
    # D*-Lite edge costs stay strictly positive under the discount
    assert node_cost({"load": 0, "cap": 8, "ada": ["ten0"]}, affinity=aff) > 0


# ---------------------------------------------------------------------------
# gossip: mixed-version `ada` compat + collector/dashboard surfaces
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_mixed_version_gossip_ada_key():
    """The new `ada` key passes bit-true through peers that predate it,
    and old records gain nothing (the PR 7/13 test_dht pattern)."""
    from inferd_tpu.control.dht import SwarmDHT

    def mk(node_id, port, bootstrap=None):
        return SwarmDHT(node_id, port, bootstrap=bootstrap or [], ttl_s=5.0,
                        gossip_period_s=0.05, host="127.0.0.1")

    new = mk("new", PORTS.gossip(1))
    old = mk("old", PORTS.gossip(2), bootstrap=[("127.0.0.1", PORTS.gossip(1))])
    obs = mk("obs", PORTS.gossip(3), bootstrap=[("127.0.0.1", PORTS.gossip(1))])
    await new.start(); await old.start(); await obs.start()
    try:
        new.announce({
            "stage": 0, "load": 1, "cap": 4, "ada": ["ten0", "ten1"],
        })
        old.announce({"stage": 0, "load": 0, "cap": 4})  # pre-adapter peer
        for _ in range(100):
            if len(obs.get_stage(0)) == 2:
                break
            await asyncio.sleep(0.05)
        stage = obs.get_stage(0)
        assert len(stage) == 2, "gossip did not converge"
        assert stage["new"]["ada"] == ["ten0", "ten1"]  # bit-true
        assert "ada" not in stage["old"]
        # an OBSERVER'S router scores the relayed residency directly
        aff = AdapterAffinity("ten1")
        assert aff.depth_frac(stage["new"]) == 1.0
        assert aff.depth_frac(stage["old"]) == 0.0
    finally:
        await new.stop(); await old.stop(); await obs.stop()


def test_collector_adapters_column_and_old_peer_blanks():
    from inferd_tpu.tools.collector import stage_rows

    swarm = {
        0: {
            "n0": {"load": 1, "cap": 4, "ada": ["ten1", "ten0"]},
            "n1": {"load": 1, "cap": 4, "ada": ["ten2"]},
            "old": {"load": 1, "cap": 4},  # pre-adapter peer
        },
        1: {"inner": {"load": 0, "cap": 4}},
    }
    rows = {r["stage"]: r for r in stage_rows(swarm, ts=1.0)}
    assert rows[0]["adapters"] == "ten0 ten1 ten2"  # sorted stage union
    assert rows[1]["adapters"] == ""  # registry-less stage: blank


def test_dashboard_ada_cell_blank_for_old_peers():
    from inferd_tpu.tools.dashboard import render_table

    swarm = {0: {
        "new": {"name": "n", "load": 0, "cap": 1, "ada": ["t0", "t1"]},
        "old": {"name": "o", "load": 0, "cap": 1},
    }}
    text = render_table(swarm, ts=0.0)
    assert "ada" in text.splitlines()[0]
    new_line = next(ln for ln in text.splitlines() if " new " in ln)
    old_line = next(ln for ln in text.splitlines() if " old " in ln)
    assert "  2 " in new_line
    assert "  - " in old_line


# ---------------------------------------------------------------------------
# kill-switch parity: --adapters absent => surfaces byte-identical
# ---------------------------------------------------------------------------


def test_kill_switch_no_registry_no_adapter_surfaces(base_params):
    from inferd_tpu.obs import devtel
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(TINY, base_params, lanes=2, max_len=64)
    assert "adapters" not in ex.stats()
    gauges, counters = devtel.adapter_series(ex)
    assert gauges == {} and counters == {}  # no adapter.* series at all


def test_kill_switch_client_envelope_byte_identical(monkeypatch):
    """adapter=None leaves the /forward envelope byte-identical to the
    pre-adapter wire format (the PR 13/14 parity contract)."""
    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.obs import trace as tracelib
    from inferd_tpu.runtime import wire

    monkeypatch.setenv("INFERD_TRACE", "0")
    # the envelope's opaque task id (no uuid4 since PR 54: obs.trace.new_id)
    monkeypatch.setattr(tracelib, "new_id", lambda: "%016x" % 9)
    plain = SwarmClient([("h", 1)])._forward_env("s", [1, 2], 0)
    manual = {
        "task_id": "%016x" % 9,
        "session_id": "s", "stage": 0,
        "payload": {
            "tokens": np.asarray([[1, 2]], dtype=np.int32),
            "start_pos": 0, "real_len": 2,
        },
    }
    assert wire.pack(plain) == wire.pack(manual)
    # a tenant client's FIRST chunk carries exactly one extra key
    env = SwarmClient([("h", 1)], adapter="ten0")._forward_env("s", [1, 2], 0)
    assert env["payload"]["adapter"] == "ten0"
    # ... and its decode steps stay byte-identical to the base wire
    step = SwarmClient([("h", 1)], adapter="ten0")._forward_env("s", [7], 5)
    assert "adapter" not in step["payload"]


def test_registry_gauges_present_with_registry(catalog, base_params):
    from inferd_tpu.obs import devtel
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    reg = AdapterRegistry(TINY, catalog)
    ex = BatchedExecutor(TINY, base_params, lanes=2, max_len=64,
                         adapters=reg)
    reg.acquire("ten0")
    gauges, counters = devtel.adapter_series(ex)
    assert gauges["adapter.resident"] == 1.0
    assert counters["adapter.loads"] == 1.0
    assert ex.stats()["adapters"]["resident"] == 1


# ---------------------------------------------------------------------------
# perf gate: the round-15 invariants
# ---------------------------------------------------------------------------


def _lt_leg(**kw):
    leg = {
        "metric": "tiny_lora_tenants_tok_per_s", "value": 400.0,
        "unit": "tok/s", "cobatch_vs_serial": 1.2,
        "serial_tok_per_s": 333.0, "token_exact": True,
        "distinct_streams": 4, "adapter_loads": 4,
    }
    leg.update(kw)
    return leg


def test_gate_lora_tenants_invariants():
    from inferd_tpu.perf import gate as gatelib

    ok = gatelib.check_artifact([("lt", _lt_leg())])
    assert not [f for f in ok if f.severity == "error"]
    bad = gatelib.check_artifact(
        [("lt", _lt_leg(value=300.0, serial_tok_per_s=333.0))]
    )
    assert any("strictly beat" in f.message for f in bad)
    bad = gatelib.check_artifact([("lt", _lt_leg(adapter_loads=0))])
    assert any("zero adapter hot-loads" in f.message for f in bad)
    bad = gatelib.check_artifact([("lt", _lt_leg(distinct_streams=1))])
    assert any("not discriminating" in f.message for f in bad)
    bad = gatelib.check_artifact([("lt", _lt_leg(token_exact=False))])
    assert any(f.severity == "error" and "token_exact" in f.message
               for f in bad)


def test_gate_lora_tenants_prior_regression_and_skip():
    from inferd_tpu.perf import gate as gatelib

    prior = [("lt", _lt_leg(cobatch_vs_serial=1.5))]
    fresh = [("lt", _lt_leg(cobatch_vs_serial=1.1))]  # 26.7% drop
    found = gatelib.check_artifact(fresh, prior)
    assert any(
        f.check == "regression" and "cobatch_vs_serial" in f.message
        for f in found
    )
    # missing ratio on either side SKIPS (no raw-tok/s fallback)
    legless = [("lt", {k: v for k, v in _lt_leg().items()
                       if k != "cobatch_vs_serial"})]
    assert not [
        f for f in gatelib.check_artifact(legless, prior)
        if f.check == "regression"
    ]


def test_committed_lora_artifact_passes_gate():
    from inferd_tpu.perf import gate as gatelib

    path = os.path.join(
        os.path.dirname(__file__), "..", "bench_artifacts",
        "BENCH_lora_cpu_r15.json",
    )
    findings, ok = gatelib.gate(path, prior_path=path)
    assert ok, [f.line() for f in findings]
    leg = dict(gatelib.load_artifact(path))["tiny_lora_tenants_tok_per_s"]
    assert leg["token_exact"] is True
    assert leg["cobatch_vs_serial"] > 1.0
    assert leg["tenants"] >= 4 and leg["adapter_loads"] >= leg["tenants"]


# ---------------------------------------------------------------------------
# sim: the committed adapter-affinity placement rehearsal
# ---------------------------------------------------------------------------


def test_adapter_affinity_fixtures_exist_and_diverge():
    with open(os.path.join(SIM_DATA, "adapter_affinity.json")) as f:
        on = json.load(f)
    with open(os.path.join(SIM_DATA, "adapter_affinity_off.json")) as f:
        off = json.load(f)
    gates_on = {tuple(g[:2]): g[2] for g in on["gates"]}
    gates_off = {tuple(g[:2]): g[2] for g in off["gates"]}
    # the committed pair IS the placement proof: the affinity-on
    # resident-hit floor sits strictly above the blind-baseline ceiling
    assert gates_on[("adapters.hit_frac", ">=")] > gates_off[
        ("adapters.hit_frac", "<=")
    ]
    # zero hung sessions in BOTH modes (a miss hot-loads, never wedges)
    assert gates_on[("sessions.hung", "==")] == 0
    assert gates_off[("sessions.hung", "==")] == 0


def test_resident_names_gossip_cap(catalog):
    reg = AdapterRegistry(TINY, catalog)
    for name in ("ten0", "ten1", "ten2"):
        reg.acquire(name)
    assert len(reg.resident_names()) <= ADA_GOSSIP_MAX
    assert reg.resident_names() == ["ten0", "ten1", "ten2"]


# ---------------------------------------------------------------------------
# review fixes: handoff rebinding, evict-after-read, target-union pools
# ---------------------------------------------------------------------------


def test_export_import_preserves_adapter_binding(catalog, base_params):
    """A tenant session handed off between replicas (drain migration /
    standby promotion) carries its adapter on the handoff payload and
    REBINDS it on the importer, continuing token-exact — and a
    registry-less importer DECLINES instead of silently resuming the
    stream on the base weights (the same corruption admission rejects
    loudly)."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    name = os.path.basename(catalog[0])
    ref = _merged_ref(base_params, catalog[0], PROMPT, 6)

    ex1 = BatchedExecutor(TINY, base_params, lanes=2, max_len=64,
                          adapters=AdapterRegistry(TINY, catalog))
    out = ex1.process("s", {"tokens": [PROMPT], "start_pos": 0,
                            "real_len": len(PROMPT), "adapter": name})
    toks = [int(np.argmax(out["logits"][0]))]
    pos = len(PROMPT)
    for _ in range(2):
        o = ex1.process("s", {"tokens": [[toks[-1]]], "start_pos": pos,
                              "real_len": 1})
        toks.append(int(np.argmax(o["logits"][0])))
        pos += 1
    exported = dict(ex1.export_sessions(only="s"))
    assert exported["s"]["adapter"] == name  # the binding rides the payload
    # base sessions' payloads gain no key (byte-identical to pre-adapter)
    ex1.process("b", {"tokens": [PROMPT], "start_pos": 0,
                      "real_len": len(PROMPT)})
    assert "adapter" not in dict(ex1.export_sessions(only="b"))["b"]

    bare = BatchedExecutor(TINY, base_params, lanes=2, max_len=64)
    assert bare.import_session("s", exported["s"]) is False

    ex2 = BatchedExecutor(TINY, base_params, lanes=2, max_len=64,
                          adapters=AdapterRegistry(TINY, catalog))
    assert ex2.import_session("s", exported["s"]) is True
    # the rebound adapter holds a live-session reference on the importer
    assert ex2.adapters.stats()["resident"] == 1
    for _ in range(3):
        o = ex2.process("s", {"tokens": [[toks[-1]]], "start_pos": pos,
                              "real_len": 1})
        toks.append(int(np.argmax(o["logits"][0])))
        pos += 1
    assert toks == ref  # the handed-off stream never left the tenant's weights


def test_unreadable_catalog_entry_never_evicts_residents(catalog, tmp_path):
    """A cataloged-but-unreadable adapter fails at the DISK READ, before
    any eviction decision — repeated admission retries for it must not
    churn-evict healthy residents one slot at a time."""
    import shutil

    ok = str(tmp_path / "ok")
    ghost = str(tmp_path / "ghost")
    shutil.copytree(catalog[0], ok)
    shutil.copytree(catalog[1], ghost)
    reg = AdapterRegistry(TINY, [ok, ghost], slots=2)  # ONE usable slot
    reg.acquire("ok")
    reg.release("ok")  # resident, idle -> LRU-evictable
    shutil.rmtree(ghost)  # becomes unreadable after startup
    for _ in range(3):
        with pytest.raises(Exception):
            reg.acquire("ghost")
    st = reg.stats()
    assert st["resident"] == 1 and st["evictions"] == 0
    assert reg.resident_names() == ["ok"]


def test_pools_cover_only_the_catalog_target_union(base_params, tmp_path):
    """An attention-only catalog allocates NO MLP pools (the
    intermediate_size-wide ones are the bulk of the memory) and pays no
    zero-math for them per dispatch — while staying token-exact vs the
    merged reference."""
    adir = str(tmp_path / "att")
    loralib.save_adapter(
        adir, _mk_layers(TINY, 7, targets=("q_proj", "v_proj")),
        alpha=8, r=4,
    )
    reg = AdapterRegistry(TINY, [adir])
    assert reg.targets == ("q_proj", "v_proj")
    reg.acquire("att")
    pools = reg.device_adapters()
    assert set(pools["a"]) == {"q_proj", "v_proj"}
    assert set(pools["b"]) == {"q_proj", "v_proj"}

    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(TINY, base_params, lanes=2, max_len=64,
                         adapters=AdapterRegistry(TINY, [adir]))
    s = _greedy_stream(ex, "s", PROMPT, 4, adapter="att")
    assert s == _merged_ref(base_params, adir, PROMPT, 4)


def test_standby_store_carries_adapter_to_promotion():
    """Replication deltas stamped with the session's adapter re-emit it
    in the promotion payload (import_session rebinds or declines); base
    sessions' shadows gain no key."""
    from inferd_tpu.runtime.repl import StandbyStore

    st = StandbyStore()
    k = np.zeros((2, 1, 4, 2, 8), np.float32)
    ok, _ = st.apply("s", 0, {"k": k, "v": k, "length": 4, "start": 0,
                              "adapter": "ten0"})
    assert ok
    assert st.payload("s")["adapter"] == "ten0"
    ok, _ = st.apply("b", 0, {"k": k, "v": k, "length": 4, "start": 0})
    assert ok
    assert "adapter" not in st.payload("b")


def test_mesh_executor_declines_adapter_stamped_import():
    """The mesh executor has no registry (--adapters is lane-executor-
    only), so an adapter-stamped handoff/standby payload must DECLINE —
    adopting it would silently resume the tenant on the base weights.
    The guard fires before any executor state is touched."""
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    class _Stub:  # the guard must return before reading any attribute
        pass

    assert MeshExecutor.import_session(
        _Stub(), "s", {"adapter": "ten0"}
    ) is False


def test_standby_pick_requires_adapter_capable_peer():
    """A tenant session's shadow only goes to a peer gossiping the
    `ada` key (the capability marker, present even when empty): an
    old-release or registry-less standby would accumulate deltas it can
    never promote. A sticky shadow on a non-capable peer re-picks
    away; base sessions keep the plain best-ranked pick."""
    from inferd_tpu.runtime.repl import SessionReplicator

    cands = [("old", {"load": 0}), ("cap", {"load": 1, "ada": []})]
    rep = SessionReplicator(lambda: cands)
    assert rep.pick_standby("s", cands) == "old"  # base: best rank wins
    assert rep.pick_standby("s", cands, require_ada=True) == "cap"
    rep.state["t"] = ("old", 7)  # sticky shadow on a non-capable peer
    assert rep.pick_standby("t", cands) == "old"
    assert rep.pick_standby("t", cands, require_ada=True) == "cap"
    plans = {sid: nid for sid, nid, _f in rep.plan(
        {"base": 4, "ten": 4}, adapters={"ten": "ten0"}
    )}
    assert plans == {"base": "old", "ten": "cap"}


def test_registry_can_serve_gates_standby_acceptance(catalog):
    """The /replicate_session receiver's serviceability check: a
    registry-less executor (or one whose catalog lacks the name) can
    never promote the shadow, so it must decline the delta up front;
    base-session deltas are always welcome."""
    from inferd_tpu.runtime.adapters import registry_can_serve

    class _Ex:
        adapters = None

    ex = _Ex()
    assert registry_can_serve(ex, None)           # base: always
    assert not registry_can_serve(ex, "ten0")     # no registry
    ex.adapters = AdapterRegistry(TINY, catalog)
    assert registry_can_serve(ex, "ten0")
    assert not registry_can_serve(ex, "other_tenant")


def test_affinity_probe_salt_scopes_prefix_matching():
    """A tenant session's prefix probe must carry its adapter salt: the
    salted probe matches digests of salted chains (its own cached
    blocks) and NOT base-session digests for the same prompt — and vice
    versa (an unsalted probe scoring salted keys would bonus a replica
    whose blocks the session cannot map)."""
    ids = list(range(32))
    bs = 8
    base_keys = {prefixlib.digest_key(k)
                 for k in prefixlib.block_keys(ids, bs)}
    ten_keys = {prefixlib.digest_key(k)
                for k in prefixlib.block_keys(ids, bs, salt="ten0")}
    assert base_keys.isdisjoint(ten_keys)
    base_rec = {"pfx": {"bs": bs, "k": sorted(base_keys)}}
    ten_rec = {"pfx": {"bs": bs, "k": sorted(ten_keys)}}
    salted = prefixlib.AffinityProbe(ids, salt="ten0")
    unsalted = prefixlib.AffinityProbe(ids)
    assert salted.depth_frac(ten_rec) == 1.0
    assert salted.depth_frac(base_rec) == 0.0
    assert unsalted.depth_frac(base_rec) == 1.0
    assert unsalted.depth_frac(ten_rec) == 0.0
