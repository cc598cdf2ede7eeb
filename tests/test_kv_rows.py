"""Keys and values of a head narrower than a tile, stored as one row a token
(core.cache.RowEntry, models/qwen3._attend_update_rows), against attention
per head over the same weights.

Two references. For one layer, a test-local per-head attention written out
in numpy (`_per_head`): mask, window, sinks, softcap and the REAL head's
scale. For a whole engine, the SAME engine over a cache built by hand in the
other layout: `KVCache.entries` chooses the write-then-read function from
the array it is handed, so rows run against heads (`_attend_update_lanes`)
for the models the row layout serves, and heads against rows for a
test-local config with heads as wide as a tile, which keeps the heads
layout under CPU cover now that every tiny preset (16-wide heads) is
stored as rows. The stored values of a lane are the same in both layouts;
a score is the sum of the same products in another order, so tokens are
equal and logits agree to float32 rounding (one layer, float32 storage) or
to a rounding step of the storage (two engines, bf16 and fp8 storage)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import get_config
from inferd_tpu.core import cache as cachelib
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.models import qwen3

TOL = dict(rtol=2e-5, atol=2e-5)  # float32 rounding
# between two engines a stored value now and then rounds the other way (a
# layer's input differs by float32 rounding); a logit then moves by that step
ENGINE_TOL = {"bfloat16": dict(rtol=2e-4, atol=2e-4), "float8_e4m3fn": dict(rtol=2e-3, atol=2e-3)}


def _config(model: str, kv: str):
    if model == "tiny-wide":  # heads as wide as a tile: the layout with a head axis
        cfg = dataclasses.replace(get_config("tiny"), name=model, head_dim=128)
    else:
        cfg = get_config(model)
    return dataclasses.replace(cfg, kv_dtype=kv)


def _other_layout(cache: cachelib.KVCache, cfg) -> cachelib.KVCache:
    """The same (empty) cache with k and v in the layout `create` did not choose."""
    if cache.k.ndim == 4:
        flip = lambda a: a.reshape(*a.shape[:3], cfg.num_kv_heads, cfg.head_dim)
    else:
        flip = lambda a: a.reshape(*a.shape[:3], -1)
    return dataclasses.replace(cache, k=flip(cache.k), v=flip(cache.v))


def _same_stored(a, b):
    """Two K or V arrays of either layout hold the same stored values: to a
    step of the storage dtype, and nearly everywhere to the bit (a layer's
    input differs by float32 rounding between the layouts, which now and then
    falls on the other side of a rounding to bf16 or fp8)."""
    eps = float(jnp.finfo(a.dtype).eps)
    a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
    a = a.reshape(b.shape)
    np.testing.assert_allclose(a, b, rtol=eps, atol=eps * 2.0 ** -6)
    assert (a == b).mean() > 0.995


# ---------------------------------------------------------------------------
# one layer against per-head attention written out
# ---------------------------------------------------------------------------


def _per_head(q, k, v, qpos, valid, scale, window=0, sinks=None, softcap=0.0):
    """q [B, S, Nq, D], k / v [B, T, Nkv, D] (slot == position), float64:
    softmax(scale * q.k) v per query head, over slots j < valid[b] with
    j <= qpos and, under a window, j > qpos - window; a sink joins the
    denominator alone."""
    b, s, nq, d = q.shape
    t, nkv = k.shape[1], k.shape[2]
    out = np.zeros((b, s, nq, d))
    for bi in range(b):
        for si in range(s):
            p = int(qpos[bi, si])
            seen = [j for j in range(t) if j < valid[bi] and j <= p and (window <= 0 or j > p - window)]
            for h in range(nq):
                n = h // (nq // nkv)
                sc = np.array([scale * float(q[bi, si, h] @ k[bi, j, n]) for j in seen])
                if softcap:
                    sc = softcap * np.tanh(sc / softcap)
                m = max(sc.max(), sinks[h]) if sinks is not None else sc.max()
                w = np.exp(sc - m)
                den = w.sum() + (np.exp(sinks[h] - m) if sinks is not None else 0.0)
                out[bi, si, h] = sum(wi * v[bi, j, n] for wi, j in zip(w / den, seen))
    return out.reshape(b, s, nq * d)


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "chunk"])
@pytest.mark.parametrize(
    "window, sinks, softcap",
    [(None, False, 0.0), (6, False, 0.0), ("traced", True, 0.0), (None, True, 30.0)],
    ids=["global", "static-window", "traced-window-sinks", "sinks-softcap"],
)
def test_a_layer_of_rows_is_attention_per_head(s, window, sinks, softcap):
    """`_attend_update_rows` at layer 1 of a three-layer stack, three lanes at
    ragged fills, the last one masked: the chunk's rows land at (layer, lane,
    write_pos) as [Nkv * D] and nowhere else, the masked lane writes nothing,
    and the output is per-head attention over the lane's history and the
    chunk, with the scale of the real head (`cfg.attn_scale`: here 1/3,
    through `query_pre_attn_scalar`), not of the row's width."""
    cfg = dataclasses.replace(
        get_config("tiny"), attn_logit_softcap=softcap, query_pre_attn_scalar=9.0,
    )
    nq, nkv, d, t, b = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 24, 3
    rng = np.random.RandomState(7)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    hist_k, hist_v = draw(3, b, t, nkv * d), draw(3, b, t, nkv * d)
    q, k, v = draw(b, s, nq, d), draw(b, s, nkv, d), draw(b, s, nkv, d)
    write_pos = np.array([9, 4, 13], np.int32)
    mask = np.array([True, True, False])
    sink = draw(nq) if sinks else None
    entry = cachelib.RowEntry(k=jnp.asarray(hist_k), v=jnp.asarray(hist_v))
    qpos = write_pos[:, None] + np.arange(s)[None]
    ctx = cachelib.KVCache.ctx(jnp.asarray(write_pos), write_mask=jnp.asarray(mask))
    w = jnp.int32(6) if window == "traced" else window
    out, new = qwen3._attend_update_rows(
        cfg, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(qpos), entry,
        jnp.int32(1), ctx, w, None if sink is None else jnp.asarray(sink),
    )
    assert isinstance(new, cachelib.RowEntry) and new.k.shape == hist_k.shape
    want_k, want_v = hist_k.copy(), hist_v.copy()
    for bi in range(2):  # lane 2 is masked: it writes nothing
        want_k[1, bi, write_pos[bi]: write_pos[bi] + s] = k[bi].reshape(s, nkv * d)
        want_v[1, bi, write_pos[bi]: write_pos[bi] + s] = v[bi].reshape(s, nkv * d)
    np.testing.assert_array_equal(np.asarray(new.k), want_k)
    np.testing.assert_array_equal(np.asarray(new.v), want_v)
    heads = lambda a: a[1].reshape(b, t, nkv, d).astype(np.float64)
    want = _per_head(
        q.astype(np.float64), heads(want_k), heads(want_v), qpos, write_pos + s,
        cfg.attn_scale, window=6 if window else 0, sinks=sink, softcap=softcap,
    )
    assert cfg.attn_scale == 1.0 / 3.0
    np.testing.assert_allclose(np.asarray(out)[:2], want[:2], **TOL)


# ---------------------------------------------------------------------------
# a whole engine in one layout against the same engine in the other
# ---------------------------------------------------------------------------

MODELS = ["tiny-granite-h", "tiny-llama", "tiny-wide"]


@pytest.mark.parametrize("kv", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("model", MODELS)
def test_an_engine_of_rows_is_the_engine_of_heads(model, kv):
    """Two prefill chunks through a lane's view (lane_slice / lane_write),
    eight ragged decode steps with a masked lane, a K-step `decode_k`,
    `grow`, a whole lane copied, a fork: the layout `KVCache.create` chooses
    against the other one over the same weights, tokens equal and logits to
    a rounding step of the storage, and the stored lanes the same values."""
    cfg = _config(model, kv)
    rows = cachelib.rows_layout(cfg)
    assert rows == (model != "tiny-wide")
    params = qwen3.init_params(cfg, jax.random.PRNGKey(11))
    lanes, max_len = 4, 32
    eng = {side: BatchedEngine(cfg, params, lanes=lanes, max_len=max_len) for side in "ab"}
    assert eng["a"].cache.layout(cfg) == ("rows" if rows else "heads")
    assert eng["a"].cache.k.shape[3:] == (
        (cfg.num_kv_heads * cfg.head_dim,) if rows else (cfg.num_kv_heads, cfg.head_dim))
    assert eng["a"].cache.k.dtype == jnp.dtype(kv)
    eng["b"].cache = _other_layout(eng["b"].cache, cfg)
    assert eng["b"].cache.layout(cfg) == ("heads" if rows else "rows")
    nbytes = eng["a"].cache.nbytes
    assert eng["b"].cache.nbytes == nbytes  # the same bytes
    rng = np.random.RandomState(5)
    draw = lambda n: rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)

    def both(fn):
        """fn(engine) on each side -> (a's result, b's)."""
        return fn(eng["a"]), fn(eng["b"])

    def prefill(lane, toks, start, n):
        def run(e):
            chunk = np.zeros((1, 8), np.int32)
            chunk[0, : len(toks)] = toks
            e.cache, logits = e._prefill_lane_logits(
                e.params, e.cache, jnp.asarray(chunk), jnp.int32(lane), jnp.int32(start), jnp.int32(n))
            return np.asarray(logits)
        la, lb = both(run)
        np.testing.assert_allclose(la, lb, **ENGINE_TOL[kv])
        assert la.argmax() == lb.argmax()
        return int(la.argmax())

    # lane 0: two chunks (the second padded to its bucket); lanes 1 and 2: one; lane 3 idle
    lengths = np.zeros((lanes,), np.int32)
    last = np.zeros((lanes,), np.int32)
    prefill(0, draw(8), 0, 8)
    last[0] = prefill(0, draw(5), 8, 5)
    last[1] = prefill(1, draw(6), 0, 6)
    last[2] = prefill(2, draw(3), 0, 3)
    lengths[:3] = (13, 6, 3)
    active = np.array([True, True, True, False])

    def decode(cache_of=None):
        def run(e):
            e.cache, logits, _ = e._decode_logits(
                e.params, e.cache, jnp.asarray(last), jnp.asarray(lengths), active=jnp.asarray(active))
            return np.asarray(logits)
        la, lb = both(run)
        np.testing.assert_allclose(la[active], lb[active], **ENGINE_TOL[kv])
        np.testing.assert_array_equal(la[active].argmax(-1), lb[active].argmax(-1))
        return la.argmax(-1).astype(np.int32)

    for _ in range(8):  # ragged fills, lane 3 masked
        nxt = decode()
        last[active] = nxt[active]
        lengths[active] += 1
    for e in eng.values():  # a masked lane wrote nothing, in either layout
        assert not bool(jnp.any(e.cache.k[:, 3] != 0) | jnp.any(e.cache.v[:, 3] != 0))
    _same_stored(eng["a"].cache.k, eng["b"].cache.k)
    _same_stored(eng["a"].cache.v, eng["b"].cache.v)

    # three fused steps (models/qwen3.decode_k), greedy
    k_steps = jax.jit(lambda p, c, t, ln, act, keys: qwen3.decode_k(p, cfg, t, c, ln, act, keys, 3)[:3])

    def fused(e):
        e.cache, seq, n_new = k_steps(
            e.params, e.cache, jnp.asarray(last), jnp.asarray(lengths), jnp.asarray(active),
            jnp.zeros((lanes, 2), jnp.uint32))
        return np.asarray(seq), np.asarray(n_new)
    (sa, na), (sb, nb) = both(fused)
    np.testing.assert_array_equal(sa[:, active], sb[:, active])
    np.testing.assert_array_equal(na, nb)
    assert list(na) == [3, 3, 3, 0]
    last[active] = sa[-1, active]
    lengths[active] += 3

    # grow: the populated slots carry over into a longer bucket
    for e in eng.values():
        e.cache = cachelib.grow(e.cache, 2 * max_len)
        assert e.cache.max_len == 2 * max_len
    assert eng["a"].cache.nbytes - eng["a"].cache.state_bytes == 2 * (nbytes - eng["a"].cache.state_bytes)
    nxt = decode()
    last[active] = nxt[active]
    lengths[active] += 1

    # a whole lane copied (lane_slice / lane_write): lane 3 becomes lane 0, state and all
    for e in eng.values():
        e.cache = cachelib.lane_write(e.cache, 3, cachelib.lane_slice(e.cache, 0))
    active[3], last[3], lengths[3] = True, last[0], lengths[0]
    nxt = decode()
    assert nxt[3] == nxt[0]
    last[:] = nxt
    lengths += 1

    # a fork: the first 5 slots of lane 1 seed lane 2 (a recurrent state has no prefix to cut)
    if cfg.has_state_layers:
        with pytest.raises(ValueError, match="recurrent state"):
            eng["a"]._fork_lane(eng["a"].cache, jnp.int32(1), jnp.int32(2), 5)
        return
    for e in eng.values():
        e.cache = e._fork_lane(e.cache, jnp.int32(1), jnp.int32(2), 5)
        assert bool(jnp.all(e.cache.k[:, 2, :5] == e.cache.k[:, 1, :5]))
        assert bool(jnp.all(e.cache.v[:, 2, :5] == e.cache.v[:, 1, :5]))
    lengths[2], last[2] = 5, 17
    nxt = decode()
    _same_stored(eng["a"].cache.k, eng["b"].cache.k)


@pytest.mark.parametrize("kv", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("model", ["tiny-llama", "tiny-wide"])
def test_a_session_crosses_the_wire_as_heads_whatever_the_layout(model, kv):
    """Export -> import between two `--batch-lanes` executors: the payload's
    k and v are [L, 1, n, Nkv, D] whether the lanes store rows or heads (a
    row-major reshape on the host), the stored values of the exporting lane;
    the importer continues with the exporter's logits; a delta export ships
    the new slots in the same shape, and the whole payload equals the delta
    appended to the first."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg = _config(model, kv)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(3))
    a = BatchedExecutor(cfg, params, lanes=2, max_len=64)
    b = BatchedExecutor(cfg, params, lanes=2, max_len=64)
    layout = "rows" if cachelib.rows_layout(cfg) else "heads"
    assert a.stats()["kv_layout"] == layout
    prompt = np.random.RandomState(2).randint(1, cfg.vocab_size, (1, 12)).astype(np.int32)
    a.process("s", {"tokens": prompt, "start_pos": 0, "real_len": 12})
    first = a.export_session_delta("s", 0)
    wire = (cfg.num_layers, 1, 12, cfg.num_kv_heads, cfg.head_dim)
    assert first["k"].shape == first["v"].shape == wire
    step = lambda pos, tok: {"tokens": np.asarray([[tok]]), "start_pos": pos, "real_len": 1}
    a.process("s", step(12, 5))
    a.process("s", step(13, 9))
    delta = a.export_session_delta("s", 12)
    assert delta["k"].shape == (cfg.num_layers, 1, 2, cfg.num_kv_heads, cfg.head_dim)
    whole = dict(a.export_sessions())["s"]
    assert whole["k"].shape == (cfg.num_layers, 1, 14, cfg.num_kv_heads, cfg.head_dim)
    np.testing.assert_array_equal(whole["k"], np.concatenate([first["k"], delta["k"]], axis=2))
    np.testing.assert_array_equal(whole["v"], np.concatenate([first["v"], delta["v"]], axis=2))
    lane = a._sessions["s"]
    stored = np.asarray(a.engine.cache.k[:, lane: lane + 1, :14].astype(jnp.float32))
    sent = whole["k"].view(jnp.dtype(kv)) if "kv_dtype" in whole else whole["k"]
    np.testing.assert_array_equal(np.asarray(sent).astype(np.float32).reshape(stored.shape), stored)
    assert b.import_session("s", whole)
    assert b.engine.cache.k.shape == a.engine.cache.k.shape
    la = a.process("s", step(14, 3))["logits"]
    lb = b.process("s", step(14, 3))["logits"]
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-5)
    bad = dict(whole, k=whole["k"].reshape(*whole["k"].shape[:3], -1))  # rows are not the wire's shape
    assert not b.import_session("s2", bad)
