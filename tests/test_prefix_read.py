# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values
"""The read rule of dense lanes (models/qwen3._lanes_read; keys and values per
head, as rows, or a latent cache's latents and roped keys): a layer's slab is
read to the shortest rung of `read_rungs(T)` that covers the longest row's
valid length, chosen inside the program, against the same program reading the
whole slab (`read_rungs` patched to the one rung `T`).

The cache is filled with random keys and values, so a lane "holds" whatever
length a case hands the program. Two things are held at every rung boundary
(the longest row's valid length at rung - 1, rung, rung + 1, and T): the
narrowed program gives the whole-slab read's tokens, and its logits to float32
rounding; and it does not touch a slot past its rung: those slots are set to
NaN in its cache (a masked slot's probability is 0, and 0 x NaN is NaN, so a
program that multiplies them answers NaN), never in the reference's."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import get_config
from inferd_tpu.core import cache as cachelib
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.models import qwen3

from conftest import own_lane_programs  # noqa: E402

T = 1024  # the shortest slab the ladder engages on: eight rungs of 128 slots
W = T // 8
LANES = 4
TOL = dict(rtol=2e-5, atol=2e-5)  # float32 rounding
# the longest row's valid length: around the first rung, one in the middle and the last but one, and T
ENDS = [r + d for r in (W, 3 * W, 7 * W) for d in (-1, 0, 1)] + [T]


@contextlib.contextmanager
def _whole():
    """Programs traced in here read every slab whole, as before the rule."""
    rungs, qwen3.read_rungs = qwen3.read_rungs, lambda cfg, stacks, *shapes, **named: (stacks[0].shape[2],)
    try:
        yield
    finally:
        qwen3.read_rungs = rungs


def _config(model: str):
    if model.endswith("-wide"):  # heads as wide as a tile: DenseEntry lanes (tests/test_kv_rows.py)
        return dataclasses.replace(get_config(model[:-5]), name=model, head_dim=128)
    return get_config(model)  # 16-wide heads: RowEntry lanes; a latent model: LatentEntry


def _stacks(t: int, row: tuple, second: tuple = None, lanes: int = LANES, dtype=jnp.bfloat16):
    """The shapes of a cache's two stacks over `t` slots: what a slot holds
    in the first is `row`, in the other `second` (the same where None)."""
    return tuple(jax.ShapeDtypeStruct((2, lanes, t, *w), dtype) for w in (row, second or row))


def _rungs(t: int, q_len: int = 1):
    return qwen3.read_rungs(_config("tiny"), _stacks(t, (256,)), q_len, LANES, False)


# the lanes' layouts: keys per head, one row a token, and a latent cache (without and with the
# stream of hidden states around it)
LAYOUTS = pytest.mark.parametrize(
    "model", ["tiny-wide", "tiny", "tiny-dsv2", "tiny-xing4"],
    ids=["heads", "rows", "latent", "latent-stream"])

_ENGINES = {}


def _engine(model: str, whole: bool, max_len: int = T) -> BatchedEngine:
    """One engine a (model, read) pair: its jits trace at their first call,
    which `_run` makes under the read they are for."""
    key = (model, whole, max_len)
    if key not in _ENGINES:
        cfg = _config(model)
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        eng = BatchedEngine(cfg, params, lanes=LANES, max_len=max_len)
        # the whole-slab read is patched in at trace time: programs of its own
        _ENGINES[key] = own_lane_programs(eng) if whole else eng
    return _ENGINES[key]


def _filled(eng: BatchedEngine, poison_from=None) -> cachelib.KVCache:
    """The engine's cache with random keys and values in every slot; NaN from
    slot `poison_from` on."""
    kk, kv = jax.random.split(jax.random.PRNGKey(7))
    k = jax.random.normal(kk, eng.cache.k.shape, jnp.float32)
    v = jax.random.normal(kv, eng.cache.v.shape, jnp.float32)
    if poison_from is not None:
        k = k.at[:, :, poison_from:].set(jnp.nan)
        v = v.at[:, :, poison_from:].set(jnp.nan)
    return dataclasses.replace(
        jax.tree.map(jnp.copy, eng.cache), k=k.astype(eng.cache.k.dtype), v=v.astype(eng.cache.v.dtype))


def _run(model, call, end: int, max_len: int = T):
    """`call(engine, cache)` of the narrowed program, over a cache that is NaN
    past the rung `end` asks for, and of the whole-slab program over the clean one."""
    rung = min(-(-end // W) * W, T)
    eng = _engine(model, False, max_len)
    got = call(eng, _filled(eng, poison_from=rung))
    with _whole():
        ref_eng = _engine(model, True, max_len)
        want = call(ref_eng, _filled(ref_eng))
    return got, want


def _same(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "the narrowed program multiplied a slot past its rung"
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


@pytest.mark.parametrize("end", ENDS)
@LAYOUTS
def test_decode_step_over_ragged_lanes_reads_to_the_longest_lanes_rung(model, end):
    """Four lanes at ragged lengths, one of them idle (length 0): the step
    reads to the rung of the longest, whose row lies at `end` - 1."""
    lens = jnp.asarray([end - 1, 0, end // 2, 7], jnp.int32)
    toks = jnp.asarray([3, 0, 5, 9], jnp.int32)

    def step(eng, cache):
        _, logits, _ = eng._decode_logits(eng.params, cache, toks, lens)
        return logits

    _same(*_run(model, step, end))


@pytest.mark.parametrize("end", ENDS)
@LAYOUTS
def test_prefill_chunk_at_a_traced_start_reads_to_the_chunks_rung(model, end):
    """A 32-token bucket holding 29 tokens, written at a traced start into
    lane 2: the chunk reads to the rung of start + 32."""
    chunk = jnp.asarray(np.arange(32, dtype=np.int32)[None] % 200 + 1)

    def prefill(eng, cache):
        _, logits = eng._prefill_lane_logits(
            eng.params, cache, chunk, jnp.int32(2), jnp.int32(end - 32), jnp.int32(29))
        return logits

    _same(*_run(model, prefill, end))


@pytest.mark.parametrize("end", [W, W + 4, 5 * W, 5 * W + 4, T])
@pytest.mark.parametrize("model", ["tiny-sdar-wide", "tiny-sdar"], ids=["heads", "rows"])
def test_block_step_reads_the_block_it_writes_beyond_the_frontier(model, end):
    """A model generated by blocks: the longest lane's frontier is `end` less
    a block, the block being denoised is written beyond the frontier, and the
    read covers it (`end` is ctx.real_end). Lane 1 is idle (not live, length
    0). Held: the tokens and the pass that made each known, to the bit, and
    the keys the commit pass left in the block's rows."""
    blk = _config(model).block_length
    front = end - blk
    lens = jnp.asarray([front, 0, front // 2 // blk * blk, blk], jnp.int32)
    live = jnp.asarray([True, False, True, True])
    toks = jnp.zeros((LANES, blk), jnp.int32).at[:, 0].set(jnp.asarray([3, 4, 5, 6]))
    known = jnp.zeros((LANES, blk), bool).at[:, 0].set(True)
    keys = jnp.zeros((LANES, 2), jnp.uint32)

    def block(eng, cache):
        cache, packed, _ = eng._block_step(eng.params, cache, toks, known, lens, live, keys)
        rows = np.asarray(cache.k[:, 0, front:front + blk], np.float32)  # lane 0's committed block
        return np.asarray(packed)[np.asarray(live)], rows

    (got_packed, got_rows), (want_packed, want_rows) = _run(model, block, end)
    np.testing.assert_array_equal(got_packed, want_packed)
    assert np.isfinite(got_rows).all(), "the narrowed program multiplied a slot past its rung"
    np.testing.assert_allclose(got_rows, want_rows, **TOL)


@pytest.mark.parametrize("end", [W, W + 1, 6 * W + 1, T])
@pytest.mark.parametrize("model", ["tiny-gemma2-wide", "tiny-gemma2"], ids=["heads", "rows"])
def test_traced_window_layer_is_masked_and_prefix_bounded(model, end):
    """Gemma-2's alternating windows under a TRACED layer offset (a pp rank):
    the window only masks, and the read is bounded by the prefix as a full
    layer's is. Softcap and the window's mask ride the same branch."""
    cfg = _config(model)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    lens = jnp.asarray([end - 1, 0, end // 2, 7], jnp.int32)
    hidden = jax.random.normal(jax.random.PRNGKey(3), (LANES, 1, cfg.hidden_size), jnp.float32)

    def layers(cache, offset):
        out, _, _ = qwen3.forward_layers_cached(
            params["layers"], cfg, hidden, lens[:, None], cache, lens, layer_offset=offset)
        return out

    def filled(poison_from=None):
        eng = _engine("tiny-wide" if model.endswith("-wide") else "tiny", False)
        return _filled(eng, poison_from)  # the same lanes: a uniform cache of 4 layers

    rung = min(-(-end // W) * W, T)
    got = jax.jit(layers)(filled(rung), jnp.int32(0))
    with _whole():
        want = jax.jit(layers)(filled(), jnp.int32(0))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@LAYOUTS
def test_a_row_that_writes_nothing_does_not_widen_the_read(model):
    """Lane 1 sits the step out (`active` False, the write_mask) at a stale
    length of 900: the read stops at the rung of the longest ACTIVE lane, and
    the active lanes' logits are the whole-slab read's."""
    lens = jnp.asarray([200, 900, 0, 50], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    toks = jnp.asarray([3, 4, 5, 9], jnp.int32)

    def step(eng, cache):
        _, logits, _ = eng._decode_logits(eng.params, cache, toks, lens, active=active)
        return logits[np.asarray(active)]

    _same(*_run(model, step, 201))


@pytest.mark.parametrize("valid", [1, W - 1, W, 2 * W + 5])
def test_a_latent_rows_softmax_is_the_same_bits_at_every_rung_that_covers_it(valid):
    """Rows of a latent step share one read, whose length the longest row
    sets: the softmax sums a rung step by step in slot order
    (`_softmax_by_steps`), so the probabilities of a row with `valid` slots
    are the same BITS at every rung that covers them (a masked step adds an
    exact zero), and jax.nn.softmax's to rounding. (On the chip one reduction
    over the axis gave other last bits at 8192 slots than at 6144, and
    `xing-latent-docs`' probe other tokens beside sessions than alone.)"""
    scores = jax.random.normal(jax.random.PRNGKey(valid), (LANES, 4, 1, T), jnp.float32) * 3
    scores = jnp.where(jnp.arange(T) < valid, scores, jnp.float32(-1e30))
    rungs = [r for r in _rungs(T) if r >= valid]
    at = {r: np.asarray(jax.jit(lambda x, r=r: qwen3._softmax_by_steps(x[..., :r], W))(scores)) for r in rungs}
    for r in rungs:
        assert (at[r][..., :rungs[0]].view(np.uint32) == at[rungs[0]].view(np.uint32)).all()
        assert not at[r][..., valid:].any()
    np.testing.assert_allclose(at[T], np.asarray(jax.nn.softmax(scores, axis=-1)), rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("model,max_len", [
    ("tiny-wide", 64), ("tiny-wide", 512), ("tiny-wide", 1000), ("tiny-dsv2", 64), ("tiny-xing4", 64)])
def test_a_slab_under_the_floor_is_read_whole_by_the_program_it_always_was(model, max_len):
    """Under 1024 slots (or not a whole number of tiles a rung) the ladder is
    the slab: the read adds no conditional to the program, and its text is the
    text of the whole-slab read (a latent lane's as a lane of heads')."""
    assert _rungs(max_len) == (max_len,)
    toks = jnp.zeros((LANES,), jnp.int32)

    def text(whole):
        cfg = _config(model)
        eng = BatchedEngine(cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0)),
                            lanes=LANES, max_len=max_len)
        return eng._decode_logits.lower(eng.params, eng.cache, toks, toks).as_text()

    here = text(False)
    with _whole():
        assert here == text(True)
    if not _config(model).is_mla:  # a routed layer has conditionals of its own
        assert "stablehlo.case" not in here


@pytest.mark.parametrize("model,scans", [("tiny-wide", 1), ("tiny-dsv2", 2), ("tiny-xing4", 2)],
                         ids=["heads", "latent", "latent-stream"])
def test_the_ladder_engages_from_1024_slots_in_one_program(model, scans):
    """At 1024 slots the step and the chunk hold ONE conditional of eight
    branches a layer scan (a latent model's dense lead is a scan of its own),
    whatever the lengths and wherever the chunk starts: nothing static was
    added to a jit."""
    eng = _engine(model, False)
    toks = jnp.zeros((LANES,), jnp.int32)
    chunk = jnp.zeros((1, 32), jnp.int32)
    lower = lambda e: (
        e._decode_logits.lower(e.params, e.cache, toks, toks).as_text(),
        e._prefill_lane_logits.lower(
            e.params, e.cache, chunk, jnp.int32(0), jnp.int32(0), jnp.int32(3)).as_text())
    with _whole():
        whole = lower(_engine(model, True))
    for text, ref in zip(lower(eng), whole):
        assert text.count("stablehlo.case") - ref.count("stablehlo.case") == scans
    assert _rungs(T) == tuple(W * i for i in range(1, 9))
    assert _rungs(4096)[0] == 512 and _rungs(4096)[-1] == 4096
    before = eng._decode_logits._cache_size(), eng._prefill_lane_logits._cache_size()
    for lens in ([5, 0, 0, 0], [700, 3, 0, 0], [1023, 1023, 1023, 1023]):
        eng.cache, _, _ = eng._decode_logits(
            eng.params, eng.cache, toks, jnp.asarray(lens, jnp.int32))
    for start in (0, 300, T - 32):
        eng.cache, _ = eng._prefill_lane_logits(
            eng.params, eng.cache, chunk, jnp.int32(1), jnp.int32(start), jnp.int32(32))
    assert eng._decode_logits._cache_size() - before[0] <= 1
    assert eng._prefill_lane_logits._cache_size() - before[1] <= 1


def test_a_slice_made_before_the_dots_is_never_longer_than_fast_memory_keeps():
    """A pinned read (a stack with a head axis; a chunk over rows) makes its
    slice before the dots: the ladder then holds no rung whose slice is over
    64 MiB, the whole slab apart. `trinl-window-docs` (16 lanes of 16 384
    slots, 8 kv heads of 128) keeps the rung of 2048 slots (64 MiB to the
    byte); `q4b-sat-chat`, `sdar-block-chat` and a stage of `q8b-pp4-sat-chat`
    keep all eight; a decode step over rows (`q3n-long-docs`) fuses its slice
    into the dots and keeps all eight whatever their size."""
    def rungs(model, t, lanes, q_len, heads):
        cfg = get_config(model)
        row = (cfg.num_kv_heads, cfg.head_dim) if heads else (cfg.kv_dim,)
        return qwen3.read_rungs(cfg, _stacks(t, row, lanes=lanes), q_len, lanes, False)

    assert rungs("trinity-large-ep8-5l", 16384, 16, 1, True) == (2048, 16384)
    assert rungs("trinity-large-ep8-5l", 16384, 1, 512, True)[:2] == (2048, 4096)
    assert rungs("qwen3-4b", 4096, 5, 1, True) == tuple(range(512, 4097, 512))
    assert rungs("sdar-30b-a3b-7l", 4096, 16, 4, True) == tuple(range(512, 4097, 512))
    assert rungs("qwen3-8b", 4096, 8, 1, True) == tuple(range(512, 4097, 512))
    assert rungs("qwen3-next-80b-ep4-8l", 32768, 16, 1, False) == tuple(range(4096, 32769, 4096))
    assert len(rungs("qwen3-next-80b-ep4-8l", 32768, 1, 512, False)) == 8


def test_a_latent_lane_keeps_its_ladder_where_the_chip_would_pick_the_kernel(monkeypatch):
    """On a TPU `auto` hands a chunk whose float32 scores pass 256 MiB to the
    Pallas kernel, and the ladder gives way to the kernel's own bound (one
    rung): `xing-latent-docs`' chunk is 4 x 32 x 512 x 16 384 = 1.07 GB. No
    kernel serves latent attention, so `flash_enabled` says no for a latent
    model and both latent cells keep eight rungs in both programs: a latent
    row is 512 columns of bf16 (and 64 of roped key), so a chunk's slice of a
    whole lane is 16 MiB. A chunk over 16 rows (a speculative verify)
    keeps the 64 MiB cap as lanes of heads do."""
    from inferd_tpu.ops import attention as attention_ops

    monkeypatch.setattr(attention_ops, "is_tpu", lambda: True)
    eighths = lambda t: tuple(range(t // 8, t + 1, t // 8))
    for model, t in (("xing4.0-29b-a4b-6l", 16384), ("deepseek-v2-lite-8l", 4096)):
        cfg = get_config(model)
        assert not attention_ops.flash_enabled(cfg, t, q_len=512, batch=1)
        latent = lambda q_len, batch: qwen3.read_rungs(
            cfg, _stacks(t, (cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,), lanes=batch), q_len, batch, False)
        assert latent(512, 1) == eighths(t)  # a prefill chunk: its slice of a whole lane is 16 MiB
        assert latent(1, 16) == eighths(t)  # a decode step: the latents' slice fuses into the dots
        row = cfg.kv_lora_rank * 2  # the wider of the two stacks' rows, in bf16
        assert latent(4, 16) == tuple(r for r in eighths(t) if 16 * row * r <= 64 * 2**20 or r == t)
    assert latent(4, 16) == eighths(4096)
    # the rule still makes way for the kernel where one serves the model
    dense = get_config("qwen3-4b")
    assert qwen3.read_rungs(dense, _stacks(16384, (dense.num_kv_heads, 128), lanes=1), 512, 1, False) == (16384,)


@pytest.mark.parametrize("end", [W, 2 * W, 2 * W + 1, 5 * W, T])
def test_a_ladder_with_rungs_left_out_reads_to_the_next_it_has(end, monkeypatch):
    """With the two shortest rungs alone under the limit of a slice made
    before the dots, a longest lane past them reads the whole slab: same
    tokens and logits, and the rung the host counts is the program's."""
    cfg = _config("tiny-wide")
    row = LANES * cfg.kv_dim * 4  # float32 lanes
    monkeypatch.setattr(qwen3, "_READ_SLICE_BYTES", 2 * W * row)
    rungs = qwen3.read_rungs(
        cfg, _stacks(T, (cfg.num_kv_heads, cfg.head_dim), dtype=jnp.float32), 1, LANES, False)
    assert rungs == (W, 2 * W, T)
    eng = own_lane_programs(  # traced under the patched limit
        BatchedEngine(cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0)), lanes=LANES, max_len=T))
    lens = jnp.asarray([end - 1, 0, end // 2, 7], jnp.int32)
    toks = jnp.asarray([3, 0, 5, 9], jnp.int32)
    rung = rungs[int(qwen3.read_rung(end, rungs))]
    assert rung == (end if end in rungs else T)
    _, got, _ = eng._decode_logits(eng.params, _filled(eng, poison_from=rung), toks, lens)
    with _whole():
        ref = _engine("tiny-wide", True)
        _, want, _ = ref._decode_logits(ref.params, _filled(ref), toks, lens)
    _same(got, want)


@pytest.mark.parametrize("t", [1024, 4096, 16384, 32768])  # 4096, 16384: the latent cells' lanes
def test_host_and_program_choose_the_same_rung(t):
    """`read_rung` is the one function both ask: on Python ints (the host's
    counter) and traced (the program) it names the same rung, the shortest
    that covers the longest lane, and the last where nothing shorter does."""
    rungs = _rungs(t)
    traced = jax.jit(lambda n: qwen3.read_rung(n, rungs))
    for longest in sorted({0, 1, t, t + 1, t + 4096} | {r + d for r in rungs for d in (-1, 0, 1)}):
        host = int(qwen3.read_rung(longest, rungs))
        assert host == int(traced(jnp.int32(longest)))
        assert rungs[host] >= min(longest, t)
        assert host == 0 or rungs[host - 1] < longest


# ---------------------------------------------------------------------------
# the counter: /stats `executor` `kv.slots_read` / `kv.slots_held`
# ---------------------------------------------------------------------------


def _reader(metric="engine.slab_read_share"):
    import importlib.util
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)  # the reader imports `arith` as the harness has it
    try:
        spec = importlib.util.spec_from_file_location(
            metric.replace(".", "_"), os.path.join(bench, "layer_metrics", f"{metric}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    return mod.read


def _serve(model, prompt_len, new, max_len=T, lanes=3):
    """One greedy session through the lane executor -> (stats before, stats after)."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg = _config(model)
    ex = BatchedExecutor(cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0)), lanes=lanes, max_len=max_len)
    before = {"executor": ex.stats()}
    prompt = [3 + i % 200 for i in range(prompt_len)]
    res = ex.process("s", {"tokens": [prompt], "start_pos": 0, "real_len": prompt_len})
    tok = int(np.argmax(res["logits"][0]))
    for i in range(new):
        res = ex.process("s", {"tokens": [[tok]], "real_len": 1, "start_pos": prompt_len + i,
                               "sampling": {}, "seed": 0})
        tok = int(res["tokens"][0][0])
    return before, {"executor": ex.stats()}


@LAYOUTS
def test_the_counter_counts_the_rung_the_program_is_handed(model):
    """A prompt of 200 tokens (one bucket of 256: two rungs) and six decode
    steps whose row lies at 200..205 (three lanes reading two rungs, the two
    idle lanes at length 0 widening nothing): counted by the function the
    program asks, from the lengths it is handed."""
    before, after = _serve(model, 200, 6)
    assert "kv" not in before["executor"] or before["executor"]["kv"]["slots_held"] == 0
    kv = after["executor"]["kv"]
    assert kv["slots_read"] == 1 * 2 * W + 6 * 3 * 2 * W
    assert kv["slots_held"] == 1 * T + 6 * 3 * T
    assert _reader()({"stats0": before, "stats1": after}) == pytest.approx(25.0)


def test_a_step_past_a_rung_boundary_is_counted_at_the_next_rung():
    before, after = _serve("tiny", 254, 4)  # rows at 254, 255 | 256, 257: ends 255, 256 | 257, 258
    kv = after["executor"]["kv"]
    assert kv["slots_read"] == 2 * W + 3 * (2 * 2 * W + 2 * 3 * W)
    assert kv["slots_held"] == T + 4 * 3 * T


def test_a_slab_under_the_floor_counts_as_read_whole():
    before, after = _serve("tiny", 20, 3, max_len=64)
    kv = after["executor"]["kv"]
    assert kv["slots_read"] == kv["slots_held"] == 64 + 3 * 3 * 64
    assert _reader()({"stats0": before, "stats1": after}) == 100.0


def test_a_block_step_counts_every_pass_and_its_prefill_the_real_end():
    """A model generated by blocks: a prompt of 8 (two whole blocks in one
    bucket of 16: the valid length is the REAL end), then two block steps of
    three passes whose block ends at 12 and 16."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg = _config("tiny-sdar")
    ex = BatchedExecutor(cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0)), lanes=3, max_len=T)
    ex.process("s", {"tokens": [[3, 4, 5, 6, 7, 8, 9, 10]], "start_pos": 0, "real_len": 8})
    for start in (8, 12):
        ex.process("s", {"tokens": [[255] * 4], "real_len": 4, "start_pos": start,
                         "block": {"known": 0, "seed": 0}})
    kv = ex.stats()["kv"]
    assert kv["slots_read"] == W + 2 * 3 * 3 * W
    assert kv["slots_held"] == T + 2 * 3 * 3 * T


def test_a_latent_cache_counts_its_lanes_and_the_parents_stats_say_nothing():
    """A latent lane under the floor counts rows x `--max-len` over the same,
    and past a rung boundary rows x the rung the program chose (rows at 254,
    255 | 256, 257 of 1024 slots); a `/stats` without the counter (the
    parent's latent node) is no number."""
    read = _reader("engine.latent_read_share")
    before, after = _serve("tiny-dsv2", 20, 2, max_len=64)
    kv = after["executor"]["kv"]
    assert kv["slots_read"] == kv["slots_held"] == 64 + 2 * 3 * 64
    assert read({"stats0": before, "stats1": after}) == 100.0
    before, after = _serve("tiny-xing4", 254, 4)
    kv = after["executor"]["kv"]
    assert kv["slots_read"] == 2 * W + 3 * (2 * 2 * W + 2 * 3 * W)
    assert kv["slots_held"] == T + 4 * 3 * T
    assert read({"stats0": before, "stats1": after}) == pytest.approx(100 * kv["slots_read"] / kv["slots_held"])
    parent = lambda stats: {"executor": {k: v for k, v in stats["executor"].items() if k != "kv"}}
    assert read({"stats0": parent(before), "stats1": parent(after)}) is None
    assert read({"stats0": {}, "stats1": {"executor": {}}}) is None


# ---------------------------------------------------------------------------
# the mesh: the same rule under shard_map (no collective inside a branch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", ["tiny-wide", "tiny"], ids=["heads", "rows"])
def test_the_mesh_passes_read_by_prefix_and_answer_as_the_whole_slab_read(model, devices8):
    """`--mesh pp=2`: the prefill step (`_pipeline_pass`) and the decode pass
    that carries its slots as rows (`_rows_pass`, an inactive slot's row under
    the write mask) over slots of 1024: a prompt of 130 tokens (past the first
    rung), then four passes of two slots at ragged lengths; logits as the
    whole-slab read gives them, and the conditional is in the pass."""
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.infer import PipelinedEngine

    cfg = _config(model)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.asarray([[3 + i % 200 for i in range(130)]], np.int32)

    def serve():
        mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2), devices8[:2])
        eng = PipelinedEngine(cfg, params, mesh, num_microbatches=4, max_len=T)
        out = [eng.step_slot(1, prompt, 130, True), eng.step_slot(3, prompt[:, :9], 9, True)]
        toks = {1: int(out[0][0].argmax()), 3: int(out[1][0].argmax())}
        for _ in range(4):
            rows = eng.step_slots(toks)
            out += [rows[1], rows[3]]
            toks = {slot: int(np.asarray(row).argmax()) for slot, row in rows.items()}
        slots = jnp.zeros((4,), jnp.int32)
        text = eng._step_raw_multi.lower(eng.params, eng.caches, slots, slots.astype(bool)).as_text()
        return [np.asarray(o, np.float32).reshape(-1) for o in out], text

    got, text = serve()
    with _whole():
        want, whole_text = serve()
    # the whole-slab pass has one conditional too: a stage's own tick
    assert text.count("stablehlo.case") == whole_text.count("stablehlo.case") + 1
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, **TOL)
        assert g.argmax() == w.argmax()
