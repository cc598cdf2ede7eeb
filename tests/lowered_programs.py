"""The ten programs the benchmark's five older cells run (a decode or block
step and a prefill, the lane programs with the sampler at both of its widths),
the two lane programs of `g4hm-many-chat`, the two of `trinl-window-docs`, the
two of `q3n-long-docs` (the prefill and the step the window runs; its
preset keeps its own heads: 2 kv heads of 32 are one row a token, as the
cell's 2 of 256 are) and the two of `olmoh-reason-chat` (PR 51; its preset
keeps the published head sizes of its state, 96 x 192, held two heads side by
side as the cell's are) and the two lane programs of `tiny-xing4` (PR 55: a stream of
four hidden states around every sublayer) and the two of `tiny-nemotron-h` (PR 57: layers
that are one sublayer each, three weight stacks by kind), lowered at the tiny presets, and (PR 49) the decode step
and the prefill of `q4b-*`, (PR 56) of `dsv2l-*` and of `xing-*` once more over lanes of 1024 slots,
where a slab (a latent lane too) is read by its prefix (models/qwen3.read_rungs: the 64-slot lanes of
the others are under that rule's floor and keep the text they had):
`texts()` gives their StableHLO text by name. What the text holds is the traced
program; sizes are not the point: a change that leaves these configurations
alone leaves every byte alone. ONE width is the point: the cells' heads are as
wide as a tile (128), so the tiny presets of the per-head cells are lowered
with `head_dim=128` (at their own 16 they would take the row layout of
core.cache.rows_layout, which no older cell runs); granite's are rows.

    python tests/lowered_programs.py <dir>      writes <dir>/<name>.txt
    python tests/lowered_programs.py --record   rewrites tests/data/lowered_programs.json

A PR that changes one of these programs on purpose records the digests anew
and says so; tests/test_model.py holds the tree to them."""

import dataclasses
import hashlib
import json
import os
import sys

DIGESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "lowered_programs.json")


def texts() -> dict:
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.core import sampling as samplib
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.infer import PipelinedEngine

    def cell_config(model):
        cfg = get_config(model)
        if cfg.is_mla or cfg.has_state_layers:
            return cfg
        return dataclasses.replace(cfg, head_dim=128)  # the width of the cell's heads

    out = {}
    i32 = jnp.int32(0)
    # cell, preset, lanes, the sampler's widths its decode step is lowered at
    for cell, model, lanes, widths in (
            ("q4b", "tiny", 5, (0, 8)), ("dsv2l", "tiny-dsv2", 16, (0, 8)), ("sdar", "tiny-sdar", 16, ()),
            ("g4hm", "tiny-granite-h", 4, (8,)), ("trinl", "tiny-afmoe", 16, (0,)),
            ("q3n", "tiny-qwen3-next", 16, (0,)), ("olmoh", "tiny-olmo-hybrid", 16, (0,)),
            ("xing", "tiny-xing4", 16, (0,)), ("nem3s", "tiny-nemotron-h", 8, (0,))):
        cfg = cell_config(model)
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        eng = BatchedEngine(cfg, params, lanes=lanes, max_len=64)
        toks = jnp.zeros((lanes,), jnp.int32)
        chunk = jnp.zeros((1, 32), jnp.int32)
        out[f"{cell}.prefill"] = eng._prefill_lane_logits.lower(
            eng.params, eng.cache, chunk, i32, i32, i32).as_text()
        if cfg.is_block_diffusion:
            blk = jnp.zeros((lanes, cfg.block_length), jnp.int32)
            out[f"{cell}.block"] = eng._block_step.lower(
                eng.params, eng.cache, blk, blk.astype(bool), toks, toks.astype(bool),
                jnp.zeros((lanes, 2), jnp.uint32)).as_text()
            continue
        ask = samplib.RowAsk(jnp.zeros((lanes, 2), jnp.uint32), jnp.zeros((lanes, 4), jnp.float32))
        # the step as its executor calls it: with the lanes' mask where a state must not move
        mask = {"active": toks.astype(bool)} if cfg.has_state_layers else {}
        for top_n in widths:
            out[f"{cell}.decode.top{top_n}"] = eng._decode_logits.lower(
                eng.params, eng.cache, toks, toks, ask=ask, top_n=top_n, **mask).as_text()
    # the two programs of `q4b-*`, and (PR 56) of the two latent cells, over
    # lanes long enough for the read by prefix
    for cell, model, lanes in (("q4b", "tiny", 5), ("dsv2l", "tiny-dsv2", 16), ("xing", "tiny-xing4", 16)):
        cfg = cell_config(model)
        eng = BatchedEngine(cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0)), lanes=lanes, max_len=1024)
        toks = jnp.zeros((lanes,), jnp.int32)
        ask = samplib.RowAsk(jnp.zeros((lanes, 2), jnp.uint32), jnp.zeros((lanes, 4), jnp.float32))
        out[f"{cell}.prefill.t1024"] = eng._prefill_lane_logits.lower(
            eng.params, eng.cache, jnp.zeros((1, 32), jnp.int32), i32, i32, i32).as_text()
        out[f"{cell}.decode.top0.t1024"] = eng._decode_logits.lower(
            eng.params, eng.cache, toks, toks, ask=ask, top_n=0).as_text()
    cfg = cell_config("tiny")
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=4), jax.devices()[:4])
    eng = PipelinedEngine(cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0)), mesh,
                          num_microbatches=8, max_len=64)
    slots = jnp.zeros((8,), jnp.int32)
    ask = samplib.RowAsk(jnp.zeros((8, 2), jnp.uint32), jnp.zeros((8, 4), jnp.float32))
    out["q8b-pp4.prefill"] = eng._step_raw.lower(
        eng.params, eng.caches, jnp.zeros((1, 1, 32), jnp.int32), i32, i32, jnp.bool_(False)).as_text()
    out["q8b-pp4.decode.top0"] = eng._step_raw_multi.lower(
        eng.params, eng.caches, slots, slots.astype(bool), ask=ask, top_n=0).as_text()
    return out


NAMES = ("q4b.prefill", "q4b.decode.top0", "q4b.decode.top8", "dsv2l.prefill", "dsv2l.decode.top0",
         "dsv2l.decode.top8", "sdar.prefill", "sdar.block", "q8b-pp4.prefill", "q8b-pp4.decode.top0",
         "g4hm.prefill", "g4hm.decode.top8", "trinl.prefill", "trinl.decode.top0",
         "q3n.prefill", "q3n.decode.top0", "q4b.prefill.t1024", "q4b.decode.top0.t1024",
         "olmoh.prefill", "olmoh.decode.top0", "xing.prefill", "xing.decode.top0",
         "dsv2l.prefill.t1024", "dsv2l.decode.top0.t1024", "xing.prefill.t1024", "xing.decode.top0.t1024",
         "nem3s.prefill", "nem3s.decode.top0")


def digests(found: dict) -> dict:
    return {name: hashlib.sha256(text.encode()).hexdigest() for name, text in sorted(found.items())}


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    found = texts()
    assert tuple(sorted(found)) == tuple(sorted(NAMES)), sorted(found)
    if sys.argv[1] == "--record":
        with open(DIGESTS, "w") as f:
            json.dump(digests(found), f, indent=1)
    else:
        os.makedirs(sys.argv[1], exist_ok=True)
        for name, text in found.items():
            with open(os.path.join(sys.argv[1], f"{name}.txt"), "w") as f:
                f.write(text)
    print(json.dumps(digests(found), indent=1))
