"""Weight loading: HF checkpoints -> inferd_tpu param pytrees.

Replaces the reference's two ad-hoc weight schemes — whole-module
`torch.save` blobs per node (/root/reference/split_model.py:104-108) and
per-layer `.pt` files fetched from a personal HF repo
(/root/reference/models/qwen3/server/qwen3_server_module.py:227-234) — with
standard HF safetensors. Layers land stacked on a leading axis (see
models/qwen3.py) so a pipeline stage's weights are a pytree slice.

Works fully offline: `params_from_hf_state_dict` converts an in-memory
state dict (e.g. a locally-initialized `transformers` model in tests), and
`load_params` reads *.safetensors from a local directory or the local HF
cache. No network calls unless the repo must be downloaded.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Mapping, Optional

import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import HF_REPOS, ModelConfig

Params = Dict[str, Any]


def _to_np(t) -> np.ndarray:
    """Convert a torch tensor / array-like to float32 numpy (lossless for bf16)."""
    if hasattr(t, "detach"):  # torch tensor
        import torch

        return t.detach().to(torch.float32).cpu().numpy()
    return np.asarray(t, dtype=np.float32)


# FP4 e2m1 code values (sign nibble-coded): the MXFP4 lookup table used by
# the official GPT-OSS checkpoints (matches transformers' mxfp4 integration,
# which tests pin this against).
_FP4_VALUES = np.array(
    [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0,
     -0.0, -0.5, -1.0, -1.5, -2.0, -3.0, -4.0, -6.0],
    dtype=np.float32,
)


def dequant_mxfp4(blocks: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Dequantize MXFP4 expert weights (GPT-OSS checkpoint storage).

    blocks [*prefix, rows, G, B] uint8 — two FP4 codes per byte (low nibble
    first); scales [*prefix, rows, G] uint8 — E8M0 shared exponents
    (value = fp4 * 2**(scale - 127)). Returns float32 [*prefix, G*B*2, rows]
    — dequantized along the packed axis, then the last two logical axes
    swapped, exactly transformers' convert_moe_packed_tensors, which yields
    the [E, in, out] orientation the param pytree stores."""
    blocks = np.asarray(blocks).astype(np.uint8)
    exp = np.asarray(scales).astype(np.int32) - 127
    lo = _FP4_VALUES[blocks & 0x0F]
    hi = _FP4_VALUES[blocks >> 4]
    out = np.empty(blocks.shape[:-1] + (blocks.shape[-1] * 2,), np.float32)
    out[..., 0::2] = lo
    out[..., 1::2] = hi
    out *= np.exp2(exp.astype(np.float32))[..., None]
    *prefix, rows, g, b2 = out.shape
    out = out.reshape(*prefix, rows, g * b2)
    return np.swapaxes(out, -1, -2)


def params_from_hf_state_dict(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """Map HF Qwen3(/Qwen3-MoE) parameter names to the stacked pytree.

    HF stores linear weights [out, in]; we store [in, out] (x @ W).
    """
    if cfg.is_mla:
        return _params_from_deepseek_v2(cfg, sd)
    if cfg.state_kind == "delta" and cfg.norm_placement == "after":
        return _params_from_olmo_hybrid(cfg, sd)
    if cfg.state_kind == "delta":
        return _params_from_qwen3_next(cfg, sd)
    if cfg.single_sublayer:
        return _params_from_nemotron_h(cfg, sd)
    if cfg.has_state_layers:
        return _params_from_granite_hybrid(cfg, sd)
    if cfg.moe_router_mode == "sigmoid_topk":
        return _params_from_afmoe(cfg, sd)
    dt = cfg.jnp_dtype

    def get_np(name: str, transpose: bool = False) -> np.ndarray:
        key = name if name in sd else f"model.{name}"
        a = _to_np(sd[key])
        return a.T if transpose else a

    def get(name: str, transpose: bool = False) -> jnp.ndarray:
        return jnp.asarray(get_np(name, transpose), dtype=dt)

    def stack(fmt: str, transpose: bool = False) -> jnp.ndarray:
        # Stack on host, transfer once per parameter (not once per layer).
        return jnp.asarray(
            np.stack([get_np(fmt.format(i=i), transpose) for i in range(cfg.num_layers)]),
            dtype=dt,
        )

    layers: Params = {
        "input_norm": stack("layers.{i}.input_layernorm.weight"),
        "q_proj": stack("layers.{i}.self_attn.q_proj.weight", transpose=True),
        "k_proj": stack("layers.{i}.self_attn.k_proj.weight", transpose=True),
        "v_proj": stack("layers.{i}.self_attn.v_proj.weight", transpose=True),
        "o_proj": stack("layers.{i}.self_attn.o_proj.weight", transpose=True),
        "post_norm": stack("layers.{i}.post_attention_layernorm.weight"),
    }
    if cfg.norm_placement == "both":  # Gemma-2's extra MLP norms
        layers["pre_ffn_norm"] = stack("layers.{i}.pre_feedforward_layernorm.weight")
        layers["post_ffn_norm"] = stack("layers.{i}.post_feedforward_layernorm.weight")
    if cfg.qk_norm:  # Qwen3
        layers["q_norm"] = stack("layers.{i}.self_attn.q_norm.weight")
        layers["k_norm"] = stack("layers.{i}.self_attn.k_norm.weight")
    if cfg.attn_bias:  # Qwen2, GPT-OSS
        layers["q_bias"] = stack("layers.{i}.self_attn.q_proj.bias")
        layers["k_bias"] = stack("layers.{i}.self_attn.k_proj.bias")
        layers["v_bias"] = stack("layers.{i}.self_attn.v_proj.bias")
    if cfg.o_bias:  # GPT-OSS
        layers["o_bias"] = stack("layers.{i}.self_attn.o_proj.bias")
    if cfg.attn_sinks:  # GPT-OSS per-head sink logits
        layers["sinks"] = stack("layers.{i}.self_attn.sinks")
    gptoss_bf16 = any(k.endswith("layers.0.mlp.experts.gate_up_proj") for k in sd)
    gptoss_mxfp4 = any(
        k.endswith("layers.0.mlp.experts.gate_up_proj_blocks") for k in sd
    )
    if cfg.is_moe and (gptoss_bf16 or gptoss_mxfp4):
        # GPT-OSS: experts are stacked tensors (not per-expert modules) —
        # gate_up_proj [E, H, 2D] interleaves gate/up on the last axis
        # (gate = [..., ::2], up = [..., 1::2]); already [in, out] oriented.
        # The official checkpoints store expert weights MXFP4-packed as
        # *_blocks/*_scales pairs — dequantized here (dequant_mxfp4).
        layers["router"] = stack("layers.{i}.mlp.router.weight", transpose=True)
        if cfg.router_bias:
            layers["router_bias"] = stack("layers.{i}.mlp.router.bias")

        def expert_tensor(i: int, name: str) -> np.ndarray:
            if gptoss_mxfp4:
                return dequant_mxfp4(
                    get_np(f"layers.{i}.mlp.experts.{name}_blocks"),
                    get_np(f"layers.{i}.mlp.experts.{name}_scales"),
                )
            return get_np(f"layers.{i}.mlp.experts.{name}")

        # per-layer dequant -> de-interleave -> cast BEFORE stacking: the
        # float32 intermediate exists for one layer at a time (a whole-model
        # f32 stack of gpt-oss-120b experts would be ~300 GB of host RAM)
        gates, ups, downs = [], [], []
        for i in range(cfg.num_layers):
            gu = expert_tensor(i, "gate_up_proj")  # [E, H, 2D] f32
            gates.append(jnp.asarray(gu[..., ::2], dtype=dt))
            ups.append(jnp.asarray(gu[..., 1::2], dtype=dt))
            del gu
            downs.append(jnp.asarray(expert_tensor(i, "down_proj"), dtype=dt))
        layers["gate_proj"] = jnp.stack(gates)
        layers["up_proj"] = jnp.stack(ups)
        layers["down_proj"] = jnp.stack(downs)
        if cfg.moe_bias:
            gub = np.stack(
                [get_np(f"layers.{i}.mlp.experts.gate_up_proj_bias") for i in range(cfg.num_layers)]
            )  # [L, E, 2D]
            layers["gate_bias"] = jnp.asarray(gub[..., ::2], dtype=dt)
            layers["up_bias"] = jnp.asarray(gub[..., 1::2], dtype=dt)
            layers["down_bias"] = stack("layers.{i}.mlp.experts.down_proj_bias")
    elif cfg.is_moe:
        # two HF naming schemes, detected from the state dict:
        #   Qwen3-MoE: mlp.gate + mlp.experts.{e}.{gate,up,down}_proj
        #   Mixtral:   block_sparse_moe.gate + ...experts.{e}.{w1,w3,w2}
        #              (w1=gate, w3=up, w2=down; routing math is identical —
        #              softmax-all, top-k, renormalize)
        mixtral = any(
            k.endswith("layers.0.block_sparse_moe.gate.weight") for k in sd
        )
        moe_prefix = "block_sparse_moe" if mixtral else "mlp"
        proj_names = (
            {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}
            if mixtral
            else {"gate_proj": "gate_proj", "up_proj": "up_proj", "down_proj": "down_proj"}
        )
        layers["router"] = stack(
            "layers.{i}." + moe_prefix + ".gate.weight", transpose=True
        )

        def stack_experts(proj: str) -> jnp.ndarray:
            per_layer = [
                np.stack(
                    [
                        get_np(
                            f"layers.{i}.{moe_prefix}.experts.{e}.{proj}.weight",
                            transpose=True,
                        )
                        for e in range(cfg.num_experts)
                    ]
                )
                for i in range(cfg.num_layers)
            ]
            return jnp.asarray(np.stack(per_layer), dtype=dt)

        layers["gate_proj"] = stack_experts(proj_names["gate_proj"])
        layers["up_proj"] = stack_experts(proj_names["up_proj"])
        layers["down_proj"] = stack_experts(proj_names["down_proj"])
    else:
        layers["gate_proj"] = stack("layers.{i}.mlp.gate_proj.weight", transpose=True)
        layers["up_proj"] = stack("layers.{i}.mlp.up_proj.weight", transpose=True)
        layers["down_proj"] = stack("layers.{i}.mlp.down_proj.weight", transpose=True)

    params: Params = {
        "embed": get("embed_tokens.weight"),
        "layers": layers,
        "final_norm": get("norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight", transpose=True)
    return params


# a latent model's leaves that stay float32 whatever the model's dtype
FLOAT32_LEAVES = ("router_select_bias", "hc_attn_bias", "hc_attn_scale", "hc_ffn_bias",
                  "hc_ffn_scale")


def _params_from_deepseek_v2(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """HF `deepseek_v2` / `deepseek_v3`-style names -> the grouped tree
    (`dense_layers` for the leading dense layers, `layers` for the sparse
    ones). The checkpoint stores each rope dimension pair interleaved (x0 y0
    x1 y1 ...); the program's `apply_rope` turns the half-split layout (x0 x1
    ... y0 y1 ...), so the rope columns of q_proj (with cfg.q_lora_rank: of
    q_b_proj, beside q_a_proj and q_a_layernorm) and of kv_a_proj_with_mqa are
    permuted here, once. A sigmoid router brings `mlp.gate.e_score_correction_bias`
    (float32). A residual stream (cfg.hc_mult) brings a sublayer's maps as
    `layers.{i}.hc_attn.*` / `hc_ffn.*`: `weight` [m (2 + m), m H] (rows pre |
    post | res), `bias`, `scale` [3], the last two float32 (the names are this
    loader's: no checkpoint of the family is described). Names the tree has
    no place for (`mtp.*`, the multi-token-prediction module) are not read."""
    dt = cfg.jnp_dtype
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])

    def raw(name: str) -> np.ndarray:  # as stored: a norm's vector, the embedding table
        key = f"{name}.weight"
        return _to_np(sd[key if key in sd else f"model.{key}"])

    def plain(key: str) -> np.ndarray:  # a tensor that is no module's `weight`
        return _to_np(sd[key if key in sd else f"model.{key}"])

    def w(name: str) -> np.ndarray:  # a linear weight as [in, out]
        return raw(name).T

    def layer(i: int) -> Params:
        at, mlp = f"layers.{i}.self_attn", f"layers.{i}.mlp"
        q = w(f"{at}.q_b_proj" if cfg.q_lora_rank else f"{at}.q_proj")
        q = q.reshape(q.shape[0], cfg.num_heads, dn + dr)
        q = np.concatenate([q[..., :dn], q[..., dn:][..., halves]], axis=-1)
        q = q.reshape(q.shape[0], -1)
        kv_a = w(f"{at}.kv_a_proj_with_mqa")
        out = {
            "input_norm": raw(f"layers.{i}.input_layernorm"),
            **({"q_a_proj": w(f"{at}.q_a_proj"), "q_a_norm": raw(f"{at}.q_a_layernorm"),
                "q_b_proj": q} if cfg.q_lora_rank else {"q_proj": q}),
            "kv_a_proj": np.concatenate([kv_a[:, :r], kv_a[:, r:][:, halves]], axis=-1),
            "kv_a_norm": raw(f"{at}.kv_a_layernorm"),
            "kv_b_proj": w(f"{at}.kv_b_proj"),
            "o_proj": w(f"{at}.o_proj"),
            "post_norm": raw(f"layers.{i}.post_attention_layernorm"),
        }
        for sub in ("attn", "ffn") if cfg.hc_mult else ():
            hc = f"layers.{i}.hc_{sub}"
            out[f"hc_{sub}_proj"] = raw(hc).reshape(cfg.hc_maps, cfg.hc_mult, cfg.hidden_size)
            out[f"hc_{sub}_bias"] = plain(f"{hc}.bias").astype(np.float32)
            out[f"hc_{sub}_scale"] = plain(f"{hc}.scale").astype(np.float32)
        if i < cfg.num_dense_layers:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                out[proj] = w(f"{mlp}.{proj}")
            return out
        out["router"] = w(f"{mlp}.gate")
        if cfg.moe_router_mode == "sigmoid_topk":
            out["router_select_bias"] = plain(
                f"{mlp}.gate.e_score_correction_bias").astype(np.float32)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[proj] = np.stack(
                [w(f"{mlp}.experts.{e}.{proj}") for e in range(cfg.num_experts)])
            out[f"shared_{proj}"] = w(f"{mlp}.shared_experts.{proj}")
        return out

    def group(ids) -> Params:
        per_layer = [layer(i) for i in ids]
        return {k: jnp.asarray(np.stack([lp[k] for lp in per_layer]),
                               dtype=jnp.float32 if k in FLOAT32_LEAVES else dt)
                for k in per_layer[0]}

    nd = cfg.num_dense_layers
    params: Params = {
        "embed": jnp.asarray(raw("embed_tokens"), dtype=dt),
        "layers": group(range(nd, cfg.num_layers)),
        "final_norm": jnp.asarray(raw("norm"), dtype=dt),
        "lm_head": jnp.asarray(w("lm_head"), dtype=dt),
    }
    if nd:
        params["dense_layers"] = group(range(nd))
    return params


def _params_from_afmoe(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """HF `afmoe` names -> the grouped tree (`dense_layers`, then `layers`).
    The attention gate is published as `self_attn.gate_proj` (ours:
    `attn_gate_proj`; `gate_proj` is the MLP's), the router as
    `mlp.router.gate`, its selection bias as the float32 buffer
    `mlp.expert_bias`. Rope is the half-split one `apply_rope` turns: nothing
    is permuted. A preset that holds a share reads the experts
    cfg.expert_offset .. + cfg.num_experts of each layer, the router whole,
    and the first cfg.vocab_size rows of the vocabulary."""
    dt = cfg.jnp_dtype
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)

    def raw(name: str) -> np.ndarray:
        return _to_np(sd[name if name in sd else f"model.{name}"])

    def w(name: str) -> np.ndarray:  # a linear weight as [in, out]
        return raw(f"{name}.weight").T

    def layer(i: int) -> Params:
        at, mlp = f"layers.{i}.self_attn", f"layers.{i}.mlp"
        out = {
            "input_norm": raw(f"layers.{i}.input_layernorm.weight"),
            "post_norm": raw(f"layers.{i}.post_attention_layernorm.weight"),
            "pre_ffn_norm": raw(f"layers.{i}.pre_mlp_layernorm.weight"),
            "post_ffn_norm": raw(f"layers.{i}.post_mlp_layernorm.weight"),
            "q_norm": raw(f"{at}.q_norm.weight"), "k_norm": raw(f"{at}.k_norm.weight"),
            "attn_gate_proj": w(f"{at}.gate_proj"),
            **{proj: w(f"{at}.{proj}") for proj in ("q_proj", "k_proj", "v_proj", "o_proj")},
        }
        if i < cfg.num_dense_layers:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                out[proj] = w(f"{mlp}.{proj}")
            return out
        out["router"] = w(f"{mlp}.router.gate")
        out["router_select_bias"] = raw(f"{mlp}.expert_bias").astype(np.float32)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[proj] = np.stack([w(f"{mlp}.experts.{e}.{proj}") for e in held])
            out[f"shared_{proj}"] = w(f"{mlp}.shared_experts.{proj}")
        return out

    def group(ids) -> Params:
        per_layer = [layer(i) for i in ids]
        return {k: jnp.asarray(np.stack([lp[k] for lp in per_layer]),
                               dtype=jnp.float32 if k == "router_select_bias" else dt)
                for k in per_layer[0]}

    nd, v = cfg.num_dense_layers, cfg.vocab_size
    params: Params = {
        "embed": jnp.asarray(raw("embed_tokens.weight")[:v], dtype=dt),
        "layers": group(range(nd, cfg.num_layers)),
        "final_norm": jnp.asarray(raw("norm.weight"), dtype=dt),
        "lm_head": jnp.asarray(w("lm_head")[:, :v], dtype=dt),
    }
    if nd:
        params["dense_layers"] = group(range(nd))
    return params


def _mamba2_leaves(raw, m: str) -> Params:
    """A published Mamba-2 mixer `m` (`raw(name)` reads a tensor) as the
    leaves `mamba_mixer` reads: linear weights [in, out], `conv1d.weight`
    [C, 1, K] as the taps [K, C]."""
    return dict(
        in_proj=raw(f"{m}.in_proj.weight").T, out_proj=raw(f"{m}.out_proj.weight").T,
        conv_w=raw(f"{m}.conv1d.weight")[:, 0, :].T, conv_b=raw(f"{m}.conv1d.bias"),
        dt_bias=raw(f"{m}.dt_bias"), A_log=raw(f"{m}.A_log"), D=raw(f"{m}.D"),
        gate_norm=raw(f"{m}.norm.weight"),
    )


def _params_from_granite_hybrid(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """HF `granitemoehybrid` names (no experts) -> `layers` for the attention
    kind, `state_layers` for the Mamba kind, each in layer order. The
    published `shared_mlp.input_linear` [2 I, H] is gate over up and is
    split here; `mamba.conv1d.weight` [C, 1, K] becomes the taps [K, C]."""
    dt = cfg.jnp_dtype

    def raw(name: str) -> np.ndarray:
        return _to_np(sd[name if name in sd else f"model.{name}"])

    def w(name: str) -> np.ndarray:  # a linear weight as [in, out]
        return raw(f"{name}.weight").T

    def layer(i: int, kind: str) -> Params:
        gate_up = w(f"layers.{i}.shared_mlp.input_linear")
        out = {
            "input_norm": raw(f"layers.{i}.input_layernorm.weight"),
            "post_norm": raw(f"layers.{i}.post_attention_layernorm.weight"),
            "gate_proj": gate_up[:, : cfg.intermediate_size],
            "up_proj": gate_up[:, cfg.intermediate_size:],
            "down_proj": w(f"layers.{i}.shared_mlp.output_linear"),
        }
        if kind == "attention":
            for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
                out[proj] = w(f"layers.{i}.self_attn.{proj}")
            return out
        out.update(_mamba2_leaves(raw, f"layers.{i}.mamba"))
        return out

    def group(kind: str) -> Params:
        per_layer = [layer(i, kind) for i, k in enumerate(cfg.layer_type_names) if k == kind]
        return {k: jnp.asarray(np.stack([lp[k] for lp in per_layer]), dtype=dt)
                for k in per_layer[0]}

    params: Params = {
        "embed": jnp.asarray(raw("embed_tokens.weight"), dtype=dt),
        "layers": group("attention"),
        "state_layers": group("mamba"),
        "final_norm": jnp.asarray(raw("norm.weight"), dtype=dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(w("lm_head"), dtype=dt)
    return params


def _params_from_nemotron_h(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """HF `nemotron_h` names -> a stack a KIND of sublayer (`layers` the
    attention kind, `state_layers` the Mamba kind, `ffn_layers` the experts),
    each in layer order. Every published layer is `backbone.layers.N` with ONE
    `norm` and ONE `mixer`, whatever the mixer is: Mamba-2 (`in_proj`,
    `conv1d` [C, 1, K] -> taps [K, C], `dt_bias`, `A_log`, `D`, `norm`,
    `out_proj`), attention (`q_proj` .. `o_proj`) or experts (`gate` with
    `e_score_correction_bias`, `experts.E.{up,down}_proj`,
    `shared_experts.{up,down}_proj`, `fc1_latent_proj` into and
    `fc2_latent_proj` out of the latent). The norm becomes `input_norm` of a
    mixer and `post_norm` of the experts, the names a layer of two sublayers
    has for them. A preset that holds a share reads the experts
    cfg.expert_offset .. + cfg.num_experts, the router whole, and the first
    cfg.vocab_size rows of the vocabulary. No published checkpoint was at
    hand: the names are as the published modelling code has them, to the
    builder's knowledge (PR 57; benchmark/configs/
    nemotron-3-super-120b-ep4-1chip.json lists them under `assumed`)."""
    dt = cfg.jnp_dtype
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)

    def raw(name: str) -> np.ndarray:
        return _to_np(sd[name if name in sd else f"backbone.{name}"])

    def w(name: str) -> np.ndarray:  # a linear weight as [in, out]
        return raw(f"{name}.weight").T

    def layer(i: int, kind: str) -> Params:
        m, norm = f"layers.{i}.mixer", raw(f"layers.{i}.norm.weight")
        if kind == "attention":
            return {"input_norm": norm,
                    **{proj: w(f"{m}.{proj}") for proj in ("q_proj", "k_proj", "v_proj", "o_proj")}}
        if kind == "mamba":
            return {"input_norm": norm, **_mamba2_leaves(raw, m)}
        return dict(
            post_norm=norm, router=w(f"{m}.gate"),
            router_select_bias=raw(f"{m}.gate.e_score_correction_bias").astype(np.float32),
            up_proj=np.stack([w(f"{m}.experts.{e}.up_proj") for e in held]),
            down_proj=np.stack([w(f"{m}.experts.{e}.down_proj") for e in held]),
            shared_up_proj=w(f"{m}.shared_experts.up_proj"),
            shared_down_proj=w(f"{m}.shared_experts.down_proj"),
            latent_in_proj=w(f"{m}.fc1_latent_proj"), latent_out_proj=w(f"{m}.fc2_latent_proj"),
        )

    def group(kind: str) -> Params:
        per_layer = [layer(i, kind) for i, k in enumerate(cfg.layer_type_names) if k == kind]
        return {k: jnp.asarray(np.stack([lp[k] for lp in per_layer]),
                               dtype=jnp.float32 if k == "router_select_bias" else dt)
                for k in per_layer[0]}

    v = cfg.vocab_size
    return {
        "embed": jnp.asarray(raw("embeddings.weight")[:v], dtype=dt),
        "layers": group("attention"),
        "state_layers": group("mamba"),
        "ffn_layers": group("moe"),
        "final_norm": jnp.asarray(raw("norm_f.weight"), dtype=dt),
        "lm_head": jnp.asarray(_to_np(sd["lm_head.weight"]).T[:, :v], dtype=dt),
    }


def _params_from_qwen3_next(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """HF `qwen3_next` names -> `layers` for the full-attention kind,
    `state_layers` for the Gated-DeltaNet kind, each in layer order, every
    layer with its router, experts, shared expert and that one's gate.

    What the checkpoint fuses is taken apart here. `linear_attn.in_proj_qkvz`
    [2 Kd + 2 Vd, H] lies key head after key head, each as [q | k | v | z] of
    its own value heads: it becomes `in_proj` [H, q | k | v | z], every part
    head after head (value head h reads key head h // (Hv / Hk) in both).
    `linear_attn.in_proj_ba` likewise becomes `ba_proj` [H, b | a].
    `self_attn.q_proj` [2 Nq D, H] lies head after head as [query | gate]: it
    becomes `q_proj` and `attn_gate_proj`. `linear_attn.conv1d.weight`
    [C, 1, K] becomes the taps [K, C]; `mlp.shared_expert_gate.weight` [1, H]
    the vector `shared_expert_gate`. A preset that holds a share reads the
    experts cfg.expert_offset .. + cfg.num_experts of each layer, the router
    whole, and the first cfg.vocab_size rows of the vocabulary. The
    multi-token-prediction module (`mtp.*`) is not read."""
    dt = cfg.jnp_dtype
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)
    hk, hv, dk, dv = (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
    r = hv // hk

    def raw(name: str) -> np.ndarray:
        return _to_np(sd[name if name in sd else f"model.{name}"])

    def w(name: str) -> np.ndarray:  # a linear weight as [in, out]
        return raw(f"{name}.weight").T

    def by_group(fused: np.ndarray, widths) -> list:
        """[H, Hk x sum(widths)], key head after key head -> one [H, Hk x width] a part."""
        per = fused.reshape(fused.shape[0], hk, sum(widths))
        cuts = np.cumsum([0, *widths])
        return [per[:, :, lo:hi].reshape(fused.shape[0], -1) for lo, hi in zip(cuts, cuts[1:])]

    def layer(i: int, kind: str) -> Params:
        mlp = f"layers.{i}.mlp"
        out = {
            "input_norm": raw(f"layers.{i}.input_layernorm.weight"),
            "post_norm": raw(f"layers.{i}.post_attention_layernorm.weight"),
            "router": w(f"{mlp}.gate"),
            "shared_expert_gate": raw(f"{mlp}.shared_expert_gate.weight")[0],
        }
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[proj] = np.stack([w(f"{mlp}.experts.{e}.{proj}") for e in held])
            out[f"shared_{proj}"] = w(f"{mlp}.shared_expert.{proj}")
        if kind == "attention":
            at = f"layers.{i}.self_attn"
            q_gate = w(f"{at}.q_proj").reshape(cfg.hidden_size, cfg.num_heads, 2, cfg.head_dim)
            out.update(
                q_proj=q_gate[:, :, 0].reshape(cfg.hidden_size, -1),
                attn_gate_proj=q_gate[:, :, 1].reshape(cfg.hidden_size, -1),
                q_norm=raw(f"{at}.q_norm.weight"), k_norm=raw(f"{at}.k_norm.weight"),
                **{proj: w(f"{at}.{proj}") for proj in ("k_proj", "v_proj", "o_proj")},
            )
            return out
        m = f"layers.{i}.linear_attn"
        out.update(
            in_proj=np.concatenate(
                by_group(w(f"{m}.in_proj_qkvz"), (dk, dk, r * dv, r * dv)), axis=1),
            ba_proj=np.concatenate(by_group(w(f"{m}.in_proj_ba"), (r, r)), axis=1),
            conv_w=raw(f"{m}.conv1d.weight")[:, 0, :].T,
            dt_bias=raw(f"{m}.dt_bias"), A_log=raw(f"{m}.A_log"),
            gate_norm=raw(f"{m}.norm.weight"), out_proj=w(f"{m}.out_proj"),
        )
        return out

    def group(kind: str) -> Params:
        per_layer = [layer(i, kind) for i, k in enumerate(cfg.layer_type_names) if k == kind]
        return {k: jnp.asarray(np.stack([lp[k] for lp in per_layer]), dtype=dt)
                for k in per_layer[0]}

    v = cfg.vocab_size
    return {
        "embed": jnp.asarray(raw("embed_tokens.weight")[:v], dtype=dt),
        "layers": group("attention"),
        "state_layers": group("delta"),
        "final_norm": jnp.asarray(raw("norm.weight"), dtype=dt),
        "lm_head": jnp.asarray(w("lm_head")[:, :v], dtype=dt),
    }


def _params_from_olmo_hybrid(cfg: ModelConfig, sd: Mapping[str, Any]) -> Params:
    """HF `olmo_hybrid` names -> `layers` for the full-attention kind,
    `state_layers` for the Gated-DeltaNet kind, each in layer order, every
    layer with its dense MLP and its two OUTPUT norms (the Olmo 2 / Olmo 3
    names: `post_attention_layernorm` on the mixer's output, here `post_norm`,
    and `post_feedforward_layernorm`, here `post_ffn_norm`; no input norm).

    A `linear_attn` layer keeps its projections apart (the GatedDeltaNet
    layer's q_proj, k_proj, v_proj, g_proj, b_proj, a_proj, o_proj): the
    program's `in_proj` is [q | k | v | g] side by side and `ba_proj` [b | a],
    the same functions of the input. `conv1d.weight` [C, 1, K] over the
    channels q | k | v becomes the taps [K, C]; `o_norm.weight` the gated
    norm. A full layer's `q_norm` / `k_norm` are vectors over the whole
    projection (cfg.qk_norm_flat)."""
    dt = cfg.jnp_dtype

    def raw(name: str) -> np.ndarray:
        return _to_np(sd[name if name in sd else f"model.{name}"])

    def w(name: str) -> np.ndarray:  # a linear weight as [in, out]
        return raw(f"{name}.weight").T

    def layer(i: int, kind: str) -> Params:
        out = {
            "post_norm": raw(f"layers.{i}.post_attention_layernorm.weight"),
            "post_ffn_norm": raw(f"layers.{i}.post_feedforward_layernorm.weight"),
            **{proj: w(f"layers.{i}.mlp.{proj}") for proj in ("gate_proj", "up_proj", "down_proj")},
        }
        if kind == "attention":
            at = f"layers.{i}.self_attn"
            out.update(
                q_norm=raw(f"{at}.q_norm.weight"), k_norm=raw(f"{at}.k_norm.weight"),
                **{proj: w(f"{at}.{proj}") for proj in ("q_proj", "k_proj", "v_proj", "o_proj")},
            )
            return out
        m = f"layers.{i}.linear_attn"
        out.update(
            in_proj=np.concatenate(
                [w(f"{m}.{proj}") for proj in ("q_proj", "k_proj", "v_proj", "g_proj")], axis=1),
            ba_proj=np.concatenate([w(f"{m}.b_proj"), w(f"{m}.a_proj")], axis=1),
            conv_w=raw(f"{m}.conv1d.weight")[:, 0, :].T,
            dt_bias=raw(f"{m}.dt_bias"), A_log=raw(f"{m}.A_log"),
            gate_norm=raw(f"{m}.o_norm.weight"), out_proj=w(f"{m}.o_proj"),
        )
        return out

    def group(kind: str) -> Params:
        per_layer = [layer(i, kind) for i, k in enumerate(cfg.layer_type_names) if k == kind]
        return {k: jnp.asarray(np.stack([lp[k] for lp in per_layer]), dtype=dt)
                for k in per_layer[0]}

    return {
        "embed": jnp.asarray(raw("embed_tokens.weight"), dtype=dt),
        "layers": group("attention"),
        "state_layers": group("delta"),
        "final_norm": jnp.asarray(raw("norm.weight"), dtype=dt),
        "lm_head": jnp.asarray(w("lm_head"), dtype=dt),
    }


# ---------------------------------------------------------------------------
# safetensors loading (local dir or HF cache)
# ---------------------------------------------------------------------------


def _find_checkpoint_dir(model: str) -> Optional[str]:
    """Resolve a local dir containing *.safetensors for `model`.

    `model` may be a path, a preset name (mapped via HF_REPOS), or an HF
    repo id; the HF cache is searched without network access.
    """
    if os.path.isdir(model):
        return model
    repo = HF_REPOS.get(model.lower(), model)
    cache = os.environ.get("HF_HOME", os.path.expanduser("~/.cache/huggingface"))
    base = os.path.join(cache, "hub", "models--" + repo.replace("/", "--"))
    hub = os.path.join(base, "snapshots")
    if not os.path.isdir(hub):
        return None
    # Resolve refs/main (the snapshot huggingface_hub considers current);
    # fall back to newest-mtime snapshot containing safetensors.
    candidates = []
    ref = os.path.join(base, "refs", "main")
    if os.path.isfile(ref):
        with open(ref) as f:
            candidates.append(os.path.join(hub, f.read().strip()))
    candidates += sorted(
        (os.path.join(hub, s) for s in os.listdir(hub)),
        key=os.path.getmtime,
        reverse=True,
    )
    for d in candidates:
        if os.path.isdir(d) and any(f.endswith(".safetensors") for f in os.listdir(d)):
            return d
    return None


def load_params(cfg: ModelConfig, model_path: Optional[str] = None) -> Params:
    """Load real weights from safetensors (local path or HF cache).

    Raises FileNotFoundError when no checkpoint is available locally —
    callers fall back to `init_params` (random weights) for benchmarking
    in zero-egress environments.
    """
    from safetensors import safe_open

    d = _find_checkpoint_dir(model_path or cfg.name)
    if d is None:
        raise FileNotFoundError(
            f"no local safetensors checkpoint for {model_path or cfg.name!r}"
        )
    sd: Dict[str, Any] = {}
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".safetensors"):
            continue
        with safe_open(os.path.join(d, fname), framework="np") as f:
            for k in f.keys():
                try:
                    sd[k] = f.get_tensor(k)
                except (TypeError, ValueError):
                    # numpy can't represent bf16; fall back to torch tensors.
                    from safetensors.torch import load_file

                    sd.update(load_file(os.path.join(d, fname)))
                    break
    return params_from_hf_state_dict(cfg, sd)
