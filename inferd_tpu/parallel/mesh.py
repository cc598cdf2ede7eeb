"""Device-mesh planning and parameter partition specs.

This is the TPU-native scaling substrate the reference never had (its only
parallelism is inter-node pipeline stages over HTTP/gRPC — SURVEY §2.1).
Here the five classic axes are first-class over one `jax.sharding.Mesh`:

  dp — data: batch sharded, params replicated, grads psum'd.
  pp — pipeline: decoder layer stack sliced per rank, activations hop via
       `lax.ppermute` over ICI (the TPU-native form of the reference's
       node→node HTTP relay, /root/reference/petals/node.py:102-117).
  sp — sequence/context: activations sharded on the sequence axis; attention
       runs as ring attention (ppermute of KV blocks — inferd_tpu.parallel.ring).
  tp — tensor: attention heads and MLP hidden sharded; partial results
       psum'd over the axis.
  ep — expert: MoE expert weights sharded over ('ep','tp') combined, expert
       outputs psum-combined (inferd_tpu.parallel.tp.moe_mlp_sharded).

Axis sizes multiply to the device count; `MeshPlan.auto` factors a device
count into a sensible default plan. All collectives ride ICI when the mesh
is a real TPU slice; the same code runs on a virtual CPU mesh for tests
(tests/conftest.py) and the driver's multi-chip dry-run.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from inferd_tpu.config import ModelConfig

AXES = ("dp", "pp", "sp", "tp", "ep")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Sizes for the five mesh axes. Product must equal the device count."""

    dp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep

    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.dp, self.pp, self.sp, self.tp, self.ep)

    @staticmethod
    def auto(n_devices: int, want_pp: bool = True) -> "MeshPlan":
        """Factor n_devices into a default plan, preferring (in order) pp, tp,
        sp, then dp — pipeline-over-mesh is this framework's north star
        (BASELINE.json:5), tensor parallelism is the cheapest intra-stage win.
        Each axis gets factors of 2 round-robin; any odd remainder lands on dp.
        """
        sizes = {"pp": 1, "tp": 1, "sp": 1, "dp": 1}
        rem = n_devices
        order = ["pp", "tp", "sp", "dp"] if want_pp else ["tp", "sp", "dp"]
        i = 0
        while rem % 2 == 0 and rem > 1:
            ax = order[i % len(order)]
            sizes[ax] *= 2
            rem //= 2
            i += 1
        sizes["dp"] *= rem  # odd factor
        return MeshPlan(dp=sizes["dp"], pp=sizes["pp"], sp=sizes["sp"], tp=sizes["tp"], ep=1)


def make_mesh(plan: MeshPlan, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    n = plan.num_devices
    if len(devices) < n:
        raise ValueError(f"plan needs {n} devices, have {len(devices)}")
    grid = np.asarray(devices[:n]).reshape(plan.axis_sizes())
    return Mesh(grid, AXES)


# ---------------------------------------------------------------------------
# Parameter partition specs
# ---------------------------------------------------------------------------
#
# Weights are stored [in, out] (models/qwen3.py), stacked on a leading layer
# axis. Sharding follows the Megatron pattern: column-parallel first matmul
# (q/k/v, gate/up — shard the OUTPUT dim over tp), row-parallel second
# matmul (o_proj, down_proj — shard the INPUT dim over tp, psum after).
# MoE experts shard their expert axis over ('ep','tp') combined.
# `layer_axis` optionally prepends a pipeline spec entry for the stacked
# layer dim ('pp' inside the pipelined train step, None for single-stage).


def layer_param_specs(cfg: ModelConfig, layer_axis: Optional[str] = None) -> Dict[str, P]:
    L = (layer_axis,)
    specs: Dict[str, P] = {
        "input_norm": P(*L, None),
        "q_proj": P(*L, None, "tp"),
        "k_proj": P(*L, None, "tp"),
        "v_proj": P(*L, None, "tp"),
        "o_proj": P(*L, "tp", None),
        "post_norm": P(*L, None),
    }
    if cfg.norm_placement == "both":
        specs["pre_ffn_norm"] = P(*L, None)
        specs["post_ffn_norm"] = P(*L, None)
    if cfg.qk_norm:
        specs["q_norm"] = P(*L, None)
        specs["k_norm"] = P(*L, None)
    if cfg.attn_bias:
        # biases follow their column-parallel projection's output shard
        specs["q_bias"] = P(*L, "tp")
        specs["k_bias"] = P(*L, "tp")
        specs["v_bias"] = P(*L, "tp")
    if cfg.o_bias:
        # added after the row-parallel psum: replicated
        specs["o_bias"] = P(*L, None)
    if cfg.attn_sinks:
        # per-q-head logits follow the head shard
        specs["sinks"] = P(*L, "tp")
    if cfg.is_moe:
        specs["router"] = P(*L, None, None)
        specs["gate_proj"] = P(*L, ("ep", "tp"), None, None)
        specs["up_proj"] = P(*L, ("ep", "tp"), None, None)
        specs["down_proj"] = P(*L, ("ep", "tp"), None, None)
        if cfg.router_bias:
            specs["router_bias"] = P(*L, None)
        if cfg.moe_bias:
            # expert biases shard with their expert axis
            specs["gate_bias"] = P(*L, ("ep", "tp"), None)
            specs["up_bias"] = P(*L, ("ep", "tp"), None)
            specs["down_bias"] = P(*L, ("ep", "tp"), None)
    else:
        specs["gate_proj"] = P(*L, None, "tp")
        specs["up_proj"] = P(*L, None, "tp")
        specs["down_proj"] = P(*L, "tp", None)
    return specs


def model_param_specs(cfg: ModelConfig, layer_axis: Optional[str] = None) -> Dict[str, Any]:
    """Specs for a full param pytree (embed + layers + head). The embedding
    and head are replicated (vocab sharding is a possible extension; at the
    model sizes in scope the decoder stack dominates)."""
    specs: Dict[str, Any] = {
        "embed": P(None, None),
        "layers": layer_param_specs(cfg, layer_axis),
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, None)
    return specs


def check_divisibility(cfg: ModelConfig, plan: MeshPlan) -> None:
    """Fail fast on shapes the mesh can't shard evenly."""
    t = plan.tp
    if cfg.num_heads % t:
        raise ValueError(f"num_heads {cfg.num_heads} not divisible by tp={t}")
    if cfg.num_kv_heads % t:
        raise ValueError(f"num_kv_heads {cfg.num_kv_heads} not divisible by tp={t}")
    if cfg.is_moe:
        if cfg.num_experts % (plan.ep * t):
            raise ValueError(
                f"num_experts {cfg.num_experts} not divisible by ep*tp={plan.ep * t}"
            )
    else:
        if cfg.intermediate_size % t:
            raise ValueError(
                f"intermediate_size {cfg.intermediate_size} not divisible by tp={t}"
            )
    if plan.pp > 1 and cfg.num_layers % plan.pp:
        raise ValueError(f"num_layers {cfg.num_layers} not divisible by pp={plan.pp}")


def param_specs_for(params, cfg: ModelConfig, layer_axis: Optional[str] = None):
    """Spec tree STRUCTURALLY matching `params` — including quantized leaves,
    which expand to a (q, scale) spec pair. int8 (ops.quant.QuantWeight):
    q takes the weight's spec, the per-output-channel scale takes that spec
    minus its contraction axis (axis -2). int4 (ops.quant.Int4Weight): the
    group-scale tensor [..., G, N] has the SAME rank as the weight with G
    standing in for K, and group boundaries subdivide any even K-shard
    (K/tp is a multiple of the group size for real dims), so the scale
    takes the weight's spec verbatim. This is what lets quantized serving
    compose with pp/tp placement and shard_map in_specs unchanged."""
    from inferd_tpu.ops.quant import Int4Weight, QuantWeight

    specs = model_param_specs(cfg, layer_axis)
    if isinstance(params, dict) and "lm_head_q" in params:
        specs["lm_head_q"] = P(None, None)  # quantized shadow of embed.T

    def expand(a, s):
        if isinstance(a, QuantWeight):
            st = tuple(s)
            s_scale = P(*(st[:-2] + st[-1:])) if len(st) >= 2 else s
            return QuantWeight(q=s, scale=s_scale)
        if isinstance(a, Int4Weight):
            # packed is static aux data: the spec node must carry the
            # weight's flag or treedef comparison rejects the pair
            return Int4Weight(q=s, scale=s, packed=a.packed)
        return s

    return jax.tree.map(
        expand, params, specs,
        is_leaf=lambda x: isinstance(x, (P, QuantWeight, Int4Weight)),
    )


def validate_quant_sharding(params, cfg: ModelConfig, mesh: Mesh,
                            layer_axis: Optional[str] = None) -> None:
    """int4 group scales shard alongside their weight's contraction axis —
    expressible only when the group COUNT divides the axis's mesh extent
    (group boundaries must land on shard boundaries). Real dims satisfy
    this trivially (e.g. G=32 groups over tp<=8); tiny single-group tests
    with a sharded K would produce an inscrutable device_put/shard_map
    shape error, so fail early with the actual constraint."""
    from inferd_tpu.ops.quant import Int4Weight

    specs = param_specs_for(params, cfg, layer_axis)

    def axes_size(entry) -> int:
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            if a is not None:
                n *= mesh.shape.get(a, 1)
        return n

    def check(a, s):
        if isinstance(a, Int4Weight):
            st = tuple(s.q)
            if len(st) >= 2 and st[-2] is not None:
                ext = axes_size(st[-2])
                if a.scale.shape[-2] % ext:
                    raise ValueError(
                        f"int4 weight {a.shape}: {a.scale.shape[-2]} "
                        f"scale groups cannot shard over a {ext}-way "
                        f"contraction axis (group boundaries must land on "
                        f"shard boundaries) — use a smaller quant group or "
                        f"drop tp for this model size"
                    )
                if a.q.shape[-2] % ext:
                    # the STORED axis is nibble-packed (K/2): an odd group
                    # size can satisfy the group check yet leave the packed
                    # extent indivisible — fail here with the constraint
                    # instead of an inscrutable device_put shape error
                    raise ValueError(
                        f"int4 weight {a.shape}: packed contraction extent "
                        f"{a.q.shape[-2]} does not divide over {ext} "
                        f"devices (nibble packing halves the stored axis; "
                        f"use an even quant group size)"
                    )
        return s

    jax.tree.map(
        check, params, specs,
        is_leaf=lambda x: isinstance(x, (P, Int4Weight)),
    )


def shard_params(params, cfg: ModelConfig, mesh: Mesh, layer_axis: Optional[str] = None):
    """Place a param pytree onto the mesh per the spec tree (GSPMD path:
    jit-compiled model code then runs tensor-parallel with XLA inserting the
    collectives — the zero-code-change TP inference story)."""
    validate_quant_sharding(params, cfg, mesh, layer_axis)
    specs = param_specs_for(params, cfg, layer_axis)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def grad_sync_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Per-leaf mesh axes each gradient must be psum'd over after per-rank AD
    in the train step (inferd_tpu.parallel.train), mirroring the param tree.

    With `tp.enter_sharded` boundaries in the forward, gradients are already
    complete over tp/ep for every leaf EXCEPT replicated params consumed
    inside the sharded region after the boundary: q/k norms (applied to
    tp-local heads) and the MoE router (all its paths run through
    (ep,tp)-sharded experts). All leaves still need the data axes (dp, sp)
    — summed then normalized to a mean by the caller — and the top-level
    leaves (embed/final_norm/lm_head), which live outside the pp-sharded
    stack, combine their per-stage contributions over pp.
    """
    data = ("dp", "sp")
    layers: Dict[str, Any] = {
        "input_norm": data,
        "q_proj": data,
        "k_proj": data,
        "v_proj": data,
        "o_proj": data,
        "post_norm": data,
        "gate_proj": data,
        "up_proj": data,
        "down_proj": data,
    }
    if cfg.norm_placement == "both":
        # post-norms consume tp-psummed sublayer outputs (replicated):
        # their grads, like input_norm's, are complete without a tp sync
        layers["pre_ffn_norm"] = data
        layers["post_ffn_norm"] = data
    if cfg.qk_norm:
        layers["q_norm"] = data + ("tp",)
        layers["k_norm"] = data + ("tp",)
    if cfg.attn_bias:
        # tp-sharded leaves (distinct shard per rank): data axes only
        layers["q_bias"] = data
        layers["k_bias"] = data
        layers["v_bias"] = data
    if cfg.o_bias:
        # replicated, consumed AFTER the row-parallel psum: per-rank grads
        # are already complete over tp
        layers["o_bias"] = data
    if cfg.attn_sinks:
        layers["sinks"] = data  # tp-sharded leaf
    if cfg.is_moe:
        layers["router"] = data + ("ep", "tp")
        if cfg.router_bias:
            layers["router_bias"] = data + ("ep", "tp")
        if cfg.moe_bias:
            # expert-sharded leaves: data axes only
            layers["gate_bias"] = data
            layers["up_bias"] = data
            layers["down_bias"] = data
    tree: Dict[str, Any] = {
        "embed": data + ("pp",),
        "layers": layers,
        "final_norm": data + ("pp",),
    }
    if not cfg.tie_word_embeddings:
        tree["lm_head"] = data + ("pp",)
    return tree


