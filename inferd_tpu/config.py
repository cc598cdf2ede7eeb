"""Model hyperparameter configs for the Qwen3 family.

Capability parity with the reference's static constants class
(/root/reference/models/qwen3/qwen3_config.py:1-25) — redesigned as a frozen
dataclass so configs are hashable (usable as jit static args) and so the
framework supports multiple model sizes, not one hardcoded set.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax.numpy as jnp


# the kinds of cfg.layer_types that hold a recurrent state and no keys and values
STATE_KINDS = ("mamba", "delta")
# the kinds that are a feed-forward and NO mixer: a layer of a model whose
# layers are one sublayer each (cfg.single_sublayer)
FFN_KINDS = ("moe",)
# how a `nemotron_h` config spells a layer's kind in its hybrid_override_pattern
PATTERN_LETTERS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a Qwen3-family causal LM."""

    name: str = "qwen3-0.6b"
    vocab_size: int = 151936
    hidden_size: int = 1024
    intermediate_size: int = 3072
    num_layers: int = 28
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    max_position_embeddings: int = 40960
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"

    # KV cache storage dtype: "model" stores cache entries in `dtype`;
    # "float8_e4m3fn" halves long-context decode's dominant HBM read (the
    # KV buffer: 28 layers x T x 8 heads x 128 dims x 2 for Qwen3-0.6B
    # already outweighs the weights past ~8K tokens). Writes SATURATE to
    # the dtype's range (e4m3 has no inf — an unclamped V outlier would
    # poison the cache with NaN); reads upcast on the XLA attention path,
    # where the convert fuses into the score einsum.
    kv_dtype: str = "model"

    # Attention implementation: "auto" (Pallas flash kernel on TPU, XLA
    # elsewhere), "flash", "flash_interpret" (kernel in the Pallas
    # interpreter — CPU-testable), or "xla".
    attn_impl: str = "auto"

    # Family knobs: Qwen3 uses per-head q/k RMSNorm and no attention bias;
    # Qwen2 (the reference's swarm-path model, Qwen2-0.5B —
    # /root/reference/petals/inferd.yaml:1) is the reverse. Llama-3 uses
    # neither knob and (3.1+) frequency-dependent "llama3" RoPE scaling.
    qk_norm: bool = True
    attn_bias: bool = False
    # the Olmo family's q/k norm: ONE RMSNorm over the whole projection
    # (num_heads x head_dim; the keys' over num_kv_heads x head_dim) before it
    # is split into heads, where Qwen3's is one of head_dim applied to each
    # head. Says WHICH norm `qk_norm` is, so it comes with qk_norm
    qk_norm_flat: bool = False

    # RoPE scaling: "none", "llama3" (Llama-3.1+ long-context scheme:
    # low-frequency bands divided by `rope_scaling_factor`, high-frequency
    # bands untouched, smooth ramp between), or "yarn" (NTK-by-parts
    # interpolation with an attention-temperature factor on cos/sin —
    # GPT-OSS) — both matching HF rope_utils exactly.
    rope_scaling: str = "none"
    rope_scaling_factor: float = 8.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # yarn-only: ramp boundaries in rotations, correction-range truncation,
    # and the cos/sin attention factor (0 = derive 0.1*ln(factor)+1)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_truncate: bool = True
    rope_attention_factor: float = 0.0

    # MoE (Qwen3-MoE family); num_experts == 0 means dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True

    # GPT-OSS family knobs (all off elsewhere):
    #   moe_router_mode — "softmax_topk" (Qwen/Mixtral: probs over ALL
    #                     experts, then top-k) or "topk_softmax" (GPT-OSS:
    #                     top-k over LOGITS, softmax over the k values)
    #                     or "sigmoid_topk" (the afmoe family: a sigmoid
    #                     score per expert in float32; the top k of score +
    #                     p["router_select_bias"], a float32 vector that
    #                     SELECTS and never weighs; the weights are the chosen
    #                     experts' own scores, with norm_topk_prob divided by
    #                     their sum + 1e-20, times routed_scaling_factor)
    #   router_bias / moe_bias — biases on the router / expert projections
    #   swiglu_limit  — >0: clamped GLU experts (gate<=limit, |up|<=limit,
    #                   glu = gate*sigmoid(1.702*gate), out = (up+1)*glu)
    #   attn_sinks    — per-head learned sink logit joining the softmax
    #                   denominator (an always-attendable virtual slot)
    #   o_bias        — bias on the attention output projection too
    moe_router_mode: str = "softmax_topk"
    router_bias: bool = False
    moe_bias: bool = False
    swiglu_limit: float = 0.0
    attn_sinks: bool = False
    o_bias: bool = False

    # Gemma-2 family knobs (all off for Qwen/Llama):
    #   norm_placement — where a sublayer's RMSNorm stands: "before" (the
    #                    pre-norm block: the sublayer reads its normed input),
    #                    "both" (Gemma's sandwich: that, and a second norm on
    #                    the sublayer's output before it joins the residual)
    #                    or "after" (the Olmo family: the sublayer reads the
    #                    residual stream as it is, h = x + Norm(Mixer(x)))
    #   rms_norm_plus_one — RMSNorm scales by (1 + w); weights init to zero
    #   hidden_act     — MLP gate activation: "silu" or "gelu_tanh"
    #   scale_embedding — multiply embeddings by sqrt(hidden_size)
    #   attn_logit_softcap / final_logit_softcap — cap*tanh(x/cap), 0 = off
    #   query_pre_attn_scalar — attention scores scale by this**-0.5
    #                    instead of head_dim**-0.5 (0 = use head_dim)
    #   sliding_window — local attention window on the "sliding" layers of
    #                    layer_pattern (with no layer_types: EVEN layer
    #                    indices, odd layers global); 0 = all layers global
    norm_placement: str = "before"
    rms_norm_plus_one: bool = False
    hidden_act: str = "silu"
    scale_embedding: bool = False
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    query_pre_attn_scalar: float = 0.0
    sliding_window: int = 0

    # DeepSeek-V2 family knobs (all absent elsewhere):
    #   kv_lora_rank   — > 0: latent attention (MLA). The cache holds per
    #                    token and layer the normed latent (kv_lora_rank)
    #                    and ONE rope key shared by all heads
    #                    (qk_rope_head_dim), nothing per head; keys and
    #                    values come from the latent through kv_b_proj.
    #                    head_dim is then the query/key head size,
    #                    qk_nope_head_dim + qk_rope_head_dim
    #   v_head_dim     — value head size (o_proj reads num_heads * v_head_dim)
    #   rope_mscale / rope_mscale_all_dim — yarn: cos/sin are multiplied by
    #                    yarn_mscale(factor, mscale) / yarn_mscale(factor,
    #                    mscale_all_dim) and the softmax scale by
    #                    yarn_mscale(factor, mscale_all_dim)**2 (0 = the
    #                    one attention factor on cos/sin above)
    #   n_shared_experts — one always-on SwiGLU of width n_shared_experts *
    #                    moe_intermediate_size added to the routed output
    #   routed_scaling_factor — multiplies the routed experts' weights
    #   first_k_dense_replace — this many leading layers keep the dense MLP
    #                    (params["dense_layers"], a group of its own)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_mscale: float = 0.0
    rope_mscale_all_dim: float = 0.0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0

    # Generation by diffusion over blocks (the SDAR family; all absent
    # elsewhere):
    #   block_length   — > 1: positions are grouped in blocks of this many.
    #                    Attention is bidirectional inside a block and
    #                    causal across blocks, the logits row at position p
    #                    speaks of the token AT p, and a block is generated
    #                    as a whole: `denoising_steps` passes over its
    #                    places, each making block_length / denoising_steps
    #                    of the masked ones known, then one pass over the
    #                    known block that writes its keys and values. 1 is
    #                    the autoregressive decoder
    #   remask         — which masked places a pass makes known:
    #                    "sequential" (the leftmost) or "low_confidence"
    #                    (those whose chosen token is most probable)
    #   mask_token_id  — what a place not yet known holds
    block_length: int = 1
    denoising_steps: int = 1
    remask: str = "sequential"
    mask_token_id: int = 0

    # State-space layers beside attention (the Granite-4.0-H family; all
    # absent elsewhere):
    #   layer_types    — one PERIOD of layer kinds, "mamba", "attention",
    #                    "sliding" or "global": global layer i has kind
    #                    layer_types[i % len]; () = the rule of
    #                    `layer_pattern`. A period with a state layer divides
    #                    num_layers; any other may end anywhere
    #   mamba_*        — a Mamba-2 mixer: `mamba_heads` heads of
    #                    `mamba_head_dim` (= mamba_expand * hidden_size in
    #                    all), a state of `mamba_state` per head and channel,
    #                    B and C shared by the heads of each of
    #                    `mamba_groups` groups, a depthwise causal
    #                    convolution of `mamba_conv` taps, the chunked form
    #                    tiled by `mamba_chunk_size`
    #   state_dtype    — what the recurrent state is held in between steps
    #   embedding_multiplier / residual_multiplier / logits_scaling —
    #                    Granite's scalars: the embedding is multiplied, each
    #                    sublayer's output is multiplied before it joins the
    #                    residual, the logits are DIVIDED (1.0 = absent).
    #                    Its attention_multiplier is `attn_scale`:
    #                    query_pre_attn_scalar 4096 gives the published 1/64
    #   position_embedding — "rope", or "nope": no rotation at all
    #   linear_*       — the other state kind, "delta": a Gated-DeltaNet mixer
    #                    (the Qwen3-Next family). `linear_key_heads` key heads
    #                    and `linear_value_heads` value heads (value head h
    #                    reads key head h // their ratio) of
    #                    `linear_key_head_dim` / `linear_value_head_dim`, a
    #                    float state of key x value a value head, q | k | v
    #                    through a depthwise causal convolution of
    #                    `linear_conv` taps without bias, the chunked form
    #                    tiled by `linear_chunk_size`. A model has ONE state
    #                    kind (`state_kind`)
    #   linear_allow_neg_eigval — the delta rule's beta_t is 2 sigmoid(b_t),
    #                    in (0, 2), so the transition I - beta k k^T has
    #                    eigenvalues in (-1, 1) (the Olmo-Hybrid family);
    #                    False: sigmoid(b_t), eigenvalues in (0, 1)
    layer_types: tuple = ()
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state: int = 0
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    state_dtype: str = "float32"
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    position_embedding: str = "rope"
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv: int = 4
    linear_chunk_size: int = 64
    linear_allow_neg_eigval: bool = False

    # Windowed and full layers by a list, and one chip's share of the experts
    # (the afmoe family; all absent elsewhere):
    #   nope_kinds     — the kinds of layer_pattern whose attention carries
    #                    NO rotation (("global",): rope on the windowed layers
    #                    only); () = what position_embedding says, for all
    #                    layers
    #   attn_gate      — out = W_o (attn * sigmoid(x W_g)), W_g
    #                    (p["attn_gate_proj"]) from the layer's normed input
    #                    to num_heads x head_dim, no bias
    #   router_experts — the router's width where it is more than the
    #                    experts whose weights are here (num_experts then
    #                    counts the HELD ones): an expert-parallel rank's
    #                    share, served on one chip. The layer routes over the
    #                    whole width and computes the part of the result that
    #                    experts expert_offset .. expert_offset + num_experts
    #                    give; what the absent ones would add is left out.
    #                    0 = every routed expert is here
    nope_kinds: tuple = ()
    attn_gate: bool = False
    router_experts: int = 0
    expert_offset: int = 0

    # The Qwen3-Next family's two further knobs (absent elsewhere):
    #   partial_rotary_factor — rope turns the first head_dim x this many
    #                    dimensions of a head (`rope_dim`), the rest pass
    #   shared_expert_gate — the shared expert's output is multiplied by
    #                    sigmoid(x . w), w (p["shared_expert_gate"]) a vector
    #                    of hidden_size
    partial_rotary_factor: float = 1.0
    shared_expert_gate: bool = False

    # The Xing4.0 family (DeepSeek-V3's layer under a changed residual path;
    # all absent elsewhere):
    #   q_lora_rank    — > 0: latent attention's queries are compressed too:
    #                    c_q = RMSNorm(x W_qa) (this wide), q = c_q W_qb; the
    #                    layer holds q_a_proj / q_a_norm / q_b_proj and no
    #                    q_proj. 0 = one q_proj (DeepSeek-V2-Lite)
    #   hc_mult        — n > 0: manifold-constrained hyper-connections
    #                    (arXiv:2512.24880). The residual is a STREAM of n
    #                    hidden states, [n, B, S, H]: the embedding enters as
    #                    n copies, each sublayer reads one mix of them
    #                    (Hpre, 1 x n), writes its output back by another
    #                    (Hpost, 1 x n) while the stream itself is mixed by a
    #                    doubly-stochastic Hres (n x n), and the n rows are
    #                    summed before the final norm. The three maps are
    #                    made per token from the RMS-normed stream, in
    #                    float32 (models/qwen3.stream_read / stream_join).
    #                    0 = one hidden state and the plain add
    #   hc_sinkhorn_iters — rounds of column-then-row normalisation that make
    #                    exp(H~res) doubly stochastic
    #   hc_eps         — added to each of those rounds' denominators
    #   hc_res_clamp   — H~res is clipped to +- this before the exp
    #   seeded_routed_scale — read by the SEEDED draw alone (models/qwen3.
    #                    init_params; a checkpoint's weights are what they
    #                    are): a routed expert's down-projection is drawn at
    #                    this times the other projections' deviation. At 1 a
    #                    near-tie between the 4th and 5th of 64 scores that
    #                    bf16 and float32 break differently swaps a quarter
    #                    of a layer's routed output for an unrelated one (in
    #                    a trained model near-tied experts are near-
    #                    substitutes) and moves the log-probabilities by
    #                    0.34 at a sixth of the positions, so that no limit
    #                    told the sound program from its 8-bit control
    #                    (PERF.md section 4)
    q_lora_rank: int = 0
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    seeded_routed_scale: float = 1.0

    # The Nemotron-H family (all absent elsewhere):
    #   layer_types with "moe" among them — every layer is ONE sublayer,
    #                    x <- x + F(RMSNorm(x)): a mixer ("mamba", "attention")
    #                    and nothing after it, or routed experts ("moe") and no
    #                    mixer before them; num_layers counts sublayers
    #                    (`single_sublayer`). A mixer's stack then holds no
    #                    feed-forward and the experts lie in a stack of their
    #                    own, params["ffn_layers"]
    #   moe_latent_size — > 0: the routed experts work in a latent this wide
    #                    (LatentMoE): one projection down before them and one
    #                    up after their combine, shared by all experts
    #                    (p["latent_in_proj"], p["latent_out_proj"]); the
    #                    router and the shared expert read the full width
    #   ffn_gated      — False: a feed-forward is down(act(up(x))), TWO
    #                    matrices (experts, the shared one): no gate_proj
    #   hidden_act "relu2" — relu(x)^2
    moe_latent_size: int = 0
    ffn_gated: bool = True

    def __post_init__(self):
        if self.layer_types:
            odd = (set(self.layer_types) - set(STATE_KINDS) - set(FFN_KINDS)
                   - {"attention", "sliding", "global"})
            if odd or (self.has_state_layers and self.num_layers % len(self.layer_types)):
                raise ValueError(
                    f"{self.name}: layer_types is one period of 'mamba' / 'delta' / 'attention' "
                    f"/ 'sliding' / 'global' / 'moe' (with a state layer it divides num_layers "
                    f"{self.num_layers}); got {self.layer_types}"
                )
            if self.single_sublayer and not (
                    self.has_state_layers and self.is_moe and self.norm_placement == "before"
                    and not self.hc_mult and self.layer_types[0] not in FFN_KINDS):
                raise ValueError(
                    f"{self.name}: layers that are one sublayer each ('moe' among layer_types) "
                    "are state mixers, global GQA layers and routed experts, a mixer first, "
                    "each behind ONE norm on its input (norm_placement 'before'), the "
                    "residual one hidden state wide"
                )
            if ("sliding" in self.layer_types) != (self.sliding_window > 0):
                raise ValueError(
                    f"{self.name}: 'sliding' layers and a sliding_window come together"
                )
            if self.has_state_layers and (
                len(set(self.layer_types) & set(STATE_KINDS)) > 1 or self.is_mla
                or self.sliding_window or self.is_block_diffusion
                or self.norm_placement == "both" or self.first_k_dense_replace
            ):
                raise ValueError(
                    f"{self.name}: state layers are of ONE kind, beside global GQA layers, "
                    "every layer with the same feed-forward (dense, or routed experts) and "
                    "ONE norm a sublayer (norm_placement 'before' or 'after', not 'both')"
                )
            if self.state_kind == "mamba" and (
                self.mamba_heads * self.mamba_head_dim != self.mamba_expand * self.hidden_size
                or self.mamba_heads % self.mamba_groups or self.mamba_state <= 0
            ):
                raise ValueError(
                    f"{self.name}: a Mamba-2 layer has mamba_heads x mamba_head_dim = "
                    "mamba_expand x hidden_size, its heads in whole groups, and a state"
                )
            if self.state_kind == "delta" and (
                min(self.linear_key_heads, self.linear_key_head_dim,
                    self.linear_value_head_dim) <= 0
                or self.linear_value_heads % self.linear_key_heads
            ):
                raise ValueError(
                    f"{self.name}: a Gated-DeltaNet layer has linear_value_heads a whole "
                    "multiple of linear_key_heads, and both head sizes"
                )
        if self.moe_latent_size and not self.is_moe:
            raise ValueError(f"{self.name}: moe_latent_size is the width the EXPERTS work in")
        if self.hidden_act not in ("silu", "gelu_tanh", "relu2"):
            raise ValueError(f"{self.name}: unknown hidden_act {self.hidden_act!r}")
        if not self.ffn_gated and (self.swiglu_limit or self.moe_bias or self.shared_expert_gate):
            raise ValueError(
                f"{self.name}: an ungated feed-forward (ffn_gated False) is down(act(up(x))) "
                "and nothing else: no clamp, no bias, no gate on the shared expert"
            )
        if self.norm_placement not in ("before", "both", "after"):
            raise ValueError(f"{self.name}: unknown norm_placement {self.norm_placement!r}")
        if self.q_lora_rank and not self.is_mla:
            raise ValueError(f"{self.name}: q_lora_rank compresses latent attention's queries")
        if self.hc_mult and (self.hc_mult < 2 or self.hc_sinkhorn_iters < 1):
            raise ValueError(
                f"{self.name}: a residual stream is hc_mult >= 2 hidden states wide and its "
                "mixing matrix takes at least one Sinkhorn round"
            )
        if self.qk_norm_flat and not self.qk_norm:
            raise ValueError(f"{self.name}: qk_norm_flat says which norm qk_norm is")
        if self.position_embedding not in ("rope", "nope"):
            raise ValueError(f"{self.name}: unknown position_embedding {self.position_embedding!r}")
        if set(self.nope_kinds) - set(self.layer_pattern):
            raise ValueError(
                f"{self.name}: nope_kinds {self.nope_kinds} names no kind of {self.layer_pattern}"
            )
        if self.moe_router_mode not in ("softmax_topk", "topk_softmax", "sigmoid_topk"):
            raise ValueError(f"{self.name}: unknown moe_router_mode {self.moe_router_mode!r}")
        if self.router_experts and not (
                0 <= self.expert_offset <= self.router_experts - self.num_experts):
            raise ValueError(
                f"{self.name}: experts {self.expert_offset} .. {self.expert_offset} + "
                f"{self.num_experts} are no share of a router {self.router_experts} wide"
            )
        if self.block_length > 1:
            if self.block_length % self.denoising_steps:
                raise ValueError(
                    f"{self.name}: denoising_steps {self.denoising_steps} must "
                    f"divide block_length {self.block_length}"
                )
            if self.remask not in ("sequential", "low_confidence"):
                raise ValueError(f"{self.name}: unknown remask {self.remask!r}")
            if self.is_mla or self.sliding_window:
                raise ValueError(
                    f"{self.name}: block generation runs on global GQA layers only"
                )

    @property
    def norm_before(self) -> bool:
        """A sublayer reads its normed input (norm_placement)."""
        return self.norm_placement != "after"

    @property
    def norm_after(self) -> bool:
        """A sublayer's output is normed before it joins the residual."""
        return self.norm_placement != "before"

    @property
    def qk_norm_kind(self) -> str:
        """Which q/k norm the attention layers have: "none", "head" (Qwen3:
        one of head_dim, each head) or "flat" (Olmo: over the whole projection)."""
        return ("flat" if self.qk_norm_flat else "head") if self.qk_norm else "none"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_block_diffusion(self) -> bool:
        return self.block_length > 1

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def has_state_layers(self) -> bool:
        """Some layer holds a recurrent state and not keys and values."""
        return self.state_kind is not None

    @property
    def single_sublayer(self) -> bool:
        """Every layer is ONE sublayer (a mixer, or a feed-forward), not a
        mixer and then a feed-forward: some kind of layer_types is a
        feed-forward alone."""
        return any(k in FFN_KINDS for k in self.layer_types)

    @property
    def sublayer_counts(self) -> dict:
        """How many of the model's layers are of each kind of layer_pattern."""
        return {k: self.layers_of(k) for k in dict.fromkeys(self.layer_pattern)}

    @property
    def hybrid_override_pattern(self) -> str:
        """Every layer's kind as a `nemotron_h` config spells them: M (Mamba-2),
        E (experts), * (attention); "" for a model with another kind."""
        letters = {kind: letter for letter, kind in PATTERN_LETTERS.items()}
        names = self.layer_type_names
        return "".join(letters[k] for k in names) if set(names) <= set(letters) else ""

    @property
    def state_kind(self) -> Optional[str]:
        """The kind of the model's state layers, "mamba" or "delta"; None: it has none."""
        return next((k for k in STATE_KINDS if k in self.layer_types), None)

    @property
    def state_shape(self) -> tuple:
        """What a session's recurrent state is in ONE state layer, after the
        lane axis: Mamba-2 [heads, head_dim, state]; the delta rule
        [value heads, key_dim, value_dim]."""
        if self.state_kind == "delta":
            return (self.linear_value_heads, self.linear_key_head_dim, self.linear_value_head_dim)
        return (self.mamba_heads, self.mamba_head_dim, self.mamba_state)

    @property
    def state_conv_shape(self) -> tuple:
        """The convolution's kept inputs of one state layer, after the lane
        axis: its last taps - 1 inputs of every channel."""
        if self.state_kind == "delta":
            return (self.linear_conv - 1, self.linear_conv_dim)
        return (self.mamba_conv - 1, self.mamba_conv_dim)

    @property
    def layer_pattern(self) -> tuple:
        """The kinds of layer, one period of them: GLOBAL layer i has kind
        layer_pattern[i % len(layer_pattern)]. "sliding" attends within
        `sliding_window`, "global" (or "attention") over everything before
        it, "mamba" or "delta" carries a recurrent state (`layer_types`, where given; a
        model with a `sliding_window` and no list alternates, windowed first).
        The one place that says which layers are which (the scan of
        models/qwen3.forward_layers, the storage of core/cache)."""
        if self.layer_types:
            return self.layer_types
        return ("sliding", "global") if self.sliding_window else ("global",)

    def layers_of(self, kind: str, num_layers: Optional[int] = None) -> int:
        """How many of the first `num_layers` layers (default: all) are `kind`."""
        kinds = self.layer_pattern
        n = self.num_layers if num_layers is None else num_layers
        return sum(kinds[i % len(kinds)] == kind for i in range(n))

    @property
    def layer_type_names(self) -> list:
        """Every layer's kind in layer order, as a published config lists them."""
        kinds = self.layer_pattern
        return [kinds[i % len(kinds)] for i in range(self.num_layers)]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels through the convolution: x, then B and C of every group."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def full_attention_interval(self) -> int:
        """As a `qwen3_next` config names the period: layer i is full
        attention iff (i + 1) % this == 0, every other a state layer."""
        return len(self.layer_pattern)

    @property
    def shared_expert_intermediate_size(self) -> int:
        """The shared expert's width, as a published config names it."""
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def linear_key_dim(self) -> int:
        return self.linear_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels through the delta rule's convolution: q, k, then v."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def hc_maps(self) -> int:
        """Outputs of a sublayer's stream projection: Hpre | Hpost | Hres."""
        return self.hc_mult * (2 + self.hc_mult)

    @property
    def router_width(self) -> int:
        """Outputs of the router: every routed expert, held here or not."""
        return self.router_experts or self.num_experts

    @property
    def num_dense_layers(self) -> int:
        """Leading layers with the dense MLP in a model with experts."""
        return min(self.first_k_dense_replace, self.num_layers) if self.is_moe else 0

    @property
    def rope_dim(self) -> int:
        """Dimensions the rotary embedding turns: the first of a head's."""
        if self.is_mla:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_jnp_dtype(self):
        return jnp.dtype(self.dtype if self.kv_dtype == "model" else self.kv_dtype)

    @property
    def attn_scale(self) -> float:
        base = self.query_pre_attn_scalar or self.head_dim
        scale = float(base) ** -0.5
        if self.rope_scaling == "yarn" and self.rope_mscale_all_dim:
            scale *= yarn_mscale(self.rope_scaling_factor, self.rope_mscale_all_dim) ** 2
        return scale

    def with_layers(self, num_layers: int) -> "ModelConfig":
        return dataclasses.replace(self, num_layers=num_layers)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Generation-time sampling knobs (reference: qwen3_config.py:5-7)."""

    temperature: float = 0.6
    top_k: int = 20
    top_p: float = 0.95
    # min-p filtering (HF MinPLogitsWarper, applied after top-p): drop
    # tokens whose probability is below min_p * max-prob; 0 = off
    min_p: float = 0.0
    max_new_tokens: int = 512


# ---------------------------------------------------------------------------
# Presets. Sizes cross-checked against the HF model cards for the Qwen3
# family; 0.6B matches the reference's constants (qwen3_config.py:10-25).
# ---------------------------------------------------------------------------

QWEN3_0_6B = ModelConfig(
    name="qwen3-0.6b",
    hidden_size=1024,
    intermediate_size=3072,
    num_layers=28,
    num_heads=16,
    num_kv_heads=8,
)

QWEN3_1_7B = ModelConfig(
    name="qwen3-1.7b",
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=28,
    num_heads=16,
    num_kv_heads=8,
    tie_word_embeddings=True,
)

QWEN3_4B = ModelConfig(
    name="qwen3-4b",
    hidden_size=2560,
    intermediate_size=9728,
    num_layers=36,
    num_heads=32,
    num_kv_heads=8,
    tie_word_embeddings=True,
)

QWEN3_8B = ModelConfig(
    name="qwen3-8b",
    hidden_size=4096,
    intermediate_size=12288,
    num_layers=36,
    num_heads=32,
    num_kv_heads=8,
    tie_word_embeddings=False,
)

QWEN3_14B = ModelConfig(
    name="qwen3-14b",
    hidden_size=5120,
    intermediate_size=17408,
    num_layers=40,
    num_heads=40,
    num_kv_heads=8,
    tie_word_embeddings=False,
)

QWEN3_32B = ModelConfig(
    name="qwen3-32b",
    hidden_size=5120,
    intermediate_size=25600,
    num_layers=64,
    num_heads=64,
    num_kv_heads=8,
    tie_word_embeddings=False,
)

# Qwen2 family (the reference swarm path serves Qwen2-0.5B,
# /root/reference/petals/inferd.yaml:1-2; sizes from the HF model cards).
QWEN2_0_5B = ModelConfig(
    name="qwen2-0.5b",
    hidden_size=896,
    intermediate_size=4864,
    num_layers=24,
    num_heads=14,
    num_kv_heads=2,
    head_dim=64,
    max_position_embeddings=32768,
    tie_word_embeddings=True,
    qk_norm=False,
    attn_bias=True,
)

QWEN2_1_5B = ModelConfig(
    name="qwen2-1.5b",
    hidden_size=1536,
    intermediate_size=8960,
    num_layers=28,
    num_heads=12,
    num_kv_heads=2,
    head_dim=128,
    max_position_embeddings=32768,
    tie_word_embeddings=True,
    qk_norm=False,
    attn_bias=True,
)

QWEN2_7B = ModelConfig(
    name="qwen2-7b",
    vocab_size=152064,  # 7B uses the larger vocab (0.5B/1.5B: 151936)
    hidden_size=3584,
    intermediate_size=18944,
    num_layers=28,
    num_heads=28,
    num_kv_heads=4,
    head_dim=128,
    max_position_embeddings=32768,
    tie_word_embeddings=False,
    qk_norm=False,
    attn_bias=True,
)

# Llama family (added TPU-first scope beyond the reference's Qwen2/Qwen3:
# the decoder is fully config-driven, so Llama = knob settings + presets).
# Sizes per the HF model cards.

LLAMA32_1B = ModelConfig(
    name="llama3.2-1b",
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rope_theta=500_000.0,
    max_position_embeddings=131072,
    tie_word_embeddings=True,
    qk_norm=False,
    attn_bias=False,
    rope_scaling="llama3",
    rope_scaling_factor=32.0,
    rope_low_freq_factor=1.0,
    rope_high_freq_factor=4.0,
    rope_original_max_position=8192,
)

LLAMA31_8B = ModelConfig(
    name="llama3.1-8b",
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=500_000.0,
    max_position_embeddings=131072,
    tie_word_embeddings=False,
    qk_norm=False,
    attn_bias=False,
    rope_scaling="llama3",
    rope_scaling_factor=8.0,
    rope_low_freq_factor=1.0,
    rope_high_freq_factor=4.0,
    rope_original_max_position=8192,
)

# Gemma-2 family (Google; sizes per the HF model cards). Architecturally
# the most distinct family in the zoo: sandwich norms, (1+w) RMSNorm,
# GeGLU, scaled embeddings, attention/final logit softcapping, and sliding-
# window attention on alternating layers — all config-driven in the shared
# decoder (models/qwen3.py).

GEMMA2_2B = ModelConfig(
    name="gemma2-2b",
    vocab_size=256000,
    hidden_size=2304,
    intermediate_size=9216,
    num_layers=26,
    num_heads=8,
    num_kv_heads=4,
    head_dim=256,
    rope_theta=10_000.0,
    max_position_embeddings=8192,
    tie_word_embeddings=True,
    qk_norm=False,
    attn_bias=False,
    norm_placement="both",
    rms_norm_plus_one=True,
    hidden_act="gelu_tanh",
    scale_embedding=True,
    attn_logit_softcap=50.0,
    final_logit_softcap=30.0,
    query_pre_attn_scalar=256.0,
    sliding_window=4096,
)

GEMMA2_9B = dataclasses.replace(
    GEMMA2_2B,
    name="gemma2-9b",
    hidden_size=3584,
    intermediate_size=14336,
    num_layers=42,
    num_heads=16,
    num_kv_heads=8,
)

GEMMA2_27B = dataclasses.replace(
    GEMMA2_2B,
    name="gemma2-27b",
    hidden_size=4608,
    intermediate_size=36864,
    num_layers=46,
    num_heads=32,
    num_kv_heads=16,
    head_dim=128,
    query_pre_attn_scalar=144.0,
)

# Mixtral (Mistral's MoE family; sizes per the HF model card). Routing is
# the same softmax-all → top-k → renormalize our moe_mlp implements for
# Qwen3-MoE (norm_topk_prob=True); arch is Llama-like (no q/k-norm, no
# attention bias, untied head).
MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=1_000_000.0,
    max_position_embeddings=32768,
    rms_norm_eps=1e-5,
    tie_word_embeddings=False,
    qk_norm=False,
    attn_bias=False,
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=14336,
    norm_topk_prob=True,
)

# GPT-OSS (OpenAI's open-weights MoE family; sizes per the HF configs).
# Every layer is MoE (top-4 of 32/128 clamped-GLU experts with biases,
# top-k-then-softmax routing), attention has per-head sink logits and
# biases on all four projections, sliding window 128 on even layers, and
# YaRN rope scaling (factor 32 over a 4096 pretraining window).
GPT_OSS_20B = ModelConfig(
    name="gpt-oss-20b",
    vocab_size=201088,
    hidden_size=2880,
    intermediate_size=2880,
    num_layers=24,
    num_heads=64,
    num_kv_heads=8,
    head_dim=64,
    rope_theta=150_000.0,
    max_position_embeddings=131072,
    rms_norm_eps=1e-5,
    tie_word_embeddings=False,
    qk_norm=False,
    attn_bias=True,
    o_bias=True,
    attn_sinks=True,
    sliding_window=128,
    rope_scaling="yarn",
    rope_scaling_factor=32.0,
    rope_original_max_position=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_truncate=False,
    num_experts=32,
    num_experts_per_tok=4,
    moe_intermediate_size=2880,
    moe_router_mode="topk_softmax",
    router_bias=True,
    moe_bias=True,
    swiglu_limit=7.0,
)

GPT_OSS_120B = dataclasses.replace(
    GPT_OSS_20B,
    name="gpt-oss-120b",
    num_layers=36,
    num_experts=128,
)

QWEN3_MOE_30B_A3B = ModelConfig(
    name="qwen3-moe-30b-a3b",
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    tie_word_embeddings=False,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
)

# SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat config.json, `sdar_moe`): the
# Qwen3-MoE decoder layer at the widths above under a block-causal mask,
# generated block by block. Block length, schedule, order and mask token are
# not in the published config (benchmark/configs/sdar-30b-a3b-1chip.json
# lists them as assumed). The -7l preset is the same model cut to its first
# 7 layers: what one v5e chip holds at the published widths.
SDAR_30B_A3B = dataclasses.replace(
    QWEN3_MOE_30B_A3B, name="sdar-30b-a3b", max_position_embeddings=32768,
    block_length=4, denoising_steps=2, mask_token_id=151669,
)

SDAR_30B_A3B_7L = dataclasses.replace(SDAR_30B_A3B.with_layers(7), name="sdar-30b-a3b-7l")

# DeepSeek-V2-Lite (deepseek-ai/DeepSeek-V2-Lite config.json): latent
# attention without query compression (q_lora_rank null: 0 here), one dense layer,
# then 26 layers of 64 routed experts (softmax over all, greedy top-6, not
# renormalised) beside two shared ones, YaRN over the 64 rope dimensions.
# The -8l preset is the same model cut to its first 8 layers (the dense one
# and 7 sparse ones): what one v5e chip holds at the published widths.
DEEPSEEK_V2_LITE = ModelConfig(
    name="deepseek-v2-lite",
    vocab_size=102400,
    hidden_size=2048,
    intermediate_size=10944,
    num_layers=27,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    rope_theta=10_000.0,
    max_position_embeddings=163840,
    tie_word_embeddings=False,
    qk_norm=False,
    rope_scaling="yarn",
    rope_scaling_factor=40.0,
    rope_original_max_position=4096,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    num_experts_per_tok=6,
    moe_intermediate_size=1408,
    norm_topk_prob=False,
    n_shared_experts=2,
    first_k_dense_replace=1,
)

DEEPSEEK_V2_LITE_8L = dataclasses.replace(
    DEEPSEEK_V2_LITE.with_layers(8), name="deepseek-v2-lite-8l"
)

# Xing4.0-29B-A4B (XingChen-AGI/Xing4.0-29B-A4B config.json, `xing4_0`):
# DeepSeek-V3's layer at 3584 wide (latent attention WITH query compression,
# 32 heads of 128 + 64 roped, YaRN 64 x 4096 with mscale = mscale_all_dim = 1;
# two dense layers, then 38 of 64 sigmoid-routed experts, top 4 by score +
# selection bias, renormalised, x 2, beside one shared expert) under
# manifold-constrained hyper-connections: a residual stream of four hidden
# states. Its multi-token-prediction module is left out. The -6l preset is
# the same model cut in depth to one dense and five sparse layers: what one
# v5e chip holds with every expert and the whole vocabulary
# (benchmark/configs/xing4.0-29b-a4b-1chip.json has the arithmetic).
XING4_29B_A4B = ModelConfig(
    name="xing4.0-29b-a4b",
    vocab_size=131072,
    hidden_size=3584,
    intermediate_size=9216,
    num_layers=40,
    num_heads=32,
    num_kv_heads=32,
    head_dim=192,
    rope_theta=10_000.0,
    max_position_embeddings=262144,
    tie_word_embeddings=False,
    qk_norm=False,
    rope_scaling="yarn",
    rope_scaling_factor=64.0,
    rope_original_max_position=4096,
    rope_mscale=1.0,
    rope_mscale_all_dim=1.0,
    kv_lora_rank=512,
    q_lora_rank=768,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    num_experts_per_tok=4,
    moe_intermediate_size=1024,
    moe_router_mode="sigmoid_topk",
    norm_topk_prob=True,
    routed_scaling_factor=2.0,
    n_shared_experts=1,
    first_k_dense_replace=2,
    hc_mult=4,
    hc_sinkhorn_iters=20,
    hc_eps=1e-6,
    hc_res_clamp=30.0,
    seeded_routed_scale=0.125,
)

XING4_29B_A4B_6L = dataclasses.replace(
    XING4_29B_A4B.with_layers(6), name="xing4.0-29b-a4b-6l", first_k_dense_replace=1,
)

# Granite-4.0-H-Micro (ibm-granite/granite-4.0-h-micro config.json,
# `granitemoehybrid` with no experts): 36 Mamba-2 layers and 4 GQA layers
# without any position embedding, one period of ten (attention at 5, 15, 25,
# 35), a SwiGLU MLP in every layer, four scalars. As published: nothing cut.
GRANITE_4_H_MICRO = ModelConfig(
    name="granite-4.0-h-micro",
    vocab_size=100352,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=40,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rms_norm_eps=1e-5,
    rope_theta=10_000.0,
    max_position_embeddings=131072,
    tie_word_embeddings=True,
    qk_norm=False,
    attn_bias=False,
    query_pre_attn_scalar=4096.0,  # attention_multiplier 1/64, not 1/sqrt(64)
    layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    mamba_heads=64,
    mamba_head_dim=64,
    mamba_state=128,
    mamba_groups=1,
    mamba_conv=4,
    mamba_expand=2,
    mamba_chunk_size=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    position_embedding="nope",
)

# NVIDIA-Nemotron-3-Super-120B-A12B (nvidia/...-BF16 config.json, `nemotron_h`):
# 88 layers that are ONE sublayer each, by hybrid_override_pattern: 40 Mamba-2
# mixers (128 heads of 64, state 128, 8 groups), 40 expert layers (LatentMoE: 512
# sigmoid-routed experts of 2688 in a 1024-wide latent, top 22, scaling 5,
# squared ReLU without a gate, beside a full-width shared expert of 5376) and 8
# GQA layers (32 query heads over 2 kv heads of 128) that rotate nothing. Its
# multi-token-prediction module is left out (generation does not run it).
# The -ep4-11l preset is ONE chip's share of a four-chip expert-parallel group
# over the first eleven sublayers, MEMEMEM*EME (the published 40 : 40 : 8):
# experts 0..127 of the router's 512, vocabulary ids 0..32 767 of 131 072, every
# width as published (benchmark/configs/nemotron-3-super-120b-ep4-1chip.json
# has the arithmetic).
NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def pattern_kinds(pattern: str) -> tuple:
    """A `nemotron_h` hybrid_override_pattern as layer_types. Its '-' (a dense
    MLP alone) has no kind here: a model with one is refused."""
    odd = set(pattern) - set(PATTERN_LETTERS)
    if odd:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: the layer kinds are M (Mamba-2), E "
            f"(experts) and * (attention); {sorted(odd)} is not runnable")
    return tuple(PATTERN_LETTERS[c] for c in pattern)


NEMOTRON_3_SUPER_120B = ModelConfig(
    name="nemotron-3-super-120b-a12b",
    vocab_size=131072,
    hidden_size=4096,
    intermediate_size=2688,
    num_layers=88,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    rms_norm_eps=1e-5,
    rope_theta=10_000.0,  # in the published config; no layer rotates anything
    max_position_embeddings=262144,
    tie_word_embeddings=False,
    qk_norm=False,
    position_embedding="nope",
    layer_types=pattern_kinds(NEMOTRON_3_SUPER_PATTERN),
    mamba_heads=128,
    mamba_head_dim=64,
    mamba_state=128,
    mamba_groups=8,
    mamba_conv=4,
    mamba_expand=2,
    mamba_chunk_size=128,
    num_experts=512,
    num_experts_per_tok=22,
    moe_intermediate_size=2688,
    moe_latent_size=1024,
    moe_router_mode="sigmoid_topk",
    norm_topk_prob=True,
    routed_scaling_factor=5.0,
    n_shared_experts=2,  # ONE shared expert of 5376 = 2 x moe_intermediate_size
    hidden_act="relu2",
    ffn_gated=False,
    # the SEEDED draw alone: 22 of 512 scores lie 0.002 apart at the cut, bf16 and
    # float32 break nearly every token-layer's last places differently, and at 1 an
    # expert that comes or goes moved the log-probabilities by 0.019-0.034 in the mean
    # (the 8-bit control: 0.050-0.071, a ratio of 1.5; PERF.md section 4)
    seeded_routed_scale=0.25,
)

NEMOTRON_3_SUPER_EP4_11L = dataclasses.replace(
    NEMOTRON_3_SUPER_120B, name="nemotron-3-super-120b-ep4-11l", num_layers=11,
    layer_types=pattern_kinds(NEMOTRON_3_SUPER_PATTERN[:11]), vocab_size=131072 // 4,
    num_experts=512 // 4, router_experts=512,
)

# Trinity-Large-Preview (arcee-ai/Trinity-Large-Preview config.json, `afmoe`,
# 400B-A13B): 60 layers, three windowed (4096, rope) to one full (no rope),
# a gated attention output, sandwich norms, 6 dense layers, then 256 sigmoid-
# routed experts (top 4) beside a shared one. The -ep8-5l preset is ONE chip's
# share of an eight-chip expert-parallel group over the first five layers
# (one dense, then a whole period of sparse ones): experts 0..31 of the
# router's 256, vocabulary ids 0..25 023 of 200 192, every width as published
# (benchmark/configs/trinity-large-ep8-1chip.json has the arithmetic).
TRINITY_LARGE = ModelConfig(
    name="trinity-large-preview",
    vocab_size=200192,
    hidden_size=3072,
    intermediate_size=12288,
    num_layers=60,
    num_heads=48,
    num_kv_heads=8,
    head_dim=128,
    rms_norm_eps=1e-5,
    rope_theta=10_000.0,
    max_position_embeddings=262144,
    tie_word_embeddings=False,
    qk_norm=True,
    norm_placement="both",
    scale_embedding=True,
    sliding_window=4096,
    layer_types=("sliding", "sliding", "sliding", "global"),
    nope_kinds=("global",),
    attn_gate=True,
    num_experts=256,
    num_experts_per_tok=4,
    moe_intermediate_size=3072,
    moe_router_mode="sigmoid_topk",
    norm_topk_prob=True,
    routed_scaling_factor=2.448,
    n_shared_experts=1,
    first_k_dense_replace=6,
)

TRINITY_LARGE_EP8_5L = dataclasses.replace(
    TRINITY_LARGE.with_layers(5), name="trinity-large-ep8-5l", vocab_size=200192 // 8,
    first_k_dense_replace=1, num_experts=256 // 8, router_experts=256,
)

# Qwen3-Next-80B-A3B-Instruct (Qwen/Qwen3-Next-80B-A3B-Instruct config.json,
# `qwen3_next`): 48 layers, three Gated-DeltaNet layers (16 key / 32 value
# heads of 128, a convolution of 4 taps) to one gated full-attention layer
# (16 query / 2 kv heads of 256, rope on the first 64 dimensions), every
# RMSNorm but the delta rule's gated one scaling by 1 + w, every layer with
# 512 softmax-routed experts (top 10, width 512) beside one gated shared
# expert. Its multi-token-prediction module is left out (generation does not
# run it). The -ep4-8l preset is ONE chip's share of a four-chip
# expert-parallel group over the first eight layers (two whole periods):
# experts 0..127 of the router's 512, vocabulary ids 0..37 983 of 151 936,
# every width as published (benchmark/configs/qwen3-next-80b-ep4-1chip.json
# has the arithmetic).
QWEN3_NEXT_80B_A3B = ModelConfig(
    name="qwen3-next-80b-a3b",
    vocab_size=151936,
    hidden_size=2048,
    intermediate_size=5120,
    num_layers=48,
    num_heads=16,
    num_kv_heads=2,
    head_dim=256,
    rms_norm_eps=1e-6,
    rope_theta=10_000_000.0,
    max_position_embeddings=262144,
    tie_word_embeddings=False,
    qk_norm=True,
    rms_norm_plus_one=True,
    partial_rotary_factor=0.25,
    attn_gate=True,
    layer_types=("delta", "delta", "delta", "attention"),
    linear_key_heads=16,
    linear_value_heads=32,
    linear_key_head_dim=128,
    linear_value_head_dim=128,
    linear_conv=4,
    linear_chunk_size=64,
    num_experts=512,
    num_experts_per_tok=10,
    moe_intermediate_size=512,
    norm_topk_prob=True,
    n_shared_experts=1,
    shared_expert_gate=True,
)

QWEN3_NEXT_80B_EP4_8L = dataclasses.replace(
    QWEN3_NEXT_80B_A3B.with_layers(8), name="qwen3-next-80b-ep4-8l", vocab_size=151936 // 4,
    num_experts=512 // 4, router_experts=512,
)

# Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json, `olmo_hybrid`): 32
# layers, three Gated-DeltaNet layers (30 key and 30 value heads, keys of 96,
# values of 192, beta in (0, 2)) to one full-attention layer of 30 query and 30
# key/value heads of 128 without rope, a SwiGLU MLP of 11 008 in every layer,
# NO norm on a sublayer's input: h = x + Norm(Mixer(x)), y = h + Norm(MLP(h)),
# and the q/k norm over the whole projection. The -16l preset is the first
# sixteen layers (four whole periods), what one v5e chip holds at the
# published widths beside sixteen lanes of 4096 slots
# (benchmark/configs/olmo-hybrid-7b-1chip.json has the arithmetic).
OLMO_HYBRID_7B = ModelConfig(
    name="olmo-hybrid-7b",
    vocab_size=100352,
    hidden_size=3840,
    intermediate_size=11008,
    num_layers=32,
    num_heads=30,
    num_kv_heads=30,
    head_dim=128,
    rms_norm_eps=1e-6,
    max_position_embeddings=65536,
    tie_word_embeddings=False,
    qk_norm=True,
    qk_norm_flat=True,
    norm_placement="after",
    position_embedding="nope",
    layer_types=("delta", "delta", "delta", "attention"),
    linear_key_heads=30,
    linear_value_heads=30,
    linear_key_head_dim=96,
    linear_value_head_dim=192,
    linear_conv=4,
    linear_chunk_size=64,
    linear_allow_neg_eigval=True,
)

OLMO_HYBRID_7B_16L = dataclasses.replace(OLMO_HYBRID_7B.with_layers(16), name="olmo-hybrid-7b-16l")

# Synthetic mid-size config for the default bench's paired pipeline leg
# (bench.py): big enough that a decode step's compute dominates the
# inter-stage hop (the regime the north-star ratio grades), small enough
# that interleaved paired trials finish in seconds on a 1-core CPU host.
# Qwen3 topology at reduced width — NOT a real checkpoint shape.
BENCH_PIPE = ModelConfig(
    name="bench-pipe",
    vocab_size=8192,
    hidden_size=512,
    intermediate_size=1536,
    num_layers=8,
    num_heads=8,
    num_kv_heads=4,
    head_dim=64,
    max_position_embeddings=2048,
    dtype="float32",
)

# Tiny configs for tests — same topology, toy widths.
TINY = ModelConfig(
    name="tiny",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_position_embeddings=512,
    dtype="float32",
)

TINY_MOE = dataclasses.replace(
    TINY,
    name="tiny-moe",
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
)

TINY_SDAR = dataclasses.replace(
    TINY_MOE, name="tiny-sdar", tie_word_embeddings=False,
    block_length=4, denoising_steps=2, mask_token_id=255,
)

TINY_QWEN2 = dataclasses.replace(
    TINY, name="tiny-qwen2", qk_norm=False, attn_bias=True
)

TINY_LLAMA = dataclasses.replace(
    TINY, name="tiny-llama", qk_norm=False, attn_bias=False,
    rope_scaling="llama3", rope_scaling_factor=8.0,
    rope_original_max_position=128, rope_theta=500_000.0,
)

TINY_GPT_OSS = dataclasses.replace(
    TINY, name="tiny-gptoss", qk_norm=False, attn_bias=True, o_bias=True,
    tie_word_embeddings=False,
    attn_sinks=True, sliding_window=8, rope_theta=150_000.0, rms_norm_eps=1e-5,
    rope_scaling="yarn", rope_scaling_factor=32.0,
    rope_original_max_position=64, rope_truncate=False,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    moe_router_mode="topk_softmax", router_bias=True, moe_bias=True,
    swiglu_limit=7.0,
)

TINY_GEMMA2 = dataclasses.replace(
    TINY, name="tiny-gemma2", qk_norm=False, attn_bias=False,
    rope_theta=10_000.0,
    norm_placement="both", rms_norm_plus_one=True, hidden_act="gelu_tanh",
    scale_embedding=True, attn_logit_softcap=50.0, final_logit_softcap=30.0,
    query_pre_attn_scalar=32.0, sliding_window=8,
)

TINY_DSV2 = dataclasses.replace(
    TINY, name="tiny-dsv2", qk_norm=False, tie_word_embeddings=False,
    head_dim=24, num_kv_heads=4, rope_theta=10_000.0,
    rope_scaling="yarn", rope_scaling_factor=40.0,
    rope_original_max_position=64, rope_mscale=0.707, rope_mscale_all_dim=0.707,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=False, n_shared_experts=2, first_k_dense_replace=1,
)

# tiny-xing4: the Xing4.0 layer at toy widths: a stream of four hidden states
# of 64, queries compressed to 24, a dense layer then three of 8
# sigmoid-routed experts (top 2, renormalised, x 2) beside one shared.
TINY_XING4 = dataclasses.replace(
    TINY_DSV2, name="tiny-xing4", rope_scaling_factor=64.0, rope_mscale=1.0,
    rope_mscale_all_dim=1.0, q_lora_rank=24, moe_router_mode="sigmoid_topk",
    norm_topk_prob=True, routed_scaling_factor=2.0, n_shared_experts=1,
    hc_mult=4,
)

TINY_GRANITE_H = dataclasses.replace(
    TINY, name="tiny-granite-h", qk_norm=False, num_layers=8, rms_norm_eps=1e-5,
    query_pre_attn_scalar=256.0,  # 1/16 where head_dim 16 would give 1/4
    layer_types=("mamba", "mamba", "attention", "mamba"),
    mamba_heads=8, mamba_head_dim=16, mamba_state=16, mamba_groups=1, mamba_conv=4,
    mamba_expand=2, mamba_chunk_size=8,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    position_embedding="nope",
)

# tiny-afmoe: the Trinity layer at toy widths: a dense layer, then two
# periods of windowed x 3 + full (9 layers: the scan meets a head of three,
# one whole period and a tail of one), window 8, 16 experts top 2, 1 shared.
TINY_AFMOE = dataclasses.replace(
    TINY, name="tiny-afmoe", num_layers=9, tie_word_embeddings=False, rms_norm_eps=1e-5,
    rope_theta=10_000.0, norm_placement="both", scale_embedding=True, sliding_window=8,
    layer_types=("sliding", "sliding", "sliding", "global"), nope_kinds=("global",),
    attn_gate=True, num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
    moe_router_mode="sigmoid_topk", norm_topk_prob=True, routed_scaling_factor=2.448,
    n_shared_experts=1, first_k_dense_replace=1,
)

# tiny-qwen3-next: the Qwen3-Next layer at toy widths: two periods of three
# delta-rule layers and a gated full one whose rope turns 8 of its 32
# dimensions, 16 experts top 4 beside a gated shared one.
TINY_QWEN3_NEXT = dataclasses.replace(
    TINY, name="tiny-qwen3-next", num_layers=8, tie_word_embeddings=False, head_dim=32,
    num_kv_heads=2, rope_theta=10_000.0, rms_norm_plus_one=True, partial_rotary_factor=0.25,
    attn_gate=True, layer_types=("delta", "delta", "delta", "attention"),
    linear_key_heads=2, linear_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv=4, linear_chunk_size=8,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32, norm_topk_prob=True,
    n_shared_experts=1, shared_expert_gate=True,
)

# tiny-olmo-hybrid: the Olmo-Hybrid layer at toy widths but the PUBLISHED head
# sizes of its state (keys of 96, values of 192: what is off the tile is the
# point, core.cache.state_fold), four heads of them: two periods of three
# delta-rule layers and a full one of 4 kv heads without rope, norms on the
# sublayers' outputs, the q/k norm over the whole projection.
TINY_OLMO_HYBRID = dataclasses.replace(
    TINY, name="tiny-olmo-hybrid", num_layers=8, tie_word_embeddings=False, num_kv_heads=4,
    qk_norm_flat=True, norm_placement="after", position_embedding="nope",
    layer_types=("delta", "delta", "delta", "attention"),
    linear_key_heads=4, linear_value_heads=4, linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv=4, linear_chunk_size=8, linear_allow_neg_eigval=True,
)

# tiny-nemotron-h: the Nemotron-H layers at toy widths, one sublayer each,
# M E M * E M E: all three kinds, a mixer with no experts behind it (the M
# before the *), Mamba-2 in 4 groups, 4 held of 16 sigmoid-routed experts (top
# 4) in a latent of 32 under a hidden state of 64, squared ReLU without a gate.
TINY_NEMOTRON_H = dataclasses.replace(
    TINY, name="tiny-nemotron-h", qk_norm=False, num_layers=7, rms_norm_eps=1e-5,
    tie_word_embeddings=False, position_embedding="nope",
    layer_types=pattern_kinds("MEM*EME"),
    mamba_heads=8, mamba_head_dim=16, mamba_state=16, mamba_groups=4, mamba_conv=4,
    mamba_expand=2, mamba_chunk_size=8,
    num_experts=4, router_experts=16, num_experts_per_tok=4, moe_intermediate_size=48,
    moe_latent_size=32, moe_router_mode="sigmoid_topk", norm_topk_prob=True,
    routed_scaling_factor=5.0, n_shared_experts=2, hidden_act="relu2", ffn_gated=False,
)

PRESETS = {
    c.name: c
    for c in [
        QWEN3_0_6B,
        QWEN3_1_7B,
        QWEN3_4B,
        QWEN3_8B,
        QWEN3_14B,
        QWEN3_32B,
        QWEN2_0_5B,
        QWEN2_1_5B,
        QWEN2_7B,
        LLAMA32_1B,
        LLAMA31_8B,
        GEMMA2_2B,
        GEMMA2_9B,
        GEMMA2_27B,
        MIXTRAL_8X7B,
        GPT_OSS_20B,
        GPT_OSS_120B,
        QWEN3_MOE_30B_A3B,
        SDAR_30B_A3B,
        SDAR_30B_A3B_7L,
        DEEPSEEK_V2_LITE,
        DEEPSEEK_V2_LITE_8L,
        XING4_29B_A4B,
        XING4_29B_A4B_6L,
        GRANITE_4_H_MICRO,
        TRINITY_LARGE,
        TRINITY_LARGE_EP8_5L,
        QWEN3_NEXT_80B_A3B,
        QWEN3_NEXT_80B_EP4_8L,
        OLMO_HYBRID_7B,
        OLMO_HYBRID_7B_16L,
        NEMOTRON_3_SUPER_120B,
        NEMOTRON_3_SUPER_EP4_11L,
        BENCH_PIPE,
        TINY,
        TINY_MOE,
        TINY_SDAR,
        TINY_QWEN2,
        TINY_LLAMA,
        TINY_GEMMA2,
        TINY_GPT_OSS,
        TINY_DSV2,
        TINY_XING4,
        TINY_GRANITE_H,
        TINY_AFMOE,
        TINY_QWEN3_NEXT,
        TINY_OLMO_HYBRID,
        TINY_NEMOTRON_H,
    ]
}

# HF hub repos for weight loading (inferd_tpu.models.loader).
HF_REPOS = {
    "qwen3-0.6b": "Qwen/Qwen3-0.6B",
    "qwen3-1.7b": "Qwen/Qwen3-1.7B",
    "qwen3-4b": "Qwen/Qwen3-4B",
    "qwen3-8b": "Qwen/Qwen3-8B",
    "qwen3-14b": "Qwen/Qwen3-14B",
    "qwen3-32b": "Qwen/Qwen3-32B",
    "qwen3-moe-30b-a3b": "Qwen/Qwen3-30B-A3B",
    "qwen2-0.5b": "Qwen/Qwen2-0.5B",
    "qwen2-1.5b": "Qwen/Qwen2-1.5B",
    "qwen2-7b": "Qwen/Qwen2-7B",
    "llama3.2-1b": "meta-llama/Llama-3.2-1B",
    "llama3.1-8b": "meta-llama/Llama-3.1-8B",
    "gemma2-2b": "google/gemma-2-2b",
    "gemma2-9b": "google/gemma-2-9b",
    "gemma2-27b": "google/gemma-2-27b",
    "mixtral-8x7b": "mistralai/Mixtral-8x7B-v0.1",
    "gpt-oss-20b": "openai/gpt-oss-20b",
    "gpt-oss-120b": "openai/gpt-oss-120b",
    "deepseek-v2-lite": "deepseek-ai/DeepSeek-V2-Lite",
    "xing4.0-29b-a4b": "XingChen-AGI/Xing4.0-29B-A4B",
    "granite-4.0-h-micro": "ibm-granite/granite-4.0-h-micro",
    "qwen3-next-80b-a3b": "Qwen/Qwen3-Next-80B-A3B-Instruct",
    "olmo-hybrid-7b": "allenai/Olmo-Hybrid-7B",
    "nemotron-3-super-120b-a12b": "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16",
}


def get_config(name: str) -> ModelConfig:
    try:
        return PRESETS[name.lower()]
    except KeyError:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
