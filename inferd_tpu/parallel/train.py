"""Full mesh-parallel training step: GPipe pipeline × tensor × expert ×
sequence × data parallelism in one shard_map'd program.

The reference is inference-only, but its elasticity story (stage migration,
rebalance) presumes stages are *re-formable units of the layer stack* —
this module is the TPU-native generalization: the decoder stack is sharded
over the `pp` mesh axis, microbatched activations hop stages via
`lax.ppermute` (the in-mesh analogue of the reference's node→node HTTP relay,
/root/reference/petals/node.py:102-117), and the whole schedule — forward,
loss, backward-through-the-collectives, SGD update — is ONE jitted SPMD
program (loss, backward, and the SGD or Adam update — Adam moments shard
exactly like their params). Gradient sync is two-part: `tp.enter_sharded`'s custom VJP
completes tp/ep-sharded leaves at their activation boundaries during the
backward pass, and an explicit per-leaf psum pass (mesh.grad_sync_axes)
then sums the remaining PARTIAL contributions — replicated leaves over
dp/sp, stage-local leaves over the data axes only — and normalizes by the
data-axis size so the result is the gradient of the mean loss.

Schedule: plain GPipe with MB microbatches over PP stages — MB + PP - 1
ticks, each tick runs every rank's layer slice on its current microbatch and
rotates activations one stage forward. Reverse-mode AD through the `lax.scan`
over ticks gives the standard 1F1B-equivalent memory profile for free
(XLA remats the per-tick compute); `jax.checkpoint` on the stage body keeps
activation memory at one microbatch per live tick.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from inferd_tpu.config import ModelConfig
from inferd_tpu.models.qwen3 import embed as qwen3_embed
from inferd_tpu.models.qwen3 import rms_norm
from inferd_tpu.ops.attention import apply_softcap
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.tp import sharded_forward_layers

Params = Dict[str, Any]


def _unembed_local(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    x = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_plus_one)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    z = (x @ head).astype(jnp.float32)
    return apply_softcap(z, cfg.final_logit_softcap)


def _pipeline_forward(
    params: Params,  # local: layers sliced over pp, embed/head replicated
    cfg: ModelConfig,
    tokens: jax.Array,  # [MB, B_local, S_local]
    positions: jax.Array,  # [B_local, S_local]
    sp_axis: Optional[str],
    collect_aux: bool = False,
):
    """Run the GPipe schedule; returns hidden outputs [MB, B, S, H] —
    valid only on the LAST pp rank (zeros elsewhere).

    collect_aux: also return this rank's summed MoE load-balancing loss
    over its layers and all REAL microbatch ticks (bubble ticks compute on
    garbage activations and are masked out)."""
    pp = lax.axis_size("pp")
    idx = lax.axis_index("pp")
    mb = tokens.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    n_local = jax.tree.leaves(params["layers"])[0].shape[0]
    stage = jax.checkpoint(
        lambda h: sharded_forward_layers(
            params["layers"], cfg, h, positions, "tp", sp_axis,
            layer_offset=idx * n_local, with_aux=collect_aux,
            aux_token_axes=("dp", "sp"),
        )
    )

    b, s = tokens.shape[1], tokens.shape[2]
    h = cfg.hidden_size
    state = jnp.zeros((b, s, h), cfg.jnp_dtype)
    outputs = jnp.zeros((mb, b, s, h), cfg.jnp_dtype)

    def tick(carry, t):
        state, outputs, aux_acc = carry
        emb = qwen3_embed(params, tokens[jnp.minimum(t, mb - 1)], cfg)
        inp = jnp.where(idx == 0, emb.astype(state.dtype), state)
        if collect_aux:
            y, aux = stage(inp)
            m = t - idx  # microbatch resident on this rank at tick t
            valid = (m >= 0) & (m < mb)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        else:
            y = stage(inp)
        out_t = t - (pp - 1)
        write = (idx == pp - 1) & (out_t >= 0)
        updated = lax.dynamic_update_index_in_dim(
            outputs, y, jnp.maximum(out_t, 0), axis=0
        )
        outputs = jnp.where(write, updated, outputs)
        state = lax.ppermute(y, "pp", perm)
        return (state, outputs, aux_acc), None

    (_, outputs, aux_acc), _ = lax.scan(
        tick, (state, outputs, jnp.float32(0.0)), jnp.arange(mb + pp - 1)
    )
    if collect_aux:
        return outputs, aux_acc / mb
    return outputs


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "mu", "nu", "count"],
    meta_fields=[],
)
@dataclasses.dataclass
class TrainState:
    """Params + Adam moments + step counter. Moments are float32 pytrees
    mirroring the params (sharded identically over the mesh); for SGD they
    are empty dicts. This is exactly the state parallel.checkpoint
    snapshots/restores (params, optimizer moments, step counter)."""

    params: Params
    mu: Any
    nu: Any
    count: jax.Array


def _ts_to_state_dict(s: TrainState):
    from flax import serialization as ser

    return {
        "params": ser.to_state_dict(s.params),
        "mu": ser.to_state_dict(s.mu),
        "nu": ser.to_state_dict(s.nu),
        "count": s.count,
    }


def _ts_from_state_dict(s: TrainState, sd):
    from flax import serialization as ser

    return TrainState(
        params=ser.from_state_dict(s.params, sd["params"]),
        mu=ser.from_state_dict(s.mu, sd["mu"]),
        nu=ser.from_state_dict(s.nu, sd["nu"]),
        count=sd["count"],
    )


try:  # checkpointable via flax msgpack (parallel.checkpoint save/restore)
    from flax import serialization as _ser

    _ser.register_serialization_state(TrainState, _ts_to_state_dict, _ts_from_state_dict)
except ImportError:  # pragma: no cover — flax is a baked-in dep
    pass


def init_train_state(params: Params, optimizer: str = "adam") -> TrainState:
    if optimizer == "adam":
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        mu, nu = zeros, jax.tree.map(jnp.copy, zeros)
    else:
        mu, nu = {}, {}
    return TrainState(params=params, mu=mu, nu=nu, count=jnp.zeros((), jnp.int32))


def train_state_specs(param_specs: Any, optimizer: str) -> TrainState:
    """Partition-spec pytree matching TrainState: moments shard exactly like
    their params, the step counter is replicated. The single source of truth
    for both the shard_map in/out specs and checkpoint-restore shardings."""
    moment_specs = param_specs if optimizer == "adam" else {}
    return TrainState(
        params=param_specs, mu=moment_specs, nu=moment_specs, count=P()
    )


@dataclasses.dataclass
class TrainStep:
    """A compiled mesh-parallel train step.

    Call with (TrainState, tokens, targets) -> (TrainState', loss), or —
    SGD only, for convenience — with a raw params pytree, returning
    (new_params, loss). Params are GLOBAL (sharding applied by shard_map
    specs); tokens/targets are [MB, B, S] int32."""

    fn: Callable
    mesh: Mesh
    plan: meshlib.MeshPlan
    param_specs: Any
    optimizer: str
    stateful_schedule: bool = False  # warmup/decay/clip track state.count

    def init_state(self, params: Params) -> TrainState:
        return init_train_state(params, self.optimizer)

    def state_specs(self) -> Any:
        """Partition-spec pytree matching TrainState (for checkpoint
        restore onto the mesh)."""
        return train_state_specs(self.param_specs, self.optimizer)

    def __call__(self, state, tokens, targets):
        if not isinstance(state, TrainState):
            if self.optimizer != "sgd":
                raise TypeError(
                    f"{self.optimizer} needs optimizer state: call with the "
                    "TrainState from .init_state(params)"
                )
            if self.stateful_schedule:
                raise TypeError(
                    "warmup/decay schedules track state.count, which the "
                    "raw-params convenience path re-initializes to 0 every "
                    "call (the schedule would freeze at step 1) — call with "
                    "the TrainState from .init_state(params)"
                )
            new, loss = self.fn(init_train_state(state, "sgd"), tokens, targets)
            return new.params, loss
        return self.fn(state, tokens, targets)


def make_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    plan: meshlib.MeshPlan,
    learning_rate: float = 1e-3,
    optimizer: str = "sgd",
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    grad_clip_norm: float = 0.0,
    warmup_steps: int = 0,
    decay_steps: int = 0,
    moe_aux_coef: float = 0.0,
) -> TrainStep:
    """Build the jitted SPMD training step for `cfg` over `mesh`.

    Sharding layout:
      tokens/targets [MB, B, S]: batch over dp, sequence over sp;
      params: layer stack over pp, heads/ffn over tp, experts over (ep, tp),
      everything else replicated (mesh.model_param_specs);
      Adam moments: sharded exactly like their params.

    Optional stabilizers (the standard LLM-training trio the reference has
    no training story for at all):
      grad_clip_norm > 0: clip by GLOBAL grad norm — computed with per-leaf
        psums over the axes each leaf is sharded on, so every rank clips by
        the same scalar;
      warmup_steps / decay_steps: linear warmup to `learning_rate`, then
        cosine decay to 10% over `decay_steps` (0 = constant after warmup);
      moe_aux_coef > 0 (MoE configs): add coef * router load-balancing loss
        (Switch-style, HF load_balancing_loss_func semantics — see
        tp.load_balance_loss) summed over layers, mean over microbatches.
    """
    if optimizer not in ("sgd", "adam"):
        raise ValueError(f"unknown optimizer {optimizer!r}")
    if moe_aux_coef and not cfg.is_moe:
        raise ValueError("moe_aux_coef needs an MoE config")
    meshlib.check_divisibility(cfg, plan)
    pspecs = meshlib.model_param_specs(cfg, layer_axis="pp" if plan.pp > 1 else None)
    sync_axes = meshlib.grad_sync_axes(cfg)
    sp_axis = "sp" if plan.sp > 1 else None
    data_spec = P(None, "dp", "sp")

    def _spec_axes(spec):
        """Mesh axes a leaf is SHARDED on (its spec entries, flattened) —
        the axes its squared-norm contribution must psum over."""
        axes = []
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                axes.extend(entry)
            else:
                axes.append(entry)
        return tuple(axes)

    shard_axes = jax.tree.map(
        _spec_axes, pspecs, is_leaf=lambda x: isinstance(x, P)
    )

    def per_rank(state: TrainState, tokens, targets):
        params = state.params
        b, s = tokens.shape[1], tokens.shape[2]
        # absolute positions of this rank's sequence block
        sp_idx = lax.axis_index("sp")
        positions = sp_idx * s + jnp.broadcast_to(jnp.arange(s), (b, s))

        def loss_fn(p):
            # LOCAL loss only — no collectives inside the differentiated
            # function. Differentiating a psum/pmean'd (replicated) loss
            # under check_vma=False hands every rank a unit cotangent for
            # the same scalar, which scaled every gradient by the device
            # count; grads of the local term compose correctly with the
            # explicit per-leaf sync below.
            if moe_aux_coef:
                outputs, aux = _pipeline_forward(
                    p, cfg, tokens, positions, sp_axis, collect_aux=True
                )
            else:
                outputs = _pipeline_forward(p, cfg, tokens, positions, sp_axis)
                aux = 0.0
            mbs, bb, ss, hh = outputs.shape
            logits = _unembed_local(p, cfg, outputs.reshape(mbs * bb, ss, hh))
            logp = jax.nn.log_softmax(logits, axis=-1)
            tgt = targets.reshape(mbs * bb, ss)
            nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
            local = jnp.mean(nll)
            # only the last pp rank holds real outputs; the aux term is
            # per-rank (each rank's OWN layer slice contributes). The aux
            # is GLOBAL over the data axes (token-means psum-combined in
            # tp.moe_mlp_sharded) while the grad sync below divides every
            # leaf by data_norm to turn summed per-shard CE grads into the
            # mean — pre-multiplying aux by data_norm cancels that division
            # exactly for its gradient paths.
            ce = jnp.where(lax.axis_index("pp") == lax.axis_size("pp") - 1, local, 0.0)
            dn = float(plan.dp * plan.sp)
            return ce + moe_aux_coef * dn * aux, (ce, aux)

        (_, (local_ce, local_aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        # reported loss: mean nll over the global batch, plus the FULL aux
        # term — the per-rank aux is scaled by 1/(ep*tp) for gradient
        # correctness (tp.moe_mlp_sharded), so the report psums it back up
        local_loss = local_ce + moe_aux_coef * _psum_axes(
            jnp.asarray(local_aux, jnp.float32), ("ep", "tp")
        )
        loss = lax.pmean(lax.pmean(lax.psum(local_loss, "pp"), "dp"), "sp")
        # sync each grad leaf over exactly the axes where its per-rank grad
        # is a PARTIAL contribution (mesh.grad_sync_axes — the forward's
        # tp.enter_sharded boundaries already complete most leaves over
        # tp/ep), then normalize by the data axes so the result is the
        # gradient of the MEAN loss
        data_norm = float(plan.dp * plan.sp)
        # axes tree first: its tuple leaves define the flattening structure
        grads = jax.tree.map(
            lambda axes, g: _psum_axes(g, axes) / data_norm,
            sync_axes,
            grads,
            is_leaf=lambda x: isinstance(x, tuple),
        )
        count = state.count + 1
        if grad_clip_norm > 0.0:
            # global grad norm: per-leaf local sum of squares, psum'd over
            # exactly the axes the leaf is sharded on (replication axes hold
            # identical values), so every rank clips by the same scalar
            sq = jax.tree.map(
                lambda axes, g: _psum_axes(
                    jnp.sum(jnp.square(g.astype(jnp.float32))), axes
                ),
                shard_axes,
                grads,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            gnorm = jnp.sqrt(
                jax.tree_util.tree_reduce(jnp.add, sq, jnp.float32(0.0))
            )
            clip = jnp.minimum(1.0, grad_clip_norm / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: (g * clip).astype(g.dtype), grads)

        # LR schedule (static config -> traced scalar): linear warmup, then
        # cosine decay to 10% of peak over decay_steps
        step = count.astype(jnp.float32)
        lr = jnp.float32(learning_rate)
        if warmup_steps > 0:
            lr = lr * jnp.minimum(1.0, step / warmup_steps)
        if decay_steps > 0:
            prog = jnp.clip((step - warmup_steps) / decay_steps, 0.0, 1.0)
            lr = lr * (0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * prog)))

        if optimizer == "adam":
            # grads are fully synced above, so per-rank Adam stays bitwise
            # consistent across replicas; moments shard like their params
            cf = count.astype(jnp.float32)
            bc1 = 1.0 - jnp.power(jnp.float32(b1), cf)
            bc2 = 1.0 - jnp.power(jnp.float32(b2), cf)
            new_mu = jax.tree.map(
                lambda m, g: b1 * m + (1.0 - b1) * g.astype(jnp.float32),
                state.mu, grads,
            )
            new_nu = jax.tree.map(
                lambda n, g: b2 * n + (1.0 - b2) * jnp.square(g.astype(jnp.float32)),
                state.nu, grads,
            )
            new_params = jax.tree.map(
                lambda p, m, n: (
                    p.astype(jnp.float32)
                    - lr * (m / bc1) / (jnp.sqrt(n / bc2) + eps)
                ).astype(p.dtype),
                params, new_mu, new_nu,
            )
        else:
            new_mu, new_nu = state.mu, state.nu
            new_params = jax.tree.map(
                lambda p, g: (
                    p.astype(jnp.float32) - lr * g.astype(jnp.float32)
                ).astype(p.dtype),
                params, grads,
            )
        return TrainState(params=new_params, mu=new_mu, nu=new_nu, count=count), loss

    def _psum_axes(g, axes):
        for ax in axes:
            g = lax.psum(g, ax)
        return g

    state_specs = train_state_specs(pspecs, optimizer)
    shmapped = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(state_specs, data_spec, data_spec),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return TrainStep(
        fn=jax.jit(shmapped), mesh=mesh, plan=plan, param_specs=pspecs,
        optimizer=optimizer,
        stateful_schedule=warmup_steps > 0 or decay_steps > 0,
    )
