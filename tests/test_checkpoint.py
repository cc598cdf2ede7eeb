# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Training checkpoint/resume tests: atomic roundtrip, retention GC, and —
the property that matters — a restored run continues BIT-IDENTICALLY to the
uninterrupted one on a sharded mesh."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import TINY
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import checkpoint as ckpt
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.train import make_train_step


def test_roundtrip_and_meta(tmp_path):
    state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3), "b": np.float32(1.5)}
    path = ckpt.save(str(tmp_path), state, step=7, meta={"lr": 0.1})
    assert os.path.basename(path) == "step_000000007.msgpack"
    got, meta = ckpt.restore(str(tmp_path))
    assert meta["step"] == 7 and meta["lr"] == 0.1
    np.testing.assert_array_equal(got["w"], state["w"])
    np.testing.assert_array_equal(got["b"], state["b"])


def test_latest_and_gc(tmp_path):
    d = str(tmp_path)
    assert ckpt.latest_step(d) is None
    for s in [1, 5, 3, 9, 12]:
        ckpt.save(d, {"x": np.zeros(1)}, step=s, keep=3)
    assert ckpt.latest_step(d) == 12
    kept = sorted(f for f in os.listdir(d) if f.endswith(".msgpack"))
    assert kept == ["step_000000005.msgpack", "step_000000009.msgpack", "step_000000012.msgpack"]
    # restore a specific retained step
    _, meta = ckpt.restore(d, step=9)
    assert meta["step"] == 9


def test_no_tmp_litter_on_success(tmp_path):
    ckpt.save(str(tmp_path), {"x": np.zeros(4)}, step=1)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path / "empty"))


def test_sharded_resume_continues_identically(tmp_path, devices8):
    """Train 4 steps straight vs train 2 + checkpoint + restore-onto-mesh +
    train 2: final params must match exactly."""
    plan = meshlib.MeshPlan(dp=2, tp=2)
    mesh = meshlib.make_mesh(plan, devices8[:4])
    meshlib.check_divisibility(TINY, plan)
    step = make_train_step(TINY, mesh, plan, learning_rate=1e-2)

    params0 = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    data = jax.random.randint(
        jax.random.PRNGKey(3), (2, 2 * plan.dp, 8 + 1), 0, TINY.vocab_size, dtype=jnp.int32
    )
    tokens, targets = data[..., :-1], data[..., 1:]

    # uninterrupted
    p = params0
    for _ in range(4):
        p, _ = step(p, tokens, targets)
    straight = jax.device_get(p)

    # interrupted at step 2
    p = params0
    for _ in range(2):
        p, _ = step(p, tokens, targets)
    ckpt.save(str(tmp_path), p, step=2)
    del p

    from jax.sharding import NamedSharding

    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        step.param_specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    restored, meta = ckpt.restore(str(tmp_path), shardings=shardings)
    assert meta["step"] == 2
    for _ in range(2):
        restored, _ = step(restored, tokens, targets)
    resumed = jax.device_get(restored)

    flat_a, _ = jax.tree.flatten(straight)
    flat_b, _ = jax.tree.flatten(resumed)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_meta_step_key_is_reserved(tmp_path):
    """A caller-supplied meta 'step' must not override the real step."""
    ckpt.save(str(tmp_path), {"x": np.zeros(1)}, step=5, meta={"step": 99, "lr": 0.1})
    _, meta = ckpt.restore(str(tmp_path))
    assert meta["step"] == 5 and meta["lr"] == 0.1


def test_adam_resume_bit_identity(tmp_path, devices8):
    """Adam training: 4 steps straight vs 2 + snapshot(params+moments+count)
    + restore-with-target-onto-mesh + 2 — params AND moments must match
    bit for bit (VERDICT r1 item 7; ADVICE r1 restore-target fix)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PSpec

    from inferd_tpu.parallel.train import TrainState

    plan = meshlib.MeshPlan(dp=2, tp=2)
    mesh = meshlib.make_mesh(plan, devices8[:4])
    step = make_train_step(TINY, mesh, plan, learning_rate=1e-3, optimizer="adam")

    params0 = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    data = jax.random.randint(
        jax.random.PRNGKey(3), (2, 2 * plan.dp, 8 + 1), 0, TINY.vocab_size, dtype=jnp.int32
    )
    tokens, targets = data[..., :-1], data[..., 1:]

    s = step.init_state(params0)
    for _ in range(4):
        s, _ = step(s, tokens, targets)
    straight = jax.device_get(s)

    s = step.init_state(params0)
    for _ in range(2):
        s, _ = step(s, tokens, targets)
    ckpt.save(str(tmp_path), s, step=2)
    del s

    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        step.state_specs(),
        is_leaf=lambda x: isinstance(x, PSpec),
    )
    restored, meta = ckpt.restore(
        str(tmp_path), target=step.init_state(params0), shardings=shardings
    )
    assert meta["step"] == 2
    assert isinstance(restored, TrainState) and int(restored.count) == 2
    for _ in range(2):
        restored, _ = step(restored, tokens, targets)
    resumed = jax.device_get(restored)

    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adam_loss_decreases(devices8):
    plan = meshlib.MeshPlan(pp=2)
    mesh = meshlib.make_mesh(plan, devices8[:2])
    step = make_train_step(TINY, mesh, plan, learning_rate=3e-3, optimizer="adam")
    s = step.init_state(qwen3.init_params(TINY, jax.random.PRNGKey(0)))
    data = jax.random.randint(
        jax.random.PRNGKey(5), (2, 2, 8 + 1), 0, TINY.vocab_size, dtype=jnp.int32
    )
    tokens, targets = data[..., :-1], data[..., 1:]
    losses = []
    for _ in range(5):
        s, loss = step(s, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and all(np.isfinite(losses)), losses


def test_adam_requires_state():
    import pytest as _pytest

    plan = meshlib.MeshPlan()
    mesh = meshlib.make_mesh(plan, jax.devices()[:1])
    step = make_train_step(TINY, mesh, plan, optimizer="adam")
    with _pytest.raises(TypeError, match="needs optimizer state"):
        step(qwen3.init_params(TINY, jax.random.PRNGKey(0)), None, None)
