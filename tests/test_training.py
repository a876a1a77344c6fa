"""Training substrate: optimizer convergence, checkpoint/restart (incl.
elastic restore), gradient compression error feedback."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config, get_model
from repro.training import checkpoint as ckpt
from repro.training.compression import (compress_psum, dequantize_int8, ef_init,
                                        quantize_int8)
from repro.training.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                      clip_by_global_norm, lr_schedule)
from repro.training.train_step import init_train_state, make_train_step


def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0, total_steps=200)
    for _ in range(150):
        grads = jax.tree.map(lambda p: 2 * p, params)  # d/dp ||p||^2
        params, state, _ = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_lr_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in [0, 5, 10, 55, 100]]
    assert lrs[0] == 0.0 and lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0, abs=0.01)
    assert lrs[3] < lrs[2] and lrs[4] < lrs[3]
    assert lrs[4] >= 0.099  # floor at 10%


def test_grad_clip():
    grads = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(1000.0), rel=1e-5)
    cn = float(jnp.sqrt(jnp.sum(jnp.square(clipped["a"]))))
    assert cn == pytest.approx(1.0, rel=1e-4)


def test_small_lm_loss_decreases():
    """A few steps of real training on a tiny qwen2-style model."""
    cfg = get_config("qwen2-0.5b").reduced().replace(n_layers=2, vocab_size=128)
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    state = init_train_state(model, key)
    step = jax.jit(make_train_step(
        model, AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40),
        remat=False))
    tokens = jax.random.randint(key, (4, 64), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    losses = []
    for _ in range(25):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[::6]


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("xlstm-125m").reduced()
    model = get_model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path), 7, state, extra={"arch": cfg.name})
    assert ckpt.latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, manifest = ckpt.restore(str(tmp_path), 7, like)
    assert manifest["extra"]["arch"] == cfg.name
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restart_training_continues(tmp_path):
    """Crash/restart: restore mid-run and keep training — loss keeps the
    trajectory (fault-tolerance contract)."""
    cfg = get_config("qwen2-0.5b").reduced().replace(n_layers=1, vocab_size=64)
    model = get_model(cfg)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3), remat=False))
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}

    state = init_train_state(model, key)
    for _ in range(3):
        state, _ = step(state, batch)
    ckpt.save(str(tmp_path), 3, state)
    state_a, _ = step(state, batch)  # uninterrupted step 4

    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, _ = ckpt.restore(str(tmp_path), 3, like)
    state_b, _ = step(restored, batch)  # step 4 after "restart"
    for a, b in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(state_b["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    state = {"w": jnp.zeros((4, 4))}
    ckpt.save(str(tmp_path), 0, state)
    like = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    with pytest.raises(ValueError, match="mismatch"):
        ckpt.restore(str(tmp_path), 0, like)


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore onto a different sharding layout (elastic rescale): the mesh
    at restore time re-applies the sharding rules — values are identical."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    ckpt.save(str(tmp_path), 1, state)
    like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    shd = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = ckpt.restore(str(tmp_path), 1, like, shardings=shd)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert restored["w"].sharding == shd["w"]


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------
def test_int8_quantization_bounded_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) / 2 + 1e-6


def test_compress_psum_error_feedback_single_device():
    """With axis size 1, compressed psum == dequantized grad and the residual
    carries the quantization error (bias correction over steps)."""
    from jax.sharding import Mesh
    from functools import partial
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("dp",))
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (64,))}
    res = ef_init(grads)

    f = jax.shard_map(partial(compress_psum, axis_name="dp"),
                  mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    mean, new_res = f(grads, res)
    np.testing.assert_allclose(np.asarray(mean["w"] + new_res["w"]),
                               np.asarray(grads["w"]), atol=1e-5)
