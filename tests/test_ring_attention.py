"""Ring/Ulysses context parallelism vs dense attention (exact-math
check on the virtual 8-device CPU mesh — the reference has no sequence
parallelism at all, SURVEY.md §5, so the reference here is our own
single-device dense attention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from deeplearning4j_tpu.parallel.mesh import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.ring_attention import (
    dense_attention, ring_attention, ulysses_attention,
)

B, H, T, D = 2, 8, 32, 16


def _mesh(sp=4, data=2):
    devs = np.array(jax.devices()[:sp * data]).reshape(data, sp)
    return Mesh(devs, ("data", "sp"))


def _qkv(key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, T, D), jnp.float32)
    return q, k, v


def _run_sharded(fn, mesh, q, k, v, kv_mask=None):
    spec = P(None, None, "sp", None)
    mspec = P(None, "sp")
    if kv_mask is None:
        f = shard_map(lambda a, b, c: fn(a, b, c), mesh=mesh,
                      in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)
        return jax.jit(f)(q, k, v)
    f = shard_map(lambda a, b, c, m: fn(a, b, c, kv_mask=m), mesh=mesh,
                  in_specs=(spec, spec, spec, mspec), out_specs=spec,
                  check_vma=False)
    return jax.jit(f)(q, k, v, kv_mask)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_matches_dense(impl):
    mesh = _mesh()
    q, k, v = _qkv()
    want = dense_attention(q, k, v)
    got = _run_sharded(impl, mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_causal_matches_dense(impl):
    mesh = _mesh()
    q, k, v = _qkv(1)

    def f(a, b, c):
        return impl(a, b, c, causal=True)

    want = dense_attention(q, k, v, causal=True)
    got = _run_sharded(f, mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_key_padding_mask(impl):
    mesh = _mesh()
    q, k, v = _qkv(2)
    mask = jnp.concatenate(
        [jnp.ones((B, T - 7)), jnp.zeros((B, 7))], axis=1)
    want = dense_attention(q, k, v, kv_mask=mask)
    got = _run_sharded(impl, mesh, q, k, v, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_ring_grads_match_dense():
    mesh = _mesh()
    q, k, v = _qkv(3)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    spec = P(None, None, "sp", None)

    def loss_ring(q, k, v):
        f = shard_map(
            lambda a, b, c: ring_attention(a, b, c), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
        return jnp.sum(f(q, k, v) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=3e-4, rtol=3e-4)


def test_ring_train_step_matches_unsharded():
    """Full context-parallel MLM step == unsharded step (dropout off)."""
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.models.transformer import (
        TransformerEncoder, tiny_config,
    )

    cfg = tiny_config(vocab=64, max_len=32, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    model = TransformerEncoder(cfg)
    updater = Adam(learning_rate=1e-3)
    mesh = _mesh(sp=4, data=2)

    params = model.init_params()
    rng = jax.random.key(7)
    ids = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    labels = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    mask_pos = jnp.ones((4, 32), jnp.float32)

    ref_step = model.make_train_step(updater)
    p1, _, loss1 = ref_step(jax.tree_util.tree_map(jnp.copy, params),
                            updater.init_state(params), jnp.asarray(0),
                            ids, labels, mask_pos, rng)

    ring_step = model.make_ring_train_step(updater, mesh)
    with mesh:
        p2, _, loss2 = ring_step(
            jax.tree_util.tree_map(jnp.copy, params),
            updater.init_state(params), jnp.asarray(0),
            ids, labels, mask_pos, rng)

    np.testing.assert_allclose(float(loss2), float(loss1),
                               atol=1e-5, rtol=1e-5)
    fl1 = jax.tree_util.tree_leaves(p1)
    fl2 = jax.tree_util.tree_leaves(p2)
    for a, b in zip(fl1, fl2):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4)


def test_ring_train_step_pad_mask_matches_unsharded():
    """Padded batch through the ring path == unsharded dense with the
    same key-padding mask (dropout off)."""
    from deeplearning4j_tpu.learning.updaters import Sgd
    from deeplearning4j_tpu.models.transformer import (
        TransformerEncoder, tiny_config,
    )

    cfg = tiny_config(vocab=64, max_len=32, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    model = TransformerEncoder(cfg)
    mesh = _mesh(sp=4, data=2)
    params = model.init_params()
    rng = jax.random.key(11)
    ids = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    pad = jnp.concatenate(
        [jnp.ones((4, 25)), jnp.zeros((4, 7))], axis=1)
    mask_pos = pad  # loss only on real tokens

    # unsharded reference loss with the same padding mask
    def ref_loss(p):
        hidden = model.encode(p, ids, mask=pad, train=False)
        logits = model.mlm_logits(p, hidden).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tok_lp = jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
        return -jnp.sum(tok_lp * mask_pos) / jnp.sum(mask_pos)

    want = float(ref_loss(params))
    upd = Sgd(learning_rate=0.0)
    step = model.make_ring_train_step(upd, mesh)
    with mesh:
        _, _, loss = step(params, upd.init_state(params), jnp.asarray(0),
                          ids, ids, mask_pos, rng, pad_mask=pad)
    np.testing.assert_allclose(float(loss), want, atol=1e-5, rtol=1e-5)


def test_ring_seq_overflow_raises():
    from deeplearning4j_tpu.learning.updaters import Sgd
    from deeplearning4j_tpu.models.transformer import (
        TransformerEncoder, tiny_config,
    )

    cfg = tiny_config(vocab=64, max_len=16, d_model=32, n_layers=1,
                      n_heads=4, d_ff=64)
    model = TransformerEncoder(cfg)
    mesh = _mesh(sp=4, data=2)
    upd = Sgd(learning_rate=1e-2)
    params = model.init_params()
    ids = jnp.zeros((4, 32), jnp.int32)  # global 32 > max_len 16
    step = model.make_ring_train_step(upd, mesh)
    with pytest.raises(ValueError, match="exceeds"):
        with mesh:
            step(params, upd.init_state(params), jnp.asarray(0), ids, ids,
                 jnp.ones((4, 32), jnp.float32), jax.random.key(0))


def test_ulysses_train_step_runs():
    from deeplearning4j_tpu.learning.updaters import Sgd
    from deeplearning4j_tpu.models.transformer import (
        TransformerEncoder, tiny_config,
    )

    cfg = tiny_config(vocab=64, max_len=32, d_model=32, n_layers=1,
                      n_heads=4, d_ff=64)
    model = TransformerEncoder(cfg)
    updater = Sgd(learning_rate=1e-2)
    mesh = _mesh(sp=4, data=2)
    params = model.init_params()
    rng = jax.random.key(0)
    ids = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    step = model.make_ring_train_step(updater, mesh, attn="ulysses")
    with mesh:
        p, _, loss = step(params, updater.init_state(params),
                          jnp.asarray(0), ids, ids,
                          jnp.ones((4, 32), jnp.float32), rng)
    assert np.isfinite(float(loss))
