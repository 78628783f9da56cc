"""The causal kernel under ``CausalLM`` (``ops/flash_attention.causal``):
its rule, its mathematics and what stays as it was.

- The rule: a fresh sequence of ``FLASH_MIN_KV`` (1,024) positions or
  more, whole lane tiles, takes the splash kernel where the program is
  lowered for a TPU; anything shorter, and any program lowered off the
  TPU, attends through the einsum exactly as before (same bits).
- The mathematics: the kernel, run here through the Pallas interpreter,
  gives the einsum's output and gradients, alone and as the attention
  of a whole training loss. The bf16 kernel is no further from a
  float32 reference than the bf16 einsum is.

What the chip compiles (kernel calls, no score matrix) is
``tests/test_causal_flash_aot.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import gpt
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.ops import flash_attention as fa

T = fa.FLASH_MIN_KV


def _old_attend(self, li, q, k, v):
    """``_Causal.attend`` as it was before the kernel: the einsum."""
    c, n, t = self.cfg, self.n, self.t
    q, k, v = (y.reshape(n, t, c.n_heads, c.head_dim)
               .transpose(0, 2, 1, 3) for y in (q, k, v))
    self.ks.append(k)
    self.vs.append(v)
    ctx = gpt._attention(q, k, v, self.mask)
    return ctx.transpose(0, 2, 1, 3).reshape(n, t, c.d_model)


def _model(dtype, max_len=T, vocab=64):
    cfg = tiny_config(vocab=vocab, max_len=max_len, d_model=128,
                      n_layers=2, n_heads=2, d_ff=256)
    cfg.dropout = 0.0
    return CausalLM(cfg, compute_dtype=dtype)


def _qkv(dtype, n=1, h=2, t=T, hd=64):
    ks = jax.random.split(jax.random.key(3), 4)
    return [jax.random.normal(k, (n, h, t, hd), jnp.float32).astype(dtype)
            for k in ks]


def _fwd_bwd(f, q, k, v, do):
    out, pullback = jax.vjp(f, q, k, v)
    return (out, *pullback(do))


def test_the_rule():
    assert [fa.causal_fits(t) for t in (512, 1000, 1023, 1024, 1100,
                                        1152, 2048)] == [
        False, False, False, True, False, True, True]
    # _Causal imports the kernel's module only from this length on
    assert gpt._Causal(_model(jnp.float32).cfg, 1, T).flash is fa.causal
    assert gpt._Causal(_model(jnp.float32).cfg, 1, T - 128).flash is None
    assert gpt._Causal(_model(jnp.float32).cfg, 1, T + 1).flash is None


def test_the_causal_path_in_a_tpu_process(monkeypatch):
    """``attention(impl="auto")`` as a TPU process routes it: a causal
    self-attention with no mask from 1,024 keys takes the splash kernel;
    a non-causal or masked call keeps the library kernel, a short one
    XLA's einsum. ``causal`` there calls the kernel directly."""
    taken = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "splash_causal",
                        lambda q, k, v: taken.append("splash") or q)
    monkeypatch.setattr(fa, "_library_flash",
                        lambda q, k, v, m, c: taken.append("library") or q)
    monkeypatch.setattr(fa, "_xla_attention",
                        lambda q, k, v, m, c: taken.append("xla") or q)
    q = jnp.zeros((1, 2, T, 64), jnp.bfloat16)
    fa.attention(q, q, q, causal=True)
    fa.attention(q, q, q)
    fa.attention(q, q, q, mask=jnp.ones((1, T)), causal=True)
    fa.attention(q[:, :, :512], q[:, :, :512], q[:, :, :512], causal=True)
    # a TPU process traces the kernel alone, no branch for other chips
    fa.causal(q, q, q, otherwise=lambda *a: taken.append("einsum") or q)
    assert taken == ["splash", "library", "library", "xla", "splash"]


@pytest.mark.parametrize("t", [512, T])
def test_forward_and_prefill_keep_the_einsum_bits(monkeypatch, t):
    """Below the cut-over the kernel is never asked for; at it, a CPU
    lowering takes the einsum branch. Both give the old bits."""
    m = _model(jnp.bfloat16)
    params = m.init_params(jax.random.key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, t)),
                      jnp.int32)
    if t < T:
        def refuse(*a, **k):
            raise AssertionError("the kernel was asked for below 1,024")
        monkeypatch.setattr(fa, "causal", refuse)
    run = jax.jit(lambda p, x: m.forward(p, x, return_kv=True))
    pre = jax.jit(lambda p, x: m.prefill(p, x[:1], t - 7))
    got, got_pre = run(params, ids), pre(params, ids)
    monkeypatch.setattr(gpt._Causal, "attend", _old_attend)
    want = jax.jit(lambda p, x: m.forward(p, x, return_kv=True))(params,
                                                                 ids)
    want_pre = jax.jit(lambda p, x: m.prefill(p, x[:1], t - 7))(params,
                                                                ids)
    for a, b in zip(jax.tree_util.tree_leaves((got, got_pre)),
                    jax.tree_util.tree_leaves((want, want_pre))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_kernel_gives_the_einsums_output_and_gradients_in_f32():
    q, k, v, do = _qkv(jnp.float32)
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    with jax.default_matmul_precision("highest"):
        want = _fwd_bwd(lambda q, k, v: gpt._attention(q, k, v, mask),
                        q, k, v, do)
    got = _fwd_bwd(lambda q, k, v: fa.splash_causal(q, k, v,
                                                    interpret=True),
                   q, k, v, do)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale, name


def test_the_bf16_kernel_is_no_further_from_f32_than_the_bf16_einsum():
    q, k, v, do = _qkv(jnp.bfloat16)
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    f32 = [a.astype(jnp.float32) for a in (q, k, v, do)]
    with jax.default_matmul_precision("highest"):
        ref = _fwd_bwd(lambda q, k, v: gpt._attention(q, k, v, mask), *f32)
    einsum = _fwd_bwd(lambda q, k, v: gpt._attention(q, k, v, mask),
                      q, k, v, do)
    kernel = _fwd_bwd(lambda q, k, v: fa.splash_causal(q, k, v,
                                                       interpret=True),
                      q, k, v, do)
    for name, a, b, r in zip(("out", "dq", "dk", "dv"), kernel, einsum,
                             ref):
        gap_k = float(jnp.max(jnp.abs(a.astype(jnp.float32) - r)))
        gap_e = float(jnp.max(jnp.abs(b.astype(jnp.float32) - r)))
        assert gap_k <= gap_e, (name, gap_k, gap_e)


def test_a_training_loss_and_its_gradients_match_through_the_kernel(
        monkeypatch):
    """The whole ``lm_loss`` at 1,024 positions, float32: the CPU's
    einsum against the kernel, steered here onto its interpreter."""
    m = _model(jnp.float32)
    params = m.init_params(jax.random.key(1))
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (1, T + 1)),
                      jnp.int32)
    loss_grad = jax.value_and_grad(lambda p: m.lm_loss(p, ids))
    with jax.default_matmul_precision("highest"):
        want = loss_grad(params)
    calls = []

    def kernel(q, k, v, otherwise):
        calls.append(q.shape)
        return fa.splash_causal(q, k, v, interpret=True)

    monkeypatch.setattr(fa, "causal", kernel)
    with jax.default_matmul_precision("highest"):
        got = loss_grad(params)
    assert calls == [(1, 2, T, 64)] * 2
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale
