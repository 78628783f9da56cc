"""GPT-2's training step attends through the causal kernel, checked with
no chip (``ops/flash_attention.causal``; PERF.md section 6).

``CausalLM.make_train_step(Adam)`` is compiled at GPT-2-medium widths
(2 of its 24 layers) for a DESCRIBED TPU v5e, with libtpu's compiler and
no device attached, and the optimised HLO is searched. Before, the
einsum attention wrote and read back ``[4, 16, 1024, 1024]`` score and
probability matrices in every layer, forward and backward (47% of the
training cell's busy time). At 1,024 positions the program now holds
the splash kernel's calls and no such array, and needs fewer temporary
bytes than the same step with the einsum, which the control compiles by
handing ``_Causal`` its own attention as the kernel. Below the cut-over
the einsum stays: a step over 512 positions holds no kernel call.

The choice is made where the program is lowered (``lax.platform_dependent``),
so the same step traced in this CPU process runs the einsum here and
holds the kernel when it is compiled for the chip.

The topology is described inside a fixture, never at import (one
process a time may load libtpu; xdist workers all import this file).
"""

import re

import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.ops import flash_attention

ROWS, LAYERS = 4, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _medium():
    """GPT-2-medium's widths (``benchmark/configs/gpt2-medium.json``),
    2 layers, computed in bf16 as the training cell runs it."""
    return CausalLM(TransformerConfig(
        vocab_size=50257, max_len=1024, d_model=1024, n_layers=LAYERS,
        n_heads=16, d_ff=4096, dropout=0.0), compute_dtype=jnp.bfloat16)


def _compile(one_chip, t):
    """The train step over ``[ROWS, t + 1]`` ids, compiled for the
    described chip: (optimised HLO, temporary bytes)."""
    model = _medium()
    updater = Adam(learning_rate=3e-4)
    step = model.make_train_step(updater)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(model.init_params)
    opt = jax.eval_shape(updater.init_state, params)
    args = placed((params, opt, jax.ShapeDtypeStruct((), jnp.int32),
                   jax.ShapeDtypeStruct((ROWS, t + 1), jnp.int32),
                   jax.eval_shape(lambda: jax.random.key(0))))
    compiled = step.lower(*args).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def _einsum_at_1024(one_chip):
    """The control: the same step with the einsum as its 'kernel'."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(flash_attention, "causal",
                   lambda q, k, v, otherwise: otherwise(q, k, v))
        return _compile(one_chip, 1024)


def _kernel_calls(text):
    return len(re.findall(r'custom_call_target="tpu_custom_call"', text))


def _scores(text, t):
    return len(re.findall(rf"\[{ROWS},16,{t},{t}\]", text))


def test_the_train_step_at_1024_holds_the_kernel_and_no_score_matrix(
        one_chip):
    text, temp = _compile(one_chip, 1024)
    # forward and the fused backward, one call each a layer
    assert _kernel_calls(text) == 2 * LAYERS
    assert _scores(text, 1024) == 0
    control, control_temp = _einsum_at_1024(one_chip)
    # the control proves the search sees the matrices it looks for
    assert _kernel_calls(control) == 0
    assert _scores(control, 1024) > 0
    assert temp < control_temp


def test_below_the_cut_over_the_einsum_stays(one_chip):
    text, _ = _compile(one_chip, 512)
    assert _kernel_calls(text) == 0
    assert _scores(text, 512) > 0
