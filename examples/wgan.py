"""WGAN on 2-D synthetic data — the DL4J GAN recipe, TPU-native.

Reference workflow (dl4j-examples MnistGAN / GAN tutorials): TWO
networks sharing critic weights — a critic trained directly, and a
"GAN" network whose head is the critic wrapped in
FrozenLayerWithBackprop so generator updates flow THROUGH the frozen
critic (params stop_gradient'ed, epsilons pass); critic weights are
copied into the frozen tail every outer step. Uses the Wasserstein
loss (LossFunction.WASSERSTEIN) with weight clipping — the WGAN
formulation. Every fit() on either network is still one compiled XLA
step.

Task (zero-egress): learn to generate points from N([3,3], 0.25*I)
starting from an 8-D normal latent. Convergence metric: distance of
the generated mean from [3,3].

Run: python examples/wgan.py [--iters 300]
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from deeplearning4j_tpu.learning import NoOp, RmsProp
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.transferlearning import FrozenLayerWithBackprop

LATENT = 8
CLIP = 0.1


def _critic_layers():
    return [DenseLayer(n_out=48, activation="leakyrelu"),
            DenseLayer(n_out=48, activation="leakyrelu")]


def build_nets():
    c0, c1 = _critic_layers()
    critic_conf = (NeuralNetConfiguration.builder().seed(1)
                   .updater(RmsProp(learning_rate=5e-3)).list()
                   .layer(c0)
                   .layer(c1)
                   .layer(OutputLayer(n_out=1, activation="identity",
                                      loss="wasserstein"))
                   .setInputType(InputType.feedForward(2)).build())
    critic = MultiLayerNetwork(critic_conf).init()

    g0, g1 = _critic_layers()     # fresh configs for the frozen tail
    gan_conf = (NeuralNetConfiguration.builder().seed(2)
                .updater(RmsProp(learning_rate=5e-3)).list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(DenseLayer(n_out=2, activation="identity"))
                .layer(FrozenLayerWithBackprop(layer=g0))
                .layer(FrozenLayerWithBackprop(layer=g1))
                .layer(OutputLayer(n_out=1, activation="identity",
                                   loss="wasserstein", updater=NoOp()))
                .setInputType(InputType.feedForward(LATENT)).build())
    gan = MultiLayerNetwork(gan_conf).init()
    return critic, gan


def sync_critic_into_gan(critic, gan):
    import jax.numpy as jnp

    # REAL copies, not references: fit() donates its param buffers to
    # XLA, so sharing arrays between the two networks would let the
    # GAN step delete the critic's live buffers
    for i in range(3):
        gan.params_list[2 + i] = jax.tree_util.tree_map(
            jnp.copy, critic.params_list[i])


def clip_critic(critic):
    import jax.numpy as jnp

    critic.params_list = [
        jax.tree_util.tree_map(lambda a: jnp.clip(a, -CLIP, CLIP), p)
        for p in critic.params_list]


def main(iters: int = 300):
    rng = np.random.default_rng(0)
    critic, gan = build_nets()
    target = np.asarray([3.0, 3.0], np.float32)
    n = 128
    minus = -np.ones((n, 1), np.float32)        # "real" direction
    plus = np.ones((n, 1), np.float32)          # "fake" direction

    def fakes(k):
        z = rng.normal(0, 1, (k, LATENT)).astype(np.float32)
        return z, np.asarray(gan.feedForward(z)[2].toNumpy())

    _, f0 = fakes(512)
    d0 = float(np.linalg.norm(f0.mean(0) - target))

    for it in range(iters):
        for _ in range(3):                      # critic steps per gen step
            real = (target + rng.normal(0, 0.5, (n, 2))).astype(np.float32)
            _, fake = fakes(n)
            x = np.concatenate([real, fake])
            y = np.concatenate([minus, plus])   # maximize f(real)-f(fake)
            critic.fit(x, y)
            clip_critic(critic)
        sync_critic_into_gan(critic, gan)
        z = rng.normal(0, 1, (n, LATENT)).astype(np.float32)
        gan.fit(z, minus)                       # generator: look "real"
        if (it + 1) % 100 == 0:
            _, f = fakes(512)
            print(f"iter {it+1}: generated mean {f.mean(0).round(2)}")

    _, f1 = fakes(512)
    d1 = float(np.linalg.norm(f1.mean(0) - target))
    print(f"mean distance to target: {d0:.2f} -> {d1:.2f}")
    assert d1 < 0.75 and d1 < d0 / 3, (d0, d1)
    # frozen critic head in the GAN must have stayed in sync, not trained
    np.testing.assert_array_equal(
        np.asarray(gan.params_list[2]["W"]),
        np.asarray(critic.params_list[0]["W"]))
    return d1


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=300)
    main(ap.parse_args().iters)
