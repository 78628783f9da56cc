"""VAE anomaly detection (the reference's headline VariationalAutoencoder
workflow: pretrain unsupervised on 'normal' data, then score new points
by importance-sampled reconstruction log-probability — low score =
anomalous).

Reference classes: conf/layers/variational/VariationalAutoencoder,
MultiLayerNetwork#pretrain, VariationalAutoencoder#
reconstructionLogProbability. Synthetic data (zero-egress environment).

Run: python examples/vae_anomaly.py [--steps 200]
"""

from __future__ import annotations

import argparse

import numpy as np

from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import (
    InputType, NeuralNetConfiguration, OutputLayer, VariationalAutoencoder,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def main(steps: int = 200):
    rng = np.random.default_rng(0)
    d = 16
    # "normal" data: two gaussian clusters
    centers = np.stack([np.full(d, 1.5), np.full(d, -1.5)])
    x_train = (centers[rng.integers(0, 2, 512)]
               + rng.normal(0, 0.3, (512, d))).astype(np.float32)

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=1e-2))
            .list()
            .layer(VariationalAutoencoder(
                n_out=4, encoder_layer_sizes=(32,),
                decoder_layer_sizes=(32,), activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))  # unused head; VAE is layer 0
            .setInputType(InputType.feedForward(d))
            .build())
    net = MultiLayerNetwork(conf).init()

    for i in range(steps):
        net.pretrainLayer(0, x_train)
        if (i + 1) % 50 == 0:
            print(f"pretrain step {i+1}: -ELBO = {net.score():.3f}")

    inliers = (centers[rng.integers(0, 2, 64)]
               + rng.normal(0, 0.3, (64, d))).astype(np.float32)
    outliers = rng.normal(0, 4.0, (64, d)).astype(np.float32)
    s_in = np.asarray(net.reconstructionLogProbability(
        0, inliers, num_samples=16).toNumpy())
    s_out = np.asarray(net.reconstructionLogProbability(
        0, outliers, num_samples=16).toNumpy())
    thresh = np.percentile(s_in, 5)
    flagged = (s_out < thresh).mean()
    print(f"median log p(x): inliers {np.median(s_in):.1f}, "
          f"outliers {np.median(s_out):.1f}")
    print(f"outliers flagged at 5%-FPR threshold: {100*flagged:.0f}%")
    assert np.median(s_in) > np.median(s_out), "anomaly score failed"
    return float(flagged)


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    main(ap.parse_args().steps)
