"""Transfer learning: pretrain a conv net on task A, freeze the
feature extractor, swap the head, fine-tune on task B.

Reference workflow (dl4j-examples EditLastLayerOthersFrozen):
TransferLearning.Builder(net).fineTuneConfiguration(...)
.setFeatureExtractor(idx).removeOutputLayer().addLayer(newHead). The
TPU-native twist: the frozen prefix still lives inside the SAME
compiled training step (frozen layers simply get a NoOp updater), so
fine-tuning stays one XLA program.

Synthetic tasks (zero-egress): task A = classify which quadrant holds
a bright blob (4 classes); task B = blob bright vs dim (2 classes,
same visual features).

Run: python examples/transfer_learning.py [--epochs 8]
"""

from __future__ import annotations

import argparse

import numpy as np

from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import (
    ConvolutionLayer, DenseLayer, InputType, NeuralNetConfiguration,
    OutputLayer, SubsamplingLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.transferlearning import (
    FineTuneConfiguration, TransferLearning,
)


def blobs(n, task, rng):
    x = rng.normal(0, 0.1, (n, 20, 20, 1)).astype(np.float32)
    if task == "quadrant":
        labels = rng.integers(0, 4, n)
        for i, lab in enumerate(labels):
            r, c = divmod(int(lab), 2)
            x[i, r * 10:r * 10 + 10, c * 10:c * 10 + 10, 0] += 1.0
        return x, np.eye(4, dtype=np.float32)[labels], labels
    labels = rng.integers(0, 2, n)         # bright vs dim, random spot
    for i, lab in enumerate(labels):
        r, c = rng.integers(0, 2, 2)
        x[i, r * 10:r * 10 + 10, c * 10:c * 10 + 10, 0] += \
            1.0 if lab else 0.35
    return x, np.eye(2, dtype=np.float32)[labels], labels


def main(epochs: int = 8):
    rng = np.random.default_rng(0)
    xa, ya, la = blobs(512, "quadrant", rng)

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=2e-3)).list()
            .layer(ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.convolutional(20, 20, 1)).build())
    base = MultiLayerNetwork(conf).init()
    for _ in range(epochs * 15):      # fit(x, y) is ONE step per call
        base.fit(xa, ya)
    acc_a = (np.asarray(base.output(xa).toNumpy()).argmax(1) == la).mean()
    print(f"task A (quadrant) accuracy: {acc_a:.3f}")

    # surgery: freeze conv features, new 2-class head
    tuned = (TransferLearning.Builder(base)
             .fineTuneConfiguration(FineTuneConfiguration(
                 updater=Adam(learning_rate=2e-3)))
             .setFeatureExtractor(1)          # freeze conv + pool
             .removeOutputLayer()
             .addLayer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent", n_in=32))
             .build())

    frozen_before = np.asarray(tuned.params_list[0]["W"])
    xb, yb, lb = blobs(512, "bright", rng)
    for _ in range(epochs * 15):
        tuned.fit(xb, yb)
    acc_b = (np.asarray(tuned.output(xb).toNumpy()).argmax(1) == lb).mean()
    frozen_after = np.asarray(tuned.params_list[0]["W"])
    print(f"task B (bright/dim) accuracy after fine-tune: {acc_b:.3f}")
    assert np.array_equal(frozen_before, frozen_after), \
        "frozen conv weights moved!"
    assert acc_b > 0.9, acc_b
    return float(acc_b)


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    main(ap.parse_args().epochs)
