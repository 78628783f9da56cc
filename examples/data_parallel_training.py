"""Data-parallel training over a device mesh — the reference's
ParallelWrapper/SharedTrainingMaster workflow collapsed into sharding
declarations (gradient all-reduce = compiler-scheduled psum on ICI).

Run on any host (uses however many devices jax exposes; set
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
to simulate 8 devices): python examples/data_parallel_training.py
"""
import numpy as np

from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import (DenseLayer, InputType,
                                        NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import ShardedTrainer


def main():
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=1e-2)).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.feedForward(10)).build())
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(512, 10)).astype(np.float32)
    lab = np.argmax(x[:, :3], axis=1)
    y = np.eye(3, dtype=np.float32)[lab]

    trainer = ShardedTrainer(net)           # mesh over all devices
    print("mesh:", trainer.mesh)
    trainer.fit(ArrayDataSetIterator(x, y, 64), epochs=10)
    acc = (np.asarray(net.output(x)).argmax(-1) == lab).mean()
    print("accuracy:", acc)
    return acc


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
