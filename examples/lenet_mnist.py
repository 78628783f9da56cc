"""LeNet-5 on MNIST — the reference's canonical first example
(dl4j-examples LeNetMNIST). Uses the real MNIST IDX files when present
under ~/.deeplearning4j_tpu/mnist (no network egress here), else a
synthetic stand-in so the example always runs.

Run: python examples/lenet_mnist.py
"""
import numpy as np

from deeplearning4j_tpu.datasets import (ArrayDataSetIterator,
                                         MnistDataSetIterator)
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import (ConvolutionLayer, DenseLayer,
                                        InputType, NeuralNetConfiguration,
                                        OutputLayer, SubsamplingLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.util.model_serializer import ModelSerializer


def data(batch=64, n=4096):
    try:
        return (MnistDataSetIterator(batch, train=True, num_examples=n),
                MnistDataSetIterator(batch, train=False, num_examples=n))
    except FileNotFoundError:
        rng = np.random.default_rng(0)
        x = rng.normal(0, 0.1, (n, 784)).astype(np.float32)
        lab = rng.integers(0, 10, n)
        for i, c in enumerate(lab):  # separable synthetic digits
            x[i, c * 78:(c + 1) * 78] += 1.0
        y = np.eye(10, dtype=np.float32)[lab]
        return (ArrayDataSetIterator(x[:n // 2], y[:n // 2], batch),
                ArrayDataSetIterator(x[n // 2:], y[n // 2:], batch))


def main(epochs=3):
    conf = (NeuralNetConfiguration.builder()
            .seed(123).updater(Adam(learning_rate=1e-3)).list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.convolutionalFlat(28, 28, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    train_it, test_it = data()
    net.fit(train_it, epochs=epochs)
    ev = net.evaluate(test_it)
    print(ev.stats())
    ModelSerializer.writeModel(net, "/tmp/lenet-mnist.zip", True)
    print("saved to /tmp/lenet-mnist.zip; accuracy:", ev.accuracy())
    return ev.accuracy()


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
