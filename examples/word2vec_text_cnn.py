"""Word2Vec embeddings + CnnSentenceDataSetIterator + 1D-conv text
classifier (reference: dl4j-examples Word2Vec + CnnSentenceClassification).
Run: python examples/word2vec_text_cnn.py
"""
import numpy as np

from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nlp import (CnnSentenceDataSetIterator,
                                    CollectionLabeledSentenceProvider,
                                    Word2Vec)
from deeplearning4j_tpu.nn.conf import (Convolution1D, GlobalPoolingLayer,
                                        InputType, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def main():
    pets = ["cat dog pet fluffy animal", "dog cat bark purr pet",
            "fluffy cat pet animal dog", "pet dog animal bark cat"]
    fin = ["stock market price trade money", "market stock trade profit",
           "price trade stock market money", "profit money market stock"]
    sentences, labels = (pets + fin) * 8, (["pets"] * 4 + ["finance"] * 4) * 8

    w2v = (Word2Vec.Builder().layerSize(16).windowSize(3)
           .minWordFrequency(1).epochs(10).seed(7)
           .iterate(sentences).build().fit())
    print("nearest to 'cat':", w2v.wordsNearest("cat", 3))

    it = CnnSentenceDataSetIterator(
        CollectionLabeledSentenceProvider(sentences, labels, rng_seed=1),
        w2v, batch_size=16, max_sentence_length=6)
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(learning_rate=5e-3)).list()
            .layer(Convolution1D(n_out=24, kernel_size=3,
                                 convolution_mode="Same",
                                 activation="relu"))
            .layer(GlobalPoolingLayer(pooling_type="max"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.recurrent(16, 6)).build())
    net = MultiLayerNetwork(conf).init()
    net.fit(it, epochs=25)
    x = it.loadSingleSentence("fluffy pet dog")
    probs = np.asarray(net.output(x))[0]
    print("p(classes | 'fluffy pet dog') =",
          dict(zip(it.getLabels(), probs.round(3))))
    return probs[it.getLabels().index("pets")]


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
