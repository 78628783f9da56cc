"""Import a REAL frozen TF BERT GraphDef and fine-tune it — the
reference's headline SameDiff path (SURVEY.md §3.4: ImportGraph +
SameDiff.fit on an imported BERT).

Builds a randomly-initialized HuggingFace TFBertForMaskedLM locally
(no network), freezes it to a GraphDef (the same artifact a user's
saved model produces), imports it node-by-node into SameDiff — where
it executes as ONE XLA program — golden-checks the logits against TF,
promotes the frozen weights to variables, and runs MLM fine-tuning.

Run: python examples/tf_import_bert.py [--layers 2] [--hidden 64]
(full BERT-base: --layers 12 --hidden 768 — needs a few minutes of
import+compile on CPU).
"""

from __future__ import annotations

import argparse

import numpy as np


def main(layers: int = 2, hidden: int = 64, steps: int = 15):
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )
    from transformers import BertConfig, TFBertForMaskedLM

    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.datasets.multi_dataset import MultiDataSet
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.modelimport.tensorflow.tf_import import (
        TFGraphMapper,
    )

    seq, vocab = 16, 200
    cfg = BertConfig(num_hidden_layers=layers, hidden_size=hidden,
                     num_attention_heads=max(2, hidden // 32),
                     intermediate_size=hidden * 4, vocab_size=vocab,
                     max_position_embeddings=seq * 2)
    m = TFBertForMaskedLM(cfg)

    @tf.function
    def f(ids, mask, tt):
        return m(input_ids=ids, attention_mask=mask, token_type_ids=tt,
                 training=False).logits

    spec = [tf.TensorSpec([None, seq], tf.int32)] * 3
    frozen = convert_variables_to_constants_v2(
        f.get_concrete_function(*spec))
    gd = frozen.graph.as_graph_def()
    ins = [t.name.split(":")[0] for t in frozen.inputs]
    out = frozen.outputs[0].name.split(":")[0]
    print(f"frozen GraphDef: {len(gd.node)} nodes")

    sd = TFGraphMapper.importGraph(gd)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (4, seq)).astype(np.int32)
    mask = np.ones((4, seq), np.int32)
    tt = np.zeros((4, seq), np.int32)
    ref = np.asarray(frozen(tf.constant(ids), tf.constant(mask),
                            tf.constant(tt))[0])
    got = np.asarray(sd.output(dict(zip(ins, [ids, mask, tt])),
                               [out])[out])
    err = float(np.abs(got - ref).max())
    print(f"golden check vs TF: max abs err {err:.2e}")
    assert err < 2e-3

    # promote frozen weights -> trainables (one atomic call), attach an
    # MLM loss, fit
    def _is_weight(v):
        if v.vtype.value != "CONSTANT":
            return False
        a = np.asarray(v.getArr())
        return a.ndim >= 2 and a.dtype.kind == "f"

    to_promote = [v.name for v in sd.variables() if _is_weight(v)]
    sd.convertConstantsToVariables(*to_promote)

    y = sd.placeholder("y_ids", shape=(None, seq))
    oh = sd.math.one_hot(y, depth=vocab)
    logp = sd.nn.log_softmax(sd.getVariable(out))
    loss = -(oh * logp).sum(-1).mean()
    sd.setLossVariables(loss.name)
    sd.setTrainingConfig(TrainingConfig(
        updater=Adam(1e-2), data_set_feature_mapping=list(ins),
        data_set_label_mapping=["y_ids"]))
    targets = rng.integers(0, vocab, (4, seq)).astype(np.int32)
    hist = sd.fit(MultiDataSet([ids, mask, tt], [targets]),
                  epochs=steps)
    print(f"fine-tune loss: {hist.loss_curve[0]:.3f} -> "
          f"{hist.loss_curve[-1]:.3f}")
    return hist.loss_curve[-1] < hist.loss_curve[0]


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    a = ap.parse_args()
    main(a.layers, a.hidden)
