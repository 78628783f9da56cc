"""Fine-tune a (tiny) BERT encoder for sequence classification — the
reference's SameDiff-BERT downstream workflow, compiled to one XLA
step. Run: python examples/bert_finetune.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.learning.updaters import AdamW
from deeplearning4j_tpu.models.bert_classifier import BertSequenceClassifier
from deeplearning4j_tpu.models.transformer import tiny_config


def main(steps=80):
    cfg = tiny_config(vocab=1000, max_len=32, d_model=64, n_layers=2,
                      n_heads=4, d_ff=128)
    model = BertSequenceClassifier(cfg, n_classes=2)
    params = model.init_params(jax.random.key(0))
    updater = AdamW(learning_rate=3e-3, weight_decay=1e-4)
    opt = updater.init_state(params)
    step = model.make_train_step(updater)

    rng = np.random.default_rng(0)
    ids = rng.integers(2, 1000, (128, 32))
    labels = (ids < 500).mean(axis=1) > 0.5   # synthetic sentiment
    ids_j = jnp.asarray(ids)
    lab_j = jnp.asarray(labels.astype(np.int64))
    for i in range(steps):
        params, opt, loss = step(params, opt, jnp.asarray(i), ids_j,
                                 lab_j, None, jax.random.key(1))
        if i % 20 == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    acc = (np.asarray(model.predict(params, ids_j)) == labels).mean()
    print("train accuracy:", acc)
    return acc


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
