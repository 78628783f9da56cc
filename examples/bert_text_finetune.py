"""Raw text → WordPiece → BertIterator → BERT fine-tune, end to end.

The reference capability this mirrors: BertWordPieceTokenizer over a
vocab file + BertIterator building (ids, segments, masks) minibatches
feeding a SameDiff BERT classifier (SURVEY.md §2.35,
deeplearning4j-nlp-parent). TPU-native: fixed-length int32 batches, so
every minibatch reuses ONE compiled train step.

Run: python examples/bert_text_finetune.py [--epochs 8]
Self-contained (builds a toy sentiment corpus + vocab inline; no
downloads — the environment has no egress).
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def build_corpus():
    pos_words = ["great", "wonderful", "excellent", "loved", "amazing"]
    neg_words = ["terrible", "awful", "boring", "hated", "dreadful"]
    rng = np.random.default_rng(0)
    data = []
    for _ in range(60):
        w = rng.choice(pos_words, 2, replace=True)
        data.append((f"the movie was {w[0]} and {w[1]}", 1))
        w = rng.choice(neg_words, 2, replace=True)
        data.append((f"the movie was {w[0]} and {w[1]}", 0))
    rng.shuffle(data)
    vocab = (["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
              "the", "movie", "was", "and"] + pos_words + neg_words +
             ["##ly", "##ing", ".", ","])
    return data, vocab


def main(epochs: int = 8, batch: int = 16) -> float:
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.models.bert_classifier import (
        BertSequenceClassifier,
    )
    from deeplearning4j_tpu.models.transformer import tiny_config
    from deeplearning4j_tpu.nlp import (BertIterator,
                                        BertWordPieceTokenizer)

    data, vocab = build_corpus()
    # vocab round-trips through the on-disk BERT vocab format
    vpath = os.path.join(tempfile.mkdtemp(), "vocab.txt")
    with open(vpath, "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")
    wp = BertWordPieceTokenizer(vpath)

    train, test = data[:96], data[96:]
    it = (BertIterator.builder().tokenizer(wp)
          .lengthHandling("FIXED_LENGTH", 16)
          .minibatchSize(batch).sentenceProvider(train)
          .task(BertIterator.SEQ_CLASSIFICATION).build())

    cfg = tiny_config(vocab=len(vocab), max_len=16, d_model=64,
                      n_layers=2, n_heads=4, d_ff=128)
    model = BertSequenceClassifier(cfg, n_classes=2)
    params = model.init_params()
    updater = Adam(learning_rate=3e-3)
    opt = updater.init_state(params)
    step = model.make_train_step(updater)

    rng = jax.random.key(0)
    for epoch in range(epochs):
        losses = []
        for b in it:
            params, opt, loss = step(params, opt, np.int32(epoch),
                                     b["ids"], b["labels"], b["mask"],
                                     rng)
            losses.append(float(loss))
        print(f"epoch {epoch}: loss {sum(losses)/len(losses):.4f}")

    test_it = BertIterator(wp, test, length=16, batch_size=len(test))
    b = next(iter(test_it))
    preds = np.asarray(model.predict(params, b["ids"], mask=b["mask"]))
    acc = float((preds == b["labels"]).mean())
    print(f"test accuracy: {acc:.3f} ({len(test)} held-out sentences)")
    assert acc >= 0.9, "text->fine-tune pipeline failed to learn"
    print("OK")
    return acc


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch", type=int, default=16)
    a = ap.parse_args()
    main(epochs=a.epochs, batch=a.batch)
