"""Time-series workflow: DataVec sequence ETL feeding an LSTM classifier.

Reference workflow (dl4j-examples UCI sequence classification):
CSVSequenceRecordReader -> TransformProcess sequence steps ->
SequenceRecordReaderDataSetIterator -> MultiLayerNetwork(LSTM) with
masks. Here the flat sensor log is grouped with convertToSequence,
enriched with a rolling mean + first difference, then batched as
padded/masked NTF tensors.

Synthetic task (zero-egress env): each device emits a noisy waveform;
class 0 = rising ramp, 1 = sine burst, 2 = decaying spike. Run:
python examples/timeseries_sequence_etl.py [--epochs 20]
"""

from __future__ import annotations

import argparse

import numpy as np

from deeplearning4j_tpu.datavec import Schema, TransformProcess
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import (
    GlobalPoolingLayer, InputType, LSTM, NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def synth_flat_records(n_series=120, seed=0):
    """Flat (unordered) rows: [series_id, t, value] + per-series label."""
    rng = np.random.default_rng(seed)
    rows, labels = [], []
    for sid in range(n_series):
        cls = sid % 3
        t_len = int(rng.integers(18, 28))
        t = np.arange(t_len)
        if cls == 0:
            v = 0.08 * t
        elif cls == 1:
            v = np.sin(t * 0.9)
        else:
            v = 2.0 * np.exp(-0.3 * t)
        v = v + rng.normal(0, 0.08, t_len)
        order = rng.permutation(t_len)     # arrives shuffled
        rows.extend([[float(sid), float(tt), float(vv)]
                     for tt, vv in zip(t[order], v[order])])
        labels.append(cls)
    return rows, np.asarray(labels)


def main(epochs: int = 20):
    rows, labels = synth_flat_records()
    schema = (Schema.Builder()
              .addColumnDouble("series").addColumnDouble("t")
              .addColumnDouble("v").build())
    tp = (TransformProcess.Builder(schema)
          .convertToSequence("series", "t")     # group + time-order
          .sequenceMovingWindowReduce("v", 4, "Mean")
          .sequenceDifference("v")              # de-trend in place
          .removeColumns("series", "t")
          .build())
    seqs = tp.execute(rows)
    print(f"sequences: {len(seqs)}, features/step: {len(seqs[0][0])}, "
          f"lengths {min(map(len, seqs))}-{max(map(len, seqs))}")

    # padded/masked NTF batch (what SequenceRecordReaderDataSetIterator
    # does; inlined here because labels are per-series, not per-step)
    t_max = max(map(len, seqs))
    n, f = len(seqs), len(seqs[0][0])
    x = np.zeros((n, t_max, f), np.float32)
    mask = np.zeros((n, t_max), np.float32)
    for i, s in enumerate(seqs):
        x[i, :len(s)] = np.asarray(s, np.float32)
        mask[i, :len(s)] = 1.0
    y = np.eye(3, dtype=np.float32)[labels]

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=5e-3)).list()
            .layer(LSTM(n_out=24, activation="tanh"))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.recurrent(f)).build())
    from deeplearning4j_tpu.datasets import DataSet
    ds = DataSet(x, y, features_mask=mask)
    net = MultiLayerNetwork(conf).init()
    for e in range(epochs):
        net.fit(ds)
        if (e + 1) % 5 == 0:
            print(f"epoch {e+1}: loss {net.score():.3f}")
    out = np.asarray(net.output(x, features_mask=mask).toNumpy())
    acc = (out.argmax(1) == labels).mean()
    print("train accuracy:", acc)
    assert acc > 0.9, acc
    return float(acc)


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=20)
    main(ap.parse_args().epochs)
