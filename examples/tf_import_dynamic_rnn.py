"""Import a LEGACY TF1 frozen graph with real control flow — a
dynamic-rnn-style while loop over TensorArrays — and run it as ONE
compiled XLA program.

This is the artifact class the reference's AbstractSession interprets
node-by-node (Switch/Merge/Enter/Exit frames, SURVEY.md §3.4): a
tf.compat.v1 Graph built with while_loop + TensorArray read/write,
frozen through the v1 graph_util path. Here the frame structure is
reconstructed AT IMPORT into a while_loop op, TensorArrays become
dense loop-state arrays, and the whole recurrence compiles on-device
— no interpreter, no host round-trips per timestep.

Run: python examples/tf_import_dynamic_rnn.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main(batch: int = 2, seq: int = 6, d_in: int = 5,
         hidden: int = 7) -> float:
    import tensorflow as tf
    tf1 = tf.compat.v1

    from deeplearning4j_tpu.modelimport.tensorflow import TFGraphMapper

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, seq, d_in)).astype(np.float32)

    # ---- build + freeze the legacy graph (the user's saved artifact)
    g = tf.Graph()
    with g.as_default():
        ph = tf1.placeholder(tf.float32, (batch, seq, d_in), name="x")
        Wz = tf1.get_variable(
            "Wz", (d_in + hidden, hidden),
            initializer=tf1.initializers.glorot_uniform(seed=1))
        Wh = tf1.get_variable(
            "Wh", (d_in + hidden, hidden),
            initializer=tf1.initializers.glorot_uniform(seed=2))
        xs = tf.transpose(ph, [1, 0, 2])                 # time-major
        in_ta = tf.TensorArray(tf.float32, size=seq,
                               element_shape=(batch, d_in)).unstack(xs)
        out_ta = tf.TensorArray(tf.float32, size=seq,
                                element_shape=(batch, hidden))

        def body(t, h, ta):
            xt = in_ta.read(t)
            cat = tf.concat([xt, h], 1)
            z = tf.sigmoid(tf.matmul(cat, Wz))
            hc = tf.tanh(tf.matmul(cat, Wh))
            h2 = (1.0 - z) * h + z * hc
            return t + 1, h2, ta.write(t, h2)

        _, hT, out_ta = tf1.while_loop(
            lambda t, h, ta: t < seq, body,
            [0, tf.zeros((batch, hidden)), out_ta])
        out = tf.identity(tf.transpose(out_ta.stack(), [1, 0, 2]),
                          name="rnn_out")
        with tf1.Session(graph=g) as sess:
            sess.run(tf1.global_variables_initializer())
            ref = sess.run(out, {ph: x})
            frozen = tf1.graph_util.convert_variables_to_constants(
                sess, g.as_graph_def(), ["rnn_out"])

    ops = sorted({n.op for n in frozen.node})
    print("frozen graph op set:", ops)

    # ---- import: frames -> while_loop, TAs -> dense loop state
    import jax

    sd = TFGraphMapper.importGraph(frozen)
    # parity vs a float32 CPU TF session: pin full-precision matmuls
    # (on TPU the default MXU precision is bf16-grade, ~3e-3 off)
    with jax.default_matmul_precision("float32"):
        got = np.asarray(sd.output({"x": x}, ["rnn_out"])["rnn_out"])
    err = float(np.abs(got - ref).max())
    print(f"imported-vs-TF max err: {err:.2e}  "
          f"(output shape {got.shape})")
    assert err < 1e-4, "import diverged from the TF session"

    # ---- fine-tune THROUGH the imported loop: the counter-bounded
    # frame lowered to a differentiable masked scan (max_trip_count
    # was derived at import), so jax.grad works and the frozen weights
    # can be trained against new targets
    node = next(n for n in sd._ops if n.op_name == "while_loop")
    print(f"derived static trip count: {node.attrs['max_trip_count']}")

    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning.updaters import Adam

    sd.convertConstantsToVariables("Wz", "Wh")
    target = rng.normal(size=(batch, seq, hidden)).astype(np.float32)
    y_ph = sd.placeholder("y", shape=(batch, seq, hidden))
    diff = sd._op("sub", ["rnn_out", y_ph.name])
    loss = sd._op("reduce_mean", [sd._op("mul", [diff.name,
                                                 diff.name]).name])
    sd.setLossVariables(loss.name)
    sd.setTrainingConfig(TrainingConfig(
        updater=Adam(learning_rate=0.01),
        data_set_feature_mapping=["x"], data_set_label_mapping=["y"]))
    hist = sd.fit(DataSet(x, target), epochs=100)
    print(f"fine-tune loss: {hist.loss_curve[0]:.4f} -> "
          f"{hist.loss_curve[-1]:.4f}")
    assert hist.loss_curve[-1] < 0.75 * hist.loss_curve[0], \
        "fine-tuning through the imported loop did not descend"
    print("OK")
    return err


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
