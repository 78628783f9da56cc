"""MultiDataSet / MultiDataSetIterator tests (reference analog:
MultiDataSetTest, ComputationGraph multi-input fit tests)."""

import numpy as np

from deeplearning4j_tpu.datasets import (
    ArrayMultiDataSetIterator, ListMultiDataSetIterator, MultiDataSet,
    MultiDataSetIteratorAdapter, ArrayDataSetIterator,
)
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import DenseLayer, InputType, OutputLayer
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph, ComputationGraphConfiguration, MergeVertex,
)


def _two_input_graph():
    b = (ComputationGraphConfiguration.graphBuilder().seed(0)
         .updater(Adam(learning_rate=5e-3)).addInputs("a", "b"))
    b.setInputTypes(InputType.feedForward(3), InputType.feedForward(3))
    b.addLayer("da", DenseLayer(n_in=3, n_out=8, activation="relu"), "a")
    b.addLayer("db", DenseLayer(n_in=3, n_out=8, activation="relu"), "b")
    b.addVertex("m", MergeVertex(), "da", "db")
    b.addLayer("out", OutputLayer(n_in=16, n_out=2, activation="softmax",
                                  loss="mcxent"), "m")
    return ComputationGraph(b.setOutputs("out").build()).init()


def _data(n=48, seed=0):
    rng = np.random.default_rng(seed)
    xa = rng.normal(size=(n, 3)).astype(np.float32)
    xb = rng.normal(size=(n, 3)).astype(np.float32)
    lab = ((xa[:, 0] + xb[:, 0]) > 0).astype(int)
    y = np.eye(2, dtype=np.float32)[lab]
    return xa, xb, y, lab


class TestMultiDataSet:
    def test_accessors_and_split(self):
        xa, xb, y, _ = _data()
        mds = MultiDataSet([xa, xb], [y])
        assert mds.numFeatureArrays() == 2
        assert mds.numLabelsArrays() == 1
        assert mds.numExamples() == 48
        parts = mds.splitBatches(20)
        assert [p.numExamples() for p in parts] == [20, 20, 8]
        np.testing.assert_allclose(parts[1].getFeatures(0), xa[20:40])

    def test_graph_fit_with_multidataset(self):
        xa, xb, y, lab = _data()
        g = _two_input_graph()
        mds = MultiDataSet([xa, xb], [y])
        s0 = None
        for _ in range(40):
            g.fit(mds)
            s0 = s0 or g.score()
        assert g.score() < s0
        pred = np.asarray(g.outputSingle(xa, xb)).argmax(-1)
        assert (pred == lab).mean() > 0.85

    def test_graph_fit_with_iterator(self):
        xa, xb, y, _ = _data()
        g = _two_input_graph()
        it = ArrayMultiDataSetIterator([xa, xb], [y], batch_size=16)
        g.fit(it, epochs=5)
        assert np.isfinite(g.score())
        # list iterator path too
        parts = MultiDataSet([xa, xb], [y]).splitBatches(16)
        g.fit(ListMultiDataSetIterator(parts), epochs=2)
        assert np.isfinite(g.score())

    def test_adapter_wraps_datasetiterator(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(32, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 32)]
        base = ArrayDataSetIterator(x, y, 8)
        adapter = MultiDataSetIteratorAdapter(base)
        batches = list(adapter)
        assert len(batches) == 4
        assert batches[0].numFeatureArrays() == 1
        assert batches[0].getFeatures(0).shape == (8, 4)


class TestMaskAndResetGuards:
    def test_masks_preserved_by_adapter_and_split(self):
        from deeplearning4j_tpu.datasets import DataSet
        x = np.ones((8, 5, 3), np.float32)
        y = np.ones((8, 5, 2), np.float32)
        lm = np.ones((8, 5), np.float32)
        mds = MultiDataSet.fromDataSet(DataSet(x, y, labels_mask=lm))
        assert len(mds.labels_mask_arrays) == 1
        parts = mds.splitBatches(3)
        assert parts[0].labels_mask_arrays[0].shape == (3, 5)

    def test_features_mask_applied_on_graph(self):
        """Graph fit honors features masks: padded steps (which carry a
        strong anti-signal here) are zeroed before the forward."""
        from deeplearning4j_tpu.nn.conf import GlobalPoolingLayer, \
            DenseLayer as DL
        b = (ComputationGraphConfiguration.graphBuilder().seed(4)
             .updater(Adam(learning_rate=1e-2)).addInputs("seq"))
        b.setInputTypes(InputType.recurrent(4, 6))
        b.addLayer("d", DL(n_in=4, n_out=8, activation="tanh"), "seq")
        b.addLayer("pool", GlobalPoolingLayer(pooling_type="avg"), "d")
        b.addLayer("out", OutputLayer(n_in=8, n_out=2,
                                      activation="softmax", loss="mcxent"),
                   "pool")
        conf = b.setOutputs("out").build()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 6, 4)).astype(np.float32)
        lab = (x[:, :3, 0].mean(1) > 0).astype(int)
        x[:, 3:] = -np.sign(lab)[:, None, None] * 5.0
        y = np.eye(2, dtype=np.float32)[lab]
        fm = np.ones((16, 6), np.float32)
        fm[:, 3:] = 0
        g_m = ComputationGraph(conf).init()
        mds = MultiDataSet([x], [y], features_mask_arrays=[fm])
        for _ in range(30):
            g_m.fit(mds)
        g_u = ComputationGraph(conf).init()
        for _ in range(30):
            g_u.fit(MultiDataSet([x], [y]))
        assert not np.allclose(np.asarray(g_m.params_map["d"]["W"]),
                               np.asarray(g_u.params_map["d"]["W"]))

    def test_label_mask_applied_in_graph_loss(self):
        """Label masks flow to the output layer's loss: masking out the
        second half of a sequence must change the loss."""
        from deeplearning4j_tpu.nn.conf import GlobalPoolingLayer, LSTM, \
            RnnOutputLayer
        b = (ComputationGraphConfiguration.graphBuilder().seed(2)
             .updater(Adam(learning_rate=1e-3)).addInputs("seq"))
        b.setInputTypes(InputType.recurrent(3, 6))
        b.addLayer("rnn", LSTM(n_in=3, n_out=5), "seq")
        b.addLayer("out", RnnOutputLayer(n_in=5, n_out=2,
                                         activation="softmax",
                                         loss="mcxent"), "rnn")
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 6, 3)).astype(np.float32)
        y = np.zeros((4, 6, 2), np.float32)
        y[..., 0] = 1
        # corrupt the second half's labels; mask them out
        y_bad = y.copy()
        y_bad[:, 3:, 0] = 0
        y_bad[:, 3:, 1] = 1
        mask = np.ones((4, 6), np.float32)
        mask[:, 3:] = 0

        g1 = ComputationGraph(b.setOutputs("out").build()).init()
        mds = MultiDataSet([x], [y_bad], labels_mask_arrays=[mask])
        g1.fit(mds)
        masked_loss = g1.score()
        # same graph, same data, NO mask -> corrupted labels contribute
        g2 = ComputationGraph(g1.conf).init()
        g2.fit(MultiDataSet([x], [y_bad]))
        unmasked_loss = g2.score()
        assert abs(masked_loss - unmasked_loss) > 1e-3

    def test_nonresettable_multi_epoch_raises(self):
        import pytest as _pytest

        class OneShot(ListMultiDataSetIterator):
            def resetSupported(self):
                return False

        xa, xb, y, _ = _data(16)
        parts = MultiDataSet([xa, xb], [y]).splitBatches(8)
        g = _two_input_graph()
        with _pytest.raises(ValueError, match="resettable"):
            g.fit(OneShot(parts), epochs=3)
        g.fit(OneShot(parts), epochs=1)  # single epoch is fine


class TestMaskSemantics:
    """compute_loss mask shapes + normalization (reference:
    ILossFunction mask/minibatch score semantics)."""

    def _ce(self, labels, logits, mask):
        import jax.numpy as jnp
        from deeplearning4j_tpu.loss import LossFunction, compute_loss
        return float(compute_loss(LossFunction.MCXENT,
                                  jnp.asarray(labels), jnp.asarray(logits),
                                  "softmax", None if mask is None
                                  else jnp.asarray(mask)))

    def test_all_ones_mask_is_identity(self):
        rng = np.random.default_rng(0)
        labels = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 6))]
        logits = rng.normal(size=(4, 6, 2)).astype(np.float32)
        unmasked = self._ce(labels, logits, None)
        masked = self._ce(labels, logits, np.ones((4, 6), np.float32))
        assert abs(unmasked - masked) < 1e-5

    def test_mask_shapes_accepted(self):
        rng = np.random.default_rng(1)
        labels = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 6))]
        logits = rng.normal(size=(4, 6, 2)).astype(np.float32)
        base = self._ce(labels, logits, np.ones((4, 6), np.float32))
        # [N,T,1] same as [N,T]
        assert abs(self._ce(labels, logits,
                            np.ones((4, 6, 1), np.float32)) - base) < 1e-5
        # [N,1] per-example weights: all-ones == unmasked
        assert abs(self._ce(labels, logits,
                            np.ones((4, 1), np.float32)) - base) < 1e-5
        # [N] per-example on 2D labels
        l2d = np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]
        z2d = rng.normal(size=(4, 3)).astype(np.float32)
        assert abs(self._ce(l2d, z2d, np.ones(4, np.float32)) -
                   self._ce(l2d, z2d, None)) < 1e-5

    def test_masked_timesteps_contribute_zero(self):
        rng = np.random.default_rng(2)
        labels = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 6))]
        logits = rng.normal(size=(4, 6, 2)).astype(np.float32)
        m = np.ones((4, 6), np.float32)
        m[:, 3:] = 0
        masked = self._ce(labels, logits, m)
        # equals CE computed on the first half only (same N divisor)
        half = self._ce(labels[:, :3], logits[:, :3], None)
        assert abs(masked - half) < 1e-5

    def test_graph_mask_count_mismatch_raises(self):
        import pytest as _pytest
        g = _two_input_graph()
        xa, xb, y, _ = _data(8)
        mds = MultiDataSet([xa, xb], [y])
        with _pytest.raises(ValueError, match="label masks"):
            g._fit_batch([xa, xb], [y], [None, np.ones(8)])

    def test_panic_env_wiring(self):
        import subprocess, sys, os
        code = (
            "import os\n"
            "os.environ['JAX_PLATFORMS']='cpu'\n"
            "from deeplearning4j_tpu.profiler import OpProfiler, ProfilerMode\n"
            "assert OpProfiler.getInstance().config.mode is ProfilerMode.NAN_PANIC\n"
            "print('WIRED')\n")
        env = dict(os.environ, DL4J_TPU_PANIC="nan", JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert "WIRED" in r.stdout, r.stderr[-500:]


class TestMaskedInference:
    def test_output_honors_features_mask(self):
        from deeplearning4j_tpu.nn.conf import GlobalPoolingLayer, \
            DenseLayer as DL
        b = (ComputationGraphConfiguration.graphBuilder().seed(9)
             .updater(Adam(learning_rate=1e-2)).addInputs("seq"))
        b.setInputTypes(InputType.recurrent(3, 4))
        b.addLayer("d", DL(n_in=3, n_out=6, activation="tanh"), "seq")
        b.addLayer("pool", GlobalPoolingLayer(pooling_type="avg"), "d")
        b.addLayer("out", OutputLayer(n_in=6, n_out=2,
                                      activation="softmax", loss="mcxent"),
                   "pool")
        g = ComputationGraph(b.setOutputs("out").build()).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 4, 3)).astype(np.float32)
        fm = np.array([[1, 1, 0, 0]] * 4, np.float32)
        o_masked = np.asarray(g.outputSingle(x, feature_masks=[fm]))
        o_plain = np.asarray(g.outputSingle(x))
        assert not np.allclose(o_masked, o_plain)
        # recompute manually: mean over first 2 steps == masked avg
        d_w = g.params_map["d"]
        h = np.tanh(x @ np.asarray(d_w["W"]) + np.asarray(d_w["b"]))
        pooled = h[:, :2].mean(1)
        ow = g.params_map["out"]
        logits = pooled @ np.asarray(ow["W"]) + np.asarray(ow["b"])
        want = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        np.testing.assert_allclose(o_masked, want, atol=1e-4)

    def test_bad_fmask_shape_raises(self):
        import pytest as _pytest
        g = _two_input_graph()
        xa, xb, y, _ = _data(8)
        with _pytest.raises(NotImplementedError, match="features mask"):
            g._fit_batch([xa, xb], [y], None,
                         [np.ones((8,), np.float32), None])

    def test_mln_output_mask_consistency(self):
        from deeplearning4j_tpu.nn.conf import (
            GlobalPoolingLayer, NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = (NeuralNetConfiguration.builder().seed(2)
                .updater(Adam(learning_rate=1e-2)).list()
                .layer(DenseLayer(n_out=5, activation="tanh"))
                .layer(GlobalPoolingLayer(pooling_type="max"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .setInputType(InputType.recurrent(3, 4)).build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.default_rng(1).normal(size=(4, 4, 3)).astype(np.float32)
        fm = np.array([[1, 1, 0, 0]] * 4, np.float32)
        o_m = np.asarray(net.output(x, features_mask=fm))
        o_p = np.asarray(net.output(x))
        assert not np.allclose(o_m, o_p)


class TestMaskBranchIsolation:
    def test_unmasked_branch_pooling_not_masked(self):
        """Masked pooling must only fire on the masked input's branch."""
        from deeplearning4j_tpu.nn.conf import GlobalPoolingLayer, \
            DenseLayer as DL
        b = (ComputationGraphConfiguration.graphBuilder().seed(0)
             .updater(Adam(learning_rate=1e-3)).addInputs("a", "b"))
        b.setInputTypes(InputType.recurrent(3, 4), InputType.recurrent(3, 4))
        b.addLayer("pa", GlobalPoolingLayer(pooling_type="avg"), "a")
        b.addLayer("pb", GlobalPoolingLayer(pooling_type="avg"), "b")
        b.addVertex("m", MergeVertex(), "pa", "pb")
        b.addLayer("out", OutputLayer(n_in=6, n_out=2,
                                      activation="softmax", loss="mcxent"),
                   "m")
        g = ComputationGraph(b.setOutputs("out").build()).init()
        xa = np.ones((2, 4, 3), np.float32)
        xb = np.ones((2, 4, 3), np.float32) * 2.0
        fm = np.array([[1, 1, 0, 0]] * 2, np.float32)  # mask only input a
        # run the training-path forward via one fit step and check the
        # pooled activations through the jitted loss by comparing to an
        # unmasked-b expectation: b's avg over ALL 4 steps stays 2.0
        import jax
        outs, _ = g._forward_all(
            g.params_map, g.states_map,
            {"a": jax.numpy.asarray(xa), "b": jax.numpy.asarray(xb)},
            False, None, {"a": jax.numpy.asarray(fm)})
        np.testing.assert_allclose(np.asarray(outs["pa"]), 1.0, atol=1e-6)
        # b unmasked: avg over 4 steps of constant 2.0 -> exactly 2.0;
        # a bug applying a's mask to b would still give 2.0 here, so
        # ALSO check a zero-suffixed b would differ:
        xb2 = xb.copy()
        xb2[:, 2:] = 0
        outs2, _ = g._forward_all(
            g.params_map, g.states_map,
            {"a": jax.numpy.asarray(xa), "b": jax.numpy.asarray(xb2)},
            False, None, {"a": jax.numpy.asarray(fm)})
        # unmasked avg over 4 steps = 1.0; masked-with-a's-mask would be 2.0
        np.testing.assert_allclose(np.asarray(outs2["pb"]), 1.0, atol=1e-6)

    def test_reference_interval_overload(self):
        from deeplearning4j_tpu.ndarray import Nd4j, NDArrayIndex
        a = Nd4j.arange(10)
        # reference 3-arg form: (begin, stride, end)
        got = a.get(NDArrayIndex.interval(0, 2, 10))
        np.testing.assert_allclose(got.toNumpy(), [0, 2, 4, 6, 8])
        # put without an index raises
        import pytest as _pytest
        with _pytest.raises(TypeError, match="put"):
            a.put(5.0)
