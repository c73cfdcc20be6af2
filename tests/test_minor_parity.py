"""Minor parity items:
JointParallelDataSetIterator, CnnSentenceDataSetIterator, and
ComputationGraph external epsilons."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    ListDataSetIterator, JointParallelDataSetIterator, InequalityHandling,
)


def _it(n, batch=2, f=3, seed=0):
    rs = np.random.RandomState(seed)
    return ListDataSetIterator(
        DataSet(rs.randn(n, f).astype(np.float32),
                np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]), batch)


class TestJointParallelIterator:
    def test_per_consumer_feeds(self):
        j = JointParallelDataSetIterator([_it(8), _it(8, seed=1)],
                                         async_prefetch=False)
        j.reset()
        assert j.num_producers == 2
        a = j.next_for(0)
        b = j.next_for(1)
        assert a.features.shape == (2, 3) and b.features.shape == (2, 3)
        assert not np.allclose(a.features, b.features)

    def test_stop_everyone(self):
        j = JointParallelDataSetIterator(
            [_it(2), _it(8)], InequalityHandling.STOP_EVERYONE,
            async_prefetch=False)
        j.reset()
        assert j.has_next_for(0)
        j.next_for(0)
        assert not j.has_next_for(0)     # producer 0 dry → everyone stops
        assert not j.has_next_for(1)
        assert j.next_for(1) is None

    def test_pass_null(self):
        j = JointParallelDataSetIterator(
            [_it(2), _it(6)], InequalityHandling.PASS_NULL,
            async_prefetch=False)
        j.reset()
        j.next_for(0)
        assert j.next_for(0) is None     # dry producer passes null
        assert j.next_for(1) is not None  # others continue

    def test_reset_policy_replays(self):
        j = JointParallelDataSetIterator(
            [_it(2)], InequalityHandling.RESET, async_prefetch=False)
        j.reset()
        seen = [j.next_for(0) for _ in range(4)]   # 1 batch/epoch, replayed
        assert all(s is not None for s in seen)

    def test_relocate_steals(self):
        j = JointParallelDataSetIterator(
            [_it(2), _it(8, seed=1)], InequalityHandling.RELOCATE,
            async_prefetch=False)
        j.reset()
        j.next_for(0)
        stolen = j.next_for(0)           # producer 0 dry → takes from 1
        assert stolen is not None

    def test_round_robin_iteration_covers_all(self):
        j = JointParallelDataSetIterator(
            [_it(4), _it(4, seed=1)], InequalityHandling.PASS_NULL,
            async_prefetch=False)
        batches = list(j)
        assert len(batches) == 4          # 2 per producer, interleaved

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            JointParallelDataSetIterator([])


class _ToyVectors:
    def __init__(self, words, dim=4, seed=0):
        rs = np.random.RandomState(seed)
        self._v = {w: rs.randn(dim).astype(np.float32) for w in words}

    def has_word(self, w):
        return w in self._v

    def word_vector(self, w):
        return self._v[w]


class TestCnnSentenceIterator:
    def _data(self):
        return [("the cat sat", "animal"), ("stocks fell hard today", "money"),
                ("a cat and a dog", "animal"), ("the market rallied", "money")]

    def _wv(self):
        words = {w for s, _ in self._data() for w in s.split()} - {"dog"}
        return _ToyVectors(sorted(words))

    def test_shapes_masks_labels(self):
        from deeplearning4j_tpu.nlp import CnnSentenceDataSetIterator
        it = CnnSentenceDataSetIterator(self._data(), self._wv(),
                                        batch_size=4)
        ds = next(iter(it))
        B, L, D, C = ds.features.shape
        assert B == 4 and D == 4 and C == 1
        assert ds.features_mask.shape == (B, L)
        # 'dog' unknown → removed: that sentence has 4 known tokens
        assert ds.labels.shape == (4, 2)
        assert set(it.labels) == {"animal", "money"}
        np.testing.assert_allclose(ds.labels.sum(1), 1.0)
        # masked positions are zero
        assert np.all(ds.features[ds.features_mask == 0] == 0)

    def test_unknown_vector_mode_keeps_tokens(self):
        from deeplearning4j_tpu.nlp import (CnnSentenceDataSetIterator,
                                            UnknownWordHandling)
        it_rm = CnnSentenceDataSetIterator(self._data(), self._wv(),
                                           batch_size=4)
        it_uk = CnnSentenceDataSetIterator(
            self._data(), self._wv(), batch_size=4,
            unknown_word_handling=UnknownWordHandling.USE_UNKNOWN_VECTOR)
        n_rm = next(iter(it_rm)).features_mask.sum()
        n_uk = next(iter(it_uk)).features_mask.sum()
        assert n_uk == n_rm + 1           # 'dog' kept as the unknown vector

    def test_load_single_sentence(self):
        from deeplearning4j_tpu.nlp import CnnSentenceDataSetIterator
        it = CnnSentenceDataSetIterator(self._data(), self._wv())
        arr = it.load_single_sentence("the cat sat")
        assert arr.shape == (1, 3, 4, 1)

    def test_trains_sentence_cnn(self):
        """End-to-end: the emitted batches actually train a conv net."""
        from deeplearning4j_tpu.nlp import CnnSentenceDataSetIterator
        from deeplearning4j_tpu import (NeuralNetConfiguration,
                                        MultiLayerNetwork)
        from deeplearning4j_tpu.nn.layers import (ConvolutionLayer,
                                                  GlobalPoolingLayer,
                                                  OutputLayer)
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.updaters import Adam
        it = CnnSentenceDataSetIterator(self._data() * 4, self._wv(),
                                        batch_size=4,
                                        max_sentence_length=6)
        ds = next(iter(it))
        L = ds.features.shape[1]
        conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
                .weight_init("xavier").list()
                .layer(ConvolutionLayer(n_out=8, kernel_size=(2, 4),
                                        activation="relu"))
                .layer(GlobalPoolingLayer(pooling_type="max"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.convolutional(L, 4, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(ds.features, ds.labels)
        assert np.isfinite(net.get_score())


class TestCGExternalEpsilons:
    def _cg(self):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models import ComputationGraph
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Sgd
        g = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(0.1))
             .weight_init("xavier").l2(1e-3).graph_builder()
             .add_inputs("in").set_input_types(InputType.feed_forward(5))
             .add_layer("h", DenseLayer(n_out=7, activation="tanh"), "in")
             .add_layer("out", OutputLayer(n_out=3, activation="identity",
                                           loss="mse"), "h"))
        return ComputationGraph(g.set_outputs("out").build()).init()

    def test_external_epsilons_match_autodiff(self):
        """backprop_external with eps = dL/d(out) must equal jax.grad of the
        same external loss composed through the graph (the
        calcBackpropGradients(externalEpsilons) contract)."""
        cg = self._cg()
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(6, 5), jnp.float32)
        tgt = jnp.asarray(rs.randn(6, 3), jnp.float32)

        out = cg.output(x)
        eps = 2.0 * (out - tgt)                 # d/d(out) of sum((out-t)^2)
        got, _ = cg.backprop_external([x], [eps])

        def external_loss(params):
            acts, _, _ = cg._forward(params, cg.state, [x], train=True,
                                     rng=None)
            reg = sum((cg.conf.nodes[n].layer.reg_loss(p)
                       for n, p in params.items()), 0.0)
            return jnp.sum((acts["out"] - tgt) ** 2) + reg

        want = jax.grad(external_loss)(cg.params)
        for name in want:
            for k in want[name]:
                np.testing.assert_allclose(
                    np.asarray(got[name][k]), np.asarray(want[name][k]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{name}/{k}")

    def test_fit_external_updates_params(self):
        cg = self._cg()
        rs = np.random.RandomState(1)
        x = rs.randn(4, 5).astype(np.float32)
        eps = rs.randn(4, 3).astype(np.float32)
        before = np.asarray(cg.params["h"]["W"]).copy()
        cg.fit_external([x], [eps])
        assert not np.allclose(before, np.asarray(cg.params["h"]["W"]))
        assert cg.iteration == 1
