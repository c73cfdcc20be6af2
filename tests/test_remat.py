"""Backward rematerialization (GlobalConf.remat): identical training math,
different schedule. Remat recomputes activations in the backward instead of
storing them — on TPU this is faster for HBM-bound conv models and is the
bench configuration for ResNet50; these tests pin that it changes NOTHING
numerically."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                          BatchNormalization, OutputLayer)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import Adam


def _conf(remat):
    b = (NeuralNetConfiguration.builder()
         .seed(7).updater(Adam(1e-2)).weight_init("xavier"))
    if remat:
        b = b.remat(remat)
    return (b.list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=3, activation="relu"))
            .layer(BatchNormalization())
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())


def _data(steps=3, b=4):
    rs = np.random.RandomState(0)
    xs = rs.rand(steps, b, 8, 8, 1).astype(np.float32)
    ys = np.eye(3, dtype=np.float32)[rs.randint(0, 3, (steps, b))]
    return jnp.asarray(xs), jnp.asarray(ys)


def test_remat_mln_identical_training():
    xs, ys = _data()
    nets = [MultiLayerNetwork(_conf(r)).init()
            for r in (False, True, "save_convs")]
    for net in nets:
        net.fit_scan(xs, ys)
    a = nets[0]
    for b in nets[1:]:
        assert np.allclose(float(a.get_score()), float(b.get_score()),
                           atol=1e-5)
        for pa, pb in zip(a.params, b.params):
            for k in pa:
                np.testing.assert_allclose(np.asarray(pa[k]),
                                           np.asarray(pb[k]), atol=1e-5)


def test_remat_rejects_unknown_mode():
    net = MultiLayerNetwork(_conf(False))
    net.conf.global_conf.remat = "bogus"      # bypasses the eager check
    with pytest.raises(ValueError, match="remat"):
        net.init().fit_scan(*_data(1))


def _small_residual_cg(remat):
    """2-block bottleneck residual CG — the ResNet shape (projection +
    identity shortcuts, ElementWiseVertex add) at a depth that compiles in
    seconds, so the CG remat modes stay pinned in tier-1 while the full
    ResNet50 parity run rides in the slow tier."""
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
    from deeplearning4j_tpu.nn.layers import ActivationLayer, GlobalPoolingLayer

    b = (NeuralNetConfiguration.builder()
         .seed(11).updater(Adam(1e-2)).weight_init("relu"))
    if remat:
        b = b.remat(remat)
    g = (b.graph_builder()
         .add_inputs("input")
         .set_input_types(InputType.convolutional(8, 8, 3)))

    def conv_bn(name, inp, n_out, k, stride=1, pad=0, act=True):
        g.add_layer(f"{name}_conv",
                    ConvolutionLayer(n_out=n_out, kernel_size=k,
                                     stride=stride, padding=pad,
                                     has_bias=False), inp)
        g.add_layer(f"{name}_bn",
                    BatchNormalization(
                        activation="relu" if act else "identity"),
                    f"{name}_conv")
        return f"{name}_bn"

    def block(name, inp, f, project=False):
        x = conv_bn(f"{name}_a", inp, f, 1)
        x = conv_bn(f"{name}_b", x, f, 3, pad=1)
        x = conv_bn(f"{name}_c", x, 2 * f, 1, act=False)
        sc = conv_bn(f"{name}_sc", inp, 2 * f, 1, act=False) if project else inp
        g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, sc)
        g.add_layer(f"{name}_out", ActivationLayer(activation="relu"),
                    f"{name}_add")
        return f"{name}_out"

    x = conv_bn("stem", "input", 8, 3, pad=1)
    x = block("res0", x, 8, project=True)
    x = block("res1", x, 8)
    g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
    g.add_layer("fc", OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent", n_in=16), "avgpool")
    g.set_outputs("fc")
    return ComputationGraph(g.build()).init()


@pytest.mark.slow
def test_remat_cg_small_identical_training():
    rs = np.random.RandomState(1)
    x = rs.rand(4, 8, 8, 3).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 4)]
    xs, ys = jnp.asarray(x[None]), jnp.asarray(y[None])
    cgs = [_small_residual_cg(r) for r in (False, True, "save_convs")]
    for cg in cgs:
        cg.fit_scan(xs, ys)
    scores = [float(c.get_score()) for c in cgs]
    assert np.isfinite(scores[0])
    for s in scores[1:]:
        assert abs(scores[0] - s) < 1e-5, scores


@pytest.mark.slow
def test_remat_cg_identical_training():
    from deeplearning4j_tpu.zoo.resnet import ResNet50Cifar
    rs = np.random.RandomState(1)
    x = rs.rand(4, 32, 32, 3).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 4)]
    xs, ys = jnp.asarray(x[None]), jnp.asarray(y[None])
    cgs = [ResNet50Cifar(num_classes=10, remat=r).init()
           for r in (False, True, "save_convs")]
    for cg in cgs:
        cg.fit_scan(xs, ys)
    scores = [float(c.get_score()) for c in cgs]
    assert np.isfinite(scores[0])
    for s in scores[1:]:
        assert abs(scores[0] - s) < 1e-4, scores


def test_remat_roundtrips_in_conf_json():
    from deeplearning4j_tpu.nn.conf.configuration import MultiLayerConfiguration
    again = MultiLayerConfiguration.from_json(_conf(True).to_json())
    assert again.global_conf.remat is True
    assert MultiLayerConfiguration.from_json(
        _conf(False).to_json()).global_conf.remat is False
    assert MultiLayerConfiguration.from_json(
        _conf("save_convs").to_json()).global_conf.remat == "save_convs"


def test_remat_builder_rejects_bad_mode_eagerly():
    with pytest.raises(ValueError, match="remat"):
        NeuralNetConfiguration.builder().remat("save_conv")


def _residual_run(n_devices, batch, steps):
    """``steps`` of ``fit`` on the small residual graph under ``save_convs``
    on a data mesh of ``n_devices``: the losses, BatchNorm's statistics and
    the compiled step's text."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.exec import build_mesh, set_default_mesh
    from deeplearning4j_tpu.exec.programs import _lowerable
    rs = np.random.RandomState(1)
    x = rs.rand(batch, 8, 8, 3).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, batch)]
    set_default_mesh(build_mesh(jax.devices()[:n_devices]))
    try:
        cg = _small_residual_cg("save_convs")
        losses = []
        for _ in range(steps):
            cg.fit(DataSet(x, y))
            losses.append(float(cg.get_score()))
        text = _lowerable(cg._train_step_cache[(False, False)]).lower(
            cg.params, cg.state, cg.opt_state, [jnp.asarray(x)],
            [jnp.asarray(y)], jnp.asarray(0, jnp.int32), None, None
        ).compile().as_text()
    finally:
        set_default_mesh(None)
    stats = {(k, s): np.asarray(v) for k, st in cg.state.items()
             for s, v in (st or {}).items()}
    return losses, stats, text


def test_save_convs_step_keeps_batchnorm_statistics_across_the_replay():
    """``save_convs`` saves the BatchNorm op's residuals (a few KB a layer,
    tagged ``bn_stats``), so the replay runs no reduction over activations;
    and the whole step has at most two reduction pairs a BatchNorm: the
    statistics, and the backward's ``sum(dy)`` and ``sum(dy * xhat)``."""
    _, stats, text = _residual_run(1, 4, 1)
    n_bn = len(stats) // 2
    assert n_bn == 8
    # per-channel: a reduce whose result is a vector (inside fusions too)
    of_bn = [o for o in re.findall(
        r'= \w+\[\d+\]\S* reduce\(.*?op_name="([^"]*)"', text)
        if ":BatchNormalization" in o]
    assert not [o for o in of_bn if "rematted_computation" in o]
    assert [o for o in of_bn if "transpose(" in o]
    assert 2 * n_bn <= len(of_bn) <= 4 * n_bn, len(of_bn)


def test_batchnorm_step_on_four_devices_equals_the_single_device_step():
    """The batch sharded over a four-device data mesh: BatchNorm's sums
    become all-reduces and the step is the single-device step."""
    many, one = _residual_run(4, 64, 3), _residual_run(1, 64, 3)
    assert "all-reduce" in many[2] and "all-reduce" not in one[2]
    np.testing.assert_allclose(many[0], one[0], rtol=1e-6, atol=1e-6)
    assert one[1] and set(many[1]) == set(one[1])
    for k, v in one[1].items():
        np.testing.assert_allclose(many[1][k], v, rtol=1e-6, atol=1e-6)
