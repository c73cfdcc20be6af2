"""Backward rematerialization (GlobalConf.remat): identical training math,
different schedule. Remat recomputes activations in the backward instead of
storing them — on TPU this is faster for HBM-bound conv models and is the
bench configuration for ResNet50; these tests pin that it changes NOTHING
numerically."""

import os
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration, ops
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                          BatchNormalization, OutputLayer)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import Adam
from perfbench.lib import arch
from perfbench.jobs import fit_lm


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


def _primitives(jaxpr, into=None):
    """How often each primitive appears in ``jaxpr``, inner jaxprs (a
    checkpoint's, a loop's, a kernel's) included."""
    into = {} if into is None else into
    for e in jaxpr.eqns:
        into[e.primitive.name] = into.get(e.primitive.name, 0) + 1
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _primitives(j, into)
    return into


def _replays(wrap, name):
    """Whether the gradient of ``sin(name(sin(x)))`` under ``wrap`` runs
    the inner ``sin`` again: False where the wrapper's policy keeps
    ``name``."""
    from jax.ad_checkpoint import checkpoint_name

    def f(x):
        return jnp.sin(checkpoint_name(jnp.sin(x), name)).sum()

    n = _primitives(jax.make_jaxpr(jax.grad(wrap(f)))(jnp.ones(3)).jaxpr)
    return {2: False, 3: True}[n["sin"]]


@pytest.mark.parametrize("name", ["conv_out", "bn_stats", "qkv", "attn_out",
                                  "routing", "expert_gate_up", "gate_up",
                                  "selection", "index_grads",
                                  "anything_else"])
def test_each_policy_keeps_its_own_names_and_no_other(name):
    """``save_convs`` keeps what it kept before ``blocks`` had names of its
    own, a block keeps ``BLOCK_KEPT``, and full remat keeps nothing."""
    from deeplearning4j_tpu.util import remat
    assert _replays(lambda f: remat.remat_loss(f, "save_convs"), name) \
        == (name not in ("conv_out", "bn_stats"))
    assert _replays(remat.block_checkpoint, name) \
        == (name not in remat.BLOCK_KEPT)
    assert _replays(lambda f: remat.remat_loss(f, True), name)
    assert remat.remat_loss(abs, "blocks") is abs


def test_keep_counts_bytes_only_while_counting():
    from deeplearning4j_tpu.util import remat
    x = jnp.ones((4, 8), jnp.bfloat16)
    assert remat.keep(x, "qkv") is not None       # no count open: no error
    seen = {"qkv": 0}
    with remat.counting_kept(seen):
        y = jax.jit(lambda a: remat.keep(a, "qkv") + remat.keep(
            a.astype(jnp.float32), "gate_up"))(x)
    remat.keep(x, "qkv")
    assert seen == {"qkv": 64, "gate_up": 128}
    np.testing.assert_array_equal(np.asarray(y), 2.0)


# ---------------------------------------------- what a block's replay keeps

HD, KV = 8, 2        # the rehearsal's head_dim and kv heads
BLOCKS = [("full_attention", "dense"), ("full_attention", "sparse"),
          ("sliding_attention", "dense"), ("sliding_attention", "sparse")]
HEADS = 6            # of each of the two blocks below, on the 2 kv heads


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's decoder configuration at its rehearsal size."""
    return arch.load_config(
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), "perfbench", "configs", "laguna-s-2.1.json"),
        rehearse=True)


@pytest.fixture
def kernels():
    """The attention kernel interpreted, as the chip runs it compiled."""
    prev = ops.set_helpers_enabled(True, interpret=True)
    yield
    ops.set_helpers_enabled(prev[0], interpret=prev[1])


def _two_blocks(cfg, kind, mlp, remat):
    """A ``SparseDecoder`` of two blocks of one sort at the rehearsal's
    widths (hidden 32, head_dim 8, 16 experts of which 4 are held), with a
    batch of 2 x 32 ids and the gradient of its loss."""
    from deeplearning4j_tpu.zoo.decoder import SparseDecoder
    keys = dict(cfg, **cfg["rehearsal"]["model"])
    keys.update(num_experts=16, layer_types=[kind] * 2,
                mlp_layer_types=[mlp] * 2,
                num_attention_heads_per_layer=[HEADS] * 2)
    net = SparseDecoder(keys, seed=3, experts_held=(4, 0), remat=remat).init()
    ids, labels = fit_lm.make_pool(cfg, {"pool_batches": 1}, 4, 2, 32)[0]
    loss = net._loss_for_grad()

    def grad(params):
        return jax.value_and_grad(loss, has_aux=True)(
            params, net.state, [jnp.asarray(ids)], [jnp.asarray(labels)],
            jax.random.PRNGKey(0), None, None)

    return net, (ids, labels), grad


@pytest.mark.parametrize("kind,mlp", BLOCKS)
def test_blocks_keep_the_gradient_and_the_state_what_they_are(
        cfg, kernels, kind, mlp):
    got = {}
    for remat in (False, "blocks"):
        net, _, grad = _two_blocks(cfg, kind, mlp, remat)
        (loss, (state, _)), grads = jax.jit(grad)(net.params)
        got[remat] = (loss, state, grads)
    assert float(got[False][0]) == pytest.approx(float(got["blocks"][0]),
                                                 rel=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
        got[False][1:], got["blocks"][1:])
    pairs = [int(s["pairs"]) for s in got["blocks"][1].values()
             if s and "pairs" in s]
    assert len(pairs) == (2 if mlp == "sparse" else 0) and all(pairs)


@pytest.mark.parametrize("kind,mlp", BLOCKS)
def test_a_block_replay_runs_no_kernel_sort_or_kept_product_again(
        cfg, kernels, kind, mlp):
    """In the gradient's jaxpr: the attention kernel's three passes, one
    sort and one ``top_k`` a block, as without replay; of the grouped
    products only the down product, whose result the pair weights' gradient
    reads; of the dense products only the head gate and the output
    projection. A name that stops keeping its value fails here."""
    seen = {}
    for remat in (False, "blocks"):
        net, _, grad = _two_blocks(cfg, kind, mlp, remat)
        seen[remat] = _primitives(jax.make_jaxpr(grad)(net.params).jaxpr)
    plain, blocks = seen[False], seen["blocks"]
    sparse = 2 if mlp == "sparse" else 0
    assert blocks.get("remat2") == 2 and "remat2" not in plain
    assert blocks["pallas_call"] == plain["pallas_call"] == 3 * 2
    assert blocks.get("sort", 0) == plain.get("sort", 0) == sparse
    assert blocks.get("top_k", 0) == plain.get("top_k", 0) == sparse
    assert blocks.get("ragged_dot_general", 0) \
        == plain.get("ragged_dot_general", 0) + sparse
    assert (plain.get("ragged_dot_general", 0) > 0) == bool(sparse)
    assert blocks["dot_general"] == plain["dot_general"] + 2 * 2


@pytest.mark.parametrize("kind,mlp", BLOCKS)
def test_the_registry_says_what_the_blocks_keep(cfg, kernels, kind, mlp):
    """Bytes by name of the step program's record and of the
    ``dl4jtpu_remat_kept_bytes`` gauge against a count by hand: float32,
    2 x 32 tokens, hidden 32, 6 + 2 + 2 heads of 8, 16 experts, top 3."""
    from deeplearning4j_tpu.exec.programs import get_programs
    from deeplearning4j_tpu.monitor.metrics import get_registry
    net, (ids, labels), _ = _two_blocks(cfg, kind, mlp, "blocks")
    net.fit(iter([DataSet(ids, labels)]))
    n, f32 = 2 * 32, 4
    want = {"qkv": 2 * n * (HEADS + 2 * KV) * HD * f32,
            "attn_out": 2 * n * HEADS * (HD + 1) * f32,     # o and lse
            "routing": 0, "expert_gate_up": 0,
            "gate_up": 2 * 2 * n * 64 * f32,
            # a layer without an indexer keeps no selection, a stack
            # without a state-space mixer no input projection
            "selection": 0, "index_grads": 0, "ssm_proj": 0}
    if mlp == "sparse":
        rows, _ = net.conf.nodes["b0.mlp"].layer.round_rows(n)
        want.update(
            # the router's product, the top 3 and their indices, the sort
            # of the pairs, the group sizes
            routing=2 * (n * 16 + 2 * n * 3 + n * 3 + 4) * f32,
            expert_gate_up=2 * 2 * rows * 16 * f32,
            gate_up=2 * 2 * n * 16 * f32)                   # shared expert
    rec = get_programs().last(net._prog_caller)
    assert rec["key"].startswith("train_step") \
        and rec["remat_kept_bytes"] == want
    fam = get_registry().get("dl4jtpu_remat_kept_bytes")
    mine = {k: c.value for k, c in fam.children()
            if net._prog_caller in k and rec["key"] in k}
    assert sorted(mine.values()) == sorted(want.values())
    plain, _, _ = _two_blocks(cfg, kind, mlp, False)
    plain.fit(iter([DataSet(ids, labels)]))
    assert get_programs().last(
        plain._prog_caller)["remat_kept_bytes"] is None
