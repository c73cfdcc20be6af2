"""What a language model asks of the fit path that the image nets did not:
integer labels in the loss, token ids through ``fit()``, a chunk rule that
knows a heavy step from small bytes, big layers outside the fused update,
and the expert layer's scopes in the compiled step."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.exec.programs import get_programs
from deeplearning4j_tpu.nn import losses, fused_update
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (DenseLayer, EmbeddingSequenceLayer,
                                          OutputLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.util import chunking
from deeplearning4j_tpu.util.remat import remat_segments, block_of
from deeplearning4j_tpu.util.timing import PipelineTimer
from perfbench.lib import arch, scopes
from perfbench.jobs import fit_lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ losses

@pytest.mark.parametrize("masked", [False, True])
def test_integer_labels_give_the_one_hot_loss(masked):
    rs = np.random.RandomState(0)
    z = jnp.asarray(rs.randn(24, 7), jnp.float32)
    ids = jnp.asarray(rs.randint(0, 7, 24), jnp.int32)
    mask = jnp.asarray(rs.rand(24) > 0.3, jnp.float32) if masked else None
    want = losses.mcxent(jax.nn.one_hot(ids, 7), z, "softmax", mask)
    assert float(losses.sparse_mcxent(ids, z, mask)) == pytest.approx(
        float(want), rel=1e-6)


def test_large_logits_are_made_in_chunks_of_rows(monkeypatch):
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(64, 8), jnp.float32)
    w = jnp.asarray(rs.randn(8, 16), jnp.float32)
    ids = jnp.asarray(rs.randint(0, 16, 64), jnp.int32)
    f = lambda x, w: losses.sparse_mcxent_from_features(ids, x, w)
    whole, g_whole = jax.value_and_grad(f, (0, 1))(x, w)
    monkeypatch.setattr(losses, "_CHUNKED_LOGITS", 256)
    monkeypatch.setattr(losses, "_LOGIT_ROWS", 16)
    chunked, g_chunked = jax.value_and_grad(f, (0, 1))(x, w)
    assert float(chunked) == pytest.approx(float(whole), rel=1e-6)
    for a, b in zip(g_chunked, g_whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_integer_labels_refuse_another_loss():
    layer = OutputLayer(n_in=4, n_out=3, loss="mse", activation="identity")
    p = layer.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="integer labels"):
        layer.compute_score(p, jnp.zeros((2, 4)), jnp.zeros((2,), jnp.int32))


def _token_net(container):
    b = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2))
         .weight_init("xavier"))
    if container == "list":
        conf = (b.list()
                .layer(EmbeddingSequenceLayer(n_in=11, n_out=8,
                                              activation="identity"))
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(RnnOutputLayer(n_out=11, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(11)).build())
        return MultiLayerNetwork(conf).init()
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    g = (b.graph_builder().add_inputs("ids")
         .set_input_types(InputType.recurrent(11)))
    g.add_layer("embed", EmbeddingSequenceLayer(
        n_in=11, n_out=8, activation="identity"), "ids")
    g.add_layer("mid", DenseLayer(n_out=8, activation="tanh"), "embed")
    g.add_layer("out", RnnOutputLayer(n_out=11, activation="softmax",
                                      loss="mcxent"), "mid")
    return ComputationGraph(g.set_outputs("out").build()).init()


@pytest.mark.parametrize("container", ["list", "graph"])
@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_token_ids_and_integer_labels_through_fit(container, dtype):
    """int32 ids in, int32 labels in the loss, under a compute dtype too:
    an id is never cast to a float (bfloat16 holds no id above 256)."""
    net = _token_net(container)
    net.conf.global_conf.compute_dtype = dtype
    rs = np.random.RandomState(2)
    ids = rs.randint(0, 11, (6, 4, 9)).astype(np.int32)
    data = [DataSet(a[:, :-1], a[:, 1:]) for a in ids]
    net.fit(iter(data))
    first = net.get_score()
    for _ in range(10):
        net.fit(iter(data))
    assert net.get_score() < first
    assert net.last_pipeline_stats["steps"] == 6


# -------------------------------------------------------------- chunk rule

@pytest.mark.parametrize("features,labels,n_params,want", [
    # a light step keeps its 64 steps a chunk
    ((np.zeros((32, 20), np.float32),), (np.zeros((32, 5), np.float32),),
     10_000, 64),
    # 155 MB of images go singly by their bytes, as before
    ((np.zeros((256, 224, 224, 3), np.float32),),
     (np.zeros((256, 1000), np.float32),), 25_600_000, 1),
    # 131 KB of token ids into 600 M parameters: a heavy step goes singly
    ((np.zeros((2, 8192), np.int32),), (np.zeros((2, 8192), np.int32),),
     602_000_000, 1),
    # time steps count as rows: a long sequence into a mid-size model
    ((np.zeros((64, 512, 16), np.float32),),
     (np.zeros((64, 512, 16), np.float32),), 5_000_000, 1),
    # bytes bind between the two
    ((np.zeros((64, 3, 32, 32), np.float32),),
     (np.zeros((64, 10), np.float32),), 100_000, 64),
    # raw uint8 pixels (the image iterators' wire format) are not tokens:
    # MNIST at batch 128, flat and as images, and CIFAR keep their chunks
    ((np.zeros((128, 784), np.uint8),), (np.zeros((128, 10), np.float32),),
     430_000, 64),
    ((np.zeros((128, 28, 28, 1), np.uint8),),
     (np.zeros((128, 10), np.float32),), 430_000, 64),
    ((np.zeros((128, 32, 32, 3), np.uint8),),
     (np.zeros((128, 10), np.float32),), 1_000_000, 64),
    # either side of the line: 13 M parameters at batch 128 (1.0e10 a step)
    # and at batch 256 (2.0e10), as measured on the chip
    ((np.zeros((128, 256), np.float32),), (np.zeros((128, 10), np.float32),),
     13_135_882, 64),
    ((np.zeros((256, 256), np.float32),), (np.zeros((256, 10), np.float32),),
     13_135_882, 1),
    # ids in, a distribution per token out: the labels say it is 16,384 rows
    ((np.zeros((2, 8192), np.int32),),
     (np.zeros((2, 8192, 16), np.float32),), 602_000_000, 1),
])
def test_steps_per_chunk(features, labels, n_params, want):
    assert chunking.steps_per_chunk(features, labels, n_params, 64,
                                    256 << 20) == want


def test_raw_uint8_images_keep_their_chunk_in_a_container():
    """MNIST as the fetchers ship it (uint8_wire) into a 0.4 M-parameter
    net: the light step fit_scan exists for."""
    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(DenseLayer(n_in=784, n_out=512, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784)).build())
    net = MultiLayerNetwork(conf).init()
    ds = DataSet(np.zeros((128, 784), np.uint8),
                 np.zeros((128, 10), np.float32))
    kind, (xs, ys) = next(net._stream_chunks([ds] * 65, None,
                                             PipelineTimer()))
    assert kind == "chunk" and xs.shape == (64, 128, 784)


def test_heavy_token_batches_go_singly_through_fit(monkeypatch):
    """The same net and data: chunks of 3 while the step is light, single
    train_step calls once the rule reads the step as heavy."""
    net = _token_net("graph")
    rs = np.random.RandomState(4)
    ids = rs.randint(0, 11, (6, 4, 9)).astype(np.int32)
    data = [DataSet(a[:, :-1], a[:, 1:]) for a in ids]
    seen = []
    real = net.fit_scan
    monkeypatch.setattr(net, "fit_scan",
                        lambda xs, ys: (seen.append(len(xs[0])),
                                        real(xs, ys))[1])
    net._CHUNK_MAX_STEPS = 3
    net.fit(iter(data))
    assert seen == [3, 3]
    monkeypatch.setattr(chunking, "STEP_MAX_FLOPS", 1.0)
    net.fit(iter(data))
    assert seen == [3, 3] and net.iteration == 12


def test_a_list_of_layers_refuses_remat_blocks():
    net = _token_net("list")
    net.conf.global_conf.remat = "blocks"
    with pytest.raises(ValueError, match="remat='blocks'"):
        MultiLayerNetwork(net.conf)


# ------------------------------------------------------------ fused update

def test_a_large_member_is_updated_by_itself(monkeypatch):
    import optax
    params = {"small": {"W": jnp.ones((4, 4))}, "big": {"W": jnp.ones((64, 64))},
              "tiny": {"b": jnp.ones((4,))}}
    grads = jax.tree_util.tree_map(lambda a: 0.5 * a, params)
    tx = {k: optax.adam(1e-2) for k in params}
    keys = {k: "adam" for k in params}
    opt = {k: tx[k].init(params[k]) for k in params}
    whole = fused_update.build_fused_update(params, tx, keys)
    assert sorted(whole.fused_keys) == ["big", "small", "tiny"]
    monkeypatch.setattr(fused_update, "FUSE_MAX_MEMBER_SIZE", 1024)
    split = fused_update.build_fused_update(params, tx, keys)
    assert split.fallback == ["big"]
    a, oa = whole.apply(params, opt, grads)
    b, ob = split.apply(params, opt, grads)
    jax.tree_util.tree_map(
        lambda u, v: np.testing.assert_array_equal(np.asarray(u),
                                                   np.asarray(v)), (a, oa),
        (b, ob))


# ------------------------------------------------- blocks and their scopes

@pytest.fixture(scope="module")
def decoder():
    cfg = arch.load_config(
        os.path.join(ROOT, "perfbench", "configs", "laguna-s-2.1.json"),
        rehearse=True)
    net = fit_lm.build_net(cfg)
    pool = fit_lm.make_pool(cfg, {"pool_batches": 1}, 0, 2, 32)
    net.fit(iter([DataSet(*pool[0])]))
    return net


def test_a_block_is_a_run_of_nodes_with_one_prefix(decoder):
    assert block_of("b3.attn") == "b3" and block_of("embed") is None
    segs = remat_segments(decoder.conf)
    blocks = [(names, outs) for names, outs in segs if outs is not None]
    assert len(blocks) == 5
    for i, (names, outs) in enumerate(blocks):
        assert names == [f"b{i}.{n}" for n in
                         ("norm1", "attn", "add1", "norm2", "mlp", "add2")]
        assert outs == [f"b{i}.add2"]        # only the residual leaves
    assert [names for names, outs in segs if outs is None] == [
        ["embed"], ["final_norm", "head"]]


def test_every_new_layer_and_the_expert_scopes_are_in_the_compiled_step(
        decoder):
    rec = [e for e in get_programs().entries()
           if e["caller"] == decoder._prog_caller
           and e["key"].startswith("train_step")][-1]
    table = get_programs().get(decoder._prog_caller, rec["key"])["op_scopes"]
    kinds, inner, phases = set(), set(), set()
    for op_name in table.values():
        phase, layer, kind = scopes.classify(op_name)
        phases.add(phase)
        kinds.add(kind)
        parts = (op_name or "").split("/")
        if kind == "ExpertLayer":
            inner |= {"route", "dispatch", "experts", "combine",
                      "shared"} & set(parts)
        if kind == "RotaryGQAttention" and "attend" in parts:
            inner.add("attend")
        if layer:
            assert ":" in layer and layer.split(":")[0] in decoder.conf.nodes
    assert {"RMSNorm", "RotaryGQAttention", "SwiGLU", "ExpertLayer",
            "EmbeddingSequenceLayer"} <= kinds
    assert inner == {"route", "dispatch", "experts", "combine", "shared",
                     "attend"}
    # one checkpoint a block: the replay is there, under the same names
    assert {"forward", "recompute", "backward", "loss", "updater"} <= phases
