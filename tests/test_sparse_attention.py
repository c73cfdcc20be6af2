"""Attention over a learned selection of keys (``RotaryGQAttention`` with an
``indexer``), the layer-loss door of both containers, and the model built
from the benchmark's configuration of that family, against the plain
reference ``perfbench/lib/reference_sparse_lm.py`` at a small size."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                ops)
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (DenseLayer, ExpertLayer,
                                          OutputLayer, RotaryGQAttention)
from deeplearning4j_tpu.nn.layers import decoder
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.ops import index_scores as index_kernel
from deeplearning4j_tpu.ops.flash_attention import (gqa_head_mean_probs,
                                                    gqa_selected_attention)
from perfbench.lib import arch, reference_lm, reference_sparse_lm as ref
from perfbench.jobs import fit_lm, fit_sparse_lm as job

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "perfbench", "configs")
C, H, D, J, DI, TOP, T, B = 32, 4, 8, 2, 4, 8, 32, 2
INDEXER = ("WqI", "WkI", "WwI", "kI_gamma", "kI_beta")


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's configuration at its rehearsal size: 4 layers,
    hidden 32, 4 heads over 2 KV heads, 2 index heads of 4, top-k 8, 16
    experts of which 4 are held."""
    return arch.load_config(
        os.path.join(CONFIGS, "keye-vl-2.0-30b-a3b.json"), rehearse=True)


@pytest.fixture
def kernels():
    prev = ops.set_helpers_enabled(True, interpret=True)
    yield
    ops.set_helpers_enabled(prev[0], interpret=prev[1])


def _layer(kv, top_k=TOP, **kw):
    return RotaryGQAttention(
        n_in=C, n_heads=H, n_kv_heads=kv, head_dim=D,
        rotary={"theta": 1e7, "dims": D}, qk_norm=True,
        indexer={"heads": J, "head_dim": DI, "top_k": top_k}, **kw)


def _dims(kv, top_k=TOP):
    return {"heads": H, "kv_heads": kv, "head_dim": D, "index_heads": J,
            "index_dim": DI, "index_top_k": top_k, "eps": 1e-6,
            "rope": {"theta": 1e7, "dims": D},
            "index_rope": {"theta": 1e7, "dims": DI}}


def _params(layer, seed):
    rs = np.random.default_rng(seed)
    return {k: jnp.asarray(rs.normal(size=v.shape).astype(np.float32))
            * (0.3 if v.ndim == 2 else 1.0)
            for k, v in layer.init(jax.random.PRNGKey(0)).items()}


def _x(seed, t=T):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(B, t, C)).astype(np.float32))


def _plain(p, x, d):
    """The reference's layer over a batch: (output, L_I, keys selected)."""
    outs = [ref.selected_attention(x[b], p, d, chunk=8)
            for b in range(x.shape[0])]
    return (jnp.stack([o[0] for o in outs]),
            sum(o[1] for o in outs) / len(outs), sum(o[2] for o in outs))


@pytest.mark.parametrize("kv", [1, 2])
@pytest.mark.parametrize("kernel", [False, True])
def test_layer_value_loss_and_every_gradient_against_the_reference(kv, kernel):
    """T = 32 past top-k 8, so the selection bites: output, the indexer's
    loss, the keys counted, and the gradient of (output . w + 3 L_I) by
    every parameter and by the input, on the plain path and with the
    kernels interpreted."""
    layer, d = _layer(kv), _dims(kv)
    p, x = _params(layer, kv), _x(7)
    w = jnp.asarray(np.random.default_rng(9).normal(
        size=(B, T, C)).astype(np.float32))
    prev = ops.set_helpers_enabled(kernel, interpret=kernel)
    try:
        with jax.default_matmul_precision("highest"):
            def prog(p, x):
                y, s = layer.apply(p, x, layer.init_state(), train=True)
                return (y * w).sum() + 3.0 * s["index_loss"], (y, s)

            def plain(p, x):
                y, li, n = _plain(p, x, d)
                return (y * w).sum() + 3.0 * li, (y, li, n)

            (_, (y, s)), g = jax.value_and_grad(prog, (0, 1), has_aux=True)(
                p, x)
            (_, (yr, li, n)), gr = jax.value_and_grad(
                plain, (0, 1), has_aux=True)(p, x)
    finally:
        ops.set_helpers_enabled(prev[0], interpret=prev[1])
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-5)
    assert float(s["index_loss"]) == pytest.approx(float(li), rel=1e-5)
    want = B * sum(min(t + 1, TOP) for t in range(T))
    assert [int(v) for v in s["keys_selected"]] == [int(n), 0] == [want, 0]
    assert [int(v) for v in s["keys_visible"]] == [B * T * (T + 1) // 2, 0]
    assert [int(v) for v in s["keys_selected_total"]] == [want, 0]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4),
        g, gr)


def test_at_top_k_positions_or_fewer_the_layer_is_the_layer_without_indexer():
    layer = _layer(2, top_k=T)
    bare = RotaryGQAttention(n_in=C, n_heads=H, n_kv_heads=2, head_dim=D,
                             rotary={"theta": 1e7, "dims": D}, qk_norm=True)
    p, x = _params(layer, 3), _x(4)
    for train in (False, True):
        y, s = layer.apply(p, x, layer.init_state(), train=train)
        yb, _ = bare.apply({k: v for k, v in p.items() if k not in INDEXER},
                           x, {}, train=train)
        np.testing.assert_allclose(y, yb, rtol=1e-5, atol=1e-6)
    assert list(s["keys_selected"]) == list(s["keys_visible"]) \
        == [B * T * (T + 1) // 2, 0]
    assert float(s["index_loss"]) > 0


def test_a_tie_at_the_last_place_goes_to_the_lower_position():
    """Rows of equal scores: the selection is the lowest positions, as
    ``jax.lax.top_k`` orders them, and never more than top-k keys."""
    scores = jnp.asarray([[1.0, 1.0, 1.0, 1.0, 1.0, 1.0],
                          [0.0, -0.0, 2.0, 0.0, 0.0, 5.0],
                          [3.0, 1.0, 3.0, 1.0, 3.0, 1.0],
                          [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]], jnp.float32)
    got = np.asarray(decoder._select_rows(scores, 5, 3))   # rows 5..8 see all
    _, idx = jax.lax.top_k(scores, 3)
    want = np.zeros(scores.shape, np.int8)
    np.put_along_axis(want, np.asarray(idx), 1, axis=1)
    np.testing.assert_array_equal(got, want)
    # a row that sees fewer than top-k keys takes all it sees
    got = np.asarray(decoder._select_rows(scores, 0, 3))
    np.testing.assert_array_equal(got[0], [1, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(got[1], [1, 1, 0, 0, 0, 0])
    np.testing.assert_array_equal(got[2], [1, 1, 1, 0, 0, 0])
    np.testing.assert_array_equal(got[3], [0, 1, 1, 1, 0, 0])
    assert got.sum(axis=1).tolist() == [1, 2, 3, 3]


@pytest.mark.parametrize("t,di,rows,forms", [
    (48, DI, 8, {"xla"}),               # under the kernel's screen
    (256, 8, 128, {"kernel"}),          # extents 128 and 256: whole blocks
    (256, 8, 64, {"kernel", "xla"}),    # extents 64 and 192 fall back
])
def test_selection_by_chunks_is_the_selection_of_top_k(kernels, t, di, rows,
                                                       forms):
    """``selected_keys_mask`` over uneven row groups against ``top_k`` of
    the whole score matrix, with the scores of the chunks from the kernel,
    from the einsum, and from both."""
    rs = np.random.default_rng(5)
    qi = jnp.asarray(rs.normal(size=(1, t, J, di)).astype(np.float32))
    wi = jnp.asarray(rs.normal(size=(1, t, J)).astype(np.float32))
    ki = jnp.asarray(rs.normal(size=(1, t, di)).astype(np.float32))
    with index_kernel.counting_calls({}) as calls:
        got = np.asarray(decoder.selected_keys_mask(qi, wi, ki, TOP,
                                                    rows=rows))[0]
    assert set(calls) == forms
    score = np.asarray(decoder.index_scores_xla(qi[0], wi[0], ki[0]))
    vis = np.tril(np.ones((t, t), bool))
    _, idx = jax.lax.top_k(jnp.where(vis, score, -jnp.inf), TOP)
    want = np.zeros((t, t), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    np.testing.assert_array_equal(got != 0, want & vis)


# ------------------------------------------- the index scores' two forms

def _index_case(r, s, di, dtype, seed=0):
    rs = np.random.default_rng(seed)
    return (jnp.asarray(rs.normal(size=(r, J, di)), dtype),
            jnp.asarray(rs.normal(size=(r, J)), jnp.float32),
            jnp.asarray(rs.normal(size=(s, di)), dtype),
            jnp.asarray(rs.normal(size=(r, s)), jnp.float32))


@pytest.mark.parametrize("r,s,di,dtype,form", [
    (32, 128, 8, "float32", "kernel"),      # one key block
    (64, 384, 16, "float32", "kernel"),     # three blocks of 128
    (512, 512, 8, "float32", "kernel"),     # the layer's chunk of rows
    (16, 256, 8, "bfloat16", "kernel"),
    (32, 192, 8, "float32", "xla"),         # not a whole number of blocks
    (32, 128, 4, "float32", "xla"),         # the head dim under a tile
    (36, 128, 8, "float32", "xla"),         # rows that are no whole tiles
])
def test_index_scores_and_their_gradients_in_both_forms(kernels, r, s, di,
                                                        dtype, form):
    """``index_scores`` against the einsum in float32 at ``highest``: the
    value and the gradients by qi, wi, ki under a cotangent; the form it
    took is the one the shape screen says, and is counted."""
    qi, wi, ki, g = _index_case(r, s, di, jnp.dtype(dtype))
    f32 = lambda a: a.astype(jnp.float32)
    assert index_kernel.supported(r, J, di, s, qi.dtype.itemsize) \
        == (form == "kernel")
    with jax.default_matmul_precision("highest"):
        with index_kernel.counting_calls({}) as calls:
            got, back = jax.vjp(decoder.index_scores, qi, wi, ki)
        want, plain = jax.vjp(decoder.index_scores_xla, f32(qi), wi, f32(ki))
        assert calls == {form: 1}
        tol = dict(rtol=2e-2, atol=2e-1) if dtype == "bfloat16" \
            else dict(rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got, want, **tol)
        for a, b in zip(back(g), plain(g)):
            np.testing.assert_allclose(f32(a), b, **tol)


def test_the_indexers_loss_over_chunks_past_the_first_in_both_forms():
    """``index_loss`` and its three gradients at 256 positions in chunks of
    64 rows (the chunks after the first start past row 0; the extents 128
    and 256 take the kernel, 64 and 192 the einsum) against the same loss
    with the kernels off."""
    rs = np.random.default_rng(11)
    t, di = 256, 8
    qi = jnp.asarray(rs.normal(size=(1, t, J, di)).astype(np.float32))
    wi = jnp.asarray(rs.normal(size=(1, t, J)).astype(np.float32))
    ki = jnp.asarray(rs.normal(size=(1, t, di)).astype(np.float32))
    p = jnp.asarray(rs.uniform(size=(1, t, t)).astype(np.float32))
    got = {}
    for on in (False, True):
        prev = ops.set_helpers_enabled(on, interpret=on)
        try:
            with jax.default_matmul_precision("highest"), \
                    index_kernel.counting_calls({}) as calls:
                mask = decoder.selected_keys_mask(qi, wi, ki, TOP, rows=64)
                got[on] = jax.value_and_grad(
                    lambda *a: decoder.index_loss(*a, mask, p, 64),
                    argnums=(0, 1, 2))(qi, wi, ki)
        finally:
            ops.set_helpers_enabled(prev[0], interpret=prev[1])
        assert set(calls) == ({"kernel", "xla"} if on else {"xla"})
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7),
        got[True], got[False])


@pytest.mark.parametrize("hq,hkv", [(4, 1), (8, 2)])
def test_the_indexers_loss_reads_the_kernels_head_mean_as_the_plain_one(
        hq, hkv):
    """``index_loss`` and its gradients by qi, wi and ki with ``p`` from
    ``gqa_head_mean_probs`` (interpreted, the kv heads' groups a step on
    tiles of 16) equal them with ``p`` from the plain path within float32
    rounding, at 128 positions in chunks of 32 rows: the chunks read ``p``
    past the diagonal inside their row group, where the kernel's zero
    tiles are."""
    rs = np.random.default_rng(12)
    t, di, dh = 128, 8, 8
    qi, wi, ki = (jnp.asarray(rs.normal(size=s).astype(np.float32))
                  for s in ((1, t, J, di), (1, t, J), (1, t, di)))
    q = jnp.asarray(rs.normal(size=(1, hq, t, dh)).astype(np.float32))
    k = jnp.asarray(rs.normal(size=(1, hkv, t, dh)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        mask = decoder.selected_keys_mask(qi, wi, ki, 2 * TOP, rows=32)
        lse = gqa_selected_attention(q, k, k, mask, 16, True)[1]
        ps = {"kernel": gqa_head_mean_probs(q, k, lse, mask, 16, True),
              "plain": decoder.selected_attention(q, k, k, mask)[1]}
        got = {n: jax.value_and_grad(
            lambda *a: decoder.index_loss(*a, mask, p, 32),
            argnums=(0, 1, 2))(qi, wi, ki) for n, p in ps.items()}
    np.testing.assert_allclose(ps["kernel"], ps["plain"], rtol=1e-6,
                               atol=1e-7)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8),
        got["kernel"], got["plain"])


# --------------------------------------------------- the layer-loss door

class Penalised(DenseLayer):
    """A dense layer that asks for small activations: its own term of the
    step's loss is the mean square of its output."""
    loss_state = "penalty"

    def init_state(self, dtype=jnp.float32):
        return {"penalty": jnp.zeros((), jnp.float32)}

    def apply(self, params, x, state=None, *, train=False, rng=None,
              mask=None):
        y, _ = super().apply(params, x, None, train=train, rng=rng, mask=mask)
        return y, ({"penalty": jnp.mean(y.astype(jnp.float32) ** 2)}
                   if train else state)


def _door_net(container, penalised=True):
    first = (Penalised if penalised else DenseLayer)(
        n_out=6, activation="tanh")
    b = NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
    out = OutputLayer(n_out=3, activation="softmax", loss="mcxent")
    if container == "list":
        conf = (b.list().layer(first).layer(out)
                .set_input_type(InputType.feed_forward(5)).build())
        return MultiLayerNetwork(conf).init()
    from deeplearning4j_tpu import ComputationGraph
    conf = (b.graph_builder().add_inputs("in")
            .set_input_types(InputType.feed_forward(5))
            .add_layer("first", first, "in").add_layer("out", out, "first")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _door_data(n=1):
    rs = np.random.default_rng(1)
    x = rs.normal(size=(n, 8, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.integers(0, 3, (n, 8))]
    return x, y


@pytest.mark.parametrize("container", ["list", "graph"])
@pytest.mark.parametrize("scan", [False, True])
def test_a_layers_own_loss_term_reaches_the_score_and_the_gradient(
        container, scan):
    """Both containers, through ``fit`` and through ``fit_scan``: the score
    is the output layer's plus the layer's term, and the step that follows
    differs from the step of the same net without the term by exactly the
    term's gradient."""
    x, y = _door_data()
    nets = {p: _door_net(container, p) for p in (True, False)}
    nets[False].params = jax.tree_util.tree_map(jnp.array, nets[True].params)
    before = jax.tree_util.tree_map(np.asarray, nets[True].params)
    for net in nets.values():
        if scan:
            net.fit_scan(jnp.asarray(x), jnp.asarray(y))
        else:
            net.fit(iter([DataSet(x[0], y[0])]))
    first = before[0] if container == "list" else before["first"]
    act = np.tanh(x[0] @ first["W"] + first["b"])
    penalty = float(np.mean(act ** 2))
    assert penalty > 1e-3
    assert float(nets[True].get_score()) == pytest.approx(
        float(nets[False].get_score()) + penalty, rel=1e-5)
    state = nets[True].state
    assert float((state[0] if container == "list" else state["first"])
                 ["penalty"]) == pytest.approx(penalty, rel=1e-5)
    # the term's gradient by the first layer's weights, by hand
    dact = 2 * act / act.size * (1 - act ** 2)
    after = {p: jax.tree_util.tree_map(np.asarray, n.params)
             for p, n in nets.items()}
    pick = (lambda t: t[0]) if container == "list" else (lambda t: t["first"])
    np.testing.assert_allclose(
        pick(after[False])["W"] - pick(after[True])["W"],
        0.1 * x[0].T @ dact, rtol=1e-4, atol=1e-7)


# ------------------------------------------------------ the model, fit()

def _net(cfg, layers=None, index_dim=None, **kw):
    cfg = dict(cfg, program=dict(cfg["program"], kwargs=dict(
        cfg["program"]["kwargs"], **kw)))
    model = dict(cfg["rehearsal"]["model"])
    if layers:
        model["num_hidden_layers"] = layers
    if index_dim:
        model["sa_config"] = dict(model["sa_config"],
                                  indexer_head_dim=index_dim)
    cfg["rehearsal"] = dict(cfg["rehearsal"], model=model)
    return cfg, job.build_net(cfg)


def _batches(cfg, n, seed=0, seq=32):
    return job.make_pool(cfg, {"pool_batches": n}, seed, 2, seq)


# (positions, index head dim, the form the index scores take with kernels
# on): the rehearsal's own size is under the kernel's shape screen; 128
# positions of index heads of 8 are one key block
SIZES = [(32, None, "xla"), (128, 8, "kernel")]


def test_sparse_decoder_from_this_familys_keys_and_from_lagunas(cfg):
    _, net = _net(cfg)
    nodes = net.conf.nodes
    assert sum(k.endswith(".attn") for k in nodes) == 4
    attn, mlp = nodes["b2.attn"].layer, nodes["b2.mlp"].layer
    assert attn.indexer == {"heads": 2, "head_dim": 4, "top_k": 8}
    assert attn.qk_norm and attn.window is None and not attn.head_gate
    assert (attn.n_heads, attn.n_kv_heads, attn.head_dim) == (4, 2, 8)
    assert attn.rotary == {"theta": 10000000, "dims": 8}
    assert isinstance(mlp, ExpertLayer) and mlp.shared_width == 0
    assert (mlp.n_experts, mlp.experts_per_token, mlp.held) == (16, 3, (4, 0))
    assert "Sg" not in net.params["b2.mlp"]
    assert set(INDEXER) < set(net.params["b2.attn"])
    # Laguna's keys build what they built: no indexer, no norm on q and k,
    # per-layer head counts, windows, a dense first layer, a shared expert
    lag = fit_lm.build_net(arch.load_config(
        os.path.join(CONFIGS, "laguna-s-2.1.json"), rehearse=True))
    for i, heads in enumerate([12, 18, 18, 18, 12]):
        a = lag.conf.nodes[f"b{i}.attn"].layer
        assert a.n_heads == heads and a.indexer is None and not a.qk_norm
        assert a.head_gate and (a.window == 8) == (heads == 18)
        assert set(lag.params[f"b{i}.attn"]) == {"Wq", "Wk", "Wv", "Wo",
                                                 "Wgate"}
        assert lag.state[f"b{i}.attn"] == {}
    assert type(lag.conf.nodes["b0.mlp"].layer).__name__ == "SwiGLU"
    assert lag.conf.nodes["b1.mlp"].layer.shared_width == 16


def test_three_fit_steps_of_two_layers_against_three_reference_steps(cfg):
    """Loss of each step (cross entropy plus both layers' L_I), L_I and the
    keys selected per layer and step, Adam's first moment after step 1, the
    parameters' change after step 3; float32 on both sides."""
    cfg2, net = _net(cfg, layers=2)
    job.set_weights(cfg2, net, ref.init_params(cfg2, 5))
    pool = _batches(cfg2, 3)
    seen = job.check_steps(
        cfg2, {"steps_per_call": 1, "check_steps": 3}, net, pool, DataSet, 5)
    want = ref.run_steps(cfg2, 5, pool)
    np.testing.assert_allclose([seen["losses"][i] for i in (1, 2, 3)],
                               want["losses"], rtol=1e-5)
    np.testing.assert_allclose(seen["trace_norms"], want["trace_norms"],
                               rtol=1e-4, atol=1e-9)
    np.testing.assert_allclose(seen["delta_norms"], want["delta_norms"],
                               rtol=5e-3)
    assert [[p for _, p in s] for s in seen["pairs"]] == want["pairs"]
    got = [[c["keys_selected"] for _, c in s] for s in seen["selection"]]
    assert got == want["keys"] == [[2 * 228] * 2] * 3
    np.testing.assert_allclose(
        [[c["index_loss"] for _, c in s] for s in seen["selection"]],
        want["index_loss"], rtol=1e-5)
    vals = job.extra_readings(seen, want, 0)
    assert vals["selected_keys_gap"] == 0 and vals["index_loss_gap"] < 1e-5


def _grads(net, pool, what):
    ids, labels = (jnp.asarray(a) for a in pool[0])

    def f(params):
        loss, (state, _) = net._loss_for_grad()(
            params, net.state, [ids], [labels], jax.random.PRNGKey(0), None,
            None)
        own = sum(s["index_loss"] for s in state.values()
                  if s and "index_loss" in s)
        return {"step": loss, "index": own}[what]

    return jax.grad(f)(net.params)


@pytest.mark.parametrize("remat", [None, "blocks"])
def test_indexer_and_main_parameters_each_receive_their_own_loss(cfg, remat):
    """Of the step's loss, WqI, WkI, WwI and the LayerNorm receive the
    gradient of the layers' L_I alone, and every other parameter none of
    it: its gradient is the cross entropy's alone. With and without the
    block replay, which the layers' terms cross."""
    cfg2, net = _net(cfg, layers=2, remat=remat)
    job.set_weights(cfg2, net, ref.init_params(cfg2, 8))
    pool = _batches(cfg2, 1, seed=2)
    step, index = _grads(net, pool, "step"), _grads(net, pool, "index")
    want = jax.grad(lambda p: ref.loss_fn(
        cfg2, p, *map(jnp.asarray, pool[0]), fault="no_index_loss")[0])(
            ref.init_params(cfg2, 8))
    for node, leaves in step.items():
        for leaf, g in leaves.items():
            own = np.asarray(index[node][leaf])
            if leaf in INDEXER:
                assert np.abs(own).max() > 0
                np.testing.assert_allclose(g, own, rtol=1e-5, atol=1e-9)
                assert not np.asarray(want[node][leaf]).any()
            else:
                assert not own.any(), (node, leaf)
                np.testing.assert_allclose(g, want[node][leaf], rtol=2e-3,
                                           atol=1e-6)


def test_block_replay_leaves_the_step_and_the_layers_terms_what_they_are(
        cfg, kernels):
    got = {}
    for remat in (None, "blocks"):
        cfg2, net = _net(cfg, layers=2, remat=remat)
        job.set_weights(cfg2, net, ref.init_params(cfg2, 6))
        pool = _batches(cfg2, 1, seed=3)
        net.fit(iter([DataSet(*pool[0])]))
        got[remat] = (net.get_score(), np.asarray(
            net.params["b1.attn"]["WqI"]), job.selection_counts(net))
    assert got[None][0] == pytest.approx(got["blocks"][0], rel=1e-6)
    np.testing.assert_allclose(got[None][1], got["blocks"][1], rtol=1e-5,
                               atol=1e-9)
    assert got[None][2] == got["blocks"][2]


def _count(jaxpr, into, inside=None, within=False):
    """How often each primitive appears in ``jaxpr``, inner jaxprs included
    (a ``jit`` by its function's name); with ``inside`` only below an
    equation of that primitive."""
    for e in jaxpr.eqns:
        name = e.primitive.name
        if name in ("jit", "pjit"):
            name = "jit:" + e.params["name"]
        if within or inside is None:
            into[name] = into.get(name, 0) + 1
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _count(j, into, inside, within or name == inside)
    return into


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (a kernel's
    body too)."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _eqns(j)


def _grad_jaxpr(cfg, remat, seq, index_dim):
    cfg2, net = _net(cfg, layers=2, remat=remat, index_dim=index_dim)
    ids, labels = (jnp.asarray(a) for a in _batches(cfg2, 1, seq=seq)[0])
    loss = net._loss_for_grad()
    return jax.make_jaxpr(jax.grad(
        lambda p: loss(p, net.state, [ids], [labels], jax.random.PRNGKey(0),
                       None, None)[0]))(net.params).jaxpr


@pytest.mark.parametrize("seq,index_dim,form", SIZES)
def test_a_block_replay_selects_no_key_and_scores_no_index_again(
        cfg, kernels, seq, index_dim, form):
    """The gradient's jaxpr under ``remat='blocks'``: the kernels of a
    layer (selected attention forward, the head-mean weights, dq, dk/dv,
    and where the index scores take the kernel their forward for the
    selection and their forward and backward under the loss) once each, as
    without replay; and inside the replayed blocks no selection, none of
    the loops over row chunks that score the index (``lax.map`` / ``scan``:
    the selection's and the indexer loss's, whose gradient was taken in the
    forward pass) and of the kernels only the attention's two backward
    ones: no index kernel."""
    seen = {}
    for remat in (None, "blocks"):
        jaxpr = _grad_jaxpr(cfg, remat, seq, index_dim)
        seen[remat] = (_count(jaxpr, {}), _count(jaxpr, {}, "remat2"))
    (plain, _), (blocks, replay) = seen[None], seen["blocks"]
    assert plain["jit:_select_rows"] >= 2 and plain["scan"] >= 4
    per_layer = 4 + 3 * (form == "kernel")
    assert blocks["pallas_call"] == plain["pallas_call"] == per_layer * 2
    assert blocks["remat2"] == 2 and "remat2" not in plain
    assert "jit:_select_rows" not in replay and "scan" not in replay
    assert replay["pallas_call"] == 2 * 2


def test_no_heads_by_rows_by_keys_array_on_the_kernel_path(cfg):
    """The step's gradient at 128 positions with index heads of 8: with the
    kernels on no matrix product anywhere in it (the kernels' bodies
    included) makes an array with the index heads over (rows, keys); with
    them off the einsum and its derivative do."""
    def heads_over_pairs(jaxpr):
        return [e.outvars[0].aval.shape for e in _eqns(jaxpr)
                if e.primitive.name == "dot_general"
                and sorted(e.outvars[0].aval.shape) == [J, 128, 128]]

    assert len(heads_over_pairs(_grad_jaxpr(cfg, "blocks", 128, 8))) >= 2 * 2
    prev = ops.set_helpers_enabled(True, interpret=True)
    try:
        jaxpr = _grad_jaxpr(cfg, "blocks", 128, 8)
    finally:
        ops.set_helpers_enabled(prev[0], interpret=prev[1])
    assert heads_over_pairs(jaxpr) == []
    assert any(e.primitive.name == "pallas_call" for e in _eqns(jaxpr))


def test_eight_shares_without_a_shared_expert_add_up_to_the_uncut_layer():
    """Guide section 4: 128 experts as 8 shares of 16, top-8, renormalised,
    no shared expert: the shares' routed parts add up to the uncut
    reference's layer."""
    whole = ExpertLayer(n_in=C, n_experts=128, experts_per_token=8,
                        expert_width=16, norm_topk=True)
    p = whole.init(jax.random.PRNGKey(3))
    assert "Sg" not in p
    x = jnp.asarray(np.random.default_rng(8).normal(
        size=(48, C)).astype(np.float32))
    total, pairs = 0.0, 0
    for s in range(8):
        share = ExpertLayer(n_in=C, n_experts=128, experts_per_token=8,
                            expert_width=16, norm_topk=True,
                            experts_held=(16, 16 * s))
        ps = dict(p, **{k: p[k][16 * s:16 * s + 16]
                        for k in ("Eg", "Eu", "Ed")})
        y, seen = share.routed(ps, x)
        total = total + y
        pairs += int(seen["pairs"])
        assert int(seen["pairs_dropped"]) == 0
    want, _ = reference_lm.experts(x, p, top_k=8, held=(128, 0),
                                   routed_scale=1.0, norm_topk=True)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)
    assert pairs == 48 * 8


def test_selection_counters_and_the_index_loss_at_the_fit_boundary(cfg):
    from deeplearning4j_tpu.monitor.metrics import get_registry
    cfg2, net = _net(cfg, layers=2)
    pool = _batches(cfg2, 2)
    net.fit(iter([DataSet(*b) for b in pool]))
    reg = get_registry()

    def mine(name):
        return {k: c.value for k, c in reg.get(name).children()
                if "b1.attn" in k}

    sel = mine("dl4jtpu_sparse_attention_keys_selected_total")
    vis = mine("dl4jtpu_sparse_attention_keys_visible_total")
    # two steps of 2 x 32 positions, top-k 8
    assert sum(sel.values()) >= 2 * 2 * 228 and sum(vis.values()) >= 2 * 2 * 528
    state = dict(job.selection_counts(net))["b1.attn"]
    assert state["keys_selected"] == 2 * 228
    assert state["keys_visible"] == 2 * 528
    loss = mine("dl4jtpu_index_loss")
    assert list(loss.values())[-1] == pytest.approx(state["index_loss"])
    assert [int(w) for w in net.state["b1.attn"]["keys_visible_total"]] \
        == [2 * 2 * 528, 0]


def test_a_running_sum_carries_past_32_bits():
    total = jnp.asarray([2 ** 32 - 5, 7], jnp.uint32)
    got = decoder._add_wide(total, decoder._wide(9))
    assert [int(w) for w in got] == [4, 8]
    got = decoder._add_wide(total, decoder._wide(2 ** 33 + 2 ** 32 - 1))
    assert [int(w) for w in got] == [2 ** 32 - 6, 7 + 2 + 1]


@pytest.mark.parametrize("b,t", [(1, 65536), (16, 16384), (1, 262144)])
def test_a_steps_counts_pass_31_bits(b, t):
    """One step's visible pairs pass 2**31 at 65,536 positions (or 16
    sequences of 16,384) and 2**32 at 92,682: the counts are wide from the
    start, and a sum over sequences carries."""
    n = b * (t * (t + 1) // 2)
    lo, hi = (int(w) for w in decoder._wide(n))
    assert (hi << 32) | lo == n >= 2 ** 31
    # sequences whose own counts fit a word and whose sum does not
    per = np.asarray([2 ** 32 - 1, 2 ** 32 - 7, 12345], np.uint64)
    lo, hi = (int(w) for w in jax.jit(decoder._fold_wide)(
        jnp.asarray(per.astype(np.uint32))))
    assert (hi << 32) | lo == int(per.sum())


def test_a_training_step_without_the_layers_state_raises():
    layer = _layer(2)
    p, x = _params(layer, 3), _x(4)
    with pytest.raises(ValueError, match="needs the layer's state"):
        layer.apply(p, x, None, train=True)
    with pytest.raises(ValueError, match="needs the layer's state"):
        layer.apply(p, x, {}, train=True)
    y, s = layer.apply(p, x, None, train=False)     # inference needs none
    assert s is None and y.shape == x.shape


@pytest.mark.parametrize("seq,index_dim,form", SIZES)
def test_the_new_scopes_are_in_the_compiled_step(cfg, kernels, seq,
                                                 index_dim, form):
    """The compiled step's ``op_scopes`` name the four scopes; the index
    scores' instructions (the kernel's, where they take it) sit under
    ``index``, innermost under ``select`` and under ``index_loss``; and the
    record says which form the step's index-score calls took."""
    from deeplearning4j_tpu.exec.programs import get_programs
    from deeplearning4j_tpu.monitor.metrics import get_registry
    cfg2, net = _net(cfg, layers=2, remat="blocks", index_dim=index_dim)
    pool = _batches(cfg2, 1, seq=seq)
    net.fit(iter([DataSet(*pool[0])]))
    recs = [e for e in get_programs().entries()
            if e["caller"] == net._prog_caller
            and e["key"].startswith("train_step")]
    table = get_programs().get(net._prog_caller, recs[-1]["key"])["op_scopes"]
    paths = {p.replace("jvp(", "").replace("transpose(", "").replace(")", "")
             for p in table.values()}
    for scope in ("index", "select", "attend", "index_loss"):
        assert any(f"RotaryGQAttention/{scope}" in p or f"/{scope}/" in p
                   or p.endswith(f"/{scope}") for p in paths), scope
    for outer in ("select", "index_loss"):
        assert any(f"/{outer}/" in p and "/index" in p.split(f"/{outer}/")[1]
                   for p in paths), outer
    # every index-score call of the step took one form: per layer the
    # selection's one row group and the loss's (traced as the loss and as
    # its forward rule)
    calls = recs[-1]["index_scores_calls"]
    assert calls == {"kernel": 0, "xla": 0, form: 2 * 3}
    fam = get_registry().get("dl4jtpu_index_scores_calls")
    mine = {k: c.value for k, c in fam.children() if net._prog_caller in k}
    assert sorted(mine.values()) == [0, 2 * 3]
    kept = recs[-1].get("remat_kept_bytes") or {}
    # 2 layers x (2 x T x T int8 mask + its count, two uint32 words)
    assert kept.get("selection") == 2 * (2 * seq * seq + 8)
    # 2 layers x float32 (qI 2 x T x 2 x D, wI 2 x T x 2, kI 2 x T x D, and
    # the loss's own value)
    di = index_dim or DI
    assert kept.get("index_grads") \
        == 2 * 4 * (2 * seq * (2 * di + 2 + di) + 1)


def test_the_chip_screen_of_the_selected_attention_runs_small(kernels):
    """``python -m deeplearning4j_tpu.ops.validate``'s case for the kernels
    under a mask, at a size the CPU takes, kernels interpreted."""
    from deeplearning4j_tpu.ops import validate
    r = validate.validate_selected_attention_case(
        1, 4, 2, 128, 8, 2, 8, 16, dtype="float32", time_it=False)
    assert r["keys_selected"] == sum(min(i + 1, 16) for i in range(128))
    assert r["selection_differs_from_top_k"] == 0
    assert r["max_err"] < 1e-3 and r["errs"]["kl"] < 1e-4
    # the index scores' rows: both forms at this size, each against the
    # plain form in float32
    assert r["index_forms"] == ["kernel", "xla"] and r["index_rows"] == 128
    for form in r["index_forms"]:
        assert r["errs"][f"index_{form}"] < 1e-5
        assert all(r["errs"][f"index_{form}_d{n}"] < 1e-4
                   for n in ("qi", "wi", "ki"))
