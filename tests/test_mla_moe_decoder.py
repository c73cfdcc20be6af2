"""A latent-attention decoder with no query bottleneck on the plain residual
path, softmax-routed experts beside shared ones and the sequence-wise
balance loss (the benchmark's DeepSeek-V2-Lite cell) against the plain
reference of the benchmark (perfbench/lib/reference_mla_moe_lm.py), at a
small size on the CPU with seeded weights: the attention layer, the balance
loss and its gradient, the unnormalised softmax route with two shared
experts, the shares of the expert layer against the uncut layer, the whole
model's loss, gradients and three Adam steps; the block structure, what the
replay keeps, the counters and the keys the builder refuses."""

import math
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.exec.programs import get_programs
from deeplearning4j_tpu.nn.layers import ExpertLayer, LatentAttention
from deeplearning4j_tpu.nn.layers import latent
from deeplearning4j_tpu.nn.layers.decoder import sequence_balance_loss
from deeplearning4j_tpu.util.remat import BLOCK_KEPT, remat_segments
from deeplearning4j_tpu.zoo.decoder import (LATENT_PAIR_KEPT, SparseDecoder,
                                           rotary_settings)
from perfbench.lib import arch
from perfbench.lib import reference_mla_moe_lm as ref
from perfbench.jobs import fit_mla_moe_lm as job

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs", "deepseek-v2-lite.json")
C = 32


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's configuration at its rehearsal size: the same 5
    layers, hidden 32, 2 latent-attention heads with no query bottleneck,
    4 of 8 experts held, top-2 unnormalised, two shared experts, the
    balance loss at alpha 0.01."""
    return arch.load_config(CONFIG, rehearse=True)


def _model_keys(cfg):
    return dict(cfg, **cfg["rehearsal"]["model"])


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _close(a, b, tol=2e-4):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _net(cfg, seed):
    net = job.build_net(cfg)
    job.set_weights(cfg, net, ref.init_params(cfg, seed))
    return net


# ------------------------------------------ (a) attention without q rank

def _mla_dims(cfg):
    """The rehearsal's attention with 8 rotated dims, q.k over 16: a width
    the grouped-query kernels take."""
    return dict(ref.dims(cfg), rope_dim=8)


def _mla(cfg):
    d = _mla_dims(cfg)
    return LatentAttention(
        n_in=C, n_out=C, n_heads=d["heads"], q_rank=0, kv_rank=d["kv_rank"],
        nope_dim=d["nope"], rope_dim=d["rope_dim"], v_dim=d["v"],
        rotary=rotary_settings(_model_keys(cfg), "full_attention"),
        weight_init="xavier")


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_latent_attention_without_a_query_bottleneck_against_reference(
        cfg, form):
    """One ``Wq`` and no query norm: the output and the gradient of every
    parameter and of the input against the reference's layer, on XLA's
    form and on the grouped-query kernels (interpreted); the call is
    counted by the form it took."""
    layer = _mla(cfg)
    p = layer.init(jax.random.PRNGKey(1))
    assert set(p) == {"Wq", "Wkva", "kv_gamma", "Wkvb", "Wo"}
    assert p["Wq"].shape == (C, 2 * (8 + 8))
    x, cot = _rand((2, 64, C), 2), _rand((2, 64, C), 3)
    d = _mla_dims(cfg)
    calls = {}
    prev = ops.set_helpers_enabled(form == "kernel", interpret=True)
    try:
        with jax.default_matmul_precision("highest"), \
                latent.counting_calls(calls):
            got = jax.value_and_grad(
                lambda p, x: (layer.apply(p, x)[0] * cot).sum(), (0, 1))(p, x)
    finally:
        ops.set_helpers_enabled(prev[0], interpret=prev[1])
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(lambda p, x: (jnp.stack(
            [ref.mla(d, xi, p) for xi in x]) * cot).sum(), (0, 1))(p, x)
    # float32 at the highest precision on both sides: rounding alone
    jax.tree_util.tree_map(lambda a, b: _close(a, b, 1e-4), got, want)
    assert calls == {form: 1}


def test_a_padding_mask_names_the_remedy(cfg):
    layer = _mla(cfg)
    p = layer.init(jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="segment mask.*R12"):
        layer.apply(p, _rand((1, 8, C), 1), mask=jnp.ones((1, 8)))


# --------------------------------------------------- (b) the balance loss

def _experts(held=None, alpha=0.01, norm_topk=False):
    return ExpertLayer(n_in=C, n_experts=8, experts_per_token=2,
                       expert_width=16, shared_width=32, routed_scale=1.0,
                       norm_topk=norm_topk, experts_held=held,
                       balance_alpha=alpha, weight_init="xavier")


def _dims(**kw):
    return dict({"experts_held": 8, "first_expert": 0, "top_k": 2,
                 "norm_topk": False, "routed_scale": 1.0, "alpha": 0.01},
                **kw)


def test_balance_loss_against_the_formula_and_its_gradient():
    """The layer's term in its state equals alpha sum_i f_i P_i averaged
    over the sequences; its gradient reaches the router's W_r (and no
    expert's weights) as the reference's does with f held constant; and
    through the scores alone: d/ds_{i,t} = alpha f_i / (T B), f carrying
    none."""
    layer = _experts()
    p = layer.init(jax.random.PRNGKey(3))
    x = _rand((2, 16, C), 4)
    d = _dims()

    def mine(p):
        _, st = layer.apply(p, x, layer.init_state(), train=True)
        return st["balance_loss"]

    def plain(p):
        def one(xi):
            s = jax.nn.softmax(xi @ p["Wr"], axis=-1)
            return ref.balance(d, s, jax.lax.top_k(s, 2)[1])
        return jnp.mean(jnp.stack([one(xi) for xi in x]))

    assert layer.loss_state == "balance_loss"
    with jax.default_matmul_precision("highest"):
        got, g = jax.value_and_grad(mine)(p)
        want, gw = jax.value_and_grad(plain)(p)
    _close(got, want, 1e-6)
    # an even routing reads alpha: sum_i f_i P_i is near 1
    assert 0.5 * 0.01 < float(got) < 2.0 * 0.01
    _close(g["Wr"], gw["Wr"], 1e-5)
    assert float(jnp.abs(g["Wr"]).max()) > 0
    for k in ("Eg", "Eu", "Ed", "Sg", "Su", "Sd"):
        assert float(jnp.abs(g[k]).max()) == 0.0, k
    # the scores' cotangent is alpha f / (T B): f counts, P carries
    s = jax.nn.softmax(_rand((2 * 16, 8), 5), axis=-1)
    idx = jax.lax.top_k(s, 2)[1]
    ds = jax.grad(lambda s: sequence_balance_loss(s, idx, 2, 0.01))(s)
    hits = jax.nn.one_hot(idx.reshape(2, 32), 8).sum(1)
    f = hits * 8 / (2 * 16)
    _close(ds.reshape(2, 16, 8), jnp.broadcast_to(
        (0.01 * f / (16 * 2))[:, None, :], (2, 16, 8)), 1e-7)


def test_the_balance_loss_is_off_by_default_and_wants_softmax():
    layer = _experts(alpha=0.0)
    p = layer.init(jax.random.PRNGKey(3))
    _, st = layer.apply(p, _rand((1, 8, C), 1), layer.init_state(),
                        train=True)
    assert layer.loss_state is None and "balance_loss" not in st
    with pytest.raises(ValueError, match="softmax"):
        ExpertLayer(n_in=C, n_experts=8, expert_width=16, score="sigmoid",
                    balance_alpha=0.01).init(jax.random.PRNGKey(0))


# ----------------------------------- (c) softmax top-k unnormalised, shared

@pytest.mark.parametrize("norm_topk", [False, True])
def test_softmax_route_and_two_shared_experts_against_reference(norm_topk):
    """Top-2 of 8 by softmax scores, the weights the scores themselves (or
    renormalised), two shared experts as one SwiGLU of twice the width."""
    layer = _experts(norm_topk=norm_topk)
    p = layer.init(jax.random.PRNGKey(6))
    assert p["Sg"].shape == (C, 32)
    x = _rand((2, 16, C), 7)
    with jax.default_matmul_precision("highest"):
        y, _ = layer.apply(p, x)
        want, pairs, _ = ref.experts(_dims(norm_topk=norm_topk),
                                     x.reshape(-1, C), p)
    _close(y.reshape(-1, C), want, 1e-5)
    assert int(pairs) == 2 * 16 * 2


# ------------------------------------------------------------ (d) shares

def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Guide section 4: one expert a chip of 8; the routed parts of every
    share plus the shared experts once add up to the uncut reference layer,
    and every share's balance term is the uncut layer's (the router is
    whole on every chip)."""
    whole = _experts()
    p = whole.init(jax.random.PRNGKey(8))
    x = _rand((2, 16, C), 9)
    x2 = x.reshape(-1, C)
    with jax.default_matmul_precision("highest"):
        want, _, bal = ref.experts(_dims(), x2, p)
        parts = [whole.shared(p, x2).astype(jnp.float32)]
        pairs = 0
        for s in range(8):
            share = _experts((1, s))
            ps = dict(p, **{k: p[k][s:s + 1] for k in ("Eg", "Eu", "Ed")})
            y, seen = share.routed(ps, x2, batch=1)
            parts.append(y)
            pairs += int(seen["pairs"])
            _close(seen["balance_loss"], bal, 1e-6)
    assert pairs == x2.shape[0] * 2
    _close(sum(parts), want)


# --------------------------------------------------- (e) the whole model

def test_logits_loss_and_every_gradient_leaf_against_reference(cfg):
    net = _net(cfg, 4)
    ids, labels = job.make_pool(cfg, {"pool_batches": 1}, 4, 2, 32)[0]
    w = ref.init_params(cfg, 4)
    with jax.default_matmul_precision("highest"):
        def mine(p):
            loss, _ = net._loss(p, net.state, [jnp.asarray(ids)],
                                [jnp.asarray(labels)], None)
            return loss

        got_loss, got = jax.value_and_grad(mine)(net.params)
        (want_loss, seen), grads = jax.value_and_grad(
            lambda p: ref.loss_fn(cfg, p, jnp.asarray(ids),
                                  jnp.asarray(labels)), has_aux=True)(w)
    # the balance terms are in both: four expert layers, each near alpha
    assert len(seen["balance"]) == 4
    assert 0.02 < float(sum(seen["balance"])) < 0.08
    _close(got_loss, want_loss, 1e-5)
    leaves = [(k, n) for k, n, _, _ in ref.param_shapes(cfg)]
    assert {k for k, _ in leaves} == {k for k, v in got.items() if v}
    for k, n in leaves:
        g, r = np.asarray(got[k][n]), np.asarray(grads[k][n])
        scale = max(float(np.abs(r).max()), 1e-12)
        assert float(np.abs(g - r).max()) / scale < 2e-4, (k, n)


@pytest.fixture(scope="module")
def trained(cfg):
    """The rehearsal's model after three checked steps from seed 5, with
    what the job read off it."""
    net = _net(cfg, 5)
    pool = job.make_pool(cfg, {"pool_batches": 3}, 0, 2, 32)
    seen = job.check_steps(cfg, {"steps_per_call": 1, "check_steps": 3}, net,
                           pool, DataSet, 5)
    return net, pool, seen


def test_three_fit_steps_against_three_reference_steps(cfg, trained):
    """Loss of each step (the balance terms in it), Adam's first moment
    after step 1, the parameters' change after step 3, the pairs and each
    layer's balance term; float32 on both sides, ``remat='blocks'``
    keeping what the configuration names."""
    net, pool, seen = trained
    assert net.conf.global_conf.remat == "blocks"
    want = ref.run_steps(cfg, 5, pool)
    np.testing.assert_allclose([seen["losses"][i] for i in (1, 2, 3)],
                               want["losses"], rtol=1e-5)
    np.testing.assert_allclose(seen["trace_norms"], want["trace_norms"],
                               rtol=1e-4)
    np.testing.assert_allclose(seen["delta_norms"], want["delta_norms"],
                               rtol=5e-2)
    assert [[p for _, p in s] for s in seen["pairs"]] == want["pairs"]
    np.testing.assert_allclose(seen["balance"], want["balance"], rtol=1e-5)
    vals = job.extra_readings(seen, want, 0)
    assert vals["balance_loss_gap"] < 1e-4
    # a program that counted no balance reads as far as it can be
    assert job.extra_readings(dict(seen, balance=[[]] * 3), want, 0)[
        "balance_loss_gap"] == 1.0


# ---------------------------------------- the block and what it keeps

def test_a_latent_block_is_the_plain_pre_norm_pair(cfg, trained):
    net = trained[0]
    blocks = [(names, outs) for names, outs in remat_segments(net.conf)
              if outs is not None]
    assert len(blocks) == 5
    for i, (names, outs) in enumerate(blocks):
        assert names == [f"b{i}.{n}" for n in (
            "norm1", "attn", "add1", "norm2", "mlp", "add2")]
        assert outs == [f"b{i}.add2"]
    kinds = {n: type(net.conf.nodes[n].layer).__name__ for n in
             net.conf.nodes if net.conf.nodes[n].kind == "layer"}
    assert not {"HyperConnection", "MultiTokenHead"} & set(kinds.values())
    assert kinds["b0.mlp"] == "SwiGLU" and kinds["b1.mlp"] == "ExpertLayer"
    experts = net.conf.nodes["b1.mlp"].layer
    assert (experts.n_experts, experts.held, experts.score, experts.norm_topk,
            experts.shared_width, experts.balance_alpha) == (
                8, (4, 0), "softmax", False, 32, 0.01)
    attn = net.conf.nodes["b0.attn"].layer
    assert attn.q_rank == 0
    assert attn.rotary["score_scale"] == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert attn.rotary["attention_factor"] == 1.0


def test_the_replay_keeps_only_the_names_the_configuration_keeps(
        cfg, trained):
    """The builder leaves q, k, v and the SwiGLUs' products of a latent
    pair to the replay (``LATENT_PAIR_KEPT``): the step's record reads 0
    bytes kept under their names and some under the names kept. The
    hyper-connected latent family and the other families keep them all."""
    net = trained[0]
    assert net.conf.global_conf.remat_keep == LATENT_PAIR_KEPT == (
        "attn_out", "routing", "expert_gate_up")
    rec = [e for e in get_programs().entries()
           if e["caller"] == net._prog_caller
           and e["key"].startswith("train_step")][-1]
    kept = rec["remat_kept_bytes"]
    assert set(kept) == set(BLOCK_KEPT)
    assert kept["qkv"] == 0 and kept["gate_up"] == 0
    assert kept["routing"] > 0 and kept["expert_gate_up"] > 0
    xing = arch.load_config(CONFIG.replace("deepseek-v2-lite",
                                           "xing4.0-29b-a4b"), rehearse=True)
    conf = SparseDecoder(_model_keys(xing), remat="blocks").conf()
    assert conf.global_conf.remat_keep is None


# ------------------------------------------------------------ counters

def test_latent_attention_calls_and_balance_counter_at_the_fit_boundary(
        cfg, trained):
    from deeplearning4j_tpu.monitor.metrics import get_registry
    net, pool, seen = trained
    # XLA's form off the chip: one call a layer, counted while traced
    assert job.latent_attention_calls(net) == {"kernel": 0, "xla": 5}
    fam = get_registry().get("dl4jtpu_latent_attention_calls")
    mine = {k: c.value for k, c in fam.children() if net._prog_caller in k}
    assert sorted(mine.values()) == [0, 5]
    net.fit(iter([DataSet(*b) for b in pool[:1]]))
    got = {k: c.value for k, c in get_registry().get(
        "dl4jtpu_moe_balance_loss").children()}
    layers = job.balance_counts(cfg, net)
    assert len(layers) == 4
    for i, v in enumerate(layers, 1):
        assert [g for k, g in got.items() if f"b{i}.mlp" in k][-1] \
            == pytest.approx(v)


# ------------------------------------------------ (g) keys refused

@pytest.mark.parametrize("keys, words", [
    ({"n_group": 8, "topk_group": 3}, "device-limited routing"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"num_nextn_predict_layers": 1}, "hyper-connected residual streams"),
    ({"num_nextn_predict_layers": 2},
     "num_nextn_predict_layers=2 in the file")])
def test_the_builder_refuses_what_it_does_not_build(cfg, keys, words):
    with pytest.raises(ValueError, match=words):
        SparseDecoder(dict(_model_keys(cfg), **keys)).conf()
