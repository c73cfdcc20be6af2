"""A latent-attention decoder over hyper-connected residual streams with one
multi-token-prediction module (the benchmark's Xing4.0-29B-A4B cell) against
the plain reference of the benchmark (perfbench/lib/reference_mla_lm.py), at
a small size on the CPU with seeded weights: logits, the loss with its
module's term and every gradient leaf; three ``fit()`` steps; Sinkhorn's
matrices; the shares of the expert and attention layers against the uncut
layer; the flash kernels with a value head of its own; the counters, the
scopes and what the repair of ``rotary_settings`` leaves as it was."""

import contextlib
import hashlib
import json
import math
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.exec.programs import get_programs
from deeplearning4j_tpu.nn.layers import (ExpertLayer, HyperConnection,
                                          LatentAttention)
from deeplearning4j_tpu.nn.layers.latent import sinkhorn, stochastic_gap
from deeplearning4j_tpu.ops import hyper_connection as hc_kernel
from deeplearning4j_tpu.ops.flash_attention import (
    gqa_flash_attention, gqa_selected_attention)
from deeplearning4j_tpu.util.remat import remat_segments
from deeplearning4j_tpu.zoo.decoder import SparseDecoder, rotary_settings
from perfbench.lib import arch, scopes
from perfbench.lib import reference_mla_lm as ref
from perfbench.jobs import fit_mla_lm as job

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs", "xing4.0-29b-a4b.json")
C = 32


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's configuration at its rehearsal size: the same 5
    layers and module, hidden 32, 4 streams, 2 latent-attention heads, 4 of
    8 experts held, top-2."""
    return arch.load_config(CONFIG, rehearse=True)


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _close(a, b, tol=2e-4):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _batches(cfg, n, seed=0):
    return job.make_pool(cfg, {"pool_batches": n}, seed, 2, 32)


def _net(cfg, seed):
    net = job.build_net(cfg)
    job.set_weights(cfg, net, ref.init_params(cfg, seed))
    return net


# --------------------------------------------- the whole model, one step

@contextlib.contextmanager
def _hc_kernels(cfg):
    """The rehearsal at hidden 128, a stream width the hyper-connection
    kernels take, with every kernel on and interpreted."""
    r = cfg["rehearsal"]
    prev = ops.set_helpers_enabled(True, interpret=True)
    try:
        yield dict(cfg, rehearsal=dict(r, model=dict(r["model"],
                                                     hidden_size=128)))
    finally:
        ops.set_helpers_enabled(prev[0], interpret=prev[1])


def test_logits_loss_and_every_gradient_leaf_against_reference(cfg):
    _check_logits_loss_and_gradients(cfg)


def test_logits_loss_and_every_gradient_leaf_on_the_hc_kernels(cfg):
    calls = {}
    with _hc_kernels(cfg) as wide, hc_kernel.counting_calls(calls):
        _check_logits_loss_and_gradients(wide)
    assert calls["kernel"] > 0 and "xla" not in calls


def _check_logits_loss_and_gradients(cfg):
    net = _net(cfg, 4)
    ids, labels = _batches(cfg, 1, seed=4)[0]
    w = ref.init_params(cfg, 4)
    with jax.default_matmul_precision("highest"):
        probs = net.output(ids, bucketed=False)
        want = jax.vmap(lambda i: ref.logits(cfg, w, i)[0])(jnp.asarray(ids))
        _close(probs, jax.nn.softmax(want, axis=-1), 1e-5)

        def mine(p):
            loss, _ = net._loss(p, net.state, [jnp.asarray(ids)],
                                [jnp.asarray(labels)], None)
            return loss

        got_loss, got = jax.value_and_grad(mine)(net.params)
        (want_loss, seen), grads = jax.value_and_grad(
            lambda p: ref.loss_fn(cfg, p, jnp.asarray(ids),
                                  jnp.asarray(labels)), has_aux=True)(w)
    # the module's term is in both: without it the loss is the trunk's alone
    assert float(seen["mtp"]) > 1.0
    _close(got_loss, want_loss, 1e-5)
    leaves = [(k, n) for k, n, _, _ in ref.param_shapes(cfg)]
    assert {k for k, _ in leaves} == set(got) - {
        k for k, v in got.items() if not v}
    for k, n in leaves:
        g, r = np.asarray(got[k][n]), np.asarray(grads[k][n])
        scale = max(float(np.abs(r).max()), 1e-12)
        assert float(np.abs(g - r).max()) / scale < 2e-4, (k, n)


def _three_steps(cfg):
    net = _net(cfg, 5)
    pool = _batches(cfg, 3)
    seen = job.check_steps(cfg, {"steps_per_call": 1, "check_steps": 3}, net,
                           pool, DataSet, 5)
    return net, pool, seen


@pytest.fixture(scope="module")
def trained(cfg):
    """The rehearsal's model after three checked steps from seed 5, with
    what the job read off it."""
    return _three_steps(cfg)


def test_three_fit_steps_against_three_reference_steps(cfg, trained):
    """Loss of each step (the module's term in it), Adam's first moment
    after step 1, the parameters' change after step 3, the pairs and the
    module's loss; float32 on both sides, ``remat='blocks'``."""
    _check_three_steps(cfg, trained)


def test_three_fit_steps_on_the_hc_kernels(cfg):
    """The same three steps with every hyper-connection pass on its kernel;
    the step program's record counts each pass of the step's trace (12
    hyper-connections x the maps, the mix-in and the mix-out) as
    ``kernel``."""
    from deeplearning4j_tpu.monitor.metrics import get_registry
    # the scales and bias of the two hyper-connections that read four equal
    # streams (the first layer's, the module's) have a first gradient that
    # cancels: at hidden 128 their change after three Adam steps is noise
    # on the order of itself, 0.24 apart from the reference on XLA's form
    # too; every other leaf is held to 5e-2 as above
    cancelling = [(k, n) for k in ("b0.hc1", "mtp.hc1")
                  for n in ("alpha", "bias")]
    with _hc_kernels(cfg) as wide:
        trained = _three_steps(wide)
        _check_three_steps(wide, trained, cancelling)
    net = trained[0]
    rec = [e for e in get_programs().entries()
           if e["caller"] == net._prog_caller
           and e["key"].startswith("train_step")][-1]
    assert rec["hyper_connection_calls"] == {"kernel": 36, "xla": 0}
    fam = get_registry().get("dl4jtpu_hyper_connection_calls")
    mine = {k: c.value for k, c in fam.children() if net._prog_caller in k}
    assert sorted(mine.values()) == [0, 36]


def _check_three_steps(cfg, trained, cancelling=()):
    """``cancelling``: (node, leaf) pairs whose change is left out of the
    comparison (see the caller)."""
    net, pool, seen = trained
    assert net.conf.global_conf.remat == "blocks"
    want = ref.run_steps(cfg, 5, pool)
    np.testing.assert_allclose([seen["losses"][i] for i in (1, 2, 3)],
                               want["losses"], rtol=1e-5)
    np.testing.assert_allclose(seen["trace_norms"], want["trace_norms"],
                               rtol=1e-4)
    held = [i for i, (k, n, _, _) in enumerate(ref.param_shapes(cfg))
            if (k, n) not in cancelling]
    np.testing.assert_allclose(np.asarray(seen["delta_norms"])[held],
                               np.asarray(want["delta_norms"])[held],
                               rtol=5e-2)
    assert [[p for _, p in s] for s in seen["pairs"]] == want["pairs"]
    np.testing.assert_allclose(seen["mtp"], want["mtp"], rtol=1e-5)
    assert max(seen["gap"]) < 1e-2 and max(want["gap"]) < 1e-2


def test_a_latent_block_is_two_hyper_connected_sublayers(cfg, trained):
    net = trained[0]
    blocks = [(names, outs) for names, outs in remat_segments(net.conf)
              if outs is not None]
    assert [names[0] for names, _ in blocks] == [
        "b0.hc1", "b1.hc1", "b2.hc1", "b3.hc1", "b4.hc1", "mtp.join"]
    for i, (names, outs) in enumerate(blocks[:5]):
        assert names == [f"b{i}.{n}" for n in (
            "hc1", "in1", "norm1", "attn", "out1",
            "hc2", "in2", "norm2", "mlp", "out2")]
        assert outs == [f"b{i}.out2"]
    kinds = {n: type(net.conf.nodes[n].layer).__name__
             for n in ("b0.mlp", "b1.mlp", "mtp.mlp", "b0.attn", "head")}
    assert kinds == {"b0.mlp": "SwiGLU", "b1.mlp": "ExpertLayer",
                     "mtp.mlp": "ExpertLayer", "b0.attn": "LatentAttention",
                     "head": "MultiTokenHead"}
    experts = net.conf.nodes["b1.mlp"].layer
    assert (experts.n_experts, experts.held, experts.score,
            experts.expert_form) == (8, (4, 0), "sigmoid", "swiglu")
    attn = net.conf.nodes["b0.attn"].layer
    assert attn.rotary["score_scale"] == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2)
    assert attn.rotary["attention_factor"] == 1.0
    with pytest.raises(ValueError,
                       match="num_nextn_predict_layers=2 in the file"):
        SparseDecoder(dict(cfg, **cfg["rehearsal"]["model"],
                           num_nextn_predict_layers=2)).conf()


# ----------------------------------------------------------------- Sinkhorn

def test_sinkhorn_leaves_a_doubly_stochastic_matrix():
    """Columns (normalised last) sum to 1 to hc_eps; rows to what 20
    rounds reach at these spreads, and to hc_eps given more rounds. The
    layer's own matrices are the reference's."""
    m = jnp.exp(_rand((512, 4, 4), 1))
    h = sinkhorn(m, 20, 1e-6)
    assert float(jnp.abs(h.sum(-2) - 1).max()) < 5e-6
    assert float(stochastic_gap(h)) < 1e-3
    assert float(stochastic_gap(sinkhorn(m, 200, 1e-6))) < 1e-5
    assert float(stochastic_gap(ref.sinkhorn(m, 20, 1e-6))) == pytest.approx(
        float(stochastic_gap(h)), abs=1e-6)
    layer = HyperConnection(n_in=4 * C, streams=4)
    p = layer.init(jax.random.PRNGKey(0))
    x = _rand((2, 8, 4 * C), 2)
    maps, st = layer.apply(p, x, layer.init_state(), train=True)
    d = {"eps": 1e-6, "clamp": (-30.0, 30.0), "sinkhorn_iters": 20,
         "hc_eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        pre, post, res = ref.hyper_maps(d, x.reshape(16, 4, C), p)
    _close(maps.reshape(16, -1), jnp.concatenate(
        [pre, post, res.reshape(16, 16)], -1), 1e-5)
    assert float(st["sinkhorn_gap"]) == pytest.approx(
        float(stochastic_gap(res)), abs=1e-6)
    assert 0.0 < float(st["res_diag_mean"]) < 1.0


# ---------------------------------------------------------------- shares

def _mla(heads):
    rot = {"theta": 10000, "dims": 8, "factor": 64,
           "original_max_position": 16, "beta_fast": 32, "beta_slow": 1,
           "attention_factor": 1.0, "score_scale": 1.7}
    return LatentAttention(n_in=C, n_out=C, n_heads=heads, q_rank=16,
                           kv_rank=8, nope_dim=8, rope_dim=8, v_dim=8,
                           rotary=rot, weight_init="xavier")


def _dims(heads):
    return {"heads": heads, "nope": 8, "rope_dim": 8, "v": 8, "kv_rank": 8,
            "eps": 1e-6, "score_scale": 1.7,
            "rope": {"theta": 10000, "dims": 8, "factor": 64,
                     "original_max_position": 16, "beta_fast": 32,
                     "beta_slow": 1, "attention_factor": 1.0}}


def _attention_shares(x):
    """Head shares of latent attention with 8 heads over 4 chips (q.k over
    8 + 8 dims, values of 8): W_qb,
    W_kvb and W_o sliced by head, the down-projections and norms whole."""
    whole = _mla(8)
    p = whole.init(jax.random.PRNGKey(1))
    want = jnp.stack([ref.mla(_dims(8), xi, p) for xi in x])
    parts = []
    for s in range(4):
        heads = slice(2 * s, 2 * s + 2)
        cols = lambda w, per: w.reshape(w.shape[0], 8, per)[:, heads] \
            .reshape(w.shape[0], -1)
        ps = dict(p, Wqb=cols(p["Wqb"], 16), Wkvb=cols(p["Wkvb"], 16),
                  Wo=p["Wo"].reshape(8, 8, C)[heads].reshape(-1, C))
        parts.append(_mla(2).apply(ps, x)[0])
    return want, parts


def _expert_layer(held=None):
    return ExpertLayer(n_in=C, n_experts=8, experts_per_token=2,
                       expert_width=16, shared_width=16, routed_scale=2.0,
                       experts_held=held, score="sigmoid",
                       weight_init="xavier")


def _expert_shares(x):
    """Expert shares, one expert a chip of 8: the routed parts of every
    share plus the shared expert once."""
    whole = _expert_layer()
    p = whole.init(jax.random.PRNGKey(3))
    x2 = x.reshape(-1, C)
    d = {"experts_held": 8, "first_expert": 0, "top_k": 2, "norm_topk": True,
         "routed_scale": 2.0}
    want, _ = ref.experts(d, x2, p)
    parts = [whole.shared(p, x2).astype(jnp.float32)]
    pairs = 0
    for s in range(8):
        share = _expert_layer((1, s))
        ps = dict(p, **{k: p[k][s:s + 1] for k in ("Eg", "Eu", "Ed")})
        y, seen = share.routed(ps, x2)
        parts.append(y)
        pairs += int(seen["pairs"])
        assert int(seen["pairs_dropped"]) == 0
    assert pairs == x2.shape[0] * 2
    return want, parts


@pytest.mark.parametrize("shares", [_attention_shares, _expert_shares],
                         ids=["attention_heads", "experts"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(shares):
    """Guide section 4: what every chip of the deployment computes of one
    layer, what they compute alike counted once, adds up to what the uncut
    reference gives for the whole layer."""
    with jax.default_matmul_precision("highest"):
        want, parts = shares(_rand((2, 24, C), 8))
    _close(sum(parts).reshape(want.shape), want)


# -------------------------------------------------------- the flash kernels

def _plain(q, k, v, scale):
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ok = jnp.tril(jnp.ones((t, t), bool))
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1), v)


def test_flash_kernels_with_a_value_head_of_its_own():
    """q.k over 24 dims, values of 16, a scale of the caller's: forward and
    the three gradients, interpreted, against XLA."""
    q, k, v = _rand((1, 4, 64, 24), 1), _rand((1, 4, 64, 24), 2), \
        _rand((1, 4, 64, 16), 3)
    cot = _rand((1, 4, 64, 16), 4)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: (gqa_flash_attention(
            *a, None, 16, True, 0.37) * cot).sum(), (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(lambda *a: (_plain(*a, 0.37) * cot).sum(),
                                  (0, 1, 2))(q, k, v)
    jax.tree_util.tree_map(lambda a, b: _close(a, b, 1e-4), got, want)


# the grouped-query kernels at equal dims, their forward and gradient
# lowered in interpret mode from a fresh trace cache: the text of the parent
# commit (aff8220), so the three language-model cells run the programs they
# ran
KERNEL_TEXT = {
    "gqa": "dd1e449ee80c31ca41bd25186d74dd8cec8130f4d27e9996d545302f11b809f0",
    "selected":
        "1805cfed8454e6266a71a383c7a70eceea65b8435a0060b8e922df1ab726728b",
}


def _kernel_text(which):
    """sha256 of the lowered forward and gradient of one grouped-query
    kernel at equal dims, interpreted, from a fresh trace cache."""
    jax.clear_caches()
    sh = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
          for s in ((1, 4, 64, 16), (1, 2, 64, 16), (1, 2, 64, 16))]
    if which == "gqa":
        def loss(q, k, v):
            return gqa_flash_attention(q, k, v, 24, 16, True).astype(
                jnp.float32).sum()
    else:
        sh = sh + [jax.ShapeDtypeStruct((1, 64, 64), jnp.int8)]

        def loss(q, k, v, m):
            return gqa_selected_attention(q, k, v, m, 16, True)[0].astype(
                jnp.float32).sum()
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        *sh).as_text(debug_info=False)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("which", sorted(KERNEL_TEXT))
def test_grouped_query_kernels_at_equal_dims_are_the_programs_they_were(
        which):
    assert _kernel_text(which) == KERNEL_TEXT[which]


def test_latent_attention_takes_the_kernel_where_the_rule_routes_it():
    layer = _mla(2)
    p = layer.init(jax.random.PRNGKey(5))
    x = _rand((1, 64, C), 6)
    prev = ops.set_helpers_enabled(True, interpret=True)
    try:
        with jax.default_matmul_precision("highest"):
            got = layer.apply(p, x)[0]
            text = str(jax.make_jaxpr(lambda x: layer.apply(p, x)[0])(x))
    finally:
        ops.set_helpers_enabled(*prev[:1], interpret=prev[1])
    assert "pallas_call" in text
    with jax.default_matmul_precision("highest"):
        _close(got, ref.mla(_dims(2) | {"score_scale": 1.7}, x[0], p)[None],
               1e-4)


# ----------------------------------------------------- counters and scopes

def test_hyper_and_mtp_counters_at_the_fit_boundary(cfg, trained):
    from deeplearning4j_tpu.monitor.metrics import get_registry
    net, pool, _ = trained
    net.fit(iter([DataSet(*b) for b in pool[:1]]))
    gap, diag, mtp = job.stream_counts(net)
    reg = get_registry()
    got = {k: c.value for k, c in reg.get("dl4jtpu_hc_sinkhorn_gap")
           .children()}
    assert max(v for k, v in got.items() if "b1.hc2" in k) <= gap
    diag_g = [c.value for k, c in reg.get("dl4jtpu_hc_res_diag_mean")
              .children() if "b0.hc1" in k]
    assert diag_g and 0.0 < diag_g[-1] < 1.0
    loss = [c.value for k, c in reg.get("dl4jtpu_mtp_loss").children()
            if "head" in k]
    assert loss and loss[-1] == pytest.approx(mtp)


def test_the_new_scopes_are_in_the_compiled_step(trained):
    net = trained[0]
    rec = [e for e in get_programs().entries()
           if e["caller"] == net._prog_caller
           and e["key"].startswith("train_step")][-1]
    table = get_programs().get(net._prog_caller, rec["key"])["op_scopes"]
    inner, layers = {}, set()
    for op_name in table.values():
        phase, layer, kind = scopes.classify(op_name)
        layers.add(layer)
        parts = {m.group(1) if (m := scopes._WRAPPED.match(p)) else p
                 for p in (op_name or "").split("/")}
        inner.setdefault(kind, set()).update(parts)
    assert {"mla", "latent_q", "latent_kv", "attend"} \
        <= inner["LatentAttention"]
    assert {"hc", "hc_maps", "sinkhorn"} <= inner["HyperConnection"]
    assert {"hc", "hc_mix"} <= inner["HyperOut"] & inner["HyperIn"]
    # the module's term of the loss: the loss phase has no layer scope
    assert "mtp" in inner["-"]
    assert {"mtp.attn:LatentAttention", "mtp.mlp:ExpertLayer"} <= layers


# ------------------------------------------------------------- rotary

# the settings the two configurations with rotary read before the repair
# (parent commit aff8220)
ROTARY_BEFORE = {
    "laguna-s-2.1": {
        "full_attention": {"attention_factor": 1.4852030263919618,
                           "beta_fast": 32, "beta_slow": 1, "dims": 64,
                           "factor": 128, "original_max_position": 8192,
                           "theta": 500000},
        "sliding_attention": {"dims": 128, "theta": 10000}},
    "keye-vl-2.0-30b-a3b": {"full_attention": {"dims": 128,
                                               "theta": 10000000}},
}


@pytest.mark.parametrize("name", sorted(ROTARY_BEFORE))
def test_rotary_settings_of_the_accepted_configurations_are_unchanged(name):
    with open(os.path.join(ROOT, "perfbench", "configs", f"{name}.json")) as f:
        c = json.load(f)
    got = {k: rotary_settings(c, k) for k in ROTARY_BEFORE[name]}
    assert got == ROTARY_BEFORE[name]


def test_a_type_yarn_file_is_yarn_in_deepseeks_form(cfg):
    r = rotary_settings(cfg, "full_attention")
    assert r["factor"] == 64 and r["dims"] == 64
    assert r["original_max_position"] == 4096
    # mscale 1 and mscale_all_dim 1: cos and sin unscaled, the scores
    # times (0.1 ln 64 + 1)^2 = 2.0048
    assert r["attention_factor"] == 1.0
    assert r["score_scale"] == pytest.approx(2.00474, rel=1e-5)
    plain = dict(cfg, rope_scaling=None)
    assert rotary_settings(plain, "full_attention") == {"theta": 10000,
                                                        "dims": 64}


# ------------------------------------------- the hybrid cell, unchanged

# sha256 of the hybrid cell's step program's lowered text at the rehearsal
# size, as the parent commit (aff8220) lowered it: the block structure, the
# expert layer and the kernels this PR touched leave it as it was
# (tests/test_hybrid_decoder.py pins the two paired decoders so)
HYBRID_LOWERED = {
    None: "f6e668600fc1def0e0d2a10ef78d24cc6a3f31f6e17fdaa88f3f4d5e317dfa35",
    "bfloat16":
        "30d32f336944825d7fbd2b4e9d3e96038186658934ba390402df9cc0dd60773f",
}


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_the_hybrid_decoder_lowers_to_the_step_it_lowered_to(dtype):
    from perfbench.lib import spec
    from deeplearning4j_tpu.exec import build_mesh, set_default_mesh
    bench = spec.load_benchmark()
    _, conf, traffic, _ = spec.cell(bench,
                                    "nemotron-3-super-120b-a12b.fit-s8k-b1")
    c = arch.load_config(os.path.join(ROOT, conf["file"]), rehearse=True)
    c["program"]["kwargs"]["compute_dtype"] = dtype
    jax.clear_caches()
    set_default_mesh(build_mesh(jax.devices()[:1]))     # one chip's step
    try:
        net = spec.load_module("jobs", traffic["job"]).build_net(c)
        ids = jnp.zeros((traffic["rehearsal_batch"],
                         traffic["rehearsal_seq"]), jnp.int32)
        text = net._make_train_step().lower(
            net.params, net.state, net.opt_state, [ids], [ids],
            jnp.asarray(0, jnp.int32), None, None).as_text()
    finally:
        set_default_mesh(None)
    assert hashlib.sha256(text.encode()).hexdigest() == HYBRID_LOWERED[dtype]
