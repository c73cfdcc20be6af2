"""A hybrid decoder of one mixer a layer (Mamba-2, latent relu^2 experts with
a sigmoid router, attention without rotation) against the plain reference of
the benchmark (perfbench/lib/reference_hybrid_lm.py), at a small size on the
CPU with seeded weights: the chunked scan against a loop over positions, the
shares of every mixer against the uncut layer, the expert layer's new forms,
the whole model through ``fit()``, what a block's replay keeps, the scopes
and counters, and that the two decoders the benchmark had lower to the steps
they lowered to."""

import hashlib
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.exec.programs import get_programs
from deeplearning4j_tpu.nn.layers import (ExpertLayer, Mamba2Mixer,
                                          RotaryGQAttention)
from deeplearning4j_tpu.nn.layers.decoder import route_top_k
from deeplearning4j_tpu.util.remat import BLOCK_KEPT, remat_segments
from deeplearning4j_tpu.zoo.decoder import SparseDecoder
from perfbench.lib import arch, scopes, spec
from perfbench.lib import reference_hybrid_lm as ref
from perfbench.jobs import fit_hybrid_lm as job

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "nemotron-3-super-120b-a12b.json")
C = 32


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's configuration at its rehearsal size: the same 11
    layers, hidden 32, 2 Mamba heads in 1 group, 4 of 16 experts in a
    latent of 16, 2 query heads on 1 KV head."""
    return arch.load_config(CONFIG, rehearse=True)


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _close(a, b, tol=2e-4):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _agree(prog, plain, params, x):
    """Forward and the gradients wrt the parameters and the input."""
    _close(prog(params, x), plain(params, x))
    cot = _rand(x.shape, 99)
    gp = jax.grad(lambda p, x: (prog(p, x) * cot).sum(), (0, 1))(params, x)
    gr = jax.grad(lambda p, x: (plain(p, x) * cot).sum(), (0, 1))(params, x)
    jax.tree_util.tree_map(lambda a, b: _close(a, b, 1e-3), gp, gr)


# ------------------------------------------------------------ Mamba-2 mixer

def _mixer(chunk=8, heads=4, groups=2):
    layer = Mamba2Mixer(n_in=C, n_out=C, n_heads=heads, head_dim=8,
                        n_groups=groups, state_size=8, chunk_size=chunk,
                        weight_init="xavier")
    p = layer.init(jax.random.PRNGKey(0))
    p["conv_b"] = _rand(p["conv_b"].shape, 5, 0.3)
    return layer, p


def _plain_mamba(layer):
    h, g = layer.n_heads, layer.n_groups

    def plain(p, x):
        return jnp.stack([ref.mamba(
            xi, p, heads=h, head_dim=8, groups=g, state=8, eps=1e-5,
            chunk=layer.chunk_size)[0] for xi in x])
    return plain


# two chunk sizes, and a length that is not whole chunks (padded with steps
# that leave the state as it is)
@pytest.mark.parametrize("chunk,t", [(8, 32), (16, 32), (8, 27), (128, 20)])
def test_chunked_scan_against_a_loop_over_positions(chunk, t):
    layer, p = _mixer(chunk)
    with jax.default_matmul_precision("highest"):
        _agree(lambda p, x: layer.apply(p, x)[0], _plain_mamba(layer), p,
               _rand((2, t, C), 1))


def test_mixer_holds_whole_groups_only():
    with pytest.raises(ValueError, match="whole groups"):
        _mixer(heads=3)
    with pytest.raises(ValueError, match="whole groups"):
        _mixer(heads=2, groups=0)
    with pytest.raises(ValueError, match="padding mask"):
        layer, p = _mixer()
        layer.apply(p, _rand((1, 8, C), 0), mask=jnp.ones((1, 8)))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        _mixer()[0].init_decode_state({}, 1, 8)


def _mamba_shares(x):
    """Head shares of a 4-head, 2-group mixer: the uncut layer's output and
    the two shares' outputs."""
    whole, p = _mixer()
    hp, gn, h = 32, 16, 4
    z, xs, b, c, dt = np.split(np.arange(2 * hp + 2 * gn + h),
                               [hp, 2 * hp, 2 * hp + gn, 2 * hp + 2 * gn])
    xbc = np.arange(hp + 2 * gn)
    want = _plain_mamba(whole)(p, x)
    parts = []
    for s in range(2):
        share, _ = _mixer(heads=2, groups=1)
        hs, gs = slice(16 * s, 16 * s + 16), slice(8 * s, 8 * s + 8)
        heads = slice(2 * s, 2 * s + 2)
        cols = np.concatenate([z[hs], xs[hs], b[gs], c[gs], dt[heads]])
        conv = np.concatenate([xbc[:hp][hs], xbc[hp:hp + gn][gs],
                               xbc[hp + gn:][gs]])
        ps = {"W_in": p["W_in"][:, cols], "conv_w": p["conv_w"][:, conv],
              "conv_b": p["conv_b"][conv], "A_log": p["A_log"][heads],
              "D": p["D"][heads], "dt_bias": p["dt_bias"][heads],
              "norm_g": p["norm_g"][hs], "W_out": p["W_out"][hs]}
        parts.append(share.apply(ps, x)[0])
    return want, parts


def _attention_shares(x):
    """KV-head shares of attention with 4 query heads on 2 KV heads."""
    whole = RotaryGQAttention(n_in=C, n_out=C, n_heads=4, n_kv_heads=2,
                              head_dim=8, rotary=None, weight_init="xavier")
    p = whole.init(jax.random.PRNGKey(1))
    want = jnp.stack([ref.attention(xi, p, heads=4, kv_heads=2, head_dim=8)
                      for xi in x])
    parts = []
    for s in range(2):
        share = RotaryGQAttention(n_in=C, n_out=C, n_heads=2, n_kv_heads=1,
                                  head_dim=8, rotary=None)
        q, kv = slice(16 * s, 16 * s + 16), slice(8 * s, 8 * s + 8)
        parts.append(share.apply(
            {"Wq": p["Wq"][:, q], "Wk": p["Wk"][:, kv], "Wv": p["Wv"][:, kv],
             "Wo": p["Wo"][q]}, x)[0])
    return want, parts


def _latent_layer(held=None, **kw):
    return ExpertLayer(n_in=C, n_experts=16, experts_per_token=3,
                       expert_width=16, shared_width=24, routed_scale=2.5,
                       experts_held=held, expert_form="relu2",
                       score="sigmoid", latent_width=16,
                       weight_init="xavier", **kw)


def _expert_shares(x):
    """Expert shares through the latent: the up-projection applied to each
    share's sum, the shared expert counted once."""
    whole = _latent_layer()
    p = whole.init(jax.random.PRNGKey(3))
    x2 = x.reshape(-1, C)
    want, _ = ref.experts(x2, p, top_k=3, held=(16, 0), routed_scale=2.5,
                          norm_topk=True)
    parts = [whole.shared(p, x2).astype(jnp.float32)]
    pairs = 0
    for s in range(4):
        share = _latent_layer((4, 4 * s))
        ps = dict(p, E1=p["E1"][4 * s:4 * s + 4], E2=p["E2"][4 * s:4 * s + 4])
        y, seen = share.routed(ps, x2)
        parts.append(y)
        pairs += int(seen["pairs"])
        assert int(seen["pairs_dropped"]) == 0
    assert pairs == x2.shape[0] * 3
    return want, parts


@pytest.mark.parametrize("shares", [_mamba_shares, _attention_shares,
                                    _expert_shares],
                         ids=["mamba_heads", "attention_kv_heads", "experts"])
def test_the_shares_of_a_mixer_add_up_to_the_uncut_layer(shares):
    """Guide section 4: what every chip of the deployment computes of one
    layer adds up to what the uncut reference gives for the whole layer."""
    with jax.default_matmul_precision("highest"):
        want, parts = shares(_rand((2, 24, C), 8))
    _close(sum(parts).reshape(want.shape), want)


# ------------------------------------------------------------ expert layer

@pytest.mark.parametrize("held", [None, (4, 4)])
def test_latent_relu2_sigmoid_layer_against_reference(held):
    layer = _latent_layer(held)
    p = layer.init(jax.random.PRNGKey(2))
    assert set(p) == {"Wr", "E1", "E2", "S1", "S2", "Wdown", "Wup"}
    assert p["E1"].shape == (layer.held[0], 16, 16)

    def plain(p, x):
        return jnp.stack([ref.experts(
            xi, p, top_k=3, held=layer.held, routed_scale=2.5,
            norm_topk=True)[0] for xi in x])

    _agree(lambda p, x: layer.apply(p, x)[0], plain, p, _rand((2, 24, C), 7))


def test_a_skewed_sigmoid_router_runs_an_overflow_round_and_drops_nothing():
    layer = _latent_layer((4, 0))
    p = layer.init(jax.random.PRNGKey(4))
    p["Wr"] = jnp.zeros_like(p["Wr"]).at[:, 1].set(1.0)
    x = jnp.abs(_rand((64, C), 9)) + 0.1       # expert 1 first, for all
    rows, rounds = layer.round_rows(64)

    def prog(p, x):
        return layer.routed(p, x)[0]

    def plain(p, x):
        r, _ = ref.experts(x, dict(p, S1=jnp.zeros((C, 1)),
                                   S2=jnp.zeros((1, C))), top_k=3,
                           held=(4, 0), routed_scale=2.5, norm_topk=True)
        return r

    _, seen = layer.routed(p, x)
    assert int(seen["load_max"]) == 64 and int(seen["pairs_dropped"]) == 0
    assert int(seen["pairs"]) > rows and rounds > 1
    _agree(prog, plain, p, x)


def test_the_selection_bias_chooses_and_does_not_weigh():
    x, wr = _rand((40, C), 11), _rand((C, 16), 12, 0.3)
    bias = jnp.zeros((16,)).at[5].set(10.0)
    idx0, p0 = route_top_k(x, wr, 3, False, 1.0, "sigmoid")
    idx, p = route_top_k(x, wr, 3, False, 1.0, "sigmoid", bias)
    s = jax.nn.sigmoid(x @ wr)
    assert bool((idx == 5).any(axis=-1).all()) \
        and not bool((idx0 == 5).any(axis=-1).all())
    # the weight of the chosen expert is its score, without the bias
    _close(p, jnp.take_along_axis(s, idx, axis=-1))
    # and no gradient reaches the bias
    g = jax.grad(lambda b: route_top_k(x, wr, 3, True, 2.0, "sigmoid",
                                       b)[1].sum())(bias)
    assert not np.asarray(g).any()
    # the reference's layer with the same bias
    layer = _latent_layer((4, 4))
    params = layer.init(jax.random.PRNGKey(6))
    state = dict(layer.init_state(), select_bias=bias)
    y, new = layer.apply(params, x[None], state, train=True)
    want, n = ref.experts(x, params, top_k=3, held=(4, 4), routed_scale=2.5,
                          norm_topk=True, bias=bias)
    _close(y[0], want)
    assert int(new["pairs"]) == int(n) >= 40
    assert np.array_equal(new["select_bias"], bias)


# ---------------------------------------------------------------- the model

def _batches(cfg, n, seed=0):
    return job.make_pool(cfg, {"pool_batches": n}, seed, 2, 32)


@pytest.fixture(scope="module")
def trained(cfg):
    """The rehearsal's model after three checked steps from seed 5, with
    what the job read off it."""
    net = job.build_net(cfg)
    job.set_weights(cfg, net, ref.init_params(cfg, 5))
    pool = _batches(cfg, 3)
    seen = job.check_steps(cfg, {"steps_per_call": 1, "check_steps": 3}, net,
                           pool, DataSet, 5)
    return net, pool, seen


def test_three_fit_steps_against_three_reference_steps(cfg, trained):
    """Loss of each step, Adam's first moment after step 1, the parameters'
    change after step 3, the pairs and the mean decay; float32 on both
    sides, ``remat='blocks'`` on the program's."""
    net, pool, seen = trained
    assert net.conf.global_conf.remat == "blocks"
    want = ref.run_steps(cfg, 5, pool)
    np.testing.assert_allclose([seen["losses"][i] for i in (1, 2, 3)],
                               want["losses"], rtol=1e-5)
    np.testing.assert_allclose(seen["trace_norms"], want["trace_norms"],
                               rtol=1e-4)
    np.testing.assert_allclose(seen["delta_norms"], want["delta_norms"],
                               rtol=5e-3)
    assert [[p for _, p in s] for s in seen["pairs"]] == want["pairs"]
    np.testing.assert_allclose([[v for _, v in s] for s in seen["decay"]],
                               want["decay"], rtol=1e-5)


def test_the_updater_leaves_the_selection_bias(cfg):
    net = job.build_net(cfg)
    name = "b1.mixer"
    bias = np.zeros((16,), np.float32)
    bias[0] = 3.0
    # the step donates its state: the layer's copy is not this one
    net.state[name] = dict(net.state[name], select_bias=jnp.asarray(bias))
    assert "select_bias" not in net.params[name]
    pool = _batches(cfg, 2, seed=4)
    before = job.expert_counts(net)
    net.fit(iter([DataSet(*b) for b in pool]))
    assert np.array_equal(net.state[name]["select_bias"], bias)
    # every token now picks expert 0, which is held: 2 steps x 64 tokens
    assert job.expert_counts(net)[name]["load_max"] == 64
    assert before[name]["pairs_total"] == 0


def test_a_hybrid_block_is_norm_mixer_add(cfg, trained):
    net = trained[0]
    segs = remat_segments(net.conf)
    blocks = [(names, outs) for names, outs in segs if outs is not None]
    assert len(blocks) == 11
    for i, (names, outs) in enumerate(blocks):
        assert names == [f"b{i}.norm", f"b{i}.mixer", f"b{i}.add"]
        assert outs == [f"b{i}.add"]
    kinds = [type(net.conf.nodes[f"b{i}.mixer"].layer).__name__
             for i in range(11)]
    assert kinds == ["RotaryGQAttention"] + ["ExpertLayer", "Mamba2Mixer"] * 5
    mixer = net.conf.nodes["b2.mixer"].layer
    assert (mixer.n_heads, mixer.n_groups) == (2, 1)
    attn = net.conf.nodes["b0.mixer"].layer
    assert (attn.n_heads, attn.n_kv_heads, attn.rotary) == (2, 1, None)
    experts = net.conf.nodes["b1.mixer"].layer
    assert (experts.n_experts, experts.held, experts.expert_form,
            experts.score, experts.latent_width) == (
                16, (4, 0), "relu2", "sigmoid", 16)
    with pytest.raises(ValueError, match="M .Mamba-2., E .experts. or"):
        SparseDecoder(dict(cfg, **cfg["rehearsal"]["model"],
                           hybrid_override_pattern="M-E")).conf()


def test_the_counts_in_the_file_build_the_share_the_reference_holds(cfg):
    """The file's counts of heads are what the chip holds (as Laguna's file
    gives its KV heads); of the experts it gives the router's width beside
    the count held: the reference's parameter shapes."""
    keys = dict(cfg, **cfg["rehearsal"]["model"])
    keys["n_routed_experts"] = keys["published"]["n_routed_experts"]
    net = SparseDecoder(keys, experts_held=(4, 0)).init()
    want = {(k, n): tuple(s) for k, n, s, _ in ref.param_shapes(cfg)}
    have = {(k, n): tuple(v.shape) for k, p in net.params.items()
            for n, v in (p or {}).items()}
    assert have == want


def test_the_new_scopes_are_in_the_compiled_step(trained):
    net = trained[0]
    rec = [e for e in get_programs().entries()
           if e["caller"] == net._prog_caller
           and e["key"].startswith("train_step")][-1]
    table = get_programs().get(net._prog_caller, rec["key"])["op_scopes"]
    inner, phases = {}, set()
    for op_name in table.values():
        phase, layer, kind = scopes.classify(op_name)
        phases.add(phase)
        parts = {m.group(1) if (m := scopes._WRAPPED.match(p)) else p
                 for p in (op_name or "").split("/")}
        inner.setdefault(kind, set()).update(parts)
    assert {"in_proj", "conv", "scan", "gate_norm", "out_proj"} \
        <= inner["Mamba2Mixer"]
    assert {"route", "latent_down", "dispatch", "experts", "combine",
            "latent_up", "shared"} <= inner["ExpertLayer"]
    assert "attend" in inner["RotaryGQAttention"]
    assert {"forward", "recompute", "backward", "loss", "updater"} <= phases
    # what the blocks keep beside their inputs, by name
    kept = rec["remat_kept_bytes"]
    assert "ssm_proj" in BLOCK_KEPT
    # 5 layers x 2 x 32 positions x (2 x 16 + 2 x 8 + 2) columns x 4 bytes
    assert kept["ssm_proj"] == 5 * 64 * 50 * 4
    assert kept["routing"] > 0 and kept["expert_gate_up"] > 0 \
        and kept["gate_up"] == 5 * 64 * 16 * 4 and kept["qkv"] > 0


def test_a_block_replay_does_not_project_the_mixers_input_again():
    """With ``ssm_proj`` kept the replay of a Mamba block holds no product
    against W_in: the gradient's jaxpr has the forward's and the two
    backward products of (T, C) x (C, 66) and no fourth."""
    from deeplearning4j_tpu.util.remat import block_checkpoint
    layer, p = _mixer()
    x = _rand((1, 32, C), 3)

    def loss(p, x):
        return block_checkpoint(lambda p, x: layer.apply(p, x)[0])(p, x).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(p, x))
    cols = p["W_in"].shape[1]
    # forward (1,32,32)x(32,cols); backward dW (32,cols) and dx
    fwd = [l for l in text.splitlines() if "dot_general" in l
           and f"f32[1,32,{cols}]" in l.split("=")[0]]
    assert len(fwd) == 1, fwd


def test_ssm_and_expert_counters_at_the_fit_boundary(cfg, trained):
    from deeplearning4j_tpu.monitor.metrics import get_registry
    net, pool, _ = trained
    net.fit(iter([DataSet(*b) for b in pool[:2]]))
    reg = get_registry()
    tokens = reg.get("dl4jtpu_ssm_tokens_total")
    mine = {k: c.value for k, c in tokens.children() if "b2.mixer" in k}
    state = dict(job.ssm_counts(net))
    assert state["b2.mixer"]["tokens_total"] == 5 * 64
    # the family is the process's: other models' mixers of this name too
    assert sum(mine.values()) >= 5 * 64
    decay = reg.get("dl4jtpu_ssm_decay_mean")
    got = [c.value for k, c in decay.children() if "b2.mixer" in k]
    assert got and 0.0 < got[-1] < 1.0
    assert got[-1] == pytest.approx(state["b2.mixer"]["decay_mean"])
    pairs = reg.get("dl4jtpu_moe_pairs_total")
    assert any("b1.mixer" in k and c.value > 0 for k, c in pairs.children())
    dropped = reg.get("dl4jtpu_moe_pairs_dropped_total")
    assert all(c.value == 0 for k, c in dropped.children()
               if "mixer" in k)


def test_model_through_the_serializer_and_back(cfg, trained, tmp_path):
    from deeplearning4j_tpu.util.model_serializer import (
        write_model, restore_computation_graph as restore_model)
    net, pool, _ = trained
    path = str(tmp_path / "hybrid.zip")
    write_model(net, path)
    back = restore_model(path)
    layer = back.conf.nodes["b2.mixer"].layer
    assert type(layer).__name__ == "Mamba2Mixer" \
        and (layer.n_heads, layer.n_groups) == (2, 1)
    assert back.conf.nodes["b1.mixer"].layer.latent_width == 16
    a = net.output(pool[0][0], bucketed=False)
    b = back.output(pool[0][0], bucketed=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_bfloat16_compute_stays_near_float32(cfg):
    pool = _batches(cfg, 1, seed=3)
    losses = {}
    for name, dtype in (("f32", None), ("bf16", "bfloat16")):
        c = dict(cfg, program=dict(cfg["program"], kwargs=dict(
            cfg["program"]["kwargs"], compute_dtype=dtype)))
        net = job.build_net(c)
        job.set_weights(c, net, ref.init_params(c, 6))
        net.fit(iter([DataSet(*pool[0])]))
        losses[name] = net.get_score()
    assert losses["bf16"] == pytest.approx(losses["f32"], rel=2e-2)


# ------------------------------- the decoders the benchmark had, unchanged

# sha256 of the step program's lowered text (no debug info) at the
# rehearsal size, as it stood before the expert layer gained its second
# form, the sigmoid router and the latent, and the decoder builder its
# hybrid family (taken at commit 72fcf92). A change that means to alter
# these steps takes new readings of the cells and puts its own hashes here.
LOWERED = {
    ("laguna-s-2.1.fit-s8k-b2", None):
        "59d8d5734fb116b00d82f7f245130a2534008d7c28d7b229be367e2673a2a8c6",
    ("laguna-s-2.1.fit-s8k-b2", "bfloat16"):
        "02408edf3a09420c2c01441280b3eb7acb2da52e51febc0e09463889316ff528",
    ("keye-vl-2.0-30b-a3b.fit-s16k-b1", None):
        "7d62f44e98bd4e3fb0ab5cf4e02dbdd62b5922ad97501b95a16f479864efa65e",
    ("keye-vl-2.0-30b-a3b.fit-s16k-b1", "bfloat16"):
        "e846da69950ebad0e1d47c5e0b1f6e2026ce6c5b73c95b6ac36fe71e1a34a87c",
}


@pytest.mark.parametrize("cell,dtype", sorted(LOWERED, key=str))
def test_the_accepted_decoders_lower_to_the_steps_they_lowered_to(cell, dtype):
    bench = spec.load_benchmark()
    _, conf, traffic, _ = spec.cell(bench, cell)
    c = arch.load_config(os.path.join(ROOT, conf["file"]), rehearse=True)
    c["program"]["kwargs"]["compute_dtype"] = dtype
    from deeplearning4j_tpu.exec import build_mesh, set_default_mesh
    # the text numbers its private functions by what the process traced
    # before: start from what a fresh process has
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
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[cell, dtype]
