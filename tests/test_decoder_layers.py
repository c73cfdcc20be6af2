"""The sparse decoder's layers, kernel and model against the plain
reference of the benchmark (perfbench/lib/reference_lm.py), at a small size
on the CPU with seeded weights."""

import importlib
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.layers import (RMSNorm, SwiGLU, RotaryGQAttention,
                                          ExpertLayer)
from deeplearning4j_tpu.nn.layers.decoder import (banded_attention,
                                                  selected_attention)
from deeplearning4j_tpu.ops.flash_attention import (gqa_flash_attention,
                                                    gqa_selected_attention)
from perfbench.lib import arch, reference_lm as ref
from perfbench.jobs import fit_lm

# ops/__init__ re-exports the flash_attention FUNCTION under the module's name
flash_attention = importlib.import_module(
    "deeplearning4j_tpu.ops.flash_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C, HD, KV = 32, 8, 2


@pytest.fixture(scope="module")
def cfg():
    """The benchmark's configuration at its rehearsal size: 1 + 4 layers,
    hidden 32, 16 experts of which 4 are held, window 8."""
    return arch.load_config(
        os.path.join(ROOT, "perfbench", "configs", "laguna-s-2.1.json"),
        rehearse=True)


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


def _rope(cfg, kind):
    return ref.rope_of(ref.dims(cfg), kind)


def test_rmsnorm_against_reference():
    layer = RMSNorm(n_in=C, eps=1e-6)
    p = {"gamma": 1.0 + 0.1 * _rand((C,), 0)}
    _agree(lambda p, x: layer.apply(p, x)[0],
           lambda p, x: ref.rms_norm(x, p["gamma"], 1e-6), p,
           _rand((2, 16, C), 1))


def test_swiglu_against_reference():
    layer = SwiGLU(n_in=C, n_out=C, width=64)
    p = layer.init(jax.random.PRNGKey(0))
    _agree(lambda p, x: layer.apply(p, x)[0],
           lambda p, x: ref.swiglu(x, p["Wg"], p["Wu"], p["Wd"]), p,
           _rand((2, 16, C), 2))


@pytest.mark.parametrize("kind,heads,kernel", [
    ("full_attention", 12, False), ("sliding_attention", 18, False),
    ("full_attention", 12, True), ("sliding_attention", 18, True)])
def test_attention_against_reference(cfg, kind, heads, kernel):
    """Full layers: 12 heads on 2 kv heads, YaRN over half of each head;
    sliding layers: 18 heads, window 8, plain rotary. With ``kernel`` the
    layer runs the Pallas kernel interpreted."""
    window = 8 if kind == "sliding_attention" else None
    rope = _rope(cfg, kind)
    layer = RotaryGQAttention(n_in=C, n_out=C, n_heads=heads, n_kv_heads=KV,
                              head_dim=HD, window=window, rotary=rope,
                              head_gate=True)
    p = layer.init(jax.random.PRNGKey(1))

    def plain(p, x):
        return jnp.stack([ref.attention(
            xi, p, heads=heads, kv_heads=KV, head_dim=HD, window=window,
            rope=rope, head_gate=True, chunk=8) for xi in x])

    prev = ops.set_helpers_enabled(True, interpret=True) if kernel else None
    try:
        _agree(lambda p, x: layer.apply(p, x)[0], plain, p,
               _rand((2, 32, C), 3))
    finally:
        if kernel:
            ops.set_helpers_enabled(prev[0], interpret=prev[1])


def _small_tiles(monkeypatch, group, narrow):
    """Blocks of 16 at 64 positions, two heads a product so that the step
    walks the group in a loop; ``narrow``: the tile rule left to itself with
    room for ``group * 16`` rows a step, so a query block of 16 stands under
    a key block of 64. Returns the ``block`` argument."""
    monkeypatch.setattr(flash_attention, "_GQA_PRODUCT_ROWS", 32)
    if not narrow:
        return 16
    row = 128 * (6 * 4 + 4) + 2 * 2 * 128 * 4       # float32, head dim 8
    monkeypatch.setattr(flash_attention, "_GQA_STEP_BYTES", group * 16 * row)
    plan = flash_attention.gqa_plan(64, group, 1, 8, None, itemsize=4)
    assert (plan.bq, plan.bk, plan.rows) == (16, 64, group * 16)
    return None


@pytest.mark.parametrize("narrow", [False, True])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (12, 2), (16, 2), (9, 1),
                                    (16, 1)])
def test_gqa_kernel_against_masked_softmax(monkeypatch, hq, hkv, window,
                                           narrow):
    """The kernel interpreted, group sizes 1, 6, 8, 9 and 16, the triangle
    and a band that crosses block boundaries (blocks of 16, window 24), on
    square tiles and with a query block smaller than the key block."""
    block = _small_tiles(monkeypatch, hq // hkv, narrow)
    q = _rand((2, hq, 64, 8), 4)
    k, v = _rand((2, hkv, 64, 8), 5), _rand((2, hkv, 64, 8), 6)
    kern = lambda q, k, v: gqa_flash_attention(q, k, v, window, block, True)
    plain = lambda q, k, v: banded_attention(q, k, v, window)
    _close(kern(q, k, v), plain(q, k, v), 1e-5)
    gk = jax.grad(lambda *a: (kern(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (plain(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gp):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("narrow", [False, True])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (12, 2), (8, 1)])
def test_gqa_selected_kernel_against_masked_softmax(monkeypatch, hq, hkv,
                                                    narrow):
    """The same kernels under an int8 mask, interpreted: value, log-sum-exp
    and the three gradients against the plain path. Row 50 selects one key,
    in its second tile of 16 (its first tile selects none); the whole tile
    of rows 32-47 and keys 16-31 selects none."""
    block = _small_tiles(monkeypatch, hq // hkv, narrow)
    t = 64
    rs = np.random.RandomState(7)
    mask = np.tril(rs.rand(2, t, t) < 0.4)
    mask[:, np.arange(t), 0] = True
    mask[:, 32:48, 16:32] = False
    mask[:, 50] = False
    mask[:, 50, 20] = True
    mask = jnp.asarray(mask, jnp.int8)
    q = _rand((2, hq, t, 8), 4)
    k, v = _rand((2, hkv, t, 8), 5), _rand((2, hkv, t, 8), 6)
    kern = lambda q, k, v: gqa_selected_attention(q, k, v, mask, block, True)
    plain = lambda q, k, v: selected_attention(q, k, v, mask)[0]
    o, lse = kern(q, k, v)
    _close(o, plain(q, k, v), 1e-5)
    s = jnp.einsum("bhqd,bhsd->bhqs", q, jnp.repeat(k, hq // hkv, axis=1)) \
        / np.sqrt(8)
    _close(lse[..., 0], jax.nn.logsumexp(
        jnp.where((mask != 0)[:, None], s, -jnp.inf), axis=-1), 1e-5)
    gk = jax.grad(lambda *a: (kern(*a)[0] ** 2).sum(), (0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: (plain(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gp):
        _close(a, b, 1e-4)


def _head_mean_tiles(monkeypatch, hq, hkv, narrow):
    """The head-mean pass at 64 positions, float32, head dim 8, two heads a
    product: square tiles of 16 holding every kv head a step, or
    (``narrow``) a step budget that leaves one kv head's group a step on
    (16, 64) tiles. Returns the ``block`` argument."""
    monkeypatch.setattr(flash_attention, "_GQA_PRODUCT_ROWS", 32)
    group = hq // hkv
    if narrow:
        monkeypatch.setattr(flash_attention, "_GQA_STEP_BYTES",
                            2 * (group * 16 * (128 * 4 + 128 * 4)
                                 + 64 * 128 * 4))
    block = None if narrow else 16
    plan = flash_attention.head_mean_plan(64, hq, hkv, 8, block, 4)
    assert plan[:4] == ((16, 64, min(group, 2), 1) if narrow
                        else (16, 16, min(group, 2), hkv))
    return block


@pytest.mark.parametrize("narrow", [False, True])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2), (8, 1), (16, 2)])
def test_gqa_head_mean_against_the_plain_head_mean(monkeypatch, hq, hkv,
                                                   narrow):
    """``gqa_head_mean_probs`` interpreted against the plain path's
    head-mean weights over the whole (B, T, T) array, zeros above the
    diagonal included: groups of 1, 2 and 8 query heads on a kv head,
    every kv head a step on square tiles and one a step on tiles wider
    than tall. Row 50 selects one key; the tile of rows 32-47 and keys
    16-31 selects none; key 10 of row 40 is not selected and its score
    sits over 100 above the row's log-sum-exp in every head, where
    ``exp`` overflows: it reads exactly 0."""
    block = _head_mean_tiles(monkeypatch, hq, hkv, narrow)
    t = 64
    rs = np.random.RandomState(8)
    mask = np.tril(rs.rand(2, t, t) < 0.4)
    mask[:, np.arange(t), 0] = True
    mask[:, 32:48, 16:32] = False
    mask[:, 50] = False
    mask[:, 50, 20] = True
    mask[:, 40, 10] = False
    mask = jnp.asarray(mask, jnp.int8)
    q = _rand((2, hq, t, 8), 4).at[:, :, 40].set(1.0)
    k = _rand((2, hkv, t, 8), 5).at[:, :, 10].set(40.0)
    _, lse = gqa_selected_attention(q, k, k, mask, block, True)
    score = jnp.einsum("bhd,bhd->bh", q[:, :, 40],
                       jnp.repeat(k, hq // hkv, axis=1)[:, :, 10])
    assert float((score / np.sqrt(8) - lse[:, :, 40, 0]).min()) > 100
    got = flash_attention.gqa_head_mean_probs(q, k, lse, mask, block, True)
    want = selected_attention(q, k, k, mask)[1]
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[:, np.triu_indices(t, 1)[0],
                               np.triu_indices(t, 1)[1]].any()
    assert float(got[:, 40, 10].max()) == 0.0
    assert float(got[:, 32:48, 16:32].max()) == 0.0
    _close(got, want, 1e-6)


# the attention layers of the benchmark's three decoder cells: (positions,
# query heads, kv heads, window) -> (query block, key block, heads a
# product, rows a step, grid steps a pass, steps a banded grid would add)
@pytest.mark.parametrize("shape,plan", [
    ((16384, 32, 4, None), (256, 512, 2, 2048, 4224, 3968)),    # Keye
    ((8192, 12, 2, None), (256, 512, 2, 1536, 544, 480)),       # Laguna, full
    ((8192, 18, 2, 512), (128, 512, 3, 1152, 248, 136)),        # ... sliding
    ((8192, 16, 1, None), (128, 512, 4, 2048, 544, 480)),       # Nemotron
])
def test_gqa_plan_of_the_cells(shape, plan):
    t, hq, hkv, window = shape
    got = flash_attention.gqa_plan(t, hq, hkv, 128, window)
    assert tuple(got) == plan
    bq, bk = got.bq, got.bk
    # every tile with a visible pair is a step, once, and no other tile is
    i = np.arange(t // bq)[:, None] * bq
    j = np.arange(t // bk)[None, :] * bk
    seen = i + bq - 1 >= j
    if window is not None:
        seen &= i - (j + bk - 1) < window
    for by_key in (False, True):
        tiles = flash_attention._tile_pairs(t, bq, bk, window, by_key)
        qb, kb = tiles[1 if by_key else 0], tiles[0 if by_key else 1]
        assert len(set(zip(qb, kb))) == tiles.shape[1] == seen.sum()
        assert seen[qb, kb].all()
        assert (np.diff(tiles[0]) >= 0).all()
        assert tiles[2].sum() == tiles[3].sum() == len(set(tiles[0]))


# the head-mean pass at the shapes of the decoder cells' attention (only
# Keye's layers have an indexer and run it): (positions, query heads, kv
# heads) -> (query block, key block, heads a product, kv heads a step, grid
# steps); the parent's grid was (T/512)^2 x Hq steps, 32,768 at Keye's
@pytest.mark.parametrize("shape,plan", [
    ((16384, 32, 4), (128, 512, 4, 4, 4096)),       # Keye
    ((8192, 12, 2), (256, 512, 2, 2, 512)),         # Laguna's full layers
    ((8192, 16, 1), (256, 512, 2, 1, 512)),         # Nemotron
])
def test_head_mean_plan_of_the_cells(shape, plan):
    t, hq, hkv = shape
    got = flash_attention.head_mean_plan(t, hq, hkv, 128)
    assert tuple(got) == plan
    bq, bk, kv_blocks = got.bq, got.bk, hkv // got.kv_heads
    steps = flash_attention._head_mean_steps(t, bq, bk, kv_blocks)
    assert steps.shape[1] == got.steps
    qb, kb, h, ob, first, last, zero = steps
    i = np.arange(t // bq)[:, None] * bq
    j = np.arange(t // bk)[None, :] * bk
    seen = i + bq - 1 >= j
    # the steps that compute are the tiles with a visible pair, once for
    # each block of kv heads, and none other
    work = zero == 0
    assert seen[qb[work], kb[work]].all() and (kb[work] == ob[work]).all()
    assert len(set(zip(qb[work], kb[work], h[work]))) == work.sum() \
        == seen.sum() * kv_blocks
    assert first.sum() == last.sum() == seen.sum()
    # every other step writes one tile above them, which it names, once,
    # and names the blocks of the step before it, so nothing is fetched
    assert not seen[qb[~work], ob[~work]].any()
    assert len(set(zip(qb[~work], ob[~work]))) == (~work).sum() \
        == (~seen).sum()
    idle = np.flatnonzero(~work)
    assert (steps[:3, idle] == steps[:3, idle - 1]).all()
    # query-major, so q and the log-sum-exp are fetched once a query block
    # where a step holds every kv head
    assert (np.diff(qb) >= 0).all()


def _expert_layer(held=None, e=16):
    return ExpertLayer(n_in=C, n_experts=e, experts_per_token=3,
                       expert_width=16, shared_width=16, routed_scale=2.5,
                       experts_held=held)


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_expert_layer_against_reference(held):
    layer = _expert_layer(held)
    p = layer.init(jax.random.PRNGKey(2))

    def plain(p, x):
        return jnp.stack([ref.experts(
            xi, p, top_k=3, held=layer.held, routed_scale=2.5,
            norm_topk=True)[0] for xi in x])

    _agree(lambda p, x: layer.apply(p, x)[0], plain, p, _rand((2, 24, C), 7))


def test_shares_add_up_to_the_uncut_layer():
    """Guide section 4: 16 experts as 4 shares of 4. The routed parts of all
    shares, plus the shared expert once, equal the uncut reference's
    layer."""
    whole = _expert_layer()
    p = whole.init(jax.random.PRNGKey(3))
    x = _rand((48, C), 8)
    total = whole.shared(p, x).astype(jnp.float32)
    pairs = 0
    for s in range(4):
        share = _expert_layer((4, 4 * s))
        ps = dict(p, **{k: p[k][4 * s:4 * s + 4] for k in ("Eg", "Eu", "Ed")})
        y, seen = share.routed(ps, x)
        total = total + y
        pairs += int(seen["pairs"])
        assert int(seen["pairs_dropped"]) == 0
    want, _ = ref.experts(x, p, top_k=3, held=(16, 0), routed_scale=2.5,
                          norm_topk=True)
    _close(total, want)
    assert pairs == 48 * 3


def test_no_pair_dropped_when_routing_is_skewed_onto_one_expert():
    layer = _expert_layer((4, 0))
    p = layer.init(jax.random.PRNGKey(4))
    p["Wr"] = jnp.zeros_like(p["Wr"]).at[:, 1].set(1.0)
    x = jnp.abs(_rand((64, C), 9)) + 0.1       # expert 1 first, for all
    rows, rounds = layer.round_rows(64)
    y, seen = layer.routed(p, x)
    assert int(seen["load_max"]) == 64 and int(seen["pairs_dropped"]) == 0
    assert int(seen["pairs"]) > rows and rounds > 1
    want, n = ref.experts(x, {k: v for k, v in p.items() if k[0] != "S"},
                          top_k=3, held=(4, 0), routed_scale=2.5,
                          norm_topk=True)
    assert int(n) == int(seen["pairs"])
    _close(y, want)


def test_a_round_left_out_reads_as_pairs_dropped(monkeypatch):
    """``pairs_dropped`` counts what the rounds computed: with the loop of
    the later rounds cut one short, the pairs of that round are missed."""
    from deeplearning4j_tpu.nn.layers import decoder
    layer = _expert_layer((4, 0))
    p = layer.init(jax.random.PRNGKey(4))
    p["Wr"] = jnp.zeros_like(p["Wr"]).at[:, 1].set(1.0)
    x = jnp.abs(_rand((64, C), 9)) + 0.1
    rows, _ = layer.round_rows(64)
    _, sound = layer.routed(p, x)
    real = jax.lax.fori_loop
    monkeypatch.setattr(
        decoder.jax.lax, "fori_loop",
        lambda lo, hi, body, init: real(lo, hi - 1, body, init))
    _, cut = layer.routed(p, x)
    last = int(sound["pairs"]) - (int(sound["pairs"]) - 1) // rows * rows
    assert int(sound["pairs_dropped"]) == 0
    assert int(cut["pairs_dropped"]) == last > 0


# classes of tokens by the experts they pick of 16, top 3, experts 0-3 held:
# three held experts, two, one, none. (tokens of each class) -> pairs held
_PICKS = [(0, 1, 2), (0, 1, 8), (0, 8, 9), (8, 9, 10)]
ROUTINGS = {"even": (4, 10, 16, 34),          # 48 pairs: round 0 alone
            "one_over": (9, 23, 0, 32),       # 73: one pair in round 1
            "worst": (64, 0, 0, 0)}           # 192: every round, to its end


def _routed_case(tokens_by_class):
    """64 tokens whose first four features say which experts they pick,
    and a router that reads them; noise elsewhere so that no score ties."""
    layer = _expert_layer((4, 0))
    p = {k: v for k, v in layer.init(jax.random.PRNGKey(5)).items()
         if k[0] != "S"}
    cls = np.repeat(np.arange(4), tokens_by_class)
    np.random.RandomState(3).shuffle(cls)
    x = 0.1 * np.asarray(_rand((64, C), 11))
    x[:, :4] = 10.0 * np.eye(4, dtype=np.float32)[cls]
    wr = 0.05 * np.asarray(p["Wr"])
    for c, picks in enumerate(_PICKS):
        wr[c, list(picks)] += 1.0
    p["Wr"] = jnp.asarray(wr)
    pairs = int(np.dot(tokens_by_class, (3, 2, 1, 0)))
    return layer, p, jnp.asarray(x), pairs


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_routed_value_and_gradients_under_one_round_two_and_all(routing):
    """The routed part and its gradients for x, Wr, Eg, Eu, Ed against the
    dense oracle in float32: the pair weights reach Wr through each round's
    own gather, and the later rounds' gradients join round 0's only where a
    later round runs."""
    layer, p, x, pairs = _routed_case(ROUTINGS[routing])
    rows, rounds = layer.round_rows(64)
    assert (rows, rounds) == (72, 3) and rounds * rows > 64 * 3
    y, seen = layer.routed(p, x)
    assert int(seen["pairs"]) == pairs and int(seen["pairs_dropped"]) == 0
    assert -(-pairs // rows) == {"even": 1, "one_over": 2, "worst": 3}[routing]
    cot = _rand((64, C), 12)

    def plain(p, x):
        return ref.experts(x, p, top_k=3, held=(4, 0), routed_scale=2.5,
                           norm_topk=True)[0]

    _close(y, plain(p, x))
    got = jax.jit(jax.grad(
        lambda p, x: (layer.routed(p, x)[0] * cot).sum(), (0, 1)))(p, x)
    want = jax.grad(lambda p, x: (plain(p, x) * cot).sum(), (0, 1))(p, x)
    assert sorted(got[0]) == ["Ed", "Eg", "Eu", "Wr"]
    assert float(jnp.abs(want[0]["Wr"]).max()) > 1e-3
    jax.tree_util.tree_map(lambda a, b: _close(a, b, 1e-3), got, want)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` with the jaxpr it sits in, inner jaxprs
    (a branch's, a loop's, a derivative rule's) included."""
    for e in jaxpr.eqns:
        yield jaxpr, e
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _eqns(j)


def test_the_routed_part_is_sized_by_the_round_and_zeroes_nothing():
    """In the jaxpr of the routed part's value and gradient, at a shape
    with three rounds: no array has a dimension of rounds * rows, and no
    loop is handed float32 zeros in the shape of x, Eg, Eu or Ed (the
    later rounds start from round 0's result and from its gradients)."""
    layer, p, x, _ = _routed_case(ROUTINGS["even"])
    rows, rounds = layer.round_rows(64)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, x: layer.routed(p, x)[0].sum(), (0, 1)))(p, x).jaxpr
    big = {tuple(a.shape) for a in (x, p["Eg"], p["Eu"], p["Ed"])}
    loops = 0
    for inside, e in _eqns(jaxpr):
        for v in e.outvars:
            assert rounds * rows not in getattr(v.aval, "shape", ()), e
        if e.primitive.name != "while":
            continue
        loops += 1
        made_by = {id(v): q for q in inside.eqns for v in q.outvars}
        for v in e.invars:
            q = made_by.get(id(v))
            assert not (q is not None
                        and q.primitive.name == "broadcast_in_dim"
                        and v.aval.dtype == jnp.float32
                        and tuple(v.aval.shape) in big), q
    assert loops == 2               # the later rounds, forward and backward


def test_the_chip_screen_of_the_overflow_rounds_runs_small():
    from deeplearning4j_tpu.ops import validate
    r = validate.validate_expert_rounds_case(*validate.EXPERT_QUICK[0],
                                             time_it=False)
    assert r["rounds_run"] > 1 and r["pairs_dropped"] == 0


# ------------------------------------------------------------- the model

def _net(cfg, **kw):
    cfg = dict(cfg, program=dict(cfg["program"], kwargs=dict(
        cfg["program"]["kwargs"], **kw)))
    return fit_lm.build_net(cfg)


def _batches(cfg, n, seed=0):
    traffic = {"pool_batches": n}
    return fit_lm.make_pool(cfg, traffic, seed, 2, 32)


def test_three_fit_steps_against_three_reference_steps(cfg):
    """Loss of each step, Adam's first moment after step 1, the parameters'
    change after step 3; float32 on both sides."""
    net = _net(cfg)
    fit_lm.set_weights(cfg, net, ref.init_params(cfg, 5))
    pool = _batches(cfg, 3)
    seen = fit_lm.check_steps(
        cfg, {"steps_per_call": 1, "check_steps": 3}, net, pool, DataSet, 5)
    want = ref.run_steps(cfg, 5, pool)
    np.testing.assert_allclose([seen["losses"][i] for i in (1, 2, 3)],
                               want["losses"], rtol=1e-5)
    np.testing.assert_allclose(seen["trace_norms"], want["trace_norms"],
                               rtol=1e-4)
    np.testing.assert_allclose(seen["delta_norms"], want["delta_norms"],
                               rtol=5e-3)
    assert [[p for _, p in s] for s in seen["pairs"]] == want["pairs"]


def test_block_replay_and_bfloat16_leave_the_step_what_it_is(cfg):
    """remat='blocks' changes no number; bfloat16 compute stays near."""
    pool = _batches(cfg, 1, seed=3)
    losses = {}
    for name, kw in (("plain", {"remat": None}), ("blocks", {}),
                     ("bf16", {"compute_dtype": "bfloat16"})):
        net = _net(cfg, **kw)
        fit_lm.set_weights(cfg, net, ref.init_params(cfg, 6))
        net.fit(iter([DataSet(*pool[0])]))
        losses[name] = (net.get_score(), np.asarray(
            net.params["b1.mlp"]["Eg"]))
    assert losses["plain"][0] == pytest.approx(losses["blocks"][0], rel=1e-6)
    np.testing.assert_allclose(losses["plain"][1], losses["blocks"][1],
                               rtol=1e-5, atol=1e-7)
    assert losses["bf16"][0] == pytest.approx(losses["plain"][0], rel=2e-2)


def test_model_through_the_serializer_and_back(cfg, tmp_path):
    from deeplearning4j_tpu.util.model_serializer import (
        write_model, restore_computation_graph as restore_model)
    net = _net(cfg)
    pool = _batches(cfg, 1)
    net.fit(iter([DataSet(*pool[0])]))
    path = str(tmp_path / "decoder.zip")
    write_model(net, path)
    back = restore_model(path)
    layer = back.conf.nodes["b1.mlp"].layer
    assert type(layer).__name__ == "ExpertLayer" and layer.held == (4, 0)
    assert back.conf.nodes["b1.attn"].layer.rotary["dims"] == HD
    a = net.output(pool[0][0], bucketed=False)
    b = back.output(pool[0][0], bucketed=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    back.fit(iter([DataSet(*pool[0])]))           # and it trains on
    assert np.isfinite(back.get_score())


def test_expert_counters_at_the_fit_boundary(cfg):
    from deeplearning4j_tpu.monitor.metrics import get_registry
    net = _net(cfg)
    pool = _batches(cfg, 2)
    net.fit(iter([DataSet(*b) for b in pool]))
    reg = get_registry()
    fam = reg.get("dl4jtpu_moe_pairs_total")
    mine = {k: c.value for k, c in fam.children() if "b1.mlp" in k}
    state = fit_lm.expert_counts(net)
    assert sum(mine.values()) >= state["b1.mlp"]["pairs_total"] > 0
    dropped = reg.get("dl4jtpu_moe_pairs_dropped_total")
    assert all(c.value == 0 for _, c in dropped.children())
    assert reg.get("dl4jtpu_moe_expert_load_max") is not None
    assert reg.get("dl4jtpu_moe_expert_load_mean") is not None


@pytest.mark.parametrize("skewed", [False, True])
def test_the_rounds_gauge_says_when_a_step_paid_for_later_rounds(cfg, skewed):
    """``dl4jtpu_moe_rounds_last`` after a streamed ``fit()``: 1 under the
    even routing of random weights; above 1 with the first expert layer's
    router turned so that half of the tokens pick three held experts."""
    from deeplearning4j_tpu.monitor.metrics import get_registry
    net = _net(cfg)
    if skewed:
        wr = np.zeros(net.params["b1.mlp"]["Wr"].shape, np.float32)
        wr[:, :3] = 5.0
        net.params["b1.mlp"]["Wr"] = jnp.asarray(wr)
    pool = _batches(cfg, 2)
    net.fit(iter([DataSet(*b) for b in pool]))
    layer = net.conf.nodes["b1.mlp"].layer
    rows, rounds = layer.round_rows(2 * 32)
    pairs = int(net.state["b1.mlp"]["pairs"])
    fam = get_registry().get("dl4jtpu_moe_rounds_last")
    mine = {k: c.value for k, c in fam.children() if "b1.mlp" in k}
    assert len(mine) == 1 and rounds > 1
    assert list(mine.values()) == [max(1, -(-pairs // rows))]
    assert (pairs > rows) == skewed
    assert int(net.state["b1.mlp"]["pairs_dropped_total"]) == 0
