"""Paged KV-cache subsystem (serving/kv/ + DecodeEngine(kv="paged")).

The load-bearing claims pinned here:
- the block pool is a correct refcounted allocator: all-or-nothing
  allocation, LRU eviction of cached blocks, scratch block pinned;
- a paged engine's greedy output is BITWISE-equal to the dense engine's
  for a transformer at f32 AND bf16 compute, sequentially and under
  concurrent arrival with chunked prefill — and still ONE compiled step
  program (trace_count == 1), at most two kv side programs;
- prefix-cache reuse (including the copy-on-write partial-block path)
  never changes output: requests sharing a prefix decode exactly as if
  they were independent;
- slot release is complete: after claim → free → re-claim cycles the
  pool's occupancy returns to baseline (the eos leak regression);
- /healthz reports ``kv_pool_exhausted`` with the pool occupancy while
  the queue head cannot claim blocks, and recovers;
- the paged flash kernel (interpret mode) matches the dense gather path.
"""

import time

import numpy as np
import pytest
import jax.numpy as jnp

from deeplearning4j_tpu.serving import (DecodeEngine, InferenceClient,
                                        InferenceServer)
from deeplearning4j_tpu.serving.kv import (BlockPool, PoolExhaustedError,
                                           PrefixCache, blocks_for_span,
                                           plan_chunks)
from deeplearning4j_tpu.zoo.simple import TinyTransformer

V = 13


def _transformer(max_len=64, compute_dtype=None, seed=7):
    kw = {"compute_dtype": compute_dtype} if compute_dtype else {}
    return TinyTransformer(vocab_size=V, n_layers=2, d_model=32, n_heads=4,
                          max_len=max_len, seed=seed, **kw).init()


def _lstm_net():
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
            .weight_init("xavier").list()
            .layer(LSTM(n_out=16, activation="tanh"))
            .layer(RnnOutputLayer(n_out=V, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(V))
            .build())
    return MultiLayerNetwork(conf).init()


def _prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, V, size=n))) for n in sizes]


# ------------------------------------------------------------------ pool

def test_pool_alloc_free_refcount():
    p = BlockPool(8, 16)
    assert p.usable == 7 and p.free_count == 7 and p.in_use == 0
    a = p.alloc(3)
    assert len(a) == 3 and 0 not in a            # scratch never handed out
    assert p.in_use == 3 and p.free_count == 4
    p.incref(a[0])
    p.decref(a[0])
    assert p.refcount(a[0]) == 1                 # still held once
    for b in a:
        p.decref(b)
    assert p.in_use == 0 and p.free_count == 7
    with pytest.raises(ValueError):
        p.decref(a[0])                           # double free
    with pytest.raises(ValueError):
        p.incref(0)                              # scratch is pinned


def test_pool_alloc_all_or_nothing():
    p = BlockPool(4, 8)
    a = p.alloc(2)
    with pytest.raises(PoolExhaustedError):
        p.alloc(2)                               # only 1 left
    assert p.in_use == 2 and p.free_count == 1   # no partial side effects
    p.decref(a[0])
    assert len(p.alloc(2)) == 2


def test_pool_cached_blocks_evict_lru():
    p = BlockPool(4, 8)
    dropped = []
    p.on_evict = dropped.append
    a = p.alloc(3)
    for b in a:
        p.mark_cached(b)
        p.decref(b)                              # ref 0 → evictable, LRU
    assert p.free_count == 3 and p.cached_count == 3 and p.in_use == 0
    # a hit revives the middle block; eviction then takes LRU order
    p.incref(a[1])
    got = p.alloc(2)                             # evicts a[0] then a[2]
    assert dropped == [a[0], a[2]]
    assert sorted(got) == sorted([a[0], a[2]])
    assert p.is_cached(a[1]) and not p.is_cached(a[0])
    p.decref(a[1])
    assert p.flush_cached() == 1                 # weight swap: drop ref-0


def test_plan_chunks_and_blocks_for_span():
    assert plan_chunks(0, 10, 4) == [(0, 4), (4, 4), (8, 2)]
    assert plan_chunks(3, 3, 4) == []
    assert blocks_for_span(1, 16) == 1
    assert blocks_for_span(16, 16) == 1
    assert blocks_for_span(17, 16) == 2


# ---------------------------------------------------------------- prefix

def test_prefix_chain_match_and_insert():
    p = BlockPool(16, 4)
    pc = PrefixCache(p)
    prompt = list(range(10))                     # blocks: [0..3] [4..7] |8,9
    blocks = p.alloc(3)
    assert pc.insert(prompt, blocks) == 2        # two FULL prompt blocks
    for b in blocks:
        p.decref(b)
    assert p.in_use == 0 and p.cached_count == 2
    # same prompt again: both full blocks claimed, skip capped at plen-1
    shared, cow, skip = pc.match(prompt)
    assert shared == blocks[:2] and skip == 8 and cow is None
    assert p.refcount(blocks[0]) == 1            # claimed read-only
    for b in shared:
        p.decref(b)
    # diverging inside block 1 → one full-block hit + CoW partial tail
    other = prompt[:6] + [99, 98, 97, 96]
    shared, cow, skip = pc.match(other)
    assert shared == blocks[:1]
    assert cow == (blocks[1], 2) and skip == 4 + 2
    p.decref(shared[0])
    p.decref(cow[0])
    # unrelated prompt: no match
    assert pc.match([7, 7, 7, 7, 7, 7]) == ([], None, 0)


def test_prefix_eviction_drops_index_entries():
    p = BlockPool(4, 4)
    pc = PrefixCache(p)
    prompt = list(range(8))
    blocks = p.alloc(2)
    pc.insert(prompt, blocks)
    for b in blocks:
        p.decref(b)
    assert len(pc) == 2
    p.alloc(3)                                   # forces both evictions
    assert len(pc) == 0
    assert pc.match(prompt) == ([], None, 0)     # index never dangles


# ------------------------------------------------- engine bitwise parity

@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_paged_engine_bitwise_equals_dense(compute_dtype):
    net = _transformer(max_len=64, compute_dtype=compute_dtype)
    prompts = _prompts((1, 5, 17, 33))
    dense = DecodeEngine(net, slots=2, max_len=64).start()
    try:
        ref = [dense.generate(p, max_new_tokens=10) for p in prompts]
    finally:
        dense.stop()
    pag = DecodeEngine(net, slots=2, max_len=64, kv="paged",
                       kv_block_size=16, prefix_cache=False).start()
    try:
        got = [pag.generate(p, max_new_tokens=10) for p in prompts]
        assert pag.trace_count == 1              # one step program
    finally:
        pag.stop()
    for a, b in zip(ref, got):
        assert a["tokens"] == b["tokens"]


def test_paged_chunked_concurrent_bitwise_equals_dense():
    net = _transformer(max_len=64)
    prompts = _prompts((1, 3, 9, 17, 33, 21), seed=3)
    dense = DecodeEngine(net, slots=4, max_len=64).start()
    try:
        ref = [dense.generate(p, max_new_tokens=12) for p in prompts]
    finally:
        dense.stop()
    pag = DecodeEngine(net, slots=4, max_len=64, kv="paged",
                       kv_block_size=16, prefix_cache=True,
                       chunk_tokens=8).start()
    try:
        futs = [pag.submit(p, max_new_tokens=12) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        st = pag.stats()
    finally:
        pag.stop()
    for a, b in zip(ref, got):
        assert a["tokens"] == b["tokens"]
    # arrival schedule never mints programs: 1 step + at most 2 kv side
    assert st["compiled_programs"] == 1
    assert st["kv"]["kv_programs"] <= 2
    assert st["kv"]["prefill_chunks"] > 0
    assert st["kv"]["blocks_in_use"] == 0        # everything released


def test_shared_prefix_reuse_and_cow_divergence():
    # two requests with a common 64-token prefix and different
    # continuations (one diverging INSIDE a block → copy-on-write):
    # outputs must equal independent decodes
    net = _transformer(max_len=96)
    rng = np.random.default_rng(11)
    common = list(map(int, rng.integers(0, V, size=64)))
    cont_a = list(map(int, rng.integers(0, V, size=16)))
    cont_b = cont_a[:4] + list(map(int, rng.integers(0, V, size=12)))
    pa, pb = common + cont_a, common + cont_b
    assert pa != pb and pa[:68] == pb[:68]

    def run(prefix_cache):
        eng = DecodeEngine(net, slots=2, max_len=96, kv="paged",
                           kv_block_size=16,
                           prefix_cache=prefix_cache).start()
        try:
            ra = eng.generate(pa, max_new_tokens=8)
            rb = eng.generate(pb, max_new_tokens=8)
            return ra, rb, eng.stats()
        finally:
            eng.stop()

    (ia, ib, _) = run(False)
    (ca, cb, st) = run(True)
    assert ca["tokens"] == ia["tokens"]
    assert cb["tokens"] == ib["tokens"]
    kv = st["kv"]
    # request B claimed A's four full prefix blocks + a CoW tail block
    assert kv["prefix_hits"] == 1
    assert kv["prefix_tokens_saved"] >= 64
    assert kv["cow_copies"] == 1
    assert kv["kv_programs"] <= 2
    assert kv["blocks_in_use"] == 0


# ------------------------------------------------------- release / leaks

def test_slot_reclaim_releases_kv_blocks():
    # the eos leak regression: claim → free → re-claim must return pool
    # occupancy to baseline — with the prefix cache ON, released blocks
    # park ref-0 in the evictable LRU (still allocatable), never leak refs
    net = _transformer(max_len=64)
    for prefix_cache in (False, True):
        eng = DecodeEngine(net, slots=2, max_len=64, kv="paged",
                           kv_block_size=16, eos_id=0,
                           prefix_cache=prefix_cache).start()
        try:
            pool = eng._pool
            baseline = (pool.in_use, pool.free_count)
            for round_ in range(3):
                for p in _prompts((17, 33), seed=round_):
                    eng.generate(p, max_new_tokens=10)
                assert pool.in_use == baseline[0] == 0
                assert pool.free_count == baseline[1]
            if prefix_cache:
                assert pool.cached_count > 0     # cached, yet allocatable
        finally:
            eng.stop()
        assert pool.in_use == 0


def test_engine_stop_releases_inflight_blocks():
    net = _transformer(max_len=64)
    eng = DecodeEngine(net, slots=2, max_len=64, kv="paged",
                       kv_block_size=16, prefix_cache=False).start()
    futs = [eng.submit(p, max_new_tokens=40) for p in _prompts((17, 9))]
    eng.stop()                                   # mid-flight abort
    assert eng._pool.in_use == 0
    for f in futs:
        assert f.done()


# ------------------------------------------------------------ validation

def test_paged_config_validation():
    net = _transformer(max_len=64)
    with pytest.raises(ValueError, match="kv_block_size"):
        DecodeEngine(net, max_len=60, kv="paged", kv_block_size=16)
    with pytest.raises(ValueError, match="chunk_tokens"):
        DecodeEngine(net, max_len=64, chunk_tokens=8)
    with pytest.raises(ValueError, match="kv must be"):
        DecodeEngine(net, max_len=64, kv="virtual")
    # recurrent decode state cannot share prefix blocks
    with pytest.raises(ValueError, match="prefix_cache"):
        DecodeEngine(_lstm_net(), max_len=64, kv="paged",
                     prefix_cache=True)
    # an LSTM paged engine is fine with the prefix cache off
    eng = DecodeEngine(_lstm_net(), max_len=64, kv="paged",
                       prefix_cache=False)
    assert eng.kv == "paged"
    # a request that could NEVER fit the pool fails fast at submit
    small = DecodeEngine(net, slots=1, max_len=64, kv="paged",
                         kv_block_size=16, kv_blocks=3, prefix_cache=False)
    with pytest.raises(ValueError, match="KV blocks"):
        small.submit(list(range(5)) * 8, max_new_tokens=20)


# --------------------------------------------------------------- healthz

def test_healthz_reports_kv_pool_exhausted():
    net = _transformer(max_len=256)
    # pool sized so ONE long request takes every block: the second queues
    # and /healthz degrades with the pool occupancy until blocks free up
    dec = DecodeEngine(net, slots=2, max_len=256, kv="paged",
                       kv_block_size=16, kv_blocks=17,
                       prefix_cache=False).start()
    srv = InferenceServer(net, port=0, decode_engine=dec).start()
    try:
        cli = InferenceClient(f"http://127.0.0.1:{srv.port}")
        assert cli.health()["status"] == "ok"
        prompt = _prompts((4,), seed=5)[0]
        f1 = dec.submit(prompt, max_new_tokens=240)   # needs all 16 blocks
        f2 = dec.submit(prompt, max_new_tokens=240)
        seen = None
        deadline = time.time() + 60
        while time.time() < deadline:
            h = cli.health()
            if h["status"] == "degraded" and h["reason"] == "kv_pool_exhausted":
                seen = h
                break
            if f2.done():
                break
            time.sleep(0.002)
        assert seen is not None, "never observed kv_pool_exhausted"
        assert seen["kv"]["blocks"] == 16
        assert seen["kv"]["blocks_free"] == 0
        f1.result(timeout=120)
        f2.result(timeout=120)
        deadline = time.time() + 30
        while cli.health()["status"] != "ok" and time.time() < deadline:
            time.sleep(0.01)
        assert cli.health()["status"] == "ok"    # recovers once released
        assert dec.stats()["kv"]["exhausted_events"] >= 1
    finally:
        srv.stop()


# ---------------------------------------------------------- paged kernel

def test_flash_decode_paged_kernel_matches_gather():
    from deeplearning4j_tpu.ops.flash_decode import (flash_decode_step,
                                                     flash_decode_step_paged,
                                                     supported_paged)
    # the compiled kernel needs whole 128-lane head dims; the interpreter
    # takes any (tests/test_tpu_compile.py holds the screen to the compiler)
    assert supported_paged(16, 128, 4) and not supported_paged(12, 128, 4)
    assert not supported_paged(16, 8, 4)
    assert supported_paged(16, 8, 4, interpret=True)
    rng = np.random.default_rng(0)
    B, H, Dh, bs, nb, MB = 3, 4, 8, 16, 9, 4
    pk = rng.standard_normal((nb, bs, H, Dh)).astype(np.float32)
    pv = rng.standard_normal((nb, bs, H, Dh)).astype(np.float32)
    q = rng.standard_normal((B, H, Dh)).astype(np.float32)
    # distinct scattered tables per row; positions mid-block
    bt = np.array([[1, 3, 5, 7], [2, 4, 6, 8], [8, 1, 2, 3]], np.int32)
    pos = np.array([37, 5, 63], np.int32)
    got = np.asarray(flash_decode_step_paged(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), pos, bt,
        interpret=True))
    # oracle: gather the dense per-row cache, run the dense flash kernel
    kc = pk[bt].reshape(B, MB * bs, H, Dh)
    vc = pv[bt].reshape(B, MB * bs, H, Dh)
    ref = np.asarray(flash_decode_step(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), pos,
        interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
