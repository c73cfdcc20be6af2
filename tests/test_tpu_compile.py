"""The Pallas kernels of the main path, compiled for a described TPU v5e.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached — so what Mosaic refuses (a misaligned block, too
much VMEM, a kernel under the SPMD partitioner) fails in this suite, not on
the chip. Interpret mode sees none of it. Nothing runs: these tests say
"compiles", never "is right" or "is fast".

Claim pinned for every case: the shape screen and the compiler AGREE — a
shape a screen accepts compiles (with the Mosaic custom call in the
program), and the edges the screens reject are shapes the compiler really
refuses.

The topology is described inside a module-scoped fixture, never at import,
in a ``parametrize`` argument or in a ``skipif``: only the worker that is
handed this file loads the TPU library (tests stay in this ONE file for the
same reason), and the persistent compile cache is off around the compiles
(an entry written for a described chip cannot be read back without one).
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from deeplearning4j_tpu.exec import routing
from deeplearning4j_tpu.ops import lstm_pallas

# ops/__init__ re-exports the flash_attention FUNCTION under the module's name
flash_attention = importlib.import_module(
    "deeplearning4j_tpu.ops.flash_attention")
flash_decode = importlib.import_module("deeplearning4j_tpu.ops.flash_decode")
index_scores = importlib.import_module("deeplearning4j_tpu.ops.index_scores")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_routes():
    """Pin the LSTM routes to the kernel: off the chip the backend check
    would answer 'scan' and the kernel under test would never be traced."""
    routing.set_route("fused_lstm", "pallas")
    routing.set_route("fused_lstm_grad", "pallas")
    yield
    routing.set_route("fused_lstm", None)
    routing.set_route("fused_lstm_grad", None)


def _compiles(fn, *shapes):
    """(compiled?, Mosaic custom calls in the program, compiler's words)."""
    try:
        text = jax.jit(fn).lower(*shapes).compile().as_text()
    except Exception as e:      # whatever the TPU compiler raises
        return False, 0, str(e)
    return True, text.count('custom_call_target="tpu_custom_call"'), ""


def _agree(screen, fn, shapes, kernels=1):
    ok, calls, err = _compiles(fn, *shapes)
    assert ok == bool(screen), (
        f"screen says {bool(screen)}, compiler says {ok}: {err[:400]}")
    if ok:
        assert calls >= kernels, f"{calls} Mosaic calls in the program"


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


# ------------------------------------------------------------- fused LSTM
# the two charRNN shapes of the zoo's TextGenerationLSTM (2 x LSTM(256))
CHARRNN = [(64, 32, 256, "float32"), (64, 256, 256, "bfloat16")]


@pytest.mark.parametrize("t,b,h,dtype", CHARRNN)
def test_fused_lstm_fwd_and_grad(one_chip, kernel_routes, t, b, h, dtype):
    dt = jnp.dtype(dtype)

    def loss(gi, rw, h0, c0):
        hs, c_t = lstm_pallas.fused_lstm_sequence(gi, rw, h0, c0, False)
        return hs.astype(jnp.float32).sum() + c_t.astype(jnp.float32).sum()

    shapes = _shapes(one_chip, ((t, b, 4 * h), dt), ((h, 4 * h), dt),
                     ((b, h), dt), ((b, h), dt))
    _agree(lstm_pallas.supported(b, t, h, dt.itemsize),
           jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), shapes, kernels=2)


@pytest.mark.parametrize("t,b,h,dtype", CHARRNN)
def test_stacked_lstm_pair_fwd_and_grad(one_chip, kernel_routes, t, b, h,
                                        dtype):
    dt = jnp.dtype(dtype)

    def loss(*a):
        return sum(o.astype(jnp.float32).sum()
                   for o in lstm_pallas.fused_lstm2_sequence(*a, False))

    g = 4 * h
    shapes = _shapes(one_chip, ((t, b, g), dt), ((h, g), dt), ((h, g), dt),
                     ((g,), dt), ((h, g), dt),
                     *[((b, h), dt)] * 4)
    _agree(lstm_pallas.supported2(b, t, h, dt.itemsize),
           jax.value_and_grad(loss, argnums=tuple(range(9))), shapes,
           kernels=3)


def test_lstm_kernel_under_a_mesh_is_refused_and_routed_to_scan(topo):
    """XLA will not auto-partition a Mosaic call, so inside a step the
    executor shards over a mesh every route answers 'scan'
    (routing._mosaic_cannot_partition)."""
    from deeplearning4j_tpu.exec import executor
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    t, b, h, dt = 64, 256, 256, jnp.bfloat16
    on = lambda spec: NamedSharding(mesh, spec)
    shapes = [jax.ShapeDtypeStruct((t, b, 4 * h), dt,
                                   sharding=on(P(None, "data"))),
              jax.ShapeDtypeStruct((h, 4 * h), dt, sharding=on(P())),
              jax.ShapeDtypeStruct((b, h), dt, sharding=on(P("data"))),
              jax.ShapeDtypeStruct((b, h), dt, sharding=on(P("data")))]
    ok, _, err = _compiles(
        lambda *a: lstm_pallas._fwd_call(*a, interpret=False,
                                         save_reserve=True), *shapes)
    assert not ok and "cannot be automatically partitioned" in err, err

    executor._PARTITIONED.on = True     # what a mesh program's trace sets
    try:
        for route in (routing.lstm_fwd_route(b, h, t=t, dtype="bfloat16"),
                      routing.lstm_grad_route(b, h, t=t, dtype="bfloat16",
                                              backend="tpu"),
                      routing.flash_attn_route(8, 4096, 128, True,
                                               backend="tpu"),
                      routing.decode_attn_route(512, 32, backend="tpu")):
            assert route == "scan"
        # the same program with the route applied compiles, kernel-free
        ok, calls, err = _compiles(
            lambda *a: lstm_pallas.fused_lstm_sequence(*a, False), *shapes)
        assert ok and calls == 0, err
    finally:
        executor._PARTITIONED.on = False


# -------------------------------------------------------- flash attention
# T 4096 is where the layer seam starts routing to the kernel; at T 8192
# K and V alone fill the 16 MiB Mosaic scopes a kernel to
@pytest.mark.parametrize("bh,t,dh", [(8, 4096, 128), (8, 8192, 128)])
def test_flash_attention_fwd_and_grad(one_chip, bh, t, dh):
    def loss(q, k, v):
        return flash_attention.flash_attention(q, k, v, True, False).sum()

    shapes = _shapes(one_chip, *[((bh, t, dh), jnp.float32)] * 3)
    _agree(flash_attention.supported(t, dh),
           jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes, kernels=3)


# the benchmark's decoder cells: 2 sequences of 8192, 2 kv heads held, 12
# query heads on full layers and 18 behind a window of 512 on sliding ones;
# and the hybrid cell's one attention layer, 16 query heads on one kv head
@pytest.mark.parametrize("b,hq,hkv,window", [(2, 12, 2, None),
                                             (2, 18, 2, 512),
                                             (1, 16, 1, None)])
def test_gqa_flash_attention_fwd_and_grad(one_chip, b, hq, hkv, window):
    def loss(q, k, v):
        return flash_attention.gqa_flash_attention(
            q, k, v, window).astype(jnp.float32).sum()

    shapes = _shapes(one_chip, ((b, hq, 8192, 128), jnp.bfloat16),
                     ((b, hkv, 8192, 128), jnp.bfloat16),
                     ((b, hkv, 8192, 128), jnp.bfloat16))
    _agree(flash_attention.gqa_supported(8192, 128, hq, hkv),
           jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes, kernels=3)


# the benchmark's cell with a learned selection of keys: one sequence of
# 16,384 positions, 32 query heads over 4 kv heads of 128, one int8 mask
# for all heads
SELECTED = ((1, 32, 16384, 128), (1, 4, 16384, 128))


def test_gqa_selected_attention_fwd_and_grad(one_chip):
    def loss(q, k, v, mask):
        return flash_attention.gqa_selected_attention(
            q, k, v, mask)[0].astype(jnp.float32).sum()

    q, kv = SELECTED
    shapes = _shapes(one_chip, (q, jnp.bfloat16), (kv, jnp.bfloat16),
                     (kv, jnp.bfloat16), ((1, 16384, 16384), jnp.int8))
    _agree(flash_attention.gqa_supported(16384, 128, 32, 4),
           jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes, kernels=3)


# Keye's layer, every kv head a step (HeadMeanPlan 128, 512, 4, 4); and 64
# heads of 256 on as many kv heads, where the step's blocks hold 8 of them
# (256, 512, 1, 8): the plan's budget holds on the compiler with no VMEM
# limit asked
@pytest.mark.parametrize("b,hq,hkv,t,dh", [(1, 32, 4, 16384, 128),
                                           (1, 64, 64, 4096, 256)])
def test_gqa_head_mean_probs(one_chip, b, hq, hkv, t, dh):
    shapes = _shapes(one_chip, ((b, hq, t, dh), jnp.bfloat16),
                     ((b, hkv, t, dh), jnp.bfloat16),
                     ((b, hq, t, 1), jnp.float32), ((b, t, t), jnp.int8))
    assert flash_attention.head_mean_plan(t, hq, hkv, dh).kv_heads \
        == min(hkv, 8)
    _agree(flash_attention.gqa_supported(t, dh, hq, hkv),
           flash_attention.gqa_head_mean_probs, shapes)


# a chunk of the same cell's indexer: 512 query rows of 16 heads of 64
# against the keys up to the end of the first and of the last row group, in
# the cell's bfloat16; and the most rows that are one block within the
# VMEM the kernels ask for, with the next size up, in each dtype
@pytest.mark.parametrize("r,s,dtype", [(512, 2048, "bfloat16"),
                                       (512, 16384, "bfloat16"),
                                       (896, 2048, "bfloat16"),
                                       (1024, 2048, "bfloat16"),
                                       (640, 2048, "float32"),
                                       (896, 2048, "float32")])
def test_index_scores_fwd_and_grad(one_chip, r, s, dtype):
    def loss(q, w, k, g):
        return (index_scores.index_scores(q, w, k) * g).sum()

    shapes = _shapes(one_chip, ((r, 16, 64), jnp.dtype(dtype)),
                     ((r, 16), jnp.float32), ((s, 64), jnp.dtype(dtype)),
                     ((r, s), jnp.float32))
    _agree(index_scores.supported(r, 16, 64, s,
                                  jnp.dtype(dtype).itemsize),
           jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes, kernels=2)


def test_ragged_dot_is_one_grouped_product_on_the_chip(one_chip):
    """The expert layer's grouped product at the cell's shape: the TPU
    compiler keeps it one kernel of M x K x N multiply-adds, not one dense
    product per expert."""
    m, k, n, e = 7680, 3072, 1024, 8
    shapes = _shapes(one_chip, ((m, k), jnp.bfloat16),
                     ((e, k, n), jnp.bfloat16), ((e,), jnp.int32))
    compiled = jax.jit(jax.lax.ragged_dot).lower(*shapes).compile()
    assert compiled.cost_analysis()["flops"] < 1.1 * 2 * m * k * n
    assert "ragged-dot" in compiled.as_text()


def test_ssd_scan_fwd_and_grad_at_the_cells_shapes(one_chip):
    """The state-space mixer's chunked scan (XLA's form, no kernel) with its
    gradient at one chip's share of the hybrid cell's layer: it compiles
    for the chip, and holds no (T, T) array: the largest temporaries are
    the (chunks, heads, 128, 128) float32 tiles, 67 MB each."""
    from deeplearning4j_tpu.nn.layers.ssm import ssd_scan

    def loss(x, dt, a, b, c):
        return ssd_scan(x, dt, a, b, c, 128).sum()

    shapes = _shapes(one_chip, ((1, 8192, 16, 64), jnp.bfloat16),
                     ((1, 8192, 16), jnp.float32), ((16,), jnp.float32),
                     ((1, 8192, 1, 128), jnp.bfloat16),
                     ((1, 8192, 1, 128), jnp.bfloat16))
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4))).lower(*shapes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8192 * 8192 * 4


# ----------------------------------------------------------- flash decode
@pytest.mark.parametrize("b,h,dh,c", [(4, 4, 32, 512), (8, 8, 128, 1024)])
def test_flash_decode_dense(one_chip, b, h, dh, c):
    shapes = _shapes(one_chip, ((b, h, dh), jnp.float32),
                     ((b, c, h, dh), jnp.float32),
                     ((b, c, h, dh), jnp.float32), ((b,), jnp.int32))
    _agree(flash_decode.supported(c, dh), flash_decode.flash_decode_step,
           shapes)


# head dim 32 is TinyTransformer's zoo default: the pool's HBM layout pads
# it to 128 lanes and Mosaic refuses to slice it, so the screen says no
@pytest.mark.parametrize("b,h,dh,c,block", [(4, 4, 32, 512, 16),
                                            (8, 8, 128, 1024, 16),
                                            (8, 8, 128, 1024, 128)])
def test_flash_decode_paged(one_chip, b, h, dh, c, block):
    blocks = b * (c // block) + 1
    shapes = _shapes(one_chip, ((b, h, dh), jnp.float32),
                     ((blocks, block, h, dh), jnp.float32),
                     ((blocks, block, h, dh), jnp.float32),
                     ((b,), jnp.int32), ((b, c // block), jnp.int32))
    _agree(flash_decode.supported_paged(block, dh, h),
           flash_decode.flash_decode_step_paged, shapes)
