"""Fused passes over the residual state of manifold-constrained
hyper-connections (Pallas TPU).

The residual state X of ``n`` streams is (R, n*C) for R tokens, stream i in
columns i*C .. (i+1)*C - 1 (nn/layers/latent.py). Every sublayer reads it
three times: for its maps, for H_pre X before the sublayer and for
H_res X + H_post^T F after it; forward, again in a block's replay, and
backward. XLA's form widens the state to float32 in HBM on the way and its
derivative keeps float32 products of the whole state. Here each pass,
forward and backward, is one kernel over blocks of tokens that reads the
state once in its own dtype, does its arithmetic in float32 in VMEM and
writes each result once, in the state's dtype (the maps in float32):

- ``maps_projection(x, phi)``: z = rsqrt(mean(x^2) + eps) (x phi), the
  sum of squares and the product from one read of x; the norm is applied
  after the product, which is the same mathematics as normalising first
  without rounding the normalised state;
- ``mix_in(x, pre)``: sum_i pre_i X_i, (R, C);
- ``mix_out(x, f, res, post)``: X'_i = sum_j res_ij X_j + post_i F.

Each is a ``jax.custom_vjp`` whose backward pass is one kernel of the same
kind: it reads the state (and the incoming cotangent) once and writes the
state's cotangent once beside the per-token cotangents of the maps, which
it reduces over C in VMEM. The residuals are the arguments themselves, and
for the maps the (R, K) result and the per-token norm.

A grid step holds a block of whole rows: every stream of its tokens, so
nothing is fetched twice. Its height is the largest power of two up to 512
whose blocks stay within ``_BLOCK_BYTES`` (at 4 x 3584 in bfloat16: 128
rows for the maps' forward pass, 32 for the mix-out's backward pass, 64
for the others); the last block may be partial,
its rows beyond R masked where a pass sums over tokens. The matrix
products take their operands in the state's dtype with float32
accumulation; the maps' cotangent is rounded to that dtype for the two
gradient products, as the flash kernels round theirs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import CallForms

# what the blocks of one grid step may hold (each is double-buffered, and
# the float32 stream slices of a step sit beside them in VMEM)
_BLOCK_BYTES = 4 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024
_LANES = 128


def supported(c, streams):
    """Shape screen: a stream ``c`` wide is a whole number of 128-lane
    tiles (each stream a lane-aligned slice of a row), and at least one
    stream."""
    return c > 0 and c % _LANES == 0 and streams >= 1


def _padded(n):
    """Bytes of ``n`` float32 lanes as VMEM lays them out."""
    return -(-n // _LANES) * _LANES * 4


def _row_block(rows, row_bytes):
    """Rows a grid step: the largest power of two in 8..512 whose blocks
    (``row_bytes`` a row) stay within ``_BLOCK_BYTES``, or all rows."""
    b = 512
    while b > 8 and b * row_bytes > _BLOCK_BYTES:
        b //= 2
    return rows if rows <= b else b


def _call(kernel, rows, block, ins, outs, args, interpret, reduce_rows=False,
          **statics):
    """``kernel`` over ``cdiv(rows, block)`` blocks of rows of ``args``:
    an operand's block is ``block`` of its rows whole where ``ins`` gives
    its width, the whole operand where ``ins`` gives its shape (a tuple);
    ``outs`` is ((width or shape, dtype), ...) likewise, the rows of each
    output ``rows``. Returns the list of outputs."""
    return _pallas(kernel, rows, block, tuple(ins), tuple(outs), interpret,
                   reduce_rows, tuple(sorted(statics.items())))(*args)


@functools.lru_cache(maxsize=64)
def _pallas(kernel, rows, block, ins, outs, interpret, reduce_rows, statics):
    # built once for its shapes: the jit inside pallas_call then traces the
    # kernel once, where a step calls each pass at the same shapes dozens of
    # times (forward, replay, backward of every sublayer)
    spec = lambda w: (pl.BlockSpec(w, lambda r: (0,) * len(w))
                      if isinstance(w, tuple)
                      else pl.BlockSpec((block, w), lambda r: (r, 0)))
    return pl.pallas_call(
        functools.partial(kernel, **dict(statics)),
        grid=(pl.cdiv(rows, block),),
        in_specs=[spec(w) for w in ins],
        out_specs=[spec(w) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(
            w if isinstance(w, tuple) else (rows, w), dt) for w, dt in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if reduce_rows else "parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _f32(ref, i, c):
    return ref[:, i * c:(i + 1) * c].astype(jnp.float32)


def _columns(cols, shape):
    """The (rows, 1) float32 columns ``cols`` side by side as one (rows,
    len(cols)) array (a lane select each: no lane concatenation)."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    out = jnp.zeros(shape, jnp.float32)
    for k, col in enumerate(cols):
        out = jnp.where(lane == k, col, out)
    return out


# ------------------------------------------------------------------ maps

def _maps_fwd_kernel(x_ref, p_ref, z_ref, r_ref, *, streams, c, eps):
    ss = jnp.zeros(r_ref.shape, jnp.float32)
    acc = jnp.zeros(z_ref.shape, jnp.float32)
    for i in range(streams):
        xi = x_ref[:, i * c:(i + 1) * c]
        xf = xi.astype(jnp.float32)
        ss = ss + jnp.sum(xf * xf, axis=1, keepdims=True)
        acc = acc + lax.dot_general(
            xi, p_ref[:, i * c:(i + 1) * c], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    r = lax.rsqrt(ss / (streams * c) + eps)
    z_ref[...] = acc * r
    r_ref[...] = r


def _maps_bwd_kernel(x_ref, p_ref, g_ref, b_ref, dx_ref, dp_ref, *, streams,
                     c, n_rows):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        dp_ref[...] = jnp.zeros(dp_ref.shape, jnp.float32)

    block = x_ref.shape[0]
    row = step * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    ok = row < n_rows       # the rows of a partial last block past R
    g = jnp.where(ok, g_ref[...], 0.0).astype(x_ref.dtype)
    b = b_ref[...]
    for i in range(streams):
        xi = x_ref[:, i * c:(i + 1) * c]
        dx = jnp.dot(g, p_ref[:, i * c:(i + 1) * c],
                     preferred_element_type=jnp.float32)
        dx_ref[:, i * c:(i + 1) * c] = (
            dx + b * xi.astype(jnp.float32)).astype(dx_ref.dtype)
        dp_ref[:, i * c:(i + 1) * c] += lax.dot_general(
            g, jnp.where(ok, xi, 0), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _maps_fwd_call(x, phi_t, eps, streams, interpret):
    rows, width = x.shape
    k = phi_t.shape[0]
    block = _row_block(rows, width * x.dtype.itemsize + _padded(k)
                       + _padded(1))
    return _call(_maps_fwd_kernel, rows, block, [width, phi_t.shape],
                 [(k, jnp.float32), (1, jnp.float32)], (x, phi_t), interpret,
                 streams=streams, c=width // streams, eps=eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def maps_projection(x, phi, eps, streams, interpret=False):
    """x (R, n*C), phi (n*C, K) in x's dtype -> (R, K) float32:
    rsqrt(mean(x^2) + eps) (x phi), the mean over the n*C values of a
    token, for ``streams`` = n and C that ``supported`` accepts.
    Differentiable in x and phi."""
    return _maps_fwd_call(x, phi.T, eps, streams, interpret)[0]


def _maps_fwd(x, phi, eps, streams, interpret):
    z, r = _maps_fwd_call(x, phi.T, eps, streams, interpret)
    return z, (x, phi, z, r)


def _maps_bwd(eps, streams, interpret, res, dz):
    x, phi, z, r = res
    rows, width = x.shape
    k = phi.shape[1]
    # z = r p with p = x phi and r = (ss / W + eps)^-1/2: dp = r dz, and
    # the norm's term dx += -(r^2 / W) (dz . z) x
    g = r * dz
    b = -(r * r / width) * jnp.sum(dz * z, axis=1, keepdims=True)
    block = _row_block(rows, 2 * width * x.dtype.itemsize + _padded(k)
                       + _padded(1))
    dx, dp = _call(_maps_bwd_kernel, rows, block, [width, (k, width), k, 1],
                   [(width, x.dtype), ((k, width), jnp.float32)],
                   (x, phi.T, g, b), interpret, reduce_rows=True,
                   streams=streams, c=width // streams, n_rows=rows)
    return dx, dp.T.astype(phi.dtype)


maps_projection.defvjp(_maps_fwd, _maps_bwd)


# ---------------------------------------------------------------- mix-in

def _mix_in_kernel(x_ref, pre_ref, y_ref, *, streams, c):
    pre = pre_ref[...]
    y = pre[:, 0:1] * _f32(x_ref, 0, c)
    for i in range(1, streams):
        y = y + pre[:, i:i + 1] * _f32(x_ref, i, c)
    y_ref[...] = y.astype(y_ref.dtype)


def _mix_in_bwd_kernel(x_ref, pre_ref, dy_ref, dx_ref, dpre_ref, *, streams,
                       c):
    pre = pre_ref[...]
    dy = dy_ref[...].astype(jnp.float32)
    cols = []
    for i in range(streams):
        dx_ref[:, i * c:(i + 1) * c] = (pre[:, i:i + 1] * dy).astype(
            dx_ref.dtype)
        cols.append(jnp.sum(_f32(x_ref, i, c) * dy, axis=1, keepdims=True))
    dpre_ref[...] = _columns(cols, dpre_ref.shape)


def _mix_in_call(x, pre, interpret):
    rows, width = x.shape
    n = pre.shape[1]
    c = width // n
    block = _row_block(rows, (width + c) * x.dtype.itemsize + _padded(n))
    return _call(_mix_in_kernel, rows, block, [width, n], [(c, x.dtype)],
                 (x, pre), interpret, streams=n, c=c)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mix_in(x, pre, interpret=False):
    """x (R, n*C), pre (R, n) float32 -> (R, C) in x's dtype:
    sum_i pre_i X_i in float32, rounded once. Differentiable in both."""
    return _mix_in_call(x, pre, interpret)


def _mix_in_fwd(x, pre, interpret):
    return _mix_in_call(x, pre, interpret), (x, pre)


def _mix_in_bwd(interpret, res, dy):
    x, pre = res
    rows, width = x.shape
    n = pre.shape[1]
    c = width // n
    block = _row_block(rows, (2 * width + c) * x.dtype.itemsize
                       + 2 * _padded(n))
    return tuple(_call(_mix_in_bwd_kernel, rows, block, [width, n, c],
                       [(width, x.dtype), (n, jnp.float32)], (x, pre, dy),
                       interpret, streams=n, c=c))


mix_in.defvjp(_mix_in_fwd, _mix_in_bwd)


# --------------------------------------------------------------- mix-out

def _mix_out_kernel(x_ref, f_ref, res_ref, post_ref, o_ref, *, streams, c):
    res, post = res_ref[...], post_ref[...]
    f = f_ref[...].astype(jnp.float32)
    xs = [_f32(x_ref, j, c) for j in range(streams)]
    for i in range(streams):
        o = post[:, i:i + 1] * f
        for j in range(streams):
            o = o + res[:, i * streams + j:i * streams + j + 1] * xs[j]
        o_ref[:, i * c:(i + 1) * c] = o.astype(o_ref.dtype)


def _mix_out_bwd_kernel(x_ref, f_ref, res_ref, post_ref, g_ref, dx_ref,
                        df_ref, dres_ref, dpost_ref, *, streams, c):
    res, post = res_ref[...], post_ref[...]
    f = f_ref[...].astype(jnp.float32)
    gs = [_f32(g_ref, i, c) for i in range(streams)]
    df = post[:, 0:1] * gs[0]
    for i in range(1, streams):
        df = df + post[:, i:i + 1] * gs[i]
    df_ref[...] = df.astype(df_ref.dtype)
    dpost_ref[...] = _columns(
        [jnp.sum(g * f, axis=1, keepdims=True) for g in gs], dpost_ref.shape)
    dres = [None] * (streams * streams)
    for j in range(streams):
        xj = _f32(x_ref, j, c)
        dx = res[:, j:j + 1] * gs[0]
        for i in range(1, streams):
            dx = dx + res[:, i * streams + j:i * streams + j + 1] * gs[i]
        dx_ref[:, j * c:(j + 1) * c] = dx.astype(dx_ref.dtype)
        for i in range(streams):
            dres[i * streams + j] = jnp.sum(gs[i] * xj, axis=1, keepdims=True)
    dres_ref[...] = _columns(dres, dres_ref.shape)


def _mix_out_call(x, f, res, post, interpret):
    rows, width = x.shape
    n = post.shape[1]
    c = width // n
    block = _row_block(rows, (2 * width + c) * x.dtype.itemsize
                       + _padded(n * n) + _padded(n))
    return _call(_mix_out_kernel, rows, block, [width, c, n * n, n],
                 [(width, x.dtype)], (x, f, res, post), interpret, streams=n,
                 c=c)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mix_out(x, f, res, post, interpret=False):
    """x (R, n*C), f (R, C), res (R, n*n) float32 row-major, post (R, n)
    float32 -> (R, n*C) in x's dtype: X'_i = sum_j res_ij X_j + post_i F in
    float32, rounded once. Differentiable in all four."""
    return _mix_out_call(x, f, res, post, interpret)


def _mix_out_fwd(x, f, res, post, interpret):
    return _mix_out_call(x, f, res, post, interpret), (x, f, res, post)


def _mix_out_bwd(interpret, saved, g):
    x, f, res, post = saved
    rows, width = x.shape
    n = post.shape[1]
    c = width // n
    block = _row_block(rows, (3 * width + 2 * c) * x.dtype.itemsize
                       + 2 * _padded(n * n) + 2 * _padded(n))
    return tuple(_call(_mix_out_bwd_kernel, rows, block,
                       [width, c, n * n, n, width],
                       [(width, x.dtype), (c, f.dtype), (n * n, jnp.float32),
                        (n, jnp.float32)], (x, f, res, post, g), interpret,
                       streams=n, c=c))


mix_out.defvjp(_mix_out_fwd, _mix_out_bwd)


# ------------------------------------------------ which form a step traced
# ``note_call(form)`` counts one pass (the maps, a mix-in or a mix-out) into
# the dict that ``counting_calls(into)`` holds open on this thread
_calls = CallForms()
FORMS, note_call, counting_calls = _calls.FORMS, _calls.note, _calls.counting
