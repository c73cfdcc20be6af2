"""The plain reference of a hybrid decoder, one mixer a layer: forward pass,
mean token cross entropy, gradient and Adam's step in straightforward
float32 ``jax.numpy`` at the highest matmul precision. It imports nothing of
the program and is given nothing the program made: weights come from
``init_params`` here, batches from the job's own pool. RMSNorm, the chunked
token loss and the leaf norms are ``reference_lm``'s.

Every layer follows the published description of the configuration
(``perfbench/configs/nemotron-3-super-120b-a12b.json``; departures are that
file's ``assumed`` list, no more). Layer i: h <- h + Mixer_i(RMSNorm(h));
a final RMSNorm and an untied head. With x = RMSNorm(h), T positions:

- ``M``, Mamba-2: H heads of P, G groups (head j reads group j // (H/G)),
  state N. [z | xBC | dt] = x W_in; xBC <- silu(conv(xBC) + b_conv), a
  depthwise causal convolution of kernel K (tap K-1 reads the position
  itself); xBC splits into X (T, H, P), B, C (T, G, N).
  delta = softplus(dt + dt_bias), a = -exp(A_log). A head's recurrence from
  S_0 = 0: S_t = exp(delta_t a) S_{t-1} + delta_t X_t B_t^T (P x N);
  y_t = S_t C_t + D X_t. y <- RMSNorm over each group's H P / G channels of
  (y * silu(z)) with a gain a channel; out = y W_out. The recurrence is A
  SCAN OVER POSITIONS here, never the chunked form the program uses: only
  its memory is cut into blocks (a ``jax.checkpoint`` every ``SCAN_BLOCK``
  positions, which changes no number).
- ``E``, latent experts: s = sigmoid(x W_r); the ``top_k`` largest of
  s + b are chosen (b the selection bias, zero unless one is given);
  weights: the chosen s (without b) over their sum, times the routed scale.
  l = x W_down; expert e is relu(l W1_e)^2 W2_e; r = sum over the chosen e
  that are held of w_e E_e(l); out = r W_up + relu(x S1)^2 S2. A loop over
  the held experts with a mask: nothing sorted, nothing dropped; what
  absent experts would add to r is left out, as in the program.
- ``*``, attention: q, k, v = x W_q, x W_k, x W_v, no positional rotation;
  query head h reads kv head h // (Hq / Hkv); causal softmax(q k^T /
  sqrt(D)) v; out = concat W_o.

``precision="fp8"`` is the control of ``correct``: every tensor between
layers rounded to float8 as ``perfbench/lib/reference.py`` rounds it.
``fault`` plants one: ``half_batch`` (half of the step's tokens left out),
``drop_expert`` (the first held expert's part dropped), ``chunk_reset`` (the
state set to zero at every ``chunk_size``-th position), ``no_conv`` (the
convolution left out: xBC <- silu(xBC)), ``softmax_scores`` (a softmax over
the experts in place of the sigmoid), ``unweighted_latent`` (the
up-projection reads the sum of the chosen held experts' outputs without
their weights).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from perfbench.lib import reference_lm as base
from perfbench.lib.reference import ROUNDERS

FAULTS = (None, "half_batch", "drop_expert", "chunk_reset", "no_conv",
          "softmax_scores", "unweighted_latent")
ATTN_CHUNK = 512          # query rows scored at once
SCAN_BLOCK = 128          # positions between two kept states

rms_norm, leaf_norms = base.rms_norm, base.leaf_norms
MIXERS = "ME*"            # Mamba-2, latent experts, attention


# ------------------------------------------------------------------- sizes

def dims(cfg) -> dict:
    """The sizes of the model as it is run, from the configuration file's
    own keys (a rehearsal reads its ``rehearsal.model`` table over them).
    The counts of heads, groups, KV heads and experts are what this chip
    holds; ``experts`` is the router's published width."""
    c = dict(cfg)
    if cfg.get("rehearsed"):
        c.update(cfg["rehearsal"]["model"])
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"] or set(pattern) - set(MIXERS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} does not give "
                         f"{c['num_hidden_layers']} layers of M, E and *")
    return {
        "pattern": pattern, "layers": len(pattern),
        "hidden": c["hidden_size"], "eps": c["layer_norm_epsilon"],
        "vocab": c["vocab_size"],
        "ssm_heads": c["mamba_num_heads"], "ssm_head_dim": c["mamba_head_dim"],
        "ssm_groups": c["n_groups"], "ssm_state": c["ssm_state_size"],
        "conv": c["conv_kernel"], "chunk": c["chunk_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "experts": c["published"]["n_routed_experts"],
        "experts_held": c["n_routed_experts"],
        "first_expert": c["deployment"]["first_expert"],
        "top_k": c["num_experts_per_tok"], "latent": c["moe_latent_size"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": (c["moe_shared_expert_intermediate_size"]
                         * c["n_shared_experts"]),
        "norm_topk": c["norm_topk_prob"],
        "routed_scale": c["routed_scaling_factor"],
        # the published depth: what the projections that write to the
        # residual stream are scaled down by at the start (``param_shapes``)
        "depth": cfg["published"]["num_hidden_layers"],
    }


def param_shapes(cfg) -> list:
    """[(node, leaf, shape, how)] in the order the weights are drawn.
    ``how``: a fan-in (normal, std 1/sqrt(fan_in)), None (ones: gains and
    D), ``"A_log"`` (the log of a uniform draw in 1..16) or ``"dt_bias"``
    (the inverse softplus of a log-uniform step in [0.001, 0.1]). The
    projections that write to the residual stream (W_out, Wup, S2, Wo) are
    drawn 1/sqrt(published depth) smaller, a fan-in that many times larger:
    the cell's own departure, wider than the configuration's
    ``rescale_prenorm_residual`` (the file's
    ``assumed.cell_residual_writers_scaled`` says why, and what it costs)."""
    d = dims(cfg)
    c, n = d["hidden"], d["depth"]
    out = [("embed", "W", (d["vocab"], c), 1)]
    for i, kind in enumerate(d["pattern"]):
        b = f"b{i}"
        out.append((f"{b}.norm", "gamma", (c,), None))
        m = f"{b}.mixer"
        if kind == "M":
            h, k = d["ssm_heads"], d["conv"]
            hp = h * d["ssm_head_dim"]
            xbc = hp + 2 * d["ssm_groups"] * d["ssm_state"]
            out += [(m, "W_in", (c, hp + xbc + h), c),
                    (m, "conv_w", (k, xbc), k), (m, "conv_b", (xbc,), k),
                    (m, "A_log", (h,), "A_log"), (m, "D", (h,), None),
                    (m, "dt_bias", (h,), "dt_bias"),
                    (m, "norm_g", (hp,), None),
                    (m, "W_out", (hp, c), hp * n)]
        elif kind == "E":
            e, l, w, s = (d["experts_held"], d["latent"], d["expert_width"],
                          d["shared_width"])
            out += [(m, "Wr", (c, d["experts"]), c),
                    (m, "Wdown", (c, l), c), (m, "Wup", (l, c), l * n),
                    (m, "E1", (e, l, w), l), (m, "E2", (e, w, l), w),
                    (m, "S1", (c, s), c), (m, "S2", (s, c), s * n)]
        else:
            hq, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
            out += [(m, "Wq", (c, hq), c), (m, "Wk", (c, kv), c),
                    (m, "Wv", (c, kv), c), (m, "Wo", (hq, c), hq * n)]
    out += [("final_norm", "gamma", (c,), None),
            ("head", "W", (c, d["vocab"]), c)]
    return out


def init_params(cfg, seed: int):
    """{node: {leaf: float32 array}} from --seed in one jitted call."""
    shapes = param_shapes(cfg)

    def build(key):
        out = {}
        for i, (node, leaf, shape, how) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if how is None:
                v = jnp.ones(shape, jnp.float32)
            elif how == "A_log":
                v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1., 16.))
            elif how == "dt_bias":
                step = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                v = step + jnp.log(-jnp.expm1(-step))
            else:
                v = jax.random.normal(k, shape, jnp.float32) / math.sqrt(how)
            out.setdefault(node, {})[leaf] = v
        return out

    return jax.jit(build)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


# ------------------------------------------------------------------ layers

def relu2(x, w1, w2):
    return jnp.square(jax.nn.relu(x @ w1)) @ w2


def causal_conv(x, w, b):
    """Depthwise over time: out[t] = sum_k w[k] x[t - (K-1) + k] + b, zeros
    before the sequence. x (T, C), w (K, C)."""
    t, k = x.shape[0], w.shape[0]
    pad = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(pad[j:j + t] * w[j] for j in range(k)) + b


def ssm_scan(dec, dtx, bh, ch, reset_every=None, block=SCAN_BLOCK):
    """The recurrence over positions: S_t = dec_t S_{t-1} + dtx_t b_t^T,
    y_t = S_t c_t. dec (T, H), dtx (T, H, P), bh, ch (T, H, N) -> (y (T, H,
    P), the last state (H, P, N)). ``reset_every``: the planted fault.
    Padded to whole blocks with steps that leave the state as it is."""
    t, h, p = dtx.shape
    n = bh.shape[-1]
    block = min(block, t)
    pad = -t % block
    start = (jnp.arange(t + pad) % reset_every == 0) if reset_every \
        else jnp.zeros((t + pad,), bool)
    xs = (jnp.pad(dec, ((0, pad), (0, 0)), constant_values=1.0),
          jnp.pad(dtx, ((0, pad), (0, 0), (0, 0))),
          jnp.pad(bh, ((0, pad), (0, 0), (0, 0))),
          jnp.pad(ch, ((0, pad), (0, 0), (0, 0))), start)
    xs = jax.tree_util.tree_map(
        lambda a: a.reshape((t + pad) // block, block, *a.shape[1:]), xs)

    def position(s, x):
        de, dx, b, c, zero = x
        s = jnp.where(zero, 0.0, de[:, None, None] * s) \
            + dx[:, :, None] * b[:, None, :]
        return s, (s * c[:, None, :]).sum(axis=-1)

    @jax.checkpoint
    def positions(s, x):
        return jax.lax.scan(position, s, x)

    s, y = jax.lax.scan(positions, jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape(t + pad, h, p)[:t], s


def mamba(x, p, *, heads, head_dim, groups, state, eps, chunk, fault=None):
    """One sequence: x (T, C) -> (y (T, C), mean over T and H of the decay
    exp(delta a))."""
    t = x.shape[0]
    hp, gn = heads * head_dim, groups * state
    z, xbc, dt = jnp.split(x @ p["W_in"], [hp, 2 * hp + 2 * gn], axis=-1)
    if fault != "no_conv":
        xbc = causal_conv(xbc, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :hp].reshape(t, heads, head_dim)
    b = xbc[:, hp:hp + gn].reshape(t, groups, state)
    c = xbc[:, hp + gn:].reshape(t, groups, state)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    dec = jnp.exp(delta * -jnp.exp(p["A_log"]))
    rep = heads // groups
    y, _ = ssm_scan(dec, delta[:, :, None] * xs, jnp.repeat(b, rep, axis=1),
                    jnp.repeat(c, rep, axis=1),
                    chunk if fault == "chunk_reset" else None)
    y = (y + p["D"][None, :, None] * xs).reshape(t, hp) * jax.nn.silu(z)
    y = y.reshape(t, groups, hp // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(t, hp) * p["norm_g"]) @ p["W_out"], dec.mean()


def experts(x, p, *, top_k, held, routed_scale, norm_topk, bias=None,
            fault=None):
    """x (T, C). ``held`` = (count, first): the experts whose part is
    computed. Returns (y, pairs that fell on held experts)."""
    count, first = held
    logits = x @ p["Wr"]
    s = jax.nn.softmax(logits, axis=-1) if fault == "softmax_scores" \
        else jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s if bias is None else s + bias, top_k)
    val = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        val = val / val.sum(axis=-1, keepdims=True)
    val = val * routed_scale
    lat = x @ p["Wdown"]
    r = jnp.zeros_like(lat)
    pairs = 0
    for e in range(count):
        on = idx == first + e                          # (T, k)
        w = jnp.where(on, 1.0 if fault == "unweighted_latent" else val,
                      0.0).sum(axis=-1)
        pairs = pairs + on.sum()
        if e == 0 and fault == "drop_expert":
            continue
        r = r + w[:, None] * jax.checkpoint(relu2)(lat, p["E1"][e], p["E2"][e])
    return r @ p["Wup"] + relu2(x, p["S1"], p["S2"]), pairs


def attention(x, p, *, heads, kv_heads, head_dim, chunk=ATTN_CHUNK):
    """One sequence: x (T, C) -> (T, C). No positional rotation."""
    t = x.shape[0]
    q = (x @ p["Wq"]).reshape(t, heads, head_dim)
    k = (x @ p["Wk"]).reshape(t, kv_heads, head_dim)
    v = (x @ p["Wv"]).reshape(t, kv_heads, head_dim)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    chunk = min(chunk, t)

    @jax.checkpoint
    def rows(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
        s = jnp.einsum("qhd,khd->hqk", qc, k) / math.sqrt(head_dim)
        ok = start + jnp.arange(chunk)[:, None] >= jnp.arange(t)[None, :]
        a = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v)

    o = jax.lax.map(rows, jnp.arange(0, t, chunk)).reshape(t, heads * head_dim)
    return o @ p["Wo"]


def mixer(d, kind, x, p, fault=None):
    """One mixer on x (T, C): (y, pairs on held experts or None, mean decay
    or None)."""
    if kind == "M":
        y, decay = mamba(x, p, heads=d["ssm_heads"],
                         head_dim=d["ssm_head_dim"], groups=d["ssm_groups"],
                         state=d["ssm_state"], eps=d["eps"], chunk=d["chunk"],
                         fault=fault)
        return y, None, decay
    if kind == "E":
        y, n = experts(x, p, top_k=d["top_k"],
                       held=(d["experts_held"], d["first_expert"]),
                       routed_scale=d["routed_scale"],
                       norm_topk=d["norm_topk"], fault=fault)
        return y, n, None
    return attention(x, p, heads=d["heads"], kv_heads=d["kv_heads"],
                     head_dim=d["head_dim"]), None, None


# -------------------------------------------------------------- the model

def sequence_loss(cfg, params, ids, labels, precision="float32", fault=None):
    """Mean cross entropy of one sequence: ids, labels (T,) int32. Returns
    (loss, {pairs: per expert layer, decay: per Mamba layer})."""
    d = dims(cfg)
    r = ROUNDERS.get(precision, lambda a: a)
    h = r(params["embed"]["W"][ids])
    seen = {"pairs": [], "decay": []}
    for i, kind in enumerate(d["pattern"]):
        def block(h, pn, pm, kind=kind):
            x = r(rms_norm(h, pn["gamma"], d["eps"]))
            y, n, decay = mixer(d, kind, x, pm, fault)
            return r(h + r(y)), n, decay

        h, n, decay = jax.checkpoint(block)(
            h, params[f"b{i}.norm"], params[f"b{i}.mixer"])
        if kind == "E":
            seen["pairs"].append(n)
        if kind == "M":
            seen["decay"].append(decay)
    h = r(rms_norm(h, params["final_norm"]["gamma"], d["eps"]))
    return base.token_losses(h, params["head"]["W"], labels).mean(), seen


def loss_fn(cfg, params, ids, labels, precision="float32", fault=None):
    """Mean token cross entropy over a batch of sequences (B, T), one
    sequence at a time. Returns (loss, {pairs summed, decay averaged over
    the sequences, per layer})."""
    if fault == "half_batch":
        if ids.shape[0] > 1:
            ids, labels = ids[: ids.shape[0] // 2], labels[: labels.shape[0] // 2]
        else:
            ids, labels = ids[:, : ids.shape[1] // 2], labels[:, : labels.shape[1] // 2]

    def one(args):
        return jax.checkpoint(
            lambda p, a, b: sequence_loss(cfg, p, a, b, precision, fault))(
                params, *args)

    losses, seen = jax.lax.map(one, (ids, labels))
    return losses.mean(), {"pairs": [n.sum() for n in seen["pairs"]],
                           "decay": [v.mean() for v in seen["decay"]]}


_STEPS = {}


def make_step(cfg, precision="float32", fault=None):
    """One jitted step per configuration, precision and fault for the life
    of the process (``jax.clear_caches()`` still frees them)."""
    key = (cfg["name"], bool(cfg.get("rehearsed")), precision, fault)
    if key not in _STEPS:
        _STEPS[key] = _make_step(cfg, precision, fault)
    return _STEPS[key]


def _make_step(cfg, precision="float32", fault=None):
    """(params, mu, nu, ids, labels, t) -> (params, mu, nu, loss, seen).
    Adam as the configuration states it, with bias correction."""
    if fault not in FAULTS:
        raise ValueError(fault)
    u = cfg["updater"]
    lr, b1, b2, eps = (u["learning_rate"], u["beta1"], u["beta2"],
                       u["epsilon"])
    tmap = jax.tree_util.tree_map

    def step(params, mu, nu, ids, labels, t):
        (loss, seen), g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, labels, precision, fault),
            has_aux=True)(params)
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = tmap(lambda p, m, v: p - lr * (m / c1)
                      / (jnp.sqrt(v / c2) + eps), params, mu, nu)
        return params, mu, nu, loss, seen

    return jax.jit(step, donate_argnums=(0, 1, 2))


def run_steps(cfg, seed, batches, *, precision="float32", fault=None):
    """Drive ``len(batches)`` steps from ``init_params(cfg, seed)``. Returns
    per step the loss, the pairs that fell on held experts per expert layer
    (``pairs``) and the mean decay per Mamba layer (``decay``); per leaf
    (``param_shapes`` order) the norm of Adam's first moment after the
    first step and of the parameters' change after the last."""
    leaves = [(k, n) for k, n, _, _ in param_shapes(cfg)]
    step = make_step(cfg, precision, fault)
    tmap = jax.tree_util.tree_map
    params = init_params(cfg, seed)
    zeros = jax.jit(lambda t: tmap(jnp.zeros_like, t))
    mu, nu = zeros(params), zeros(params)
    norms = jax.jit(lambda t: leaf_norms(t, leaves))
    out = {"losses": [], "trace_norms": None, "step_seconds": [], "pairs": [],
           "decay": [], "state_delta_norms": []}
    with jax.default_matmul_precision("highest"):
        for i, (ids, labels) in enumerate(batches):
            t = time.perf_counter()
            params, mu, nu, loss, seen = step(
                params, mu, nu, jnp.asarray(ids, jnp.int32),
                jnp.asarray(labels, jnp.int32),
                jnp.asarray(i + 1, jnp.float32))
            out["losses"].append(float(loss))
            out["pairs"].append([int(n) for n in seen["pairs"]])
            out["decay"].append([float(v) for v in seen["decay"]])
            out["step_seconds"].append(time.perf_counter() - t)
            if i == 0:
                out["trace_norms"] = jax.device_get(norms(mu))
    mu = nu = None
    delta = jax.jit(lambda a, b: leaf_norms(
        tmap(jnp.subtract, a, b), leaves))
    out["delta_norms"] = jax.device_get(delta(params, init_params(cfg, seed)))
    return out
