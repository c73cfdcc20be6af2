"""The plain reference of a sparse decoder language model: forward pass,
mean token cross entropy, gradient and Adam's step in straightforward
float32 ``jax.numpy`` at the highest matmul precision. It imports nothing of
the program and is given nothing the program made: weights come from
``init_params`` here, batches from the job's own pool.

Every layer follows the published description of the configuration
(``perfbench/configs/laguna-s-2.1.json``; departures are that file's
``assumed`` list, no more). With x = RMSNorm(h):

- attention: q = x Wq (T, Hq, D), k, v = x Wk, x Wv (T, Hkv, D); rotary on
  q and k; query head h reads kv head h // (Hq / Hkv); causal, and with a
  window key j only if i - j < window; A_h = softmax(q_h k^T / sqrt(D)) v;
  o_h = sigmoid(x w_h) A_h; out = concat(o) Wo.
- experts: s = softmax(x Wr) over all experts; I = top-k(s);
  p_i = scale s_i / sum_{j in I} s_j; y = sum_{i in I, held} p_i E_i(x)
  + E_shared(x); E(x) = (silu(x Wg) (x Wu)) Wd. A loop over the held
  experts with a mask: no capacity, nothing sorted, nothing dropped. What
  absent experts would add is left out, as in the program.

Memory: one ``jax.checkpoint`` a block and a sequence, attention in chunks
of query rows, the loss in chunks of rows, so that a float32 step at the
timed batch fits the chip once the program's state is freed.

``precision="fp8"`` is the control of ``correct``: every tensor between
layers rounded to float8 as ``perfbench/lib/reference.py`` rounds it.
``fault`` plants one fault: ``half_batch`` (half of the sequences left
out), ``drop_expert`` (the tokens of the first held expert dropped),
``no_window`` (the window left off the sliding layers).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from perfbench.lib.reference import ROUNDERS

FAULTS = (None, "half_batch", "drop_expert", "no_window")
ATTN_CHUNK = 512          # query rows scored at once
LOSS_CHUNK = 2048         # rows of logits held at once


# ------------------------------------------------------------------- sizes

def dims(cfg) -> dict:
    """The sizes of the model as it is run, from the configuration file's
    own keys (a rehearsal reads its ``rehearsal.model`` table over them)."""
    c = dict(cfg)
    if cfg.get("rehearsed"):
        c.update(cfg["rehearsal"]["model"])
    n = c["num_hidden_layers"]
    return {
        "layers": n, "hidden": c["hidden_size"], "head_dim": c["head_dim"],
        "kv_heads": c["num_key_value_heads"],
        "heads": list(c["num_attention_heads_per_layer"])[:n],
        "layer_types": list(c["layer_types"])[:n],
        "mlp_types": list(c["mlp_layer_types"])[:n],
        "window": c["sliding_window"], "eps": c["rms_norm_eps"],
        "vocab": c["vocab_size"], "dense_width": c["intermediate_size"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["shared_expert_intermediate_size"],
        "experts": c["published"]["num_experts"],
        "experts_held": c["num_experts"],
        "first_expert": c["deployment"]["first_expert"],
        "top_k": c["num_experts_per_tok"],
        "norm_topk": c["norm_topk_prob"],
        "routed_scale": c["moe_routed_scaling_factor"],
        "head_gate": c["gating"] == "per-head",
        "rope": c["rope_parameters"],
    }


def rope_of(d, layer_type):
    """The rotary settings of one layer type, in the form both sides read:
    theta, rotated dims, YaRN's numbers, attention factor."""
    r = d["rope"][layer_type]
    out = {"theta": r["rope_theta"],
           "dims": int(d["head_dim"] * r.get("partial_rotary_factor", 1))}
    if r.get("rope_type") == "yarn":
        out.update(factor=r["factor"],
                   original_max_position=r["original_max_position_embeddings"],
                   beta_fast=r["beta_fast"], beta_slow=r["beta_slow"],
                   attention_factor=r["attention_factor"])
    return out


def param_shapes(cfg) -> list:
    """[(node, leaf, shape, fan_in or None)] in the order the weights are
    drawn; fan_in None marks a gain, which starts at 1."""
    d = dims(cfg)
    c, hd, kv = d["hidden"], d["head_dim"], d["kv_heads"]
    out = [("embed", "W", (d["vocab"], c), 1)]
    for i in range(d["layers"]):
        h = d["heads"][i]
        b = f"b{i}"
        out += [(f"{b}.norm1", "gamma", (c,), None),
                (f"{b}.attn", "Wq", (c, h * hd), c),
                (f"{b}.attn", "Wk", (c, kv * hd), c),
                (f"{b}.attn", "Wv", (c, kv * hd), c),
                (f"{b}.attn", "Wo", (h * hd, c), h * hd)]
        if d["head_gate"]:
            out.append((f"{b}.attn", "Wgate", (c, h), c))
        out.append((f"{b}.norm2", "gamma", (c,), None))
        if d["mlp_types"][i] == "dense":
            w = d["dense_width"]
            out += [(f"{b}.mlp", "Wg", (c, w), c), (f"{b}.mlp", "Wu", (c, w), c),
                    (f"{b}.mlp", "Wd", (w, c), w)]
        else:
            e, w, s = d["experts_held"], d["expert_width"], d["shared_width"]
            out += [(f"{b}.mlp", "Wr", (c, d["experts"]), c),
                    (f"{b}.mlp", "Eg", (e, c, w), c),
                    (f"{b}.mlp", "Eu", (e, c, w), c),
                    (f"{b}.mlp", "Ed", (e, w, c), w),
                    (f"{b}.mlp", "Sg", (c, s), c), (f"{b}.mlp", "Su", (c, s), c),
                    (f"{b}.mlp", "Sd", (s, c), s)]
    out += [("final_norm", "gamma", (c,), None), ("head", "W", (c, d["vocab"]), c)]
    return out


def init_params(cfg, seed: int):
    """{node: {leaf: float32 array}} from --seed in one jitted call: normal
    with std 1/sqrt(fan_in) (the embedding 1), gains 1."""
    shapes = param_shapes(cfg)

    def build(key):
        out = {}
        for i, (node, leaf, shape, fan) in enumerate(shapes):
            if fan is None:
                v = jnp.ones(shape, jnp.float32)
            else:
                v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) / math.sqrt(fan)
            out.setdefault(node, {})[leaf] = v
        return out

    return jax.jit(build)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


# ------------------------------------------------------------------ layers

def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def inv_freq(rope):
    """Rotary frequencies; with ``factor`` YaRN's (arXiv:2309.00071)."""
    n = rope["dims"]
    i = jnp.arange(0, n, 2, dtype=jnp.float32)
    freq = rope["theta"] ** (-i / n)
    if not rope.get("factor"):
        return freq

    def correction(turns):
        return n * math.log(rope["original_max_position"]
                            / (turns * 2 * math.pi)) \
            / (2 * math.log(rope["theta"]))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), n - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(n // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return freq / rope["factor"] * ramp + freq * (1 - ramp)


def rotary(x, rope):
    """x: (T, H, D). The first ``dims`` dims rotate in halves."""
    t, n = x.shape[0], rope["dims"]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq(rope)[None, :]
    f = rope.get("attention_factor") or 1.0
    cos, sin = (jnp.cos(ang) * f)[:, None, :], (jnp.sin(ang) * f)[:, None, :]
    x1, x2 = x[..., :n // 2], x[..., n // 2:n]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., n:]], axis=-1)


def attention(x, p, *, heads, kv_heads, head_dim, window, rope, head_gate,
              chunk=ATTN_CHUNK):
    """One sequence: x (T, C) -> (T, C)."""
    t = x.shape[0]
    q = rotary((x @ p["Wq"]).reshape(t, heads, head_dim), rope)
    k = rotary((x @ p["Wk"]).reshape(t, kv_heads, head_dim), rope)
    v = (x @ p["Wv"]).reshape(t, kv_heads, head_dim)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    chunk = min(chunk, t)

    @jax.checkpoint
    def rows(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
        s = jnp.einsum("qhd,khd->hqk", qc, k) / math.sqrt(head_dim)
        i = start + jnp.arange(chunk)[:, None]
        j = jnp.arange(t)[None, :]
        ok = i >= j
        if window is not None:
            ok = ok & (i - j < window)
        a = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v)

    o = jax.lax.map(rows, jnp.arange(0, t, chunk)).reshape(t, heads, head_dim)
    if head_gate:
        o = o * jax.nn.sigmoid(x @ p["Wgate"])[..., None]
    return o.reshape(t, heads * head_dim) @ p["Wo"]


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def experts(x, p, *, top_k, held, routed_scale, norm_topk, drop_expert=None):
    """x (T, C). ``held`` = (count, first): the experts whose part is
    computed. Returns (y, pairs that fell on held experts)."""
    count, first = held
    s = jax.nn.softmax(x @ p["Wr"], axis=-1)
    val, idx = jax.lax.top_k(s, top_k)
    if norm_topk:
        val = val / val.sum(axis=-1, keepdims=True)
    val = val * routed_scale
    y = jnp.zeros_like(x)
    pairs = 0
    for e in range(count):
        on = idx == first + e                          # (T, k)
        w = jnp.where(on, val, 0.0).sum(axis=-1)       # weight or 0
        pairs = pairs + on.sum()
        if e == drop_expert:
            continue
        y = y + w[:, None] * swiglu(x, p["Eg"][e], p["Eu"][e], p["Ed"][e])
    if "Sg" in p:
        y = y + swiglu(x, p["Sg"], p["Su"], p["Sd"])
    return y, pairs


def token_losses(h, w, labels, chunk=LOSS_CHUNK):
    """-log softmax(h W)[label] per row, the logits in chunks of rows."""
    t = h.shape[0]
    chunk = min(chunk, t)

    @jax.checkpoint
    def rows(start):
        z = jax.lax.dynamic_slice_in_dim(h, start, chunk, 0) @ w
        lab = jax.lax.dynamic_slice_in_dim(labels, start, chunk, 0)
        return -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                                    lab[:, None], axis=1)[:, 0]

    return jax.lax.map(rows, jnp.arange(0, t, chunk)).reshape(t)


# -------------------------------------------------------------- the model

def sequence_loss(cfg, params, ids, labels, precision="float32", fault=None):
    """Mean cross entropy of one sequence: ids, labels (T,) int32. Returns
    (loss, pairs per expert layer)."""
    d = dims(cfg)
    r = ROUNDERS.get(precision, lambda a: a)
    h = r(params["embed"]["W"][ids])
    pairs = []
    for i in range(d["layers"]):
        kind = d["layer_types"][i]
        window = d["window"] if kind == "sliding_attention" else None
        if fault == "no_window":
            window = None

        def block(h, p1, pa, p2, pm, i=i, kind=kind, window=window):
            x = r(rms_norm(h, p1["gamma"], d["eps"]))
            h = r(h + r(attention(
                x, pa, heads=d["heads"][i], kv_heads=d["kv_heads"],
                head_dim=d["head_dim"], window=window, rope=rope_of(d, kind),
                head_gate=d["head_gate"])))
            x = r(rms_norm(h, p2["gamma"], d["eps"]))
            if d["mlp_types"][i] == "dense":
                return r(h + r(swiglu(x, pm["Wg"], pm["Wu"], pm["Wd"]))), 0
            y, n = experts(
                x, pm, top_k=d["top_k"],
                held=(d["experts_held"], d["first_expert"]),
                routed_scale=d["routed_scale"], norm_topk=d["norm_topk"],
                drop_expert=0 if fault == "drop_expert" else None)
            return r(h + r(y)), n

        b = f"b{i}"
        h, n = jax.checkpoint(block)(
            h, params[f"{b}.norm1"], params[f"{b}.attn"],
            params[f"{b}.norm2"], params[f"{b}.mlp"])
        if d["mlp_types"][i] != "dense":
            pairs.append(n)
    h = r(rms_norm(h, params["final_norm"]["gamma"], d["eps"]))
    return token_losses(h, params["head"]["W"], labels).mean(), pairs


def loss_fn(cfg, params, ids, labels, precision="float32", fault=None):
    """Mean token cross entropy over a batch of sequences (B, T), one
    sequence at a time. Returns (loss, pairs per expert layer)."""
    if fault == "half_batch":
        ids, labels = ids[: ids.shape[0] // 2], labels[: labels.shape[0] // 2]

    def one(args):
        return jax.checkpoint(
            lambda p, a, b: sequence_loss(cfg, p, a, b, precision, fault))(
                params, *args)

    losses, pairs = jax.lax.map(one, (ids, labels))
    return losses.mean(), [n.sum() for n in pairs]


_STEPS = {}


def make_step(cfg, precision="float32", fault=None):
    """``_make_step``, one jitted function per configuration, precision and
    fault for the life of the process (a tool that reads many seeds compiles
    each once; ``jax.clear_caches()`` still frees them)."""
    key = (cfg["name"], bool(cfg.get("rehearsed")), precision, fault)
    if key not in _STEPS:
        _STEPS[key] = _make_step(cfg, precision, fault)
    return _STEPS[key]


def _make_step(cfg, precision="float32", fault=None):
    """One step, jitted: (params, mu, nu, ids, labels, t) -> (params, mu,
    nu, loss, pairs). Adam as the configuration states it, with bias
    correction: mu = b1 mu + (1 - b1) g; nu = b2 nu + (1 - b2) g^2;
    w -= lr (mu / (1 - b1^t)) / (sqrt(nu / (1 - b2^t)) + eps)."""
    if fault not in FAULTS:
        raise ValueError(fault)
    u = cfg["updater"]
    lr, b1, b2, eps = (u["learning_rate"], u["beta1"], u["beta2"],
                       u["epsilon"])
    tmap = jax.tree_util.tree_map

    def step(params, mu, nu, ids, labels, t):
        (loss, pairs), g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, labels, precision, fault),
            has_aux=True)(params)
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = tmap(lambda p, m, v: p - lr * (m / c1)
                      / (jnp.sqrt(v / c2) + eps), params, mu, nu)
        return params, mu, nu, loss, pairs

    return jax.jit(step, donate_argnums=(0, 1, 2))


def leaf_norms(tree, leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[k][n].astype(jnp.float32)))) for k, n in leaves])


def run_steps(cfg, seed, batches, *, precision="float32", fault=None):
    """Drive ``len(batches)`` steps from ``init_params(cfg, seed)``. Returns
    per-step losses, per leaf (``param_shapes`` order) the norm of Adam's
    first moment after the first step and of the parameters' change after
    the last, and the pairs that fell on held experts per step and layer.
    The starting weights are drawn anew for the change, not kept: five
    float32 copies of the parameters do not fit beside the activations."""
    leaves = [(k, n) for k, n, _, _ in param_shapes(cfg)]
    step = make_step(cfg, precision, fault)
    tmap = jax.tree_util.tree_map
    params = init_params(cfg, seed)
    zeros = jax.jit(lambda t: tmap(jnp.zeros_like, t))
    mu, nu = zeros(params), zeros(params)
    norms = jax.jit(lambda t: leaf_norms(t, leaves))
    out = {"losses": [], "trace_norms": None, "step_seconds": [], "pairs": [],
           "state_delta_norms": []}
    with jax.default_matmul_precision("highest"):
        for i, (ids, labels) in enumerate(batches):
            t = time.perf_counter()
            params, mu, nu, loss, pairs = step(
                params, mu, nu, jnp.asarray(ids, jnp.int32),
                jnp.asarray(labels, jnp.int32),
                jnp.asarray(i + 1, jnp.float32))
            out["losses"].append(float(loss))
            out["pairs"].append([int(n) for n in pairs])
            out["step_seconds"].append(time.perf_counter() - t)
            if i == 0:
                out["trace_norms"] = jax.device_get(norms(mu))
    mu = nu = None
    delta = jax.jit(lambda a, b: leaf_norms(
        tmap(jnp.subtract, a, b), leaves))
    out["delta_norms"] = jax.device_get(delta(params, init_params(cfg, seed)))
    return out
