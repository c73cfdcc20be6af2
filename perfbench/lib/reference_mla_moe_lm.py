"""The plain reference of a latent-attention decoder on the plain residual
path with softmax-routed experts and DeepSeek's sequence-wise balance loss:
forward pass, loss (next token + every expert layer's balance term),
gradient and Adam's step in straightforward float32 ``jax.numpy`` at the
highest matmul precision. It imports nothing of the program and is given
nothing the program made: weights come from ``init_params`` here, batches
from the job's own pool. RMSNorm, rotary, the chunked token loss and the
leaf norms are ``reference_lm``'s; the fault flags are
``reference_mla_lm``'s.

Every layer follows the published description of the configuration
(``perfbench/configs/deepseek-v2-lite.json``; departures are that file's
``assumed`` list, no more). One sequence of T positions, h the residual:

- layer i: h <- h + MLA(RMSNorm(h)); h <- h + MLP(RMSNorm(h));
- attention (MLA, DeepSeek-V2, no query bottleneck): [q_nope | q_rope] =
  x W_q per head; [c_kv | k_rope] = x W_kva, c_kv = RMSNorm(c_kv),
  [k_nope | v] = c_kv W_kvb per head; q_rope and the one k_rope rotate
  (YaRN); causal softmax((q.k) mscale^2 / sqrt(nope + rope)) v; out =
  concat W_o;
- the MLP: SwiGLU for the first ``first_k_dense_replace`` layers, else
  softmax scores s = softmax(x W_r) over all experts, the top k chosen and
  weighted by their scores (renormalised only where ``norm_topk_prob``)
  times the routed scale, each held expert SwiGLU, plus the shared SwiGLU
  expert; a loop over the held experts with a mask: nothing sorted,
  nothing dropped. With ``seq_aux`` the layer's balance term alpha sum_i
  f_i P_i, P_i = mean_t s_{i,t}, f_i = E / (k T) #{t : i in top-k(t)} (a
  count, no gradient), joins the loss;
- the next-token loss on RMSNorm(h) W_head, chunked over rows.

Memory: one ``jax.checkpoint`` a layer and a sequence; attention four heads
at a time and in blocks of query rows that score only the keys of their
part of the triangle, the MLPs and the loss in blocks of rows, so that a
float32 step at 32,768 positions fits the chip once the program's state is
freed.

``precision="fp8"`` is the control of ``correct``: every tensor between
layers rounded to float8 as ``perfbench/lib/reference.py`` rounds it.
``fault`` plants one: ``half_batch`` (the second half of each sequence's
positions left out of the loss), ``no_balance`` (the balance terms left
out: of the loss, and the value read as 0), ``norm_topk`` (the top k's
weights renormalised to sum to 1), ``no_score_scale`` (YaRN's mscale^2
left out of the scores' scale).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import reference_lm as base
from perfbench.lib.reference import ROUNDERS
from perfbench.lib.reference_mla_lm import _pick

FAULTS = (None, "half_batch", "no_balance", "norm_topk", "no_score_scale")
ATTN_CHUNK = 256          # query rows scored at once
ATTN_GROUPS = 8           # parts of the triangle, each with its own keys
MLP_CHUNK = 4096          # rows through an MLP at once
HEAD_GROUP = 4            # attention heads at once

rms_norm, leaf_norms, token_losses, swiglu = (
    base.rms_norm, base.leaf_norms, base.token_losses, base.swiglu)


def _is(fault, name):
    """Whether the planted ``fault`` is ``name``: a Python bool for a name
    or None, a traced one for a fault's index in ``FAULTS`` (one program
    serves the sound run and every fault)."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name)


# ------------------------------------------------------------------- sizes

def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def dims(cfg) -> dict:
    """The sizes of the model as it is run, from the configuration file's
    own keys (a rehearsal reads its ``rehearsal.model`` table over them).
    The counts of experts are what this chip holds; ``experts`` is the
    router's published width."""
    c = dict(cfg)
    if cfg.get("rehearsed"):
        c.update(cfg["rehearsal"]["model"])
    r = c["rope_scaling"]
    f = r["factor"]
    rope = {"theta": c["rope_theta"], "dims": c["qk_rope_head_dim"],
            "factor": f, "original_max_position":
                r["original_max_position_embeddings"],
            "beta_fast": r["beta_fast"], "beta_slow": r["beta_slow"],
            "attention_factor": (_mscale(f, r["mscale"])
                                 / _mscale(f, r["mscale_all_dim"]))}
    return {
        "layers": c["num_hidden_layers"], "dense": c["first_k_dense_replace"],
        "hidden": c["hidden_size"], "eps": c["rms_norm_eps"],
        "vocab": c["vocab_size"], "heads": c["num_attention_heads"],
        "kv_rank": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
        "rope_dim": c["qk_rope_head_dim"], "v": c["v_head_dim"],
        "rope": rope, "score_scale": _mscale(f, r["mscale_all_dim"]) ** 2,
        "dense_width": c["intermediate_size"],
        "experts": c["published"]["n_routed_experts"],
        "experts_held": c["n_routed_experts"],
        "first_expert": c["deployment"]["first_expert"],
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["moe_intermediate_size"] * c["n_shared_experts"],
        "norm_topk": c["norm_topk_prob"],
        "routed_scale": c["routed_scaling_factor"],
        "alpha": c["aux_loss_alpha"] if c["seq_aux"] else 0.0,
    }


def moe_layers(cfg) -> list:
    """The nodes of the expert layers in model order."""
    d = dims(cfg)
    return [f"b{i}.mlp" for i in range(d["dense"], d["layers"])]


def param_shapes(cfg) -> list:
    """[(node, leaf, shape, fan_in or None)] in the order the weights are
    drawn; fan_in None marks a gain, which starts at 1."""
    d = dims(cfg)
    c, h = d["hidden"], d["heads"]
    qk, kvr = d["nope"] + d["rope_dim"], d["kv_rank"]
    out = [("embed", "W", (d["vocab"], c), 1)]
    for i in range(d["layers"]):
        b = f"b{i}"
        a, m = f"{b}.attn", f"{b}.mlp"
        out += [(f"{b}.norm1", "gamma", (c,), None),
                (a, "Wq", (c, h * qk), c),
                (a, "Wkva", (c, kvr + d["rope_dim"]), c),
                (a, "kv_gamma", (kvr,), None),
                (a, "Wkvb", (kvr, h * (d["nope"] + d["v"])), kvr),
                (a, "Wo", (h * d["v"], c), h * d["v"]),
                (f"{b}.norm2", "gamma", (c,), None)]
        if i < d["dense"]:
            w = d["dense_width"]
            out += [(m, "Wg", (c, w), c), (m, "Wu", (c, w), c),
                    (m, "Wd", (w, c), w)]
        else:
            e, w, s = d["experts_held"], d["expert_width"], d["shared_width"]
            out += [(m, "Wr", (c, d["experts"]), c),
                    (m, "Eg", (e, c, w), c), (m, "Eu", (e, c, w), c),
                    (m, "Ed", (e, w, c), w),
                    (m, "Sg", (c, s), c), (m, "Su", (c, s), c),
                    (m, "Sd", (s, c), s)]
    out += [("final_norm", "gamma", (c,), None),
            ("head", "W", (c, d["vocab"]), c)]
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

def by_rows(fn, x, rows=MLP_CHUNK):
    """fn over blocks of ``rows`` rows of x (T, C), each rematerialised."""
    t = x.shape[0]
    rows = min(rows, t)
    return jax.lax.map(jax.checkpoint(fn), x.reshape(t // rows, rows, -1)
                       ).reshape(t, -1)


def causal_attention(q, k, v, scale, chunk=ATTN_CHUNK, groups=ATTN_GROUPS):
    """softmax(q.k scale) v over the causal triangle: q, k (T, H, D), v (T,
    H, Dv). The rows fall in ``groups`` parts, each scoring only the keys
    up to its own end, in blocks of ``chunk`` rows."""
    t = q.shape[0]
    chunk = min(chunk, t)
    groups = max(1, min(groups, t // chunk))
    part = t // groups
    out = []
    for g in range(groups):
        end = (g + 1) * part
        kg, vg = k[:end], v[:end]

        @jax.checkpoint
        def rows(start, kg=kg, vg=vg, end=end):
            qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
            s = jnp.einsum("qhd,khd->hqk", qc, kg) * scale
            ok = start + jnp.arange(chunk)[:, None] >= jnp.arange(end)[None, :]
            a = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", a, vg)

        out.append(jax.lax.map(rows, jnp.arange(g * part, end, chunk)))
    return jnp.concatenate(out).reshape(t, -1)


def mla(d, x, p, fault=None, group=HEAD_GROUP):
    """One sequence: x (T, C) -> (T, C). The heads go ``group`` at a time,
    each group rematerialised, its part of the output projection added up:
    the same sum, in a quarter of the memory at the cell's 16 heads."""
    t, c = x.shape
    h, nope, rd, kvr = d["heads"], d["nope"], d["rope_dim"], d["kv_rank"]
    vd = d["v"]
    group = min(group, h)
    g = h // group
    rot = dict(d["rope"], dims=rd)
    ckv = x @ p["Wkva"]
    latent = rms_norm(ckv[:, :kvr], p["kv_gamma"], d["eps"])
    k_rope = base.rotary(
        jnp.broadcast_to(ckv[:, None, kvr:], (t, group, rd)), rot)
    scale = _pick(_is(fault, "no_score_scale"), 1.0, d["score_scale"]) \
        / math.sqrt(nope + rd)

    @jax.checkpoint
    def heads(out, w):
        wq, wkvb, wo = w
        q = (x @ wq).reshape(t, group, nope + rd)
        q = jnp.concatenate([q[..., :nope], base.rotary(q[..., nope:], rot)],
                            -1)
        kv = (latent @ wkvb).reshape(t, group, nope + vd)
        k = jnp.concatenate([kv[..., :nope], k_rope], -1)
        return out + causal_attention(q, k, kv[..., nope:], scale) @ wo, None

    split = lambda w, rows, per: w.reshape(rows, g, group * per) \
        .transpose(1, 0, 2)
    out, _ = jax.lax.scan(heads, jnp.zeros((t, c), x.dtype), (
        split(p["Wq"], c, nope + rd), split(p["Wkvb"], kvr, nope + vd),
        p["Wo"].reshape(g, group * vd, c)))
    return out


def balance(d, s, idx):
    """One sequence's balance term: alpha sum_i f_i P_i, s (T, E) the
    softmax scores, idx (T, k) the experts chosen."""
    t, e = s.shape
    hits = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=(0, 1))
    f = jax.lax.stop_gradient(hits * e / (d["top_k"] * t))
    return d["alpha"] * (f * s.mean(axis=0)).sum()


def experts(d, x, p, fault=None):
    """x (T, C) -> (y, pairs that fell on held experts, balance term)."""
    count, first = d["experts_held"], d["first_expert"]
    s = jax.nn.softmax(x @ p["Wr"], axis=-1)
    val, idx = jax.lax.top_k(s, d["top_k"])
    renorm = val / val.sum(axis=-1, keepdims=True)
    val = d["routed_scale"] * _pick(_is(fault, "norm_topk") != d["norm_topk"],
                                    renorm, val)

    def expert(y, e):
        wg, wu, wd, i = e
        w = jnp.where(idx == first + i, val, 0.0).sum(axis=-1)
        return y + w[:, None] * by_rows(lambda a: swiglu(a, wg, wu, wd), x), \
            None

    shared = by_rows(lambda a: swiglu(a, p["Sg"], p["Su"], p["Sd"]), x)
    y, _ = jax.lax.scan(jax.checkpoint(expert), shared,
                        (p["Eg"], p["Eu"], p["Ed"], jnp.arange(count)))
    pairs = ((idx >= first) & (idx < first + count)).sum()
    bal = _pick(_is(fault, "no_balance"), 0.0, balance(d, s, idx))
    return y, pairs, bal


def layer(d, x, params, b, dense, r, fault=None):
    """Layer ``b`` on the residual x (T, C): (x, pairs or None, balance
    term or None)."""
    pn = params[f"{b}.norm1"]["gamma"], params[f"{b}.norm2"]["gamma"]
    x = r(x + r(mla(d, r(rms_norm(x, pn[0], d["eps"])),
                    params[f"{b}.attn"], fault)))
    h = r(rms_norm(x, pn[1], d["eps"]))
    p = params[f"{b}.mlp"]
    if dense:
        return r(x + r(by_rows(lambda a: swiglu(a, p["Wg"], p["Wu"], p["Wd"]),
                               h))), None, None
    y, pairs, bal = experts(d, h, p, fault)
    return r(x + r(y)), pairs, bal


# -------------------------------------------------------------- the model

def sequence_loss(cfg, params, ids, labels, precision="float32", fault=None):
    """The loss of one sequence: ids, labels (T,) int32. Returns
    (next-token mean + the balance terms (not under ``no_balance``),
    {pairs: per expert layer, balance: per expert layer})."""
    d = dims(cfg)
    r = ROUNDERS.get(precision, lambda a: a)
    x = r(params["embed"]["W"][ids])
    seen = {"pairs": [], "balance": []}
    for i in range(d["layers"]):
        b = f"b{i}"
        x, pairs, bal = jax.checkpoint(
            lambda x, p, b=b, dense=i < d["dense"]: layer(
                d, x, p, b, dense, r, fault))(
                    x, {k: v for k, v in params.items()
                        if k.startswith(b + ".")})
        if pairs is not None:
            seen["pairs"].append(pairs)
            seen["balance"].append(bal)
    h = r(rms_norm(x, params["final_norm"]["gamma"], d["eps"]))
    t = labels.shape[0]
    keep = _pick(_is(fault, "half_batch"),
                 (jnp.arange(t) < t // 2).astype(jnp.float32), jnp.ones((t,)))
    loss = (token_losses(h, params["head"]["W"], labels) * keep).sum() \
        / keep.sum()
    return loss + sum(seen["balance"]), seen


def loss_fn(cfg, params, ids, labels, precision="float32", fault=None):
    """The loss over a batch of sequences (B, T), one sequence at a time.
    Returns (loss, {pairs summed, balance averaged over the sequences, per
    expert layer})."""
    def one(args):
        return jax.checkpoint(
            lambda p, a, b: sequence_loss(cfg, p, a, b, precision, fault))(
                params, *args)

    losses, seen = jax.lax.map(one, (ids, labels))
    return losses.mean(), {"pairs": [v.sum() for v in seen["pairs"]],
                           "balance": [v.mean() for v in seen["balance"]]}


_STEPS = {}


def make_step(cfg, precision="float32"):
    """The jitted gradient and update of one configuration and precision,
    for the life of the process (``jax.clear_caches()`` still frees them).
    The fault is an argument of the programs, not a constant of them: the
    sound run and every fault share one compile."""
    key = (cfg["name"], bool(cfg.get("rehearsed")), precision)
    if key not in _STEPS:
        _STEPS[key] = _make_step(cfg, precision)
    return _STEPS[key]


def _make_step(cfg, precision="float32"):
    """(grads: (params, ids, labels, fault) -> (loss, seen, g), update:
    (params, mu, nu, g, t) -> (params, mu, nu)), Adam as the configuration
    states it, with bias correction; ``fault`` the planted fault's index in
    ``FAULTS`` (0: none). Two programs, so that the gradient is taken with
    Adam's moments off the chip (``run_steps``)."""
    u = cfg["updater"]
    lr, b1, b2, eps = (u["learning_rate"], u["beta1"], u["beta2"],
                       u["epsilon"])
    tmap = jax.tree_util.tree_map

    def grads(params, ids, labels, fault):
        (loss, seen), g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, labels, precision, fault),
            has_aux=True)(params)
        return loss, seen, g

    def update(params, mu, nu, g, t):
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = tmap(lambda p, m, v: p - lr * (m / c1)
                      / (jnp.sqrt(v / c2) + eps), params, mu, nu)
        return params, mu, nu

    return (jax.jit(grads),
            jax.jit(update, donate_argnums=(0, 1, 2)))


def run_steps(cfg, seed, batches, *, precision="float32", fault=None):
    """Drive ``len(batches)`` steps from ``init_params(cfg, seed)``. Returns
    per step the loss, the pairs that fell on held experts per expert layer
    (``pairs``) and each expert layer's balance term (``balance``); per
    leaf (``param_shapes`` order) the norm of Adam's first moment after the
    first step and of the parameters' change after the last. Adam's moments
    wait on the host while the gradient is taken."""
    if fault not in FAULTS:
        raise ValueError(fault)
    leaves = [(k, n) for k, n, _, _ in param_shapes(cfg)]
    grads, update = make_step(cfg, precision)
    code = jnp.asarray(FAULTS.index(fault), jnp.int32)
    tmap = jax.tree_util.tree_map
    params = init_params(cfg, seed)
    mu = nu = tmap(lambda a: np.zeros(a.shape, np.float32), params)
    norms = jax.jit(lambda t: leaf_norms(t, leaves))
    out = {"losses": [], "trace_norms": None, "step_seconds": [], "pairs": [],
           "balance": [], "state_delta_norms": []}
    with jax.default_matmul_precision("highest"):
        for i, (ids, labels) in enumerate(batches):
            t = time.perf_counter()
            loss, seen, g = grads(params, jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(labels, jnp.int32), code)
            params, mu, nu = update(params, jax.device_put(mu),
                                    jax.device_put(nu), g,
                                    jnp.asarray(i + 1, jnp.float32))
            g = None
            if i == 0:
                out["trace_norms"] = jax.device_get(norms(mu))
            mu, nu = jax.device_get((mu, nu))
            out["losses"].append(float(loss))
            out["pairs"].append([int(n) for n in seen["pairs"]])
            out["balance"].append([float(v) for v in seen["balance"]])
            out["step_seconds"].append(time.perf_counter() - t)
    mu = nu = None
    delta = jax.jit(lambda a, b: leaf_norms(
        tmap(jnp.subtract, a, b), leaves))
    out["delta_norms"] = jax.device_get(delta(params, init_params(cfg, seed)))
    return out
