"""The plain reference of a decoder whose attention reads a learned
selection of keys: forward pass, loss, gradient and Adam's step in
straightforward float32 ``jax.numpy`` at the highest matmul precision. It
imports nothing of the program and is given nothing the program made:
weights come from ``init_params`` here, batches from the job's own pool.
The pieces it shares with ``reference_lm`` (RMSNorm, rotary, SwiGLU, the
chunked token loss) are that module's.

Every layer follows the published description of the configuration
(``perfbench/configs/keye-vl-2.0-30b-a3b.json``; departures are that file's
``assumed`` list, no more). With x = RMSNorm(h), T positions and
x' = stop_gradient(x):

- main heads: q = x Wq (T, H, D); k, v = x Wk, x Wv (T, Hkv, D); q and k
  through an RMSNorm over D with a gain each; rotary over all D dims in
  halves; query head h reads kv head h // (H / Hkv).
- indexer: qI = x' WqI (T, J, Di); kI = LayerNorm(x' WkI) (T, Di), one key
  head for all J; wI = (x' WwI) / sqrt(J Di); rotary over all Di dims on qI
  and kI. I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s]) for s <= t.
- selection: S_t = the ``topk`` keys s <= t of largest I[t, s]
  (``jax.lax.top_k``: a tie goes to the lower position), all of them while
  t < topk. One selection a query, shared by the heads.
- output: A_h[t] = softmax over S_t of (q_h[t] . k[s] / sqrt(D)) applied
  to v; out = concat(A) Wo.
- the indexer's loss: P[t] = stop_gradient of the mean over heads of those
  softmax weights; L_I = mean over t of KL(P[t] || softmax over S_t of
  I[t, .]). The step's loss is the token cross entropy plus the sum over
  layers of L_I; by the two stop-gradients the main parameters receive the
  cross entropy's gradient alone and the indexer's L_I's alone.
- experts: softmax over all, top-k renormalised, a loop over the held
  experts with a mask (``reference_lm.experts``' mathematics), no shared
  expert.

Memory: one ``jax.checkpoint`` a block, a sequence and an expert, attention
and index scores in chunks of query rows, the loss in chunks of rows.

``precision="fp8"`` is the control of ``correct``. ``fault`` plants one:
``half_batch`` (half of the step's tokens left out: half of the sequences,
or the later half of the one sequence), ``no_selection`` (every visible key
attended), ``recent_keys`` (the ``topk`` nearest keys instead of the top
ones), ``no_index_loss`` (L_I left out of the loss), ``drop_expert`` (the
tokens of the first held expert dropped).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from perfbench.lib import reference_lm as base
from perfbench.lib.reference import ROUNDERS

FAULTS = (None, "half_batch", "no_selection", "recent_keys", "no_index_loss",
          "drop_expert")
# query rows scored at once: (H, rows, T) float32 main scores and
# (J, rows, T) index scores live at a time, several copies of each in the
# backward pass, beside 7.4 GB of float32 parameters, gradient and Adam's
# moments (at 256 the step compiles to 16.5 GB for a 16.9 GB chip)
ATTN_CHUNK = 128

rms_norm, rotary, swiglu = base.rms_norm, base.rotary, base.swiglu
leaf_norms = base.leaf_norms


# ------------------------------------------------------------------- sizes

def dims(cfg) -> dict:
    """The sizes of the model as it is run, from the configuration file's
    own keys (a rehearsal reads its ``rehearsal.model`` table over them)."""
    c = dict(cfg)
    if cfg.get("rehearsed"):
        c.update(cfg["rehearsal"]["model"])
    n, sa = c["num_hidden_layers"], c["sa_config"]
    step, dense = c["decoder_sparse_step"], c["mlp_only_layers"]
    return {
        "layers": n, "hidden": c["hidden_size"], "head_dim": c["head_dim"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "eps": c["rms_norm_eps"],
        "vocab": c["vocab_size"], "dense_width": c["intermediate_size"],
        "mlp_types": ["dense" if i in dense or (i + 1) % step else "sparse"
                      for i in range(n)],
        "expert_width": c["moe_intermediate_size"],
        "experts": c["published"]["num_experts"],
        "experts_held": c["num_experts"],
        "first_expert": c["deployment"]["first_expert"],
        "top_k": c["num_experts_per_tok"], "norm_topk": c["norm_topk_prob"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "index_top_k": sa["topk"],
        "rope": {"theta": c["rope_theta"], "dims": c["head_dim"]},
        "index_rope": {"theta": c["rope_theta"],
                       "dims": sa["indexer_head_dim"]},
    }


def param_shapes(cfg) -> list:
    """[(node, leaf, shape, fan_in or None or 0)] in the order the weights
    are drawn; fan_in None marks a gain, which starts at 1, and 0 a shift,
    which starts at 0."""
    d = dims(cfg)
    c, hd, h, kv = d["hidden"], d["head_dim"], d["heads"], d["kv_heads"]
    j, di = d["index_heads"], d["index_dim"]
    out = [("embed", "W", (d["vocab"], c), 1)]
    for i in range(d["layers"]):
        b = f"b{i}"
        out += [(f"{b}.norm1", "gamma", (c,), None),
                (f"{b}.attn", "Wq", (c, h * hd), c),
                (f"{b}.attn", "Wk", (c, kv * hd), c),
                (f"{b}.attn", "Wv", (c, kv * hd), c),
                (f"{b}.attn", "Wo", (h * hd, c), h * hd),
                (f"{b}.attn", "q_gamma", (hd,), None),
                (f"{b}.attn", "k_gamma", (hd,), None),
                (f"{b}.attn", "WqI", (c, j * di), c),
                (f"{b}.attn", "WkI", (c, di), c),
                (f"{b}.attn", "WwI", (c, j), c),
                (f"{b}.attn", "kI_gamma", (di,), None),
                (f"{b}.attn", "kI_beta", (di,), 0),
                (f"{b}.norm2", "gamma", (c,), None)]
        if d["mlp_types"][i] == "dense":
            w = d["dense_width"]
            out += [(f"{b}.mlp", "Wg", (c, w), c), (f"{b}.mlp", "Wu", (c, w), c),
                    (f"{b}.mlp", "Wd", (w, c), w)]
        else:
            e, w = d["experts_held"], d["expert_width"]
            out += [(f"{b}.mlp", "Wr", (c, d["experts"]), c),
                    (f"{b}.mlp", "Eg", (e, c, w), c),
                    (f"{b}.mlp", "Eu", (e, c, w), c),
                    (f"{b}.mlp", "Ed", (e, w, c), w)]
    out += [("final_norm", "gamma", (c,), None), ("head", "W", (c, d["vocab"]), c)]
    return out


def init_params(cfg, seed: int):
    """{node: {leaf: float32 array}} from --seed in one jitted call: normal
    with std 1/sqrt(fan_in) (the embedding 1), gains 1, shifts 0."""
    shapes = param_shapes(cfg)

    def build(key):
        out = {}
        for i, (node, leaf, shape, fan) in enumerate(shapes):
            if fan is None:
                v = jnp.ones(shape, jnp.float32)
            elif fan == 0:
                v = jnp.zeros(shape, jnp.float32)
            else:
                v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) / math.sqrt(fan)
            out.setdefault(node, {})[leaf] = v
        return out

    return jax.jit(build)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


# ------------------------------------------------------------------ layers

def layer_norm(x, gamma, beta, eps):
    m = x.mean(axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * gamma + beta


def selected_attention(x, p, d, fault=None, chunk=None):
    """One sequence: x (T, C) -> (out (T, C), L_I, keys selected)."""
    t = x.shape[0]
    h, kv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    j, di, top = d["index_heads"], d["index_dim"], d["index_top_k"]
    q = rotary(rms_norm((x @ p["Wq"]).reshape(t, h, hd), p["q_gamma"],
                        d["eps"]), d["rope"])
    k = rotary(rms_norm((x @ p["Wk"]).reshape(t, kv, hd), p["k_gamma"],
                        d["eps"]), d["rope"])
    v = (x @ p["Wv"]).reshape(t, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    xs = jax.lax.stop_gradient(x)
    qi = rotary((xs @ p["WqI"]).reshape(t, j, di), d["index_rope"])
    ki = rotary(layer_norm(xs @ p["WkI"], p["kI_gamma"], p["kI_beta"],
                           d["eps"])[:, None, :], d["index_rope"])[:, 0]
    wi = (xs @ p["WwI"]) / math.sqrt(j * di)
    chunk = min(chunk or ATTN_CHUNK, t)

    @jax.checkpoint
    def rows(start):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk, 0)
        tpos = start + jnp.arange(chunk)[:, None]
        spos = jnp.arange(t)[None, :]
        vis = spos <= tpos
        score = (jax.nn.relu(jnp.einsum("qjd,kd->jqk", sl(qi), ki))
                 * sl(wi).T[:, :, None]).sum(axis=0)           # (chunk, T)
        if t <= top or fault == "no_selection":
            sel = vis
        elif fault == "recent_keys":
            sel = vis & (tpos - spos < top)
        else:
            _, idx = jax.lax.top_k(jnp.where(vis, score, -jnp.inf), top)
            sel = jnp.zeros((chunk, t), bool).at[
                jnp.arange(chunk)[:, None], idx].set(True) & vis
        s = jnp.einsum("qhd,khd->hqk", sl(q), k) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(sel[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v)
        pm = jax.lax.stop_gradient(a.mean(axis=0))
        logq = jax.nn.log_softmax(jnp.where(sel, score, -jnp.inf), axis=-1)
        kl = jnp.where(sel, jax.scipy.special.xlogy(pm, pm)
                       - pm * jnp.where(sel, logq, 0.0), 0.0).sum()
        return o, kl, sel.sum()

    o, kl, n = jax.lax.map(rows, jnp.arange(0, t, chunk))
    return o.reshape(t, h * hd) @ p["Wo"], kl.sum() / t, n.sum()


def experts(x, p, d, drop_expert=None):
    """``reference_lm.experts`` without a shared expert and with one
    ``jax.checkpoint`` an expert, so that the backward pass holds one
    expert's (T, width) products at a time and not all sixteen's. x (T, C).
    Returns (y, pairs that fell on held experts)."""
    s = jax.nn.softmax(x @ p["Wr"], axis=-1)
    val, idx = jax.lax.top_k(s, d["top_k"])
    if d["norm_topk"]:
        val = val / val.sum(axis=-1, keepdims=True)
    y, pairs = jnp.zeros_like(x), 0
    for e in range(d["experts_held"]):
        on = idx == d["first_expert"] + e                  # (T, k)
        pairs = pairs + on.sum()
        if e == drop_expert:
            continue
        y = y + jax.checkpoint(
            lambda x, w, eg, eu, ed: w[:, None] * swiglu(x, eg, eu, ed))(
                x, jnp.where(on, val, 0.0).sum(axis=-1), p["Eg"][e],
                p["Eu"][e], p["Ed"][e])
    return y, pairs


# -------------------------------------------------------------- the model

def sequence_loss(cfg, params, ids, labels, precision="float32", fault=None):
    """One sequence: ids, labels (T,) int32. Returns (cross entropy plus
    the layers' L_I, {pairs, index_loss, keys: one entry a layer})."""
    d = dims(cfg)
    r = ROUNDERS.get(precision, lambda a: a)
    h = r(params["embed"]["W"][ids])
    seen = {"pairs": [], "index_loss": [], "keys": []}
    extra = 0.0
    for i in range(d["layers"]):
        def block(h, p1, pa, p2, pm, i=i):
            x = r(rms_norm(h, p1["gamma"], d["eps"]))
            a, li, keys = selected_attention(x, pa, d, fault)
            h = r(h + r(a))
            x = r(rms_norm(h, p2["gamma"], d["eps"]))
            if d["mlp_types"][i] == "dense":
                return r(h + r(swiglu(x, pm["Wg"], pm["Wu"], pm["Wd"]))), \
                    li, keys, 0
            y, n = experts(x, pm, d, 0 if fault == "drop_expert" else None)
            return r(h + r(y)), li, keys, n

        b = f"b{i}"
        h, li, keys, n = jax.checkpoint(block)(
            h, params[f"{b}.norm1"], params[f"{b}.attn"],
            params[f"{b}.norm2"], params[f"{b}.mlp"])
        seen["index_loss"].append(li)
        seen["keys"].append(keys)
        if d["mlp_types"][i] != "dense":
            seen["pairs"].append(n)
        if fault != "no_index_loss":
            extra = extra + li
    h = r(rms_norm(h, params["final_norm"]["gamma"], d["eps"]))
    ce = base.token_losses(h, params["head"]["W"], labels).mean()
    return ce + extra, seen


def loss_fn(cfg, params, ids, labels, precision="float32", fault=None):
    """The step's loss over a batch of sequences (B, T), one sequence at a
    time: the mean of the sequences' losses. Returns (loss, {pairs, keys:
    summed over the sequences, index_loss: their mean, per layer})."""
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
    return losses.mean(), {
        "pairs": [n.sum() for n in seen["pairs"]],
        "keys": [n.sum() for n in seen["keys"]],
        "index_loss": [v.mean() for v in seen["index_loss"]]}


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
    per step the loss, L_I per layer (``index_loss``), the pairs that fell
    on held experts (``pairs``) and the keys selected (``keys``) per layer;
    per leaf (``param_shapes`` order) the norm of Adam's first moment after
    the first step and of the parameters' change after the last."""
    leaves = [(k, n) for k, n, _, _ in param_shapes(cfg)]
    step = make_step(cfg, precision, fault)
    tmap = jax.tree_util.tree_map
    params = init_params(cfg, seed)
    zeros = jax.jit(lambda t: tmap(jnp.zeros_like, t))
    mu, nu = zeros(params), zeros(params)
    norms = jax.jit(lambda t: leaf_norms(t, leaves))
    out = {"losses": [], "trace_norms": None, "step_seconds": [], "pairs": [],
           "keys": [], "index_loss": [], "state_delta_norms": []}
    with jax.default_matmul_precision("highest"):
        for i, (ids, labels) in enumerate(batches):
            t = time.perf_counter()
            params, mu, nu, loss, seen = step(
                params, mu, nu, jnp.asarray(ids, jnp.int32),
                jnp.asarray(labels, jnp.int32),
                jnp.asarray(i + 1, jnp.float32))
            out["losses"].append(float(loss))
            out["pairs"].append([int(n) for n in seen["pairs"]])
            out["keys"].append([int(n) for n in seen["keys"]])
            out["index_loss"].append([float(v) for v in seen["index_loss"]])
            out["step_seconds"].append(time.perf_counter() - t)
            if i == 0:
                out["trace_norms"] = jax.device_get(norms(mu))
    mu = nu = None
    delta = jax.jit(lambda a, b: leaf_norms(
        tmap(jnp.subtract, a, b), leaves))
    out["delta_norms"] = jax.device_get(delta(params, init_params(cfg, seed)))
    return out
