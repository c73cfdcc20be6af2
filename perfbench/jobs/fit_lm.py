"""Job kind ``fit_lm``: a pool of token batches cycled through an iterator
into ONE ``net.fit(iterator)`` call for a timed window, as a user trains a
language model. An example is a sequence.

The traffic file gives ``batch`` (sequences a step), ``seq`` (tokens a
sequence), ``pool_batches``, ``steps_per_call`` (what the program's own
chunk rule gives at this batch; the job checks that each call took so many
steps and changes nothing of the program to make it so), ``check_steps``,
``warmup_calls``, ``trace_seconds`` and the rehearsal's ``rehearsal_batch``
and ``rehearsal_seq``. Ids are uniform over the configuration's vocabulary
slice from ``--seed``; labels are the ids shifted by one; one document a
sequence.

From the program this module takes what a user calls (the zoo class,
``fit``) and reads ``last_pipeline_stats``, ``_compile_count``, ``params``,
``state``, ``opt_state``, the program registry's ``op_scopes`` and the
``dl4jtpu_moe_*`` counters; it sets ``params`` to the benchmark's weights.
The plain reference is ``perfbench/lib/reference_lm.py``.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from perfbench.lib import compare, reference_lm
from perfbench.jobs.fit import PoolIterator


def make_pool(cfg, traffic, seed, batch, seq):
    """``pool_batches`` distinct (ids, labels) int32 host batches."""
    rs = np.random.default_rng(int(seed))
    vocab = reference_lm.dims(cfg)["vocab"]
    pool = []
    for _ in range(traffic["pool_batches"]):
        doc = rs.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        pool.append((np.ascontiguousarray(doc[:, :-1]),
                     np.ascontiguousarray(doc[:, 1:])))
    return pool


def _resolve(path):
    mod, _, name = path.partition(":")
    try:
        return getattr(importlib.import_module(mod), name)
    except (ImportError, AttributeError) as e:
        raise SystemExit(f"perfbench: the program has no {path} ({e}); "
                         "this cell cannot run on it")


def build_net(cfg):
    """The zoo model as a user builds it from the configuration's own
    keys: ``num_experts`` is the router's width there, and the share held
    is ``experts_held``."""
    prog, upd, d = cfg["program"], cfg["updater"], reference_lm.dims(cfg)
    model_cls = _resolve(prog["class"])
    updater = _resolve(prog["updater_class"])(
        upd["learning_rate"], beta1=upd["beta1"], beta2=upd["beta2"],
        epsilon=upd["epsilon"])
    keys = dict(cfg)
    if cfg.get("rehearsed"):
        keys.update(cfg["rehearsal"]["model"])
    keys["num_experts"] = d["experts"]
    return model_cls(keys, seed=prog["seed"], updater=updater,
                     experts_held=(d["experts_held"], d["first_expert"]),
                     **{k: v for k, v in prog["kwargs"].items() if v}).init()


def set_weights(cfg, net, w):
    want = {(k, n): tuple(s) for k, n, s, _ in reference_lm.param_shapes(cfg)}
    have = {(k, n): tuple(v.shape) for k, p in net.params.items()
            for n, v in (p or {}).items()}
    if want != have:
        odd = set(want.items()) ^ set(have.items())
        raise SystemExit("perfbench: the configuration file and the program "
                         f"disagree on parameters: {sorted(map(str, odd))[:8]}")
    net.params = {k: dict(w.get(k, {})) for k in net.params}


def _first_moments(net, leaves):
    """Adam's first moment per parameter leaf, from the optimizer's state
    (optax: ``ScaleByAdamState.mu`` holds the parameters' tree)."""
    import jax
    out = {}
    for k in {k for k, _ in leaves}:
        mus = [s.mu for s in jax.tree_util.tree_leaves(
            net.opt_state[k], is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")]
        if len(mus) != 1 or set(mus[0]) != set(net.params[k]):
            raise SystemExit(f"perfbench: optimizer state of {k!r} holds no "
                             "first moment per parameter")
        out[k] = mus[0]
    return out


def observe(cfg, net, seed, what):
    import jax
    leaves = [(k, n) for k, n, _, _ in reference_lm.param_shapes(cfg)]
    norms = jax.jit(lambda t: reference_lm.leaf_norms(t, leaves))
    if what == "trace":
        return jax.device_get(norms(_first_moments(net, leaves)))
    sub = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda u, v: u - v, a, b))
    p = {k: {n: net.params[k][n] for kk, n in leaves if kk == k}
         for k in {k for k, _ in leaves}}
    return jax.device_get(norms(sub(p, reference_lm.init_params(cfg, seed))))


def expert_counts(net):
    """{layer: {pairs_total, pairs_dropped_total, pairs, load_max}} read
    off the expert layers' state, as Python ints."""
    import jax
    got = jax.device_get({k: s for k, s in net.state.items()
                          if s and "pairs_total" in s})
    return {k: {n: int(v) for n, v in s.items()} for k, s in got.items()}


def check_steps(cfg, traffic, net, pool, dataset_cls, seed):
    """Set-up's first steps, through the window's own call and feed."""
    import jax
    k, n = traffic["steps_per_call"], traffic["check_steps"]
    seen = {"losses": {}, "pairs": []}
    done = 0
    while done < n:
        before = net.iteration
        net.fit(PoolIterator(pool, dataset_cls, start=done, count=k))
        if net.iteration - before != k:
            raise SystemExit(f"perfbench: a call of {k} batches took "
                             f"{net.iteration - before} steps")
        done += k
        seen["losses"][done] = float(net.get_score())
        seen["pairs"].append(sorted(
            (name, c["pairs"]) for name, c in expert_counts(net).items()))
        if done == k:
            seen["trace_norms"] = observe(cfg, net, seed, "trace")
    seen["delta_norms"] = observe(cfg, net, seed, "delta")
    seen["state_delta_norms"] = []
    seen["steps"] = done
    jax.block_until_ready(net.params)
    return seen


# XLA's own grouped product on the TPU: the compiler names the custom call
# and drops the program's scope from it, so the device plane knows it by
# its instruction's name alone. It is what the expert layer's ``experts``
# scope runs, forward, replay and backward.
RAGGED_DOT = "ragged-dot"
INNER_SCOPES = (("route", "ExpertLayer"), ("dispatch", "ExpertLayer"),
                ("experts", "ExpertLayer"), ("combine", "ExpertLayer"),
                ("shared", "ExpertLayer"), ("attend", "RotaryGQAttention"))


def device_seconds(ctx, net_caller):
    """After ``stop_trace``: device seconds of the traced window by layer
    class and under the two scopes the roofline shares read (``experts``
    inside the expert layer, ``attend`` inside attention), from the device
    plane (``lib/trace.py``) joined to the step program's ``op_scopes``
    (``lib/scopes.py``). Readers are handed only the ten largest
    operations, so the job does this itself. None where there is no device
    plane or no table."""
    from perfbench.lib import scopes, trace as tr
    from deeplearning4j_tpu.exec.programs import get_programs
    path = tr.find_xplane(ctx["trace"])
    if path is None:
        return None
    planes, _ = tr.read_xplane(path)
    chips = tr.chips_from_events(planes)
    recs = [e for e in get_programs().entries()
            if e["caller"] == net_caller and e["key"].startswith("train_step")]
    if not chips or not chips[0].ops or not recs:
        return None
    table = get_programs().get(net_caller, recs[-1]["key"]).get("op_scopes")
    if not table:
        return None
    by_kind, by_phase, inner = {}, {}, {}
    total = 0.0
    for s, e, text in chips[0].ops:
        if tr.CONTAINER.match(text):
            continue
        name = tr.short_name(text)
        sec = (e - s) / 1e9
        total += sec
        op_name = table.get(name) or ""
        parts = op_name.split("/")
        phase, _, kind = scopes.classify(op_name)
        if name.startswith(RAGGED_DOT):
            phase, kind, parts = "grouped", "ExpertLayer", ["experts"]
        by_kind[kind or "-"] = by_kind.get(kind or "-", 0.0) + sec
        by_phase[phase] = by_phase.get(phase, 0.0) + sec
        for scope, cls in INNER_SCOPES:
            if kind == cls and scope in parts:
                inner[scope] = inner.get(scope, 0.0) + sec
    return {"total_s": total, "by_kind": by_kind, "by_phase": by_phase,
            "unscoped_share": by_phase.get("unscoped", 0.0) / total,
            "inner": inner, "experts_s": inner.get("experts", 0.0),
            "attend_s": inner.get("attend", 0.0)}


def run(ctx):
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    say = ctx["say"]
    rehearsed = bool(cfg.get("rehearsed"))
    batch = traffic["rehearsal_batch"] if rehearsed else traffic["batch"]
    seq = traffic["rehearsal_seq"] if rehearsed else traffic["seq"]
    marks = {"imports": time.perf_counter() - ctx["t_start"]}
    t = time.perf_counter()
    devices = jax.devices()
    net = build_net(cfg)
    set_weights(cfg, net, reference_lm.init_params(cfg, seed))
    jax.block_until_ready(net.params)
    marks["init"] = time.perf_counter() - t
    t = time.perf_counter()
    pool = make_pool(cfg, traffic, seed, batch, seq)
    marks["pool"] = time.perf_counter() - t
    t = time.perf_counter()
    seen = check_steps(cfg, traffic, net, pool, DataSet, seed)
    marks["first_steps"] = time.perf_counter() - t
    t = time.perf_counter()
    k = traffic["steps_per_call"]
    for _ in range(traffic.get("warmup_calls", 1)):
        net.fit(PoolIterator(pool, DataSet, start=seen["steps"],
                             count=2 * k))
    jax.block_until_ready(net.params)
    marks["warmup"] = time.perf_counter() - t
    compiles_before = net._compile_count
    iteration_before = net.iteration
    counts_before = expert_counts(net)

    # ------------------------------------------------ the measured window
    seconds = ctx["seconds"]
    if ctx["trace"]:
        seconds = min(seconds, traffic["trace_seconds"])
        jax.profiler.start_trace(ctx["trace"],
                                 profiler_options=ctx.get("profiler_options"))
    setup_s = time.perf_counter() - ctx["t_start"]
    t0 = time.perf_counter()
    it = PoolIterator(pool, DataSet, start=seen["steps"] + 2 * k,
                      deadline=t0 + seconds, multiple=k)
    if ctx["trace"]:
        with jax.profiler.TraceAnnotation("perfbench_window"):
            net.fit(it)
            jax.block_until_ready(net.params)
    else:
        net.fit(it)
        jax.block_until_ready(net.params)
    window_s = time.perf_counter() - t0
    if ctx["trace"]:
        jax.profiler.stop_trace()
    steps = net.iteration - iteration_before
    if steps != it.served:
        raise SystemExit(f"perfbench: {it.served} batches fed, "
                         f"{steps} steps taken")
    stats = dict(net.last_pipeline_stats or {})
    counts = expert_counts(net)
    window = {name: {n: (c[n] - counts_before[name][n]) & 0xFFFFFFFF
                     for n in ("pairs_total", "pairs_dropped_total")}
              for name, c in counts.items()}
    held = reference_lm.dims(cfg)["experts_held"]
    obs = {
        "setup_s": setup_s, "setup_split": marks, "window_s": window_s,
        "steps": steps, "examples": steps * batch, "batch": batch,
        "seq": seq, "attempted": steps, "failed": 0,
        "pipeline_stats": stats,
        "programs_traced": net._compile_count - compiles_before,
        "end_to_end": {"train_examples_per_s": steps * batch / window_s},
        "last_loss": float(net.get_score()),
        "moe_pairs": sum(w["pairs_total"] for w in window.values()),
        "moe_pairs_dropped": sum(w["pairs_dropped_total"]
                                 for w in window.values()),
        "expert_load_max_over_mean": max(
            (c["load_max"] * held / max(c["pairs"], 1)
             for c in counts.values()), default=None),
        "expert_counts": counts,
    }
    if ctx["trace"] and not rehearsed:
        ds = obs["device_seconds"] = device_seconds(ctx, net._prog_caller)
        if ds:
            ms = lambda d: {k: round(1e3 * v / steps, 2) for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])}
            say(f"device ms a step, {steps} steps: total "
                f"{1e3 * ds['total_s'] / steps:.1f}; by layer class "
                f"{ms(ds['by_kind'])}; by phase {ms(ds['by_phase'])}; inside "
                f"the expert layer and attention {ms(ds['inner'])}")
    mem = [d.memory_stats() or {} for d in devices[:ctx["chips"]]]
    obs["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in mem), default=0)
    obs["memory_limit_bytes"] = max(
        (s.get("bytes_limit", 0) for s in mem), default=0)

    # ---------- close: free the program's state, then the plain reference
    net = it = None
    gc.collect()
    jax.clear_caches()
    t = time.perf_counter()
    ref = reference_lm.run_steps(
        cfg, seed, [pool[i % len(pool)] for i in range(seen["steps"])])
    jax.clear_caches()
    obs["reference_s"] = time.perf_counter() - t
    say("reference steps took "
        + ", ".join(f"{v:.1f}" for v in ref["step_seconds"]) + " s")
    obs["compared"] = compare.numbers(seen, ref, ctx["limits"])
    obs["compared"] += extra_numbers(seen, ref, obs, ctx["limits"])
    return obs


def extra_numbers(seen, ref, obs, limits):
    """Beside ``lib/compare.py``'s numbers: pairs left uncomputed over the
    checked steps and the window (limit 0), the largest gap between the
    pairs the program's router sent to the experts held and the
    reference's, as a share of the reference's, and a loss that is not
    finite."""
    dropped = sum(c["pairs_dropped_total"]
                  for c in obs["expert_counts"].values())
    gap = max((abs(p - r) / max(r, 1)
               for step_p, step_r in zip(seen["pairs"], ref["pairs"])
               for (_, p), r in zip(step_p, step_r)), default=0.0)
    vals = {"pairs_dropped": float(dropped), "routed_pairs_gap": float(gap)}
    out = [{"name": n, "value": v, "limit": limits[n]}
           for n, v in vals.items() if n in limits]
    if not np.isfinite(obs["last_loss"]):
        out.append({"name": "last_loss_finite", "value": 1.0, "limit": 0.0})
    return out
