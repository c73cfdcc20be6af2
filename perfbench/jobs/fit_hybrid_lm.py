"""Job kind ``fit_hybrid_lm``: ``fit_lm``'s flow (a pool of token batches
cycled through an iterator into ONE ``net.fit(iterator)`` call for a timed
window; an example is a sequence) for a hybrid decoder of one mixer a layer:
Mamba-2 mixers, latent experts, attention. The traffic file's keys are
``fit_lm``'s.

Beside what ``fit_lm`` takes from the program (the zoo class, ``fit``,
``last_pipeline_stats``, ``_compile_count``, ``params``, ``state``,
``opt_state``, the program registry's ``op_scopes``, the expert layers'
counters) this module reads the Mamba mixers' state: ``tokens_total`` and
``decay_mean``. The plain reference is
``perfbench/lib/reference_hybrid_lm.py``; ``correct`` compares what
``lib/compare.py`` compares plus ``pairs_dropped``, ``routed_pairs_gap`` and
``decay_mean_gap`` (the worst Mamba layer's mean decay against the
reference's, relative: a number that reads the mixers' state).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from perfbench.lib import compare, reference_hybrid_lm as ref
from perfbench.jobs.fit import PoolIterator
from perfbench.jobs.fit_lm import RAGGED_DOT, _first_moments, _resolve

COUNTERS = ("pairs_total", "pairs_dropped_total", "pairs", "load_max")


def make_pool(cfg, traffic, seed, batch, seq):
    """``pool_batches`` distinct (ids, labels) int32 host batches."""
    rs = np.random.default_rng(int(seed))
    vocab = ref.dims(cfg)["vocab"]
    pool = []
    for _ in range(traffic["pool_batches"]):
        doc = rs.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        pool.append((np.ascontiguousarray(doc[:, :-1]),
                     np.ascontiguousarray(doc[:, 1:])))
    return pool


def build_net(cfg):
    """The zoo model as a user builds it from the configuration's own keys:
    the counts of Mamba heads and groups and of attention heads are the
    file's (what this chip holds); ``n_routed_experts`` is the router's
    width there, and the share held is ``experts_held``."""
    prog, upd, d = cfg["program"], cfg["updater"], ref.dims(cfg)
    model_cls = _resolve(prog["class"])
    updater = _resolve(prog["updater_class"])(
        upd["learning_rate"], beta1=upd["beta1"], beta2=upd["beta2"],
        epsilon=upd["epsilon"])
    keys = dict(cfg)
    if cfg.get("rehearsed"):
        keys.update(cfg["rehearsal"]["model"])
    keys["n_routed_experts"] = d["experts"]
    try:
        return model_cls(
            keys, seed=prog["seed"], updater=updater,
            experts_held=(d["experts_held"], d["first_expert"]),
            **{k: v for k, v in prog["kwargs"].items() if v}).init()
    except KeyError as e:
        raise SystemExit(f"perfbench: the program's {prog['class']} does "
                         f"not build from this configuration's keys ({e!r}); "
                         "this cell cannot run on it")


def set_weights(cfg, net, w):
    want = {(k, n): tuple(s) for k, n, s, _ in ref.param_shapes(cfg)}
    have = {(k, n): tuple(v.shape) for k, p in net.params.items()
            for n, v in (p or {}).items()}
    if want != have:
        odd = set(want.items()) ^ set(have.items())
        raise SystemExit("perfbench: the configuration file and the program "
                         f"disagree on parameters: {sorted(map(str, odd))[:8]}")
    net.params = {k: dict(w.get(k, {})) for k in net.params}


def observe(cfg, net, seed, what):
    import jax
    leaves = [(k, n) for k, n, _, _ in ref.param_shapes(cfg)]
    norms = jax.jit(lambda t: ref.leaf_norms(t, leaves))
    if what == "trace":
        return jax.device_get(norms(_first_moments(net, leaves)))
    sub = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda u, v: u - v, a, b))
    p = {k: {n: net.params[k][n] for kk, n in leaves if kk == k}
         for k in {k for k, _ in leaves}}
    return jax.device_get(norms(sub(p, ref.init_params(cfg, seed))))


def expert_counts(net):
    """{layer: {pairs_total, pairs_dropped_total, pairs, load_max}} read
    off the expert layers' state, as Python ints."""
    import jax
    got = jax.device_get({k: {n: s[n] for n in COUNTERS}
                          for k, s in net.state.items()
                          if s and "pairs_total" in s})
    return {k: {n: int(v) for n, v in s.items()} for k, s in got.items()}


def ssm_counts(net):
    """[(layer, {tokens_total, decay_mean})] off the Mamba mixers' state,
    in the model's layer order."""
    import jax
    got = jax.device_get({k: s for k, s in net.state.items()
                          if s and "decay_mean" in s})
    order = [k for k in net.conf.topological_order if k in got]
    return [(k, {"tokens_total": (int(got[k]["tokens_total"][1]) << 32)
                 | int(got[k]["tokens_total"][0]),
                 "decay_mean": float(got[k]["decay_mean"])}) for k in order]


def check_steps(cfg, traffic, net, pool, dataset_cls, seed):
    """Set-up's first steps, through the window's own call and feed."""
    import jax
    k, n = traffic["steps_per_call"], traffic["check_steps"]
    seen = {"losses": {}, "pairs": [], "decay": []}
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
        seen["decay"].append([(name, c["decay_mean"])
                              for name, c in ssm_counts(net)])
        if done == k:
            seen["trace_norms"] = observe(cfg, net, seed, "trace")
    seen["delta_norms"] = observe(cfg, net, seed, "delta")
    seen["state_delta_norms"] = []
    seen["steps"] = done
    jax.block_until_ready(net.params)
    return seen


# XLA's own grouped product on the TPU carries the compiler's name
# (``RAGGED_DOT``, jobs/fit_lm.py) and no scope: what the expert layer's
# ``experts`` scope runs
MIXER, EXPERTS = "Mamba2Mixer", "ExpertLayer"
INNER_SCOPES = tuple((s, MIXER) for s in (
    "in_proj", "conv", "scan", "gate_norm", "out_proj")) + tuple(
    (s, EXPERTS) for s in ("route", "latent_down", "dispatch", "experts",
                           "combine", "latent_up", "shared")) + (
    ("attend", "RotaryGQAttention"),)


def device_seconds(ctx, net_caller):
    """After ``stop_trace``: device seconds of the traced window by layer
    class, by phase and under the scopes inside the mixers (a path that
    holds two of them counts under the innermost), from the device plane
    (``lib/trace.py``) joined to the step program's ``op_scopes``
    (``lib/scopes.py``). None where there is no device plane or no table."""
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
    by_kind, by_phase, by_phase_kind, inner = {}, {}, {}, {}
    total = 0.0
    for s, e, text in chips[0].ops:
        if tr.CONTAINER.match(text):
            continue
        name = tr.short_name(text)
        sec = (e - s) / 1e9
        total += sec
        op_name = table.get(name) or ""
        parts = [m.group(1) if (m := scopes._WRAPPED.match(p)) else p
                 for p in op_name.split("/")]
        phase, _, kind = scopes.classify(op_name)
        if name.startswith(RAGGED_DOT):
            phase, kind, parts = "grouped", EXPERTS, ["experts"]
        kind = kind or "-"
        by_kind[kind] = by_kind.get(kind, 0.0) + sec
        by_phase[phase] = by_phase.get(phase, 0.0) + sec
        key = f"{phase}/{kind}"
        by_phase_kind[key] = by_phase_kind.get(key, 0.0) + sec
        mine = [scope for scope, cls in INNER_SCOPES
                if kind == cls and scope in parts]
        if mine:
            last = max(mine, key=lambda sc: len(parts) - 1
                       - parts[::-1].index(sc))
            inner[last] = inner.get(last, 0.0) + sec
    return {"total_s": total, "by_kind": by_kind, "by_phase": by_phase,
            "by_phase_kind": by_phase_kind,
            "unscoped_share": by_phase.get("unscoped", 0.0) / total,
            "inner": inner}


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
    set_weights(cfg, net, ref.init_params(cfg, seed))
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
    held = ref.dims(cfg)["experts_held"]
    ssm = ssm_counts(net)
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
        "ssm_counts": dict(ssm),
    }
    if ctx["trace"] and not rehearsed:
        ds = obs["device_seconds"] = device_seconds(ctx, net._prog_caller)
        if ds:
            ms = lambda d: {k: round(1e3 * v / steps, 2) for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])}
            say(f"device ms a step, {steps} steps: total "
                f"{1e3 * ds['total_s'] / steps:.1f}; by layer class "
                f"{ms(ds['by_kind'])}; by phase {ms(ds['by_phase'])}; by "
                f"phase and class {ms(ds['by_phase_kind'])}; inside the "
                f"mixers {ms(ds['inner'])}")
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
    got = ref.run_steps(
        cfg, seed, [pool[i % len(pool)] for i in range(seen["steps"])])
    jax.clear_caches()
    obs["reference_s"] = time.perf_counter() - t
    say("reference steps took "
        + ", ".join(f"{v:.1f}" for v in got["step_seconds"]) + " s")
    obs["compared"] = compare.numbers(seen, got, ctx["limits"])
    obs["compared"] += extra_numbers(seen, got, obs, ctx["limits"])
    return obs


def extra_readings(seen, got, dropped):
    """{name: value} of the job's own numbers: pairs left uncomputed
    (``dropped``), and the largest gaps, each as a share of the
    reference's, over the checked steps and the layers: the pairs routed to
    the experts held, and the Mamba mixers' mean decay."""
    def worst(mine, theirs):
        return float(max((abs(m - r) / max(abs(r), 1e-30)
                          for sm, sr in zip(mine, theirs)
                          for m, r in zip(sm, sr)), default=0.0))

    return {
        "pairs_dropped": float(dropped),
        "routed_pairs_gap": worst([[p for _, p in s] for s in seen["pairs"]],
                                  [[max(r, 1) for r in s]
                                   for s in got["pairs"]]),
        "decay_mean_gap": worst([[v for _, v in s] for s in seen["decay"]],
                                got["decay"])}


def extra_numbers(seen, got, obs, limits):
    dropped = sum(c["pairs_dropped_total"]
                  for c in obs["expert_counts"].values())
    vals = extra_readings(seen, got, dropped)
    out = [{"name": n, "value": v, "limit": limits[n]}
           for n, v in vals.items() if n in limits]
    if not np.isfinite(obs["last_loss"]):
        out.append({"name": "last_loss_finite", "value": 1.0, "limit": 0.0})
    return out
