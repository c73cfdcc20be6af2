"""Job kind ``fit``: a pool of host batches cycled through an iterator into
``net.fit(iterator)`` for a timed window, as a user trains.

The traffic file gives ``batch`` (global), ``pool_batches``,
``steps_per_call`` (how many steps one compiled call of the program takes at
this batch: the iterator ends only on a multiple of it, so the tail never
meets a new shape), ``check_steps`` and ``trace_seconds``.

From the program this module takes what a user calls (the zoo class,
``fit``) and reads ``last_pipeline_stats``, ``_compile_count``, ``params``,
``state`` and ``opt_state``; it sets ``params`` to the benchmark's weights.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from perfbench.lib import arch, compare, reference, weights


# ------------------------------------------------------------ the inputs

def make_pool(cfg, traffic, seed, batch):
    """``pool_batches`` distinct host batches from the seed: float32 NHWC
    images in [0, 1) and one-hot labels. Every row differs."""
    rs = np.random.default_rng(int(seed))
    shape = (batch, cfg["image"], cfg["image"], cfg["channels"])
    eye = np.eye(cfg["num_classes"], dtype=np.float32)
    pool = []
    for _ in range(traffic["pool_batches"]):
        x = rs.random(shape, dtype=np.float32)
        y = eye[rs.integers(0, cfg["num_classes"], batch)]
        pool.append((x, y))
    return pool


class PoolIterator:
    """Cycles the pool from ``start``; ends after ``count`` batches, or at
    the first multiple of ``multiple`` batches past ``deadline``."""

    def __init__(self, pool, dataset_cls, *, start=0, count=None,
                 deadline=None, multiple=1):
        self.items = [dataset_cls(x, y) for x, y in pool]
        self.start, self.count = start, count
        self.deadline, self.multiple = deadline, multiple
        self.served = 0

    def reset(self):
        pass                      # one pass: the window is one epoch

    def __iter__(self):
        return self

    def __next__(self):
        if self.count is not None and self.served >= self.count:
            raise StopIteration
        if (self.deadline is not None and self.served % self.multiple == 0
                and self.served > 0
                and time.perf_counter() >= self.deadline):
            raise StopIteration
        item = self.items[(self.start + self.served) % len(self.items)]
        self.served += 1
        return item


# ----------------------------------------------------------- the program

def _resolve(path):
    mod, _, name = path.partition(":")
    return getattr(importlib.import_module(mod), name)


def build_net(cfg):
    """The zoo model as a user builds it, initialised by ``init()``. Its
    seed is the configuration file's, not --seed: the program bakes it into
    the compiled step as the dropout key, and the weights are the
    benchmark's."""
    prog = cfg["program"]
    upd = cfg["updater"]
    updater = _resolve(prog["updater_class"])(upd["learning_rate"],
                                              momentum=upd["momentum"])
    model = _resolve(prog["class"])(
        num_classes=cfg["num_classes"],
        input_shape=(cfg["image"], cfg["image"], cfg["channels"]),
        seed=prog["seed"], updater=updater, **prog["kwargs"])
    net = model.init()
    layers = (net.layers if prog["container"] == "list" else
              [n.layer for n in net.conf.nodes.values() if n.kind == "layer"])
    # F1 (PERF.md, Open questions): the zoo's ResNet50 leaves its
    # convolutions' activation to the builder's default. This hook sets the
    # published one on the built network, so the cell is not the zoo's net
    # as shipped; it goes, here and in the configuration file, in the first
    # benchmark PR after zoo/resnet.py is repaired.
    for o in prog.get("layer_overrides", ()):
        for layer in layers:
            if type(layer).__name__ == o["layer_class"]:
                for attr, value in o["set"].items():
                    setattr(layer, attr, value)
    return net


def _container_keys(cfg, tree):
    return list(range(len(tree))) if cfg["program"]["container"] == "list" \
        else list(tree.keys())


def set_weights(cfg, net, w):
    """Put the benchmark's weights in the place of the program's own,
    leaf for leaf; a leaf that has no partner is an error."""
    want = {(k, n): s for k, n, s, _ in arch.param_leaves(cfg)}
    have = {}
    for k in _container_keys(cfg, net.params):
        for n, v in (net.params[k] or {}).items():
            have[(k, n)] = tuple(v.shape)
    if want != have:
        odd = set(want.items()) ^ set(have.items())
        raise SystemExit(f"perfbench: the configuration file and the "
                         f"program disagree on parameters: {sorted(map(str, odd))[:8]}")
    if cfg["program"]["container"] == "list":
        net.params = [dict(w.get(k, {})) for k in range(len(net.params))]
    else:
        net.params = {k: dict(w.get(k, {})) for k in net.params}


def _as_tree(container, leaves):
    """{key: {leaf: array}} view of a program container for ``leaves``."""
    out = {}
    for k, n in leaves:
        out.setdefault(k, {})[n] = container[k][n]
    return out


def _trace_tree(net, leaves):
    """The momentum trace per parameter leaf, from the optimizer's state:
    Nesterov keeps one array per parameter, in the parameters' order."""
    import jax
    out = {}
    for k in {k for k, _ in leaves}:
        names = sorted(net.params[k])
        arrs = [a for a in jax.tree_util.tree_leaves(net.opt_state[k])
                if getattr(a, "ndim", 0) > 0]
        if [a.shape for a in arrs] != [net.params[k][n].shape for n in names]:
            raise SystemExit(f"perfbench: optimizer state of {k!r} is not "
                             "one trace per parameter")
        out[k] = dict(zip(names, arrs))
    return out


def observe(cfg, net, seed, what):
    """Per-leaf norms read off the program's state, as host arrays."""
    import jax
    pleaves = [(k, n) for k, n, _, _ in arch.param_leaves(cfg)]
    sleaves = [(k, n) for k, n, _ in arch.state_leaves(cfg)]

    def norms(tree, leaves):
        return jax.device_get(jax.jit(
            lambda t: reference.leaf_norms(t, leaves))(tree))

    if what == "trace":
        return norms(_trace_tree(net, pleaves), pleaves)
    sub = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda u, v: u - v, a, b))
    if what == "delta":
        p = _as_tree(net.params, pleaves)
        return norms(sub(p, weights.make_weights(cfg, seed)), pleaves)
    if what == "state_delta":
        if not sleaves:
            return []
        s = _as_tree(net.state, sleaves)
        return norms(sub(s, weights.initial_state(cfg)), sleaves)
    raise ValueError(what)


# --------------------------------------------------------------- the job

def check_steps(cfg, traffic, net, pool, dataset_cls, seed):
    """Set-up's first steps, through the window's own call and feed: the
    first ``check_steps`` steps in calls of ``steps_per_call``. Returns
    what ``correct`` compares on the program's side."""
    import jax
    k = traffic["steps_per_call"]
    n = traffic["check_steps"]
    seen = {"losses": {}, "trace_after": k}
    done = 0
    while done < n:
        net.fit(PoolIterator(pool, dataset_cls, start=done, count=k))
        done += k
        seen["losses"][done] = float(net.get_score())
        if done == k:
            seen["trace_norms"] = observe(cfg, net, seed, "trace")
    seen["delta_norms"] = observe(cfg, net, seed, "delta")
    seen["state_delta_norms"] = observe(cfg, net, seed, "state_delta")
    seen["steps"] = done
    jax.block_until_ready(net.params)
    return seen


def reference_steps(cfg, traffic, pool, seed, steps, *, precision="float32",
                    fault=None):
    """The plain reference over the same first steps."""
    import jax
    out = reference.run_steps(
        cfg, weights.make_weights(cfg, seed), weights.initial_state(cfg),
        [pool[i % len(pool)] for i in range(steps)], cfg["program"]["seed"],
        precision=precision, fault=fault,
        trace_after=traffic["steps_per_call"])
    jax.clear_caches()
    return out


def rehearse_chunking(net, pool, traffic):
    """A rehearsal's tiny batches would stack into chunks of 64 steps;
    hold the program's chunk size to the cell's own, so that the rehearsal
    drives the same program kind (train_step or fit_scan) the chip does."""
    per = sum(a.nbytes for a in pool[0])
    net._CHUNK_MAX_BYTES = int(per * (traffic["steps_per_call"] + 0.5))


def run(ctx):
    """Set-up, window, close; returns the observations the harness turns
    into a result line. ``ctx``: cfg, traffic, seed, seconds, trace (dir or
    None), chips, limits, t_start, say."""
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    say = ctx["say"]
    batch = traffic["batch"] if not cfg.get("rehearsed") \
        else traffic["rehearsal_batch"]
    marks = {"imports": time.perf_counter() - ctx["t_start"]}
    t = time.perf_counter()
    devices = jax.devices()
    net = build_net(cfg)
    set_weights(cfg, net, weights.make_weights(cfg, seed))
    jax.block_until_ready(net.params)
    marks["init"] = time.perf_counter() - t
    t = time.perf_counter()
    pool = make_pool(cfg, traffic, seed, batch)
    if cfg.get("rehearsed"):
        rehearse_chunking(net, pool, traffic)
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
    obs = {
        "setup_s": setup_s, "setup_split": marks, "window_s": window_s,
        "steps": steps, "examples": steps * batch, "batch": batch,
        "attempted": steps, "failed": 0,
        "pipeline_stats": dict(net.last_pipeline_stats or {}),
        "programs_traced": net._compile_count - compiles_before,
        "end_to_end": {"train_examples_per_s": steps * batch / window_s},
        "last_loss": float(net.get_score()),
    }
    stats = [d.memory_stats() or {} for d in devices[:ctx["chips"]]]
    obs["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    obs["memory_limit_bytes"] = max(
        (s.get("bytes_limit", 0) for s in stats), default=0)

    # ---------- close: free the program's state, then the plain reference
    net = it = None
    gc.collect()
    jax.clear_caches()
    t = time.perf_counter()
    ref = reference_steps(cfg, traffic, pool, seed, seen["steps"])
    obs["reference_s"] = time.perf_counter() - t
    say("reference steps took "
        + ", ".join(f"{v:.1f}" for v in ref["step_seconds"]) + " s")
    obs["compared"] = compare.numbers(seen, ref, ctx["limits"])
    if not np.isfinite(obs["last_loss"]):
        obs["compared"].append({"name": "last_loss_finite", "value": 1.0,
                                "limit": 0.0})
    return obs
