"""ResNet50 train-step ablation profiler.

Attribution by ablation: each variant is a compiled program timed with the
same interleaved min-differencing the bench uses, and the deltas between
variants attribute the step time. (A device trace says the same with less
guesswork; this tool predates one.) Run on the chip:

    python tools/profile_resnet.py [cifar512|imagenet128] ...

Variants:
  full        train step (loss+grad+updater)            — the bench number
  fwd         forward pass only (train-mode BN)
  grad        loss+grad, no updater/optimizer apply
  bn_eval     full step but BN uses running stats (no batch-stat
              reductions + no stat EMA) — attributes BN's train-mode cost
  remat       full step with jax.checkpoint over the loss (recompute
              activations in backward: trades FLOPs for HBM)
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

V5E_PEAK = 197e12


def _bench_core():
    import bench
    bench._setup_compile_cache()
    return bench


def _time_jitted(fn, args, pairs=5):
    """min-differenced seconds per call of jitted fn (state-chained by
    re-feeding params output, here approximated by back-to-back calls —
    the 1-vs-2 scheme from bench._time_fit_scan)."""
    import jax
    from deeplearning4j_tpu.util.timing import host_sync
    out = fn(*args)
    host_sync(out[0] if isinstance(out, tuple) else out)

    def sample(n):
        t0 = time.perf_counter()
        r = None
        for _ in range(n):
            r = fn(*args)
        host_sync(r[0] if isinstance(r, tuple) else r)
        return time.perf_counter() - t0

    t1s, t2s = [], []
    for _ in range(pairs):
        t1s.append(sample(2))
        t2s.append(sample(4))
    return (min(t2s) - min(t1s)) / 2.0


def profile(config="cifar512", variants=None):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    _bench_core()
    if config == "cifar512":
        batch, shape, classes = 512, (32, 32, 3), 10
    else:
        batch, shape, classes = 128, (224, 224, 3), 1000
    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.rand(batch, *shape).astype(np.float32))
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rs.randint(0, classes, size=batch)])

    net = ResNet50(num_classes=classes, input_shape=shape, seed=7,
                   compute_dtype="bfloat16").init()

    def loss_fn(params, state, xx, yy):
        # CG takes input/label LISTS (multi-input graphs)
        l, st = net._loss(params, state, [xx], [yy], None, None, None)
        return l, st

    def make(variant):
        if variant == "fwd":
            def f(params, state):
                l, st = loss_fn(params, state, x, y)
                return l
            return jax.jit(f), (net.params, net.state)
        if variant == "grad":
            def f(params, state):
                (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, state, x, y)
                return l, g
            return jax.jit(f), (net.params, net.state)
        if variant == "remat":
            rloss = jax.checkpoint(
                lambda p, s: loss_fn(p, s, x, y)[0])

            def f(params, state, opt_state):
                l, g = jax.value_and_grad(rloss)(params, state)
                p2, o2 = net._dp_apply_updates(params, opt_state, g)
                return l, p2, o2
            return jax.jit(f), (net.params, net.state, net.opt_state)
        if variant not in ("full", "bn_eval"):
            raise ValueError(f"unknown variant '{variant}'")
        if variant == "bn_eval":
            # eval-mode forward (BN running stats: no batch-stat reductions,
            # no EMA) + softmax-CE on the output activations
            def f(params, state, opt_state):
                def l_fn(p):
                    acts, _, _ = net._forward(p, state, [x], train=False,
                                              rng=None)
                    act = acts[net.conf.network_outputs[0]]
                    eps = 1e-9
                    return -jnp.mean(jnp.sum(
                        y * jnp.log(act.astype(jnp.float32) + eps), -1))
                l, g = jax.value_and_grad(l_fn)(params)
                p2, o2 = net._dp_apply_updates(params, opt_state, g)
                return l, p2, o2
            return jax.jit(f), (net.params, net.state, net.opt_state)
        # full
        def f(params, state, opt_state):
            (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, state, x, y)
            p2, o2 = net._dp_apply_updates(params, opt_state, g)
            return l, p2, o2, st
        return jax.jit(f), (net.params, net.state, net.opt_state)

    variants = variants or ["full", "fwd", "grad", "bn_eval", "remat"]
    results = {}
    bench = _bench_core()
    for v in variants:
        fn, args = make(v)
        fl = bench._cost_flops(fn, *args)
        sec = _time_jitted(fn, args)
        mfu = fl / sec / V5E_PEAK if fl else None
        results[v] = (sec, fl, mfu)
        print(f"{config} {v:8s}: {sec*1e3:8.2f} ms  "
              f"imgs/s={batch/sec:9.1f}  "
              f"mfu={mfu:.4f}" if mfu else f"{config} {v}: {sec*1e3:.2f} ms",
              flush=True)
    return results


if __name__ == "__main__":
    cfgs = sys.argv[1:] or ["cifar512"]
    for c in cfgs:
        profile(c)
