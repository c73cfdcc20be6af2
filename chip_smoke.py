#!/usr/bin/env python3
"""Chip smoke: the trainer takes steps and the server answers requests on
the attached TPU, through the entry points a user calls, at the full width
of models the zoo has. The quickest proof that the system still starts on
the chip; it measures nothing.

    python chip_smoke.py             # one chip: train + serve, every phase
    python chip_smoke.py --chips 4   # four chips: sharded fit vs one device

One process holds the chip for the whole run and starts no other. Any
exception, mismatch or non-finite value ends the run non-zero before the
last line is printed. Lines starting ``smoke:`` are information (wall
seconds of one run, compile counts, which kernels were found in which
compiled program) — smoke output, not measurements. The last line of
standard output is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases with one chip:

- train, full width, no kernel: zoo ResNet50 (1000 classes, 224x224, bf16
  compute, ``remat="save_convs"``), batch 128, through ``fit(iterator)``;
- train, kernel path: zoo TextGenerationLSTM at (B 256, T 64, bf16) and
  (B 32, T 64, f32) with the helpers on by detection — the compiled step
  must hold the Mosaic LSTM kernel — against the same steps with the
  helpers off;
- serve, predict: an InferenceServer over the trained ResNet50, real HTTP;
  answers equal ``net.output``;
- serve, generate: ``POST /generate`` through DecodeEngines over the LSTM
  and over TinyTransformer with dense and with paged KV, on the route
  ``exec/routing.py`` names and with ``decode_attn`` pinned to ``scan``.

With ``--chips 4`` only: the ResNet50 phase through plain ``fit()`` on the
default four-device mesh, and the same steps on a one-device mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The run's sizes. Widths and the image size are the zoo's own and are never
# cut; the batch, steps and request counts are what keeps a cold run (empty
# compile cache) inside its time limit. tests/test_chip_smoke.py passes a
# tiny table of the same shape to rehearse the control flow on the CPU.
FULL = {
    "resnet": {"batch": 128, "image": 224, "classes": 1000,
               "width_mult": 1.0, "fits": 2, "steps_per_fit": 3,
               "predict_sizes": (1, 2, 3), "max_batch": 4},
    "charrnn": {"vocab": 77, "cases": ((256, 64, "bfloat16"),
                                       (32, 64, "float32")),
                "fits": 3, "steps_per_fit": 2},
    "generate": {"prompts": 3, "prompt_len": 8, "new_tokens": 16,
                 "slots": 4, "lstm_max_len": 64},
    # zoo defaults first (d_model 128, 4 heads -> head dim 32, max_len 512);
    # the second has the 128-wide heads the paged kernel compiles for
    "transformers": ({"d_model": 128, "n_heads": 4, "max_len": 512,
                      "kv": ("dense", "paged")},
                     {"d_model": 512, "n_heads": 4, "max_len": 512,
                      "kv": ("paged",)}),
    "kernel_cases": {"dense": ((4, 4, 32, 512), (2, 8, 128, 1024)),
                     "paged": ((2, 8, 128, 16, 64), (2, 8, 128, 128, 8))},
}

# ops/validate.py: validate_lstm_case (x16 for bf16 streams) and
# validate_attention_case — default-precision MXU rounding under a
# different blocking order, not exactness
LSTM_RTOL, LSTM_ATOL = 2e-3, 2e-4
ATTN_RTOL, ATTN_ATOL = 1e-2, 1e-3
# A greedy token against the model's own full forward: how far below the
# reference's best log-probability the chosen token may sit. The decode
# step and the full forward are different programs, each rounding every
# matmul to bf16 passes in its own order, so near-ties flip; a wrong
# position, mask or page table moves logits by O(1), not by this.
GREEDY_LOGP_TOL = 0.1


class SmokeFailure(AssertionError):
    """A phase ran and what came out is wrong."""


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CacheEvents:
    """What the persistent compile cache was asked and what it had, for
    this process, off the compile ledger's counter
    (monitor/compile_ledger.py: the one place that listens to JAX)."""

    def __init__(self):
        from deeplearning4j_tpu.monitor import compile_ledger
        compile_ledger.install()

    @staticmethod
    def _count(*results):
        from deeplearning4j_tpu.monitor import get_registry
        family = get_registry().get("dl4jtpu_compile_requests_total")
        return int(sum(child.value for (_, result), child
                       in (family.children() if family else ())
                       if result in results))

    @property
    def requests(self):
        return self._count("hit", "miss")

    @property
    def hits(self):
        return self._count("hit")


def program_keys():
    from deeplearning4j_tpu.exec.programs import get_programs
    return {(p["caller"], p["key"]) for p in get_programs().entries()}


def programs_since(before):
    from deeplearning4j_tpu.exec.programs import get_programs
    return [p for p in get_programs().entries()
            if (p["caller"], p["key"]) not in before]


def check_kernel_programs(progs, want_kernel: bool, on_tpu: bool, what: str):
    """The compiled programs of ``what`` hold Mosaic kernels iff the route
    says so. Off the chip (a test's rehearsal) kernels run interpreted or
    not at all, so there is nothing compiled to look for."""
    require(progs, f"{what}: no program was registered")
    calls = [p["mosaic_calls"] for p in progs]
    say(f"{what}: programs "
        f"{[(p['caller'], p['key'], p['mosaic_calls']) for p in progs]}")
    if not on_tpu:
        return
    require(all(c is not None for c in calls),
            f"{what}: a program could not be analysed: {progs}")
    if want_kernel:
        require(sum(calls) > 0,
                f"{what}: no Mosaic kernel in the compiled program")
    else:
        require(sum(calls) == 0,
                f"{what}: a Mosaic kernel in a program routed to scan")


def one_hot(tokens, vocab):
    import numpy as np
    return np.eye(vocab, dtype=np.float32)[np.asarray(tokens)]


# --------------------------------------------------------------- training

def resnet50(cfg):
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.resnet import ResNet50
    # the zoo's optimizer at a step size a fixed random batch descends on
    # without warm-up (its default 0.1 is for ImageNet with a schedule)
    return ResNet50(num_classes=cfg["classes"],
                    input_shape=(cfg["image"], cfg["image"], 3), seed=7,
                    compute_dtype="bfloat16", remat="save_convs",
                    width_mult=cfg["width_mult"],
                    updater=Nesterovs(5e-4, momentum=0.9)).init()


def fixed_image_batch(cfg, seed=11):
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    rs = np.random.RandomState(seed)
    x = rs.rand(cfg["batch"], cfg["image"], cfg["image"], 3).astype(np.float32)
    y = np.eye(cfg["classes"], dtype=np.float32)[
        rs.randint(0, cfg["classes"], cfg["batch"])]
    return DataSet(x, y)


def fit_resnet(net, ds, cfg, devices):
    """``fits`` calls of ``fit(iterator)`` over the fixed batch. Returns the
    loss trajectory [before, after each fit..., after] and what the input
    prefetcher handed the step. Asserts the contract of the train phase."""
    import jax
    import numpy as np
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator

    staged = []
    fit_scan = net.fit_scan

    def watch(xs, ys):          # what the prefetcher staged, before the step
        staged.extend(jax.tree_util.tree_leaves((xs, ys)))
        return fit_scan(xs, ys)

    net.fit_scan = watch
    before = {k: np.asarray(net.params[k]["W"]) for k in ("stem_conv", "fc")}
    t0 = time.perf_counter()
    losses = [net.score(ds)]
    say(f"resnet50: loss before {losses[0]:.4f} "
        f"(eager score, {time.perf_counter() - t0:.1f}s)")
    for i in range(cfg["fits"]):
        t0 = time.perf_counter()
        net.fit(ExistingDataSetIterator([ds] * cfg["steps_per_fit"]))
        losses.append(net.get_score())
        say(f"resnet50: fit {i} ({cfg['steps_per_fit']} steps) "
            f"{time.perf_counter() - t0:.1f}s, last step loss "
            f"{losses[-1]:.4f}, programs traced {net._compile_count}")
    losses.append(net.score(ds))
    say(f"resnet50: loss after {losses[-1]:.4f}")

    require(all(np.isfinite(losses)), f"resnet50: non-finite loss {losses}")
    require(losses[-1] < losses[0],
            f"resnet50: loss did not fall on the fixed batch: {losses}")
    require(net._compile_count == 1,
            f"resnet50: the step traced {net._compile_count} times, not once")
    for k, w0 in before.items():
        require(not np.array_equal(w0, np.asarray(net.params[k]["W"])),
                f"resnet50: parameters of {k} did not change")
    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(net.params):
        require(leaf.sharding.device_set == want,
                f"resnet50: a parameter lives on {leaf.sharding.device_set}")
    require(staged, "resnet50: the streamed path never reached fit_scan")
    for a in staged:
        require(a.sharding.device_set == want,
                f"resnet50: a staged batch lives on {a.sharding.device_set}, "
                f"not on {want}")
    return losses, staged


def phase_train_resnet(cfg, dev, on_tpu):
    say(f"train resnet50: batch {cfg['batch']}, image {cfg['image']}, "
        f"classes {cfg['classes']}, width x{cfg['width_mult']}, bf16, "
        "remat save_convs")
    seen = program_keys()
    net = resnet50(cfg)
    ds = fixed_image_batch(cfg)
    fit_resnet(net, ds, cfg, [dev])
    # convolutions and batch norm are XLA's own: no kernel belongs here
    check_kernel_programs(programs_since(seen), False, on_tpu,
                          "resnet50 train step")
    return net


def char_batches(batch, t, vocab, n, seed=5):
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        tok = rs.randint(0, vocab, (batch, t + 1))
        out.append(DataSet(one_hot(tok[:, :-1], vocab),
                           one_hot(tok[:, 1:], vocab)))
    return out


def fit_charrnn(cfg, batch, t, dtype):
    import numpy as np
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.zoo.simple import TextGenerationLSTM
    net = TextGenerationLSTM(
        cfg["vocab"], seed=123,
        compute_dtype=dtype if dtype != "float32" else None).init()
    batches = char_batches(batch, t, cfg["vocab"], cfg["steps_per_fit"])
    losses = []
    for _ in range(cfg["fits"]):
        net.fit(ExistingDataSetIterator(batches))
        losses.append(net.get_score())
    require(all(np.isfinite(losses)),
            f"charrnn B{batch} {dtype}: non-finite loss {losses}")
    return net, losses


def phase_train_charrnn(cfg, on_tpu):
    """Helpers on by detection against helpers off, same seed and data."""
    import numpy as np
    from deeplearning4j_tpu import ops
    if on_tpu:
        require(ops.helpers_enabled() and not ops.interpret_mode(),
                "on a TPU the helpers are on by detection and compiled")
    served = None
    for batch, t, dtype in cfg["cases"]:
        what = f"charrnn B{batch} T{t} {dtype}"
        seen = program_keys()
        t0 = time.perf_counter()
        net, with_kernel = fit_charrnn(cfg, batch, t, dtype)
        say(f"{what}: helpers {'on' if ops.helpers_enabled() else 'off'}, "
            f"losses {[round(v, 4) for v in with_kernel]}, "
            f"{time.perf_counter() - t0:.1f}s")
        check_kernel_programs(programs_since(seen), True, on_tpu, what)
        if on_tpu:
            require(not ops.interpret_mode(), "a kernel ran interpreted")

        seen = program_keys()
        prev = ops.set_helpers_enabled(False)
        try:
            _, with_scan = fit_charrnn(cfg, batch, t, dtype)
        finally:
            ops.set_helpers_enabled(prev[0], interpret=prev[1])
        say(f"{what}: helpers off, losses "
            f"{[round(v, 4) for v in with_scan]}")
        check_kernel_programs(programs_since(seen), False, on_tpu,
                              what + " (helpers off)")
        scale = 16 if dtype == "bfloat16" else 1
        require(np.allclose(with_kernel, with_scan, rtol=LSTM_RTOL * scale,
                            atol=LSTM_ATOL * scale),
                f"{what}: kernel and scan trajectories differ: "
                f"{with_kernel} vs {with_scan}")
        require(with_kernel[-1] < with_kernel[0],
                f"{what}: loss did not fall: {with_kernel}")
        if dtype == "float32":
            served = net
    return served


# ---------------------------------------------------------------- serving

def greedy_is_teacher_forced(net, vocab, prompt, tokens, what):
    """Every generated token is the argmax of the model's own full forward
    over prompt + tokens (an independent program, run at full matmul
    precision) at its position, or ties with it inside GREEDY_LOGP_TOL.
    Returns the largest margin by which the reference prefers its own."""
    import jax
    import numpy as np
    seq = list(prompt) + list(tokens)
    with jax.default_matmul_precision("highest"):
        probs = np.asarray(net.output(one_hot(seq, vocab)[None]))[0]
    require(np.all(np.isfinite(probs)) and np.all(probs > 0),
            f"{what}: bad probabilities from the full forward")
    worst = 0.0
    for i, tok in enumerate(tokens):
        logp = np.log(probs[len(prompt) + i - 1])
        margin = float(logp.max() - logp[tok])
        require(margin <= GREEDY_LOGP_TOL,
                f"{what}: token {i} ({tok}) sits {margin:.3g} below the "
                f"teacher-forced argmax ({int(logp.argmax())}) in log-prob")
        worst = max(worst, margin)
    return worst


def get_json(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


class Served:
    """An InferenceServer on localhost with its client; stops both engines
    and the listener on exit."""

    def __init__(self, model, decode_engine=None):
        from deeplearning4j_tpu.serving.client import InferenceClient
        from deeplearning4j_tpu.serving.server import InferenceServer
        self.decode = decode_engine
        if decode_engine is not None:
            decode_engine.warmup()
            decode_engine.start()
        self.server = InferenceServer(model, port=0,
                                      decode_engine=decode_engine).start()
        self.url = f"http://127.0.0.1:{self.server.port}"
        self.client = InferenceClient(self.url, timeout=600.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.client.close()
        self.server.stop()
        if self.decode is not None:
            self.decode.stop()
        return False


def generate_all(served, prompts, new_tokens):
    return [served.client.generate(p, max_new_tokens=new_tokens)["tokens"]
            for p in prompts]


def make_prompts(cfg, vocab, seed=3):
    import numpy as np
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(0, vocab, cfg["prompt_len"])]
            for _ in range(cfg["prompts"])]


def phase_serve(resnet, lstm, cfg, dev, events):
    """/predict over the trained ResNet50 and /generate over the LSTM
    (carry state), one server, real HTTP on localhost."""
    import numpy as np
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    rcfg, gcfg = cfg["resnet"], cfg["generate"]
    vocab = cfg["charrnn"]["vocab"]
    dec = DecodeEngine(lstm, slots=gcfg["slots"],
                       max_len=gcfg["lstm_max_len"])
    with Served(resnet, dec) as s:
        stats = s.client.stats()
        require(stats["device"]["platform"] == dev.platform,
                f"/stats names {stats['device']}, JAX found {dev.platform}")
        req0 = events.requests
        t0 = time.perf_counter()
        warmed = s.client.warmup([rcfg["image"], rcfg["image"], 3],
                                 max_batch=rcfg["max_batch"])
        say(f"predict: warmup buckets {warmed['buckets']} "
            f"{time.perf_counter() - t0:.1f}s, {events.requests - req0} "
            "compiles asked of the persistent cache")
        require(events.requests > req0,
                "warmup did not go through the compile cache")
        traced = s.server.engine.trace_count
        rs = np.random.RandomState(17)
        buckets = set()
        for n in rcfg["predict_sizes"]:
            x = rs.rand(n, rcfg["image"], rcfg["image"], 3).astype(np.float32)
            got = s.client.predict(x)
            want = np.asarray(resnet.output(x))
            require(got.shape == (n, rcfg["classes"])
                    and np.all(np.isfinite(got)),
                    f"/predict batch {n}: bad answer {got.shape}")
            require(np.array_equal(got, want),
                    f"/predict batch {n} differs from net.output by "
                    f"{np.abs(got - want).max():.3g}")
            buckets.add(min(b for b in warmed["buckets"] if b >= n))
        require(len(buckets) == len(rcfg["predict_sizes"]),
                f"requests landed in buckets {buckets}, not distinct ones")
        require(s.server.engine.trace_count == traced,
                "a warmed server traced a program on a request")
        say(f"predict: {len(rcfg['predict_sizes'])} requests in buckets "
            f"{sorted(buckets)} equal net.output")

        prompts = make_prompts(gcfg, vocab)
        worst = 0.0
        for p, toks in zip(prompts,
                           generate_all(s, prompts, gcfg["new_tokens"])):
            require(len(toks) == gcfg["new_tokens"],
                    f"/generate (lstm) returned {len(toks)} tokens")
            worst = max(worst, greedy_is_teacher_forced(
                lstm, vocab, p, toks, "generate lstm"))
        say(f"generate lstm: {len(prompts)} requests x "
            f"{gcfg['new_tokens']} tokens are the teacher-forced argmax "
            f"(worst log-prob margin {worst:.3g} of {GREEDY_LOGP_TOL})")


def kernel_parity(cases, on_tpu):
    """The two decode kernels against plain jax.numpy on random data, at
    the shapes tests/test_tpu_compile.py compiles."""
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu import ops
    interp = ops.interpret_mode()
    if not (on_tpu or interp):
        say("decode kernels: not compiled off the chip, parity not run")
        return

    def reference(q, kc, vc, pos):
        s = jnp.einsum("bhd,bkhd->bhk", q, kc) / np.sqrt(q.shape[-1])
        live = jnp.arange(kc.shape[1])[None, None, :] <= pos[:, None, None]
        p = jnp.exp(s - jnp.max(jnp.where(live, s, -jnp.inf), -1,
                                keepdims=True))
        p = jnp.where(live, p, 0.0)
        return jnp.einsum("bhk,bkhd->bhd", p / p.sum(-1, keepdims=True), vc)

    def agree(got, want, what):
        err = float(jnp.max(jnp.abs(got - want)))
        bound = ATTN_ATOL + ATTN_RTOL * float(jnp.max(jnp.abs(want)))
        require(np.isfinite(err) and err <= bound,
                f"{what}: max error {err:.3g} over {bound:.3g}")
        say(f"{what}: max error {err:.3g} (bound {bound:.3g})")

    rs = np.random.RandomState(23)
    rand = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)
    for B, H, Dh, C in cases["dense"]:
        q, kc, vc = rand(B, H, Dh), rand(B, C, H, Dh), rand(B, C, H, Dh)
        pos = jnp.asarray(rs.randint(0, C, B), jnp.int32)
        agree(ops.flash_decode_step(q, kc, vc, pos, interpret=interp),
              reference(q, kc, vc, pos),
              f"flash_decode_step B{B} H{H} Dh{Dh} C{C}")
    for B, H, Dh, bs, MB in cases["paged"]:
        nb = B * MB + 1
        q, pk, pv = rand(B, H, Dh), rand(nb, bs, H, Dh), rand(nb, bs, H, Dh)
        tables = rs.permutation(np.arange(1, nb)).reshape(B, MB)
        pos = jnp.asarray(rs.randint(0, bs * MB, B), jnp.int32)
        kc = pk[tables].reshape(B, MB * bs, H, Dh)
        vc = pv[tables].reshape(B, MB * bs, H, Dh)
        agree(ops.flash_decode_step_paged(q, pk, pv, pos, tables,
                                          interpret=interp),
              reference(q, kc, vc, pos),
              f"flash_decode_step_paged B{B} H{H} Dh{Dh} block{bs}x{MB}")


def phase_generate_transformer(cfg, on_tpu):
    """/generate over TinyTransformer, dense and paged KV, on the route
    exec/routing.py names and with decode_attn pinned to scan."""
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.exec.routing import decode_attn_route, set_route
    from deeplearning4j_tpu.ops import flash_decode
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    from deeplearning4j_tpu.zoo.simple import TinyTransformer
    gcfg = cfg["generate"]
    kernel_parity(cfg["kernel_cases"], on_tpu)
    block = 16                                  # DecodeEngine's default
    for tcfg in cfg["transformers"]:
        net = TinyTransformer(d_model=tcfg["d_model"],
                              n_heads=tcfg["n_heads"],
                              max_len=tcfg["max_len"]).init()
        vocab, dh = net.conf.input_types[0].size, \
            tcfg["d_model"] // tcfg["n_heads"]
        prompts = make_prompts(gcfg, vocab)
        for kv in tcfg["kv"]:
            what = (f"generate transformer d{tcfg['d_model']} "
                    f"Dh{dh} kv={kv}")
            # what the layer seam will decide, from the same screens and
            # the same route it asks (nn/layers/attention.py)
            interp = ops.interpret_mode()
            screened = (flash_decode.supported_paged(
                block, dh, tcfg["n_heads"], interpret=interp)
                if kv == "paged"
                else flash_decode.supported(tcfg["max_len"], dh))
            route = decode_attn_route(
                tcfg["max_len"], dh, paged=kv == "paged",
                backend=None if interp else "tpu" if on_tpu else "cpu")
            want_kernel = (ops.helpers_enabled() and screened
                           and route == "pallas")
            say(f"{what}: screen {screened}, route {route} -> "
                f"{'kernel' if want_kernel else 'dense math'}")

            def run(tag, kernel):
                dec = DecodeEngine(net, slots=gcfg["slots"],
                                   max_len=tcfg["max_len"], kv=kv,
                                   kv_block_size=block)
                t0 = time.perf_counter()
                with Served(net, dec) as s:
                    toks = generate_all(s, prompts, gcfg["new_tokens"])
                    progs = [p for p in get_json(s.url + "/programs")
                             ["programs"] if p["caller"] == dec.id]
                say(f"{what} [{tag}]: {time.perf_counter() - t0:.1f}s")
                check_kernel_programs(progs, kernel, on_tpu,
                                      f"{what} [{tag}]")
                return toks

            routed = run("routed", want_kernel)
            set_route("decode_attn", "scan")
            try:
                pinned = run("pinned scan", False)
            finally:
                set_route("decode_attn", None)
            worst = 0.0
            for p, a, b in zip(prompts, routed, pinned):
                require(len(a) == len(b) == gcfg["new_tokens"],
                        f"{what}: wrong token count {len(a)}, {len(b)}")
                # both runs are held to the full forward; where they part
                # from each other it is at a tie the tolerance cannot order
                worst = max(worst,
                            greedy_is_teacher_forced(net, vocab, p, a,
                                                     what + " routed"),
                            greedy_is_teacher_forced(net, vocab, p, b,
                                                     what + " pinned"))
            same = sum(a == b for a, b in zip(routed, pinned))
            say(f"{what}: {same}/{len(prompts)} requests token-identical "
                "to the pinned-scan run; every token is the teacher-forced "
                f"argmax (worst log-prob margin {worst:.3g} of "
                f"{GREEDY_LOGP_TOL})")


# ------------------------------------------------------------- four chips

def phase_four_chips(cfg, devices):
    """Data-parallel fit over every attached chip against the same steps on
    a one-device mesh."""
    import numpy as np
    from deeplearning4j_tpu.exec import (build_mesh, default_mesh,
                                         set_default_mesh)
    rcfg = cfg["resnet"]
    ds = fixed_image_batch(rcfg)
    require(default_mesh().size == len(devices),
            f"default mesh has {default_mesh().size} devices")

    seen = program_keys()
    say(f"fit on the default mesh: {len(devices)} devices")
    sharded = resnet50(rcfg)
    many, staged = fit_resnet(sharded, ds, rcfg, devices)
    for a in staged:
        rows = {s.data.shape[1] for s in a.addressable_shards}
        require(len({s.device for s in a.addressable_shards})
                == len(devices) and rows == {rcfg["batch"] // len(devices)},
                f"a prefetched batch is not split over the chips: {rows}")
    say(f"prefetched batches arrive split {len(devices)} ways "
        f"({rcfg['batch'] // len(devices)} rows a chip)")
    progs = programs_since(seen)
    say(f"sharded step: {[(p['key'], p['all_reduces']) for p in progs]}")
    require(progs and all(p["all_reduces"] for p in progs),
            f"no all-reduce in the sharded step: {progs}")

    set_default_mesh(build_mesh(devices[:1]))
    try:
        say("fit on a one-device mesh")
        seen = program_keys()
        single = resnet50(rcfg)
        one, _ = fit_resnet(single, ds, rcfg, devices[:1])
        progs = programs_since(seen)
        require(progs and not any(p["all_reduces"] for p in progs),
                f"an all-reduce in the one-device step: {progs}")
    finally:
        set_default_mesh(None)
    say(f"losses on {len(devices)} devices {[round(v, 4) for v in many]}")
    say(f"losses on 1 device  {[round(v, 4) for v in one]}")
    # bf16 activations: the same tolerance the bf16 LSTM streams get
    require(np.allclose(many, one, rtol=LSTM_RTOL * 16, atol=LSTM_ATOL * 16),
            f"sharded and one-device trajectories differ: {many} vs {one}")


# -------------------------------------------------------------------- main

def attached_chips(chips: int):
    """The devices the run uses: exactly ``chips`` TPU chips, as JAX finds
    them, or no run at all — a smoke off the chip proves nothing about it."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        print(f"chip_smoke: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return devices


def run(cfg, chips: int) -> dict:
    devices = attached_chips(chips)
    from deeplearning4j_tpu.util.compile_cache import (cache_stats,
                                                       setup_compile_cache)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    t_start = time.perf_counter()
    events = CacheEvents()
    cache_dir = setup_compile_cache()
    entries0 = cache_stats(ttl=0)["entries"]
    say(f"device {device}")
    say(f"compile cache {cache_dir}: {entries0} entries at start "
        f"({'warm' if entries0 else 'cold'})")

    def phase(name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        say(f"phase {name}: passed, {time.perf_counter() - t0:.1f}s")
        return out

    if chips == 1:
        resnet = phase("train resnet50", phase_train_resnet, cfg["resnet"],
                       devices[0], on_tpu)
        lstm = phase("train charrnn", phase_train_charrnn, cfg["charrnn"],
                     on_tpu)
        phase("serve predict + generate lstm", phase_serve, resnet, lstm,
              cfg, devices[0], events)
        phase("serve generate transformer", phase_generate_transformer, cfg,
              on_tpu)
    else:
        phase("four chips", phase_four_chips, cfg, devices)

    say(f"compile cache {cache_dir}: {cache_stats(ttl=0)['entries']} "
        f"entries at end, {events.hits} hits of {events.requests} compiles "
        f"asked of it ({'hit' if events.hits else 'no hit'})")
    say(f"wall {time.perf_counter() - t_start:.1f}s (smoke, one run, not a "
        "measurement)")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded fit on the four-device mesh "
                         "and its one-device comparison")
    args = ap.parse_args(argv)
    device = run(FULL, args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
