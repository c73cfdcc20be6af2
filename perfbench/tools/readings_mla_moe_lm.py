#!/usr/bin/env python3
"""Readings for the limits of a ``fit_mla_moe_lm`` cell's ``correct``
(``tools/readings_mla_lm.py``'s flow over the reference of latent
attention with softmax-routed experts and the balance loss):

    python3 perfbench/tools/readings_mla_moe_lm.py \
        --workload <cell> --seeds 11,12 --control-seeds 11 \
        [--controls fp8,half_batch,...] [--f32-seeds 11] \
        --out readings_mla_moe_lm.json

on the cell's TPU chip (``--rehearse`` on the CPU).

Per seed it runs the cell's first steps under the program exactly as the
job does (``jobs/fit_mla_moe_lm.py:check_steps``), frees the program, runs
the plain reference (``lib/reference_mla_moe_lm.py``) and prints every
number ``correct`` compares (``lib/compare.py:readings`` plus the job's own
three). For the control seeds it also runs, in the program's place, the
reference with every tensor between layers rounded to fp8 and the
reference with each planted fault (the second half of the positions left
out of the loss, the balance terms left out, the top k's weights
renormalised, YaRN's mscale^2 left out of the scores' scale), the faults
in one compile for all control seeds and in the order ``--controls``
gives. ``--f32-seeds`` runs the program in float32 at the highest matmul
precision: the second witness.

Once the limits file has limits, every row carries the verdict of
``compare.correct`` under them, and the tool exits 1 unless every sound row
is correct and every control and fault is not. ``--rehearse`` drives the
same flow on the CPU at the rehearsal size.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = (("fp8", "fp8", None),) + tuple(
    (f, "float32", f) for f in ("half_batch", "no_balance", "norm_topk",
                                "no_score_scale"))


def say(msg):
    print(f"readings_mla_moe_lm: {msg}", file=sys.stderr, flush=True)


def as_seen(ref):
    return {"losses": {i + 1: l for i, l in enumerate(ref["losses"])},
            "trace_norms": ref["trace_norms"],
            "delta_norms": ref["delta_norms"], "state_delta_norms": [],
            "pairs": [[(str(j), p) for j, p in enumerate(s)]
                      for s in ref["pairs"]],
            "balance": ref["balance"]}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--f32-seeds", default="")
    p.add_argument("--controls", default=",".join(c[0] for c in CONTROLS),
                   help="which of the control and the faults to run, in "
                        "this order")
    p.add_argument("--out")
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]

    from perfbench.lib import arch, compare, spec
    from perfbench.lib import reference_mla_moe_lm as reference_lm
    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, a.workload,
                                            rehearse=a.rehearse)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet
    if jax.devices()[0].platform != ("cpu" if a.rehearse else "tpu"):
        say("needs a TPU (or --rehearse on the CPU)")
        return 3
    if not a.rehearse:
        spec.enable_compile_cache()
    cfg = arch.load_config(os.path.join(ROOT, conf["file"]),
                           rehearse=a.rehearse)
    job = spec.load_module("jobs", traffic["job"])
    batch = traffic["rehearsal_batch" if a.rehearse else "batch"]
    seq = traffic["rehearsal_seq" if a.rehearse else "seq"]
    rows, wrong = [], 0
    leaves = reference_lm.param_shapes(cfg)

    nets = {}

    def program(seed, pool, f32=False):
        """The cell's first steps under the program. One network per
        precision for the whole tool, so that its step compiles once: each
        seed gets the benchmark's weights, a fresh optimizer state and
        iteration 0, and the arrays are dropped afterwards to make room
        for the reference."""
        c = cfg if not f32 else dict(cfg, program=dict(
            cfg["program"], kwargs=dict(cfg["program"]["kwargs"],
                                        compute_dtype=None)))
        w = reference_lm.init_params(c, seed)
        if f32 not in nets:
            nets[f32] = job.build_net(c)
            job.set_weights(c, nets[f32], w)        # checks the shapes
        net = nets[f32]
        net.params = {k: dict(w.get(k, {})) for k in net.params}
        net.opt_state = {n: t.init(net.params[n])
                         for n, t in net._transforms.items()}
        net.state = {n: net.conf.nodes[n].layer.init_state()
                     for n in net.params}
        net.iteration = 0
        seen = job.check_steps(c, traffic, net, pool, DataSet, seed)
        dropped = sum(v["pairs_dropped_total"]
                      for v in job.expert_counts(net).values())
        net.params = {n: {} for n in net.params}
        net.opt_state = net.state = net._last_input = None
        net._score = 0.0
        gc.collect()
        return seen, dropped

    def row(kind, seed, seen, ref, dropped=0):
        nonlocal wrong
        vals = compare.readings(seen, ref)
        vals.update(job.extra_readings(seen, ref, dropped))
        out = {"kind": kind, "seed": seed, "readings": vals,
               # pairs on the experts held, per step and expert layer: how
               # uneven this seed's routing is (an even one sends 3/32 of
               # the pairs); each expert layer's balance term, both sides
               "pairs": [[n for _, n in step] for step in seen["pairs"]],
               "balance": seen["balance"], "reference_balance": ref["balance"],
               # the leaves that read the two worst gaps
               "worst_leaves": {
                   name: "/".join(leaves[compare.worst_gap(
                       seen[a], ref[a])[1]][:2])
                   for name, a in (("trace", "trace_norms"),
                                   ("delta", "delta_norms"))}}
        if limits:
            compared = [{"name": k, "value": v, "limit": limits[k]}
                        for k, v in vals.items() if k in limits]
            out["correct"] = compare.correct(compared)
            out["over"] = [c["name"] for c in compared
                           if not c["value"] <= c["limit"]]
            if out["correct"] != kind.startswith(("program", "witness")):
                wrong += 1
                out["WRONG_VERDICT"] = True
        rows.append(out)
        print(json.dumps(out), flush=True)
        if a.out:       # after every row: a call cut short keeps its rows
            os.makedirs(os.path.dirname(os.path.abspath(a.out)),
                        exist_ok=True)
            with open(a.out, "w") as f:
                json.dump({"workload": a.workload, "rows": rows}, f, indent=1)

    sound = {}      # seed -> (the steps' batches, the sound reference)
    for seed in sorted(set(ints(a.seeds)) | set(ints(a.control_seeds))
                       | set(ints(a.f32_seeds))):
        t = time.perf_counter()
        pool = job.make_pool(cfg, traffic, seed, batch, seq)
        steps = [pool[i % len(pool)] for i in range(traffic["check_steps"])]
        seen = program(seed, pool) if seed in ints(a.seeds) else None
        seen32 = None
        if seed in ints(a.f32_seeds):
            try:
                with jax.default_matmul_precision("highest"):
                    seen32 = program(seed, pool, f32=True)
            except Exception as e:      # the float32 step may not fit
                say(f"witness seed {seed}: {type(e).__name__}: "
                    f"{str(e)[:300]}")
                nets.pop(True, None)
                gc.collect()
        ref = reference_lm.run_steps(cfg, seed, steps)
        if seen:
            row("program", seed, seen[0], ref, seen[1])
        if seen32:
            row("witness_f32_highest", seed, seen32[0], ref, seen32[1])
        if seed in ints(a.control_seeds):
            sound[seed] = (steps, ref)
        say(f"seed {seed} took {time.perf_counter() - t:.0f} s")
    # a control's step compiles once for all its seeds and is dropped
    # before the next one compiles
    # the reference's faults are arguments of its one float32 program
    # (``reference_mla_moe_lm.make_step``): only the fp8 control compiles
    # again
    known = {c[0]: c for c in CONTROLS}
    for name, precision, fault in [known[n] for n in a.controls.split(",")
                                   if n and sound]:
        t = time.perf_counter()
        for seed, (steps, ref) in sound.items():
            row(name, seed, as_seen(reference_lm.run_steps(
                cfg, seed, steps, precision=precision, fault=fault)), ref)
        if precision != "float32":
            reference_lm._STEPS.pop((cfg["name"], bool(cfg.get(
                "rehearsed")), precision), None)
            jax.clear_caches()
        say(f"{name} took {time.perf_counter() - t:.0f} s")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
