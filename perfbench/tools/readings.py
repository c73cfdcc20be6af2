#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process on the
chip at the cell's own size (no measured window: a training cell's numbers
need none):

    python3 perfbench/tools/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out file.json]

For every seed the program's first steps against the plain reference (the
lower readings); for every control seed the reference in the precisions
below the configuration's (``--controls``: fp8, int8) against the float32
reference; for every fault seed each fault of ``tools/plant.py`` planted
under the program (a state left unchanged; half the batch left out) and
half the batch left out of the reference, against the sound reference; for
every ``--as-built-seeds`` seed the program without the configuration
file's ``layer_overrides``. Prints one JSON line per reading, with the
verdict of ``compare.correct`` under the cell's own limits file, and a
summary: per number the largest sound reading and the smallest of each
control and fault. Exits 1 where a sound row comes out not correct, or a
control, a fault or the net as built comes out correct.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


# rows that have to come out correct; every other kind has to come out not
SOUND = ("program", "program_f32")


def ints(s):
    return [int(v) for v in s.split(",") if v]


def as_seen(ref_like):
    return dict(ref_like, losses={i + 1: l for i, l
                                  in enumerate(ref_like["losses"])})


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=ints, default=[])
    p.add_argument("--control-seeds", type=ints, default=[])
    p.add_argument("--fault-seeds", type=ints, default=[])
    p.add_argument("--f32-seeds", type=ints, default=[],
                   help="the program in float32 at the highest matmul "
                        "precision: the second witness")
    p.add_argument("--controls", default="fp8,int8",
                   type=lambda v: [c for c in v.split(",") if c])
    p.add_argument("--as-built-seeds", type=ints, default=[],
                   help="the program without layer_overrides (F1)")
    p.add_argument("--raw", action="store_true",
                   help="keep every leaf's norms in --out")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out")
    a = p.parse_args()
    from perfbench.lib import arch, compare, spec
    from perfbench.tools import plant
    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, a.workload,
                                            rehearse=a.rehearse)
    chips = int(cell["chips"])
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={chips}"
    import jax
    if not a.rehearse:
        if jax.devices()[0].platform != "tpu" or len(jax.devices()) < chips:
            sys.exit("readings: needs the cell's TPU chips")
        spec.enable_compile_cache()
    from deeplearning4j_tpu.data.dataset import DataSet
    fit = spec.load_module("jobs", traffic["job"])
    cfg = arch.load_config(os.path.join(ROOT, conf["file"]), a.rehearse)
    batch = traffic["rehearsal_batch"] if a.rehearse else traffic["batch"]
    steps = traffic["check_steps"]
    rows = []

    raw = []

    def emit(kind, seed, seen, ref, secs):
        vals = compare.readings(seen, ref)
        verdict = compare.correct(compare.numbers(seen, ref, limits))
        row = {"kind": kind, "seed": seed, "seconds": round(secs, 1),
               "correct": verdict, **vals}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if a.raw:
            tolist = lambda d: {k: (v.tolist() if hasattr(v, "tolist") else v)
                                for k, v in d.items()}
            raw.append({"kind": kind, "seed": seed, "seen": tolist(seen),
                        "ref": tolist(ref)})

    def program(seed, pool, cfg_used, highest=False, fault=None):
        import contextlib
        net = fit.build_net(cfg_used)
        if fault:
            plant.break_net(net, fault)
        fit.set_weights(cfg_used, net, fit.weights.make_weights(cfg_used, seed))
        with (jax.default_matmul_precision("highest") if highest
              else contextlib.nullcontext()):
            seen = fit.check_steps(cfg_used, traffic, net, pool, DataSet,
                                   seed)
        net = None
        gc.collect()
        jax.clear_caches()
        return seen

    import copy
    cfg32 = copy.deepcopy(cfg)
    cfg32["program"]["kwargs"]["compute_dtype"] = None
    cfg_built = copy.deepcopy(cfg)
    cfg_built["program"].pop("layer_overrides", None)
    for seed in sorted(set(a.seeds) | set(a.control_seeds)
                       | set(a.fault_seeds) | set(a.f32_seeds)
                       | set(a.as_built_seeds)):
        pool = fit.make_pool(cfg, traffic, seed, batch)
        ref = None
        if seed in a.seeds:
            t = time.perf_counter()
            seen = program(seed, pool, cfg)
            t1 = time.perf_counter()
            ref = fit.reference_steps(cfg, traffic, pool, seed, steps)
            emit("program", seed, seen, ref, time.perf_counter() - t)
            print(f"readings: seed {seed} program {t1 - t:.1f}s reference "
                  f"{time.perf_counter() - t1:.1f}s losses {seen['losses']} "
                  f"ref {ref['losses']}", file=sys.stderr, flush=True)
        if ref is None:
            ref = fit.reference_steps(cfg, traffic, pool, seed, steps)
        if seed in a.f32_seeds:
            t = time.perf_counter()
            emit("program_f32", seed, program(seed, pool, cfg32, True), ref,
                 time.perf_counter() - t)
        if seed in a.as_built_seeds:
            t = time.perf_counter()
            emit("as_built", seed, program(seed, pool, cfg_built), ref,
                 time.perf_counter() - t)
        if seed in a.control_seeds:
            for c in a.controls:
                t = time.perf_counter()
                ctl = fit.reference_steps(cfg, traffic, pool, seed, steps,
                                          precision=c)
                emit(f"control_{c}", seed, as_seen(ctl), ref,
                     time.perf_counter() - t)
        if seed in a.fault_seeds:
            t = time.perf_counter()
            bad = fit.reference_steps(cfg, traffic, pool, seed, steps,
                                      fault="half_batch")
            emit("reference_half_batch", seed, as_seen(bad), ref,
                 time.perf_counter() - t)
            for f in plant.FAULTS:
                t = time.perf_counter()
                emit(f"program_{f}", seed, program(seed, pool, cfg, fault=f),
                     ref, time.perf_counter() - t)
        pool = None
        gc.collect()
    summary = {}
    for row in rows:
        for k, v in row.items():
            if k in ("kind", "seed", "seconds", "correct"):
                continue
            s = summary.setdefault(k, {})
            if row["kind"] in SOUND:
                key = row["kind"] + "_max"
                s[key] = max(s.get(key, 0.0), v)
            else:
                key = row["kind"] + "_min"
                s[key] = min(s.get(key, float("inf")), v)
    verdicts = {}
    for row in rows:
        verdicts.setdefault(row["kind"], {})[str(row["seed"])] = row["correct"]
    wrong = [(r["kind"], r["seed"]) for r in rows
             if r["correct"] is not (r["kind"] in SOUND)]
    print(json.dumps({"summary": summary, "verdicts": verdicts,
                      "wrong_verdicts": wrong}), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "limits": limits,
                       "rows": rows, "summary": summary,
                       "verdicts": verdicts, "raw": raw}, f)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
