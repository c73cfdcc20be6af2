#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip(s) and starts no other. It finds the cell
in BENCHMARK.json, the configuration, traffic, limits, job kind, metrics and
readers in files of their own under perfbench/ (see README.md), runs the
job, and prints one JSON object as the last line of standard output.

``--rehearse`` runs the same control flow on the CPU at the configuration
file's tiny rehearsal size (as many virtual devices as the cell has chips)
and prints the same line with the device named as it is and no rate, time,
share or memory reading: nothing from such a run is a device metric.
Without it the run refuses to start unless JAX reports a TPU with at least
the cell's chips.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def say(msg):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--keep-trace", metavar="DIR",
                   help="copy the traced run's xplane file there")
    return p.parse_args(argv)


def profiler_options():
    import jax
    o = jax.profiler.ProfileOptions()
    # the trace is for the device. With the host tracer on, each 155 MB
    # batch's host-side linearize writes some 10^6 events: a 600 MB trace
    # and a device three quarters idle (my chip run, PR 27)
    o.python_tracer_level = 0
    o.host_tracer_level = 0
    return o


def main(argv=None):
    args = parse(argv)
    from perfbench.lib import arch, compare, spec, trace as tr

    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, args.workload,
                                             rehearse=args.rehearse)
    chips = int(cell["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}")
    try:
        import deeplearning4j_tpu  # noqa: F401  the system under test
    except ImportError as e:
        say(f"the program is not here: {e}")
        return 3
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            say("--rehearse is for the CPU")
            return 3
    elif platform != "tpu":
        say(f"needs {chips} TPU chip(s); JAX reports {len(devices)} "
            f"{platform} device(s). No result.")
        return 3
    if len(devices) != chips:
        # the program's default mesh spans every device JAX reports
        say(f"the cell is for {chips} device(s); JAX reports "
            f"{len(devices)}. No result.")
        return 3
    if not args.rehearse:
        spec.enable_compile_cache()

    cfg = arch.load_config(os.path.join(ROOT, conf["file"]),
                           rehearse=args.rehearse)
    kind = devices[0].device_kind
    peaks = None if args.rehearse else spec.peaks(kind)
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
    job = spec.load_module("jobs", traffic["job"])
    ctx = {"cfg": cfg, "traffic": traffic, "seed": args.seed,
           "seconds": args.seconds, "trace": trace_dir, "chips": chips,
           "limits": limits, "t_start": T_START, "say": say,
           "profiler_options": profiler_options() if args.trace else None}
    try:
        obs = job.run(ctx)
        reduced, spans = {}, []
        if trace_dir and not args.rehearse:
            t = time.perf_counter()
            path = tr.find_xplane(trace_dir)
            if path is None:
                say("the profiler wrote no trace")
                return 4
            size = os.path.getsize(path)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            planes, spans = tr.read_xplane(path)
            reduced = tr.reduce_chips(tr.chips_from_events(planes))
            say(f"trace {size} bytes, read in "
                f"{time.perf_counter() - t:.1f}s")
            if not reduced:
                say("no operation ran on the device in the traced window")
                return 4
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    cellctx = {"cfg": cfg, "chips": chips, "peaks": peaks,
               "workload": args.workload}
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not spec.applies(m, args.workload):
                continue
            mf = spec.metric_file(m["name"])
            if args.rehearse and m["source"] != "program_counter":
                continue
            if args.rehearse and "memory" in mf["reader"]:
                continue
            v = spec.load_module("readers", mf["reader"]).read(
                obs, reduced, cellctx, mf.get("args", {}))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    elif not args.rehearse:
        values = dict(obs["end_to_end"], setup_s=obs["setup_s"])
        for m in bench["end_to_end"]:
            if spec.applies(m, args.workload) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    device = {"platform": platform, "kind": kind, "count": len(devices)}
    if not args.rehearse:
        device["memory_peak_bytes"] = obs["memory_peak_bytes"]
        if args.trace:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    compared = obs["compared"]
    result = {"correct": compare.correct(compared),
              "attempted": obs["attempted"], "failed": obs["failed"],
              "metrics": metrics, "device": device}
    if args.trace and reduced:
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": tr.name_gaps(reduced["gaps"], spans)}
    if args.rehearse:
        result["rehearsal"] = True
    result["workload"] = args.workload
    result["seed"] = args.seed
    result["steps"] = obs["steps"]
    result["window_s"] = obs["window_s"] if not args.rehearse else None
    result["setup_split"] = obs["setup_split"] if not args.rehearse else None
    result["reference_s"] = obs["reference_s"] if not args.rehearse else None
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    for c in compared:
        say(f"compared {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})"
            + ("" if c["value"] <= c["limit"] else "  <-- over"))
    say(f"correct: {result['correct']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
