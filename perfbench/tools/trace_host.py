#!/usr/bin/env python3
"""One traced window of a fit cell with the program's own tracer on and the
profiler's host tracer at a level of the caller's choosing, read for what
the benchmark's traced run cannot show yet (PERF.md §7, S8):

    chiprun -- python3 perfbench/tools/trace_host.py --workload <cell> \
        --seed <n> [--seconds 4] [--host-tracer 1] [--tracer 1] \
        [--out chiprun_out/trace_host.json] [--cut <file.json>]

It edits nothing: it calls the cell's job (``jobs/fit.py``) with profiler
options of its own, reads the xplane with ``lib/trace.py`` and prints

(a) the ten longest idle gaps of the device, each named by the innermost
    span of the program the host was in (``train_step`` > ``wait`` >
    ``fetch``/``decode``/``stack``/``h2d``, ``train_step`` > ``dispatch`` >
    ``callback``), and the host plane's most frequent event names (what a
    flood would consist of);
(b) device milliseconds a step by phase and by layer kind, from the named
    scopes inside the step program: ``lib/scopes.py`` over the registry's
    ``op_scopes`` of the program that ran, with the ``unscoped`` share;
(c) what the capture cost: trace bytes, ``stop_trace`` seconds, the
    device's idle share and the rate over the traced window.

Named scopes are not in the compile cache's key, so a step loaded from an
entry that an older build compiled carries that build's names: for (b)
point ``JAX_COMPILATION_CACHE_DIR`` at an empty directory. ``--cut`` keeps
every eighth operation of one step with its rows of the table, the small
recorded input of perfbench/tests/test_scopes.py. ``--rehearse`` runs the
control flow on the CPU, where there is no device plane to read.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# the spans the program's fit path opens (PERF.md §3), and the harness's own
PROGRAM_SPANS = ("train_step", "wait", "fetch", "decode", "stack", "h2d",
                 "dispatch", "callback")


def say(msg):
    print(f"trace_host: {msg}", file=sys.stderr, flush=True)


def step_ops(chip):
    """The operations of the program that took most of the device's time
    (the step), by the module events that hold them, its name and its
    calls as (start, end)."""
    by_name = {}
    for s, e, n in chip.modules:
        by_name.setdefault(n.split("(")[0], []).append((s, e))
    if not by_name:
        return chip.ops, "?", []
    name = max(by_name, key=lambda n: sum(e - s for s, e in by_name[n]))
    calls = sorted(by_name[name])
    ops, i = [], 0
    for s, e, n in sorted(chip.ops):
        while i < len(calls) and calls[i][1] <= s:
            i += 1
        if i < len(calls) and calls[i][0] <= s:
            ops.append((s, e, n))
    return ops, name, calls


def table(title, rows, steps, total=None):
    print(title)
    rows = list(rows)
    total = total or sum(v for _, v in rows) or 1.0
    for k, v in sorted(rows, key=lambda kv: -kv[1]):
        print(f"  {k:42s} {1e3 * v / steps:9.3f} ms  {100 * v / total:5.1f} %")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=4.0)
    p.add_argument("--host-tracer", type=int, default=1)
    p.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    p.add_argument("--out")
    p.add_argument("--cut")
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args(argv)

    from perfbench.lib import arch, scopes, spec, trace as tr
    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, a.workload,
                                            rehearse=a.rehearse)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from deeplearning4j_tpu.exec.programs import get_programs
    from deeplearning4j_tpu.monitor.tracing import trace as tracer

    platform = jax.devices()[0].platform
    if platform != ("cpu" if a.rehearse else "tpu"):
        say(f"needs a TPU (or --rehearse on the CPU); JAX reports {platform}")
        return 3
    if not a.rehearse:
        say(f"compile cache at {spec.enable_compile_cache()}")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = a.host_tracer
    stop_s = []
    stop_trace = jax.profiler.stop_trace

    def timed_stop():
        t = time.perf_counter()
        stop_trace()
        stop_s.append(time.perf_counter() - t)

    jax.profiler.stop_trace = timed_stop
    tracer.enable(bool(a.tracer))
    trace_dir = tempfile.mkdtemp(prefix="trace_host_")
    cfg = arch.load_config(os.path.join(ROOT, conf["file"]),
                           rehearse=a.rehearse)
    job = spec.load_module("jobs", traffic["job"])
    try:
        obs = job.run({"cfg": cfg, "traffic": traffic, "seed": a.seed,
                       "seconds": a.seconds, "trace": trace_dir,
                       "chips": int(cell["chips"]), "limits": limits,
                       "t_start": T_START, "say": say,
                       "profiler_options": options})
    finally:
        jax.profiler.stop_trace = stop_trace
        tracer.enable(False)
    path = tr.find_xplane(trace_dir)
    try:
        if path is None:
            say("the profiler wrote no trace")
            return 4
        trace_bytes = os.path.getsize(path)
        t = time.perf_counter()
        planes, events = tr.read_xplane(path, host_prefix="")
        read_s = time.perf_counter() - t
    finally:
        # a level-1 capture of this cell is most of a gigabyte
        shutil.rmtree(trace_dir, ignore_errors=True)
    names = {}
    for _, _, n in events:
        names[n] = names.get(n, 0) + 1
    spans = [ev for ev in events if ev[2] in PROGRAM_SPANS
             or ev[2].startswith("perfbench_")]
    out = {"workload": a.workload, "seed": a.seed,
           "host_tracer_level": a.host_tracer, "tracer": a.tracer,
           "device": jax.devices()[0].device_kind,
           "trace_bytes": trace_bytes,
           "stop_trace_s": stop_s[0] if stop_s else None, "read_s": read_s,
           "host_events": len(events),
           "host_event_names": sorted(names.items(),
                                      key=lambda kv: -kv[1])[:10],
           "program_spans": {n: names.get(n, 0) for n in PROGRAM_SPANS},
           "steps": obs["steps"], "window_s": obs["window_s"],
           "train_examples_per_s":
               obs["end_to_end"]["train_examples_per_s"],
           "pipeline_stats": obs["pipeline_stats"],
           "setup_s": obs["setup_s"], "setup_split": obs["setup_split"]}
    print(f"(c) trace {out['trace_bytes']} bytes, stop_trace "
          f"{out['stop_trace_s']:.2f} s, read in {read_s:.1f} s; "
          f"{len(events)} host events, of the program's spans "
          f"{out['program_spans']}")
    print("    most frequent host events: " + ", ".join(
        f"{n} x{c}" for n, c in out["host_event_names"]))
    print(f"    {obs['steps']} steps in {obs['window_s']:.3f} s: "
          f"{out['train_examples_per_s']:.1f} examples/s; "
          f"pipeline_stats {obs['pipeline_stats']}")

    chips = tr.chips_from_events(planes)
    reduced = tr.reduce_chips(chips)
    if not reduced:
        print("no device plane in the trace: nothing to split or to name")
    else:
        out["idle_share"] = reduced["idle_share"]
        out["busy_s"], out["device_window_s"] = (reduced["busy_s"],
                                                 reduced["window_s"])
        out["idle_gaps"] = tr.name_gaps(reduced["gaps"], spans)
        print(f"    device busy {reduced['busy_s']:.3f} of "
              f"{reduced['window_s']:.3f} s, idle "
              f"{100 * reduced['idle_share']:.3f} %")
        print("(a) the ten longest idle gaps, by the program's innermost "
              "span:")
        for name, sec in out["idle_gaps"]:
            print(f"  {name:20s} {1e3 * sec:10.4f} ms")

        mine = [e for e in get_programs().entries()
                if e["key"].startswith(("train_step", "fit_scan"))]
        rec = mine[-1] if mine else None
        op_scopes = (get_programs().get(rec["caller"], rec["key"])
                     ["op_scopes"] if rec else None) or {}
        ops, module, calls = step_ops(chips[0])
        sp = scopes.split(ops, op_scopes)
        k = traffic["steps_per_call"]
        steps = max(1, len(calls) * k)
        out["step_program"] = {"module": module, "calls": len(calls),
                               "key": rec and rec["key"],
                               "instructions": len(op_scopes),
                               "memory_bytes": rec and rec["memory_bytes"],
                               "aot_seconds": rec and rec["aot_seconds"],
                               "compile_seconds":
                                   rec and rec["compile_seconds"]}
        out["split"] = {"total_s": sp["total_s"], "steps": steps,
                        "unscoped_share": sp["unscoped_share"],
                        "by_phase": sp["by_phase"], "by_kind": sp["by_kind"],
                        "by_phase_kind": [[ph, kd, v] for (ph, kd), v
                                          in sp["by_phase_kind"].items()]}
        print(f"(b) {module}: {len(calls)} calls of {k} step(s), "
              f"{1e3 * sp['total_s'] / steps:.3f} ms of operations a step, "
              f"{len(op_scopes)} instructions in the table, unscoped "
              f"{100 * sp['unscoped_share']:.2f} % of device time")
        table("  by phase:", sp["by_phase"].items(), steps)
        table("  by layer kind:", sp["by_kind"].items(), steps)
        table("  by phase and kind:",
              [(f"{ph} / {kd}", v)
               for (ph, kd), v in sp["by_phase_kind"].items()], steps)
        # the compiler's own names beside ours: which kinds' time sits in
        # each family of fused operation (``multiply_reduce_fusion.5`` is of
        # the family ``multiply_reduce_fusion``), and what stayed unscoped
        un, fam = {}, {}
        for s, e, n in ops:
            if tr.CONTAINER.match(n):
                continue
            key = tr.short_name(n)
            phase, _, kind = scopes.classify(op_scopes.get(key))
            sec = (e - s) / 1e9
            if phase == "unscoped":
                un[key] = un.get(key, 0.0) + sec
            f = (key.rstrip("0123456789").rstrip("."), phase, kind or "-")
            fam[f] = fam.get(f, 0.0) + sec
        out["split"]["by_family_phase_kind"] = [
            [*k, v] for k, v in sorted(fam.items(), key=lambda kv: -kv[1])]
        table("  by the compiler's family, phase and kind (largest 16):",
              [(" / ".join(k), v) for k, v in
               sorted(fam.items(), key=lambda kv: -kv[1])[:16]], steps,
              sp["total_s"])
        table("  the largest unscoped operations (share of the unscoped):",
              sorted(un.items(), key=lambda kv: -kv[1])[:10], steps,
              sum(un.values()))
        if a.cut:
            lo, hi = calls[min(1, len(calls) - 1)]
            one = [(s, e, n) for s, e, n in ops if lo <= s < hi][::8]
            keys = {tr.short_name(n) for _, _, n in one}
            cut_sp = scopes.split(one, op_scopes)
            with open(a.cut, "w") as f:
                json.dump({
                    "from": f"{a.workload} seed {a.seed}, every eighth "
                            "operation of one step",
                    "ops": [[s - lo, e - lo, tr.short_name(n)]
                            for s, e, n in one],
                    "op_scopes": {k_: op_scopes[k_] for k_ in sorted(keys)
                                  if k_ in op_scopes},
                    "expect": {"total_s": cut_sp["total_s"],
                               "by_phase": cut_sp["by_phase"],
                               "unscoped_share": cut_sp["unscoped_share"]},
                }, f)
            say(f"cut {len(one)} operations to {a.cut}")
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
