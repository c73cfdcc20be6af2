"""A later PR adds a configuration, a mix, a job kind, a metric and a
reader as new files and new entries, and edits no file that is there: do
that in a copy of the benchmark and run the new cell end to end."""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

ECHO_JOB = '''
import time


def run(ctx):
    """A job kind of its own: counts what the traffic file says."""
    n = ctx["traffic"]["count"] * ctx["cfg"]["num_classes"]
    return {"setup_s": time.perf_counter() - ctx["t_start"],
            "setup_split": {}, "window_s": 1.0, "steps": n, "examples": n,
            "attempted": n, "failed": 0, "reference_s": 0.0,
            "end_to_end": {"echo_per_s": float(n)}, "echoed": n,
            "memory_peak_bytes": 0,
            "compared": [{"name": "echo_gap", "value": 0.0,
                          "limit": ctx["limits"]["echo_gap"]}]}
'''

ECHO_READER = '''
def read(obs, trace, cell, args):
    return None if args.get("silent") else obs["echoed"] * args["times"]
'''


def files_of(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            p = os.path.join(d, n)
            if "__pycache__" not in p:
                out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = files_of(root / "perfbench")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    new = root / "perfbench"
    cfg = json.load(open(new / "configs" / "vgg16-224.json"))
    cfg["name"] = "echo-net"
    (new / "configs" / "echo-net.json").write_text(json.dumps(cfg))
    (new / "traffic" / "echo-mix.json").write_text(
        json.dumps({"job": "echo", "count": 3}))
    (new / "jobs" / "echo.py").write_text(ECHO_JOB)
    (new / "readers" / "echo_reader.py").write_text(ECHO_READER)
    (new / "limits" / "echo-net.echo-mix.json").write_text(
        json.dumps({"limits": {"echo_gap": 0.5},
                    "rehearsal_limits": {"echo_gap": 0.5}}))
    for name, args in (("echo_count", {"times": 2}),
                       ("echo_silent", {"silent": True})):
        m = {"name": name, "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "execution core",
             "moves": "echo_per_s", "workloads": ["echo-net.echo-mix"]}
        bench["per_layer"].append(dict(m))
        (new / "metrics" / f"{name}.json").write_text(
            json.dumps(dict(m, reader="echo_reader", args=args)))
    bench["configs"].append({"name": "echo-net", "source": "a test",
                             "file": "perfbench/configs/echo-net.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "echo-net.echo-mix",
                               "config": "echo-net", "traffic": "echo-mix",
                               "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "echo_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["echo-net.echo-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = files_of(new)
    assert all(after[k] == v for k, v in before.items()), \
        "a file that was there was edited"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, str(new / "run.py"), "--workload",
         "echo-net.echo-mix", "--seed", "5", "--seconds", "1", "--trace",
         "1", "--rehearse"], env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    # rehearsal config: 10 classes x count 3, the reader doubles it; the
    # reader that finds nothing to read is left out, never reported as 0
    assert line["metrics"] == {"echo_count": {"value": 60, "unit": "count"}}
    assert list(line)[-1] == "compared"
    assert "echo_gap: 0 (limit 0.5)" in r.stderr


def test_benchmark_json_and_metric_files_agree():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        f = json.load(open(os.path.join(BENCH, "metrics",
                                        f"{m['name']}.json")))
        assert {k: f[k] for k in m} == m
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           f"{f['reader']}.py"))
    names = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           f"{w['name']}.json"))
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert set(m.get("workloads", ())) <= names
