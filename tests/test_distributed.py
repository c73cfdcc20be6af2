"""Two-process multi-host test for parallel/distributed.py.

Spawns two real OS processes, each with 2 virtual CPU devices, forms the
jax.distributed cluster through a local coordinator, and asserts a pod-mesh
psum sums across the process boundary. CI-runnable, no TPU — the moral
equivalent of the reference's Spark `local[N]` distributed tests
(BaseSparkTest.java, SURVEY.md §4).

The cluster runs ONCE (module fixture); cluster formation, pod_mesh and
local_batch_slice assert unconditionally against it. Only the psum test is
gated on the jaxlib build actually shipping cross-process CPU collectives —
a missing transport must not mask a formation regression (it used to skip
the whole module).
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

_WORKER = Path(__file__).with_name("_dist_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def cluster_outs():
    """[(returncode, stdout)] for the two workers of one real cluster."""
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(_WORKER.parents[1])
                         + os.pathsep + env.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, str(_WORKER), str(port), str(pid), "2"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True)
        for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("distributed workers hung:\n" + "\n".join(outs))
    return [(p.returncode, out) for p, out in zip(procs, outs)]


def test_cluster_forms_across_real_processes(cluster_outs):
    for pid, (rc, out) in enumerate(cluster_outs):
        assert rc == 0, f"worker {pid} failed:\n{out}"
        assert f"WORKER_{pid}_OK" in out, out


def test_pod_mesh_and_batch_slice_span_the_cluster(cluster_outs):
    # the worker asserts jax.process_count/index, the 4-device global mesh
    # and its local_batch_slice offsets before printing the marker
    for pid, (rc, out) in enumerate(cluster_outs):
        assert f"WORKER_{pid}_FORMED global=4 local=2" in out, out


def test_cross_process_psum(cluster_outs):
    if any("psum=unsupported" in out for _, out in cluster_outs):
        # formation/mesh/slice DID validate (tests above); only the
        # collective transport is absent in this jaxlib build
        pytest.skip("this jaxlib's CPU backend implements no cross-process "
                    "collectives (psum raises INVALID_ARGUMENT); "
                    "run on TPU/GPU or a gloo-enabled jaxlib for the "
                    "psum assertion")
    for pid, (_, out) in enumerate(cluster_outs):
        assert f"WORKER_{pid}_OK psum=10.0" in out, out
