"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script proves the system on a TPU and nowhere else, so: (a) run as the
driver runs it, on a machine without the chip, it exits non-zero and prints
no ``ok``; (b) with its device check stubbed here, and a tiny size table of
the same shape as the real one, every phase runs — Pallas kernels through
the interpreter — and the last line has the contract's shape. The chip run
itself goes through the builder's chip tool, never through this suite.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# widths of the zoo models are fixed by the zoo (LSTM 256); everything the
# real table cuts for time is cut further here
TINY = {
    "resnet": {"batch": 8, "image": 32, "classes": 10, "width_mult": 0.125,
               "fits": 3, "steps_per_fit": 3, "predict_sizes": (1, 2),
               "max_batch": 2},
    "charrnn": {"vocab": 12, "cases": ((8, 8, "bfloat16"),
                                       (4, 8, "float32")),
                "fits": 2, "steps_per_fit": 2},
    "generate": {"prompts": 2, "prompt_len": 4, "new_tokens": 4, "slots": 2,
                 "lstm_max_len": 16},
    "transformers": ({"d_model": 32, "n_heads": 4, "max_len": 32,
                      "kv": ("dense", "paged")},),
    "kernel_cases": {"dense": ((2, 2, 8, 16),), "paged": ((2, 2, 8, 8, 2),)},
}


def test_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs 1 TPU chip" in r.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_every_phase_runs_at_tiny_size(chips, monkeypatch, capsys):
    import chip_smoke
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.exec import build_mesh, set_default_mesh

    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    devices = jax.devices()[:chips]
    # a batch shards only at 16 rows a device (Executor.min_rows)
    tiny = dict(TINY, resnet=dict(TINY["resnet"], batch=8 if chips == 1
                                  else 16 * chips))
    monkeypatch.setattr(chip_smoke, "FULL", tiny)
    monkeypatch.setattr(chip_smoke, "attached_chips", lambda n: devices)
    if chips > 1:
        # a ResNet50 this small is chaotic: two mathematically identical
        # fits (a permuted batch is enough) part by 0.2-0.4 in loss within
        # nine steps on the CPU, in f32 as in bf16, so the sharded and the
        # one-device trajectory cannot meet the bf16 tolerance here. At
        # full size on the chip they part by 0.007 (PERF.md, PR 21) and
        # the real run holds the real tolerance; this one rehearses the
        # control flow and the placement assertions.
        monkeypatch.setattr(chip_smoke, "LSTM_RTOL", 1.0)
    set_default_mesh(build_mesh(devices))      # the machine has these only
    prev = ops.set_helpers_enabled(True, interpret=True)
    try:
        assert chip_smoke.main(["--chips", str(chips)]) == 0
    finally:
        ops.set_helpers_enabled(prev[0], interpret=prev[1])
        set_default_mesh(None)
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": devices[0].platform,
                               "kind": devices[0].device_kind,
                               "count": chips}}
    passed = [l for l in out if l.startswith("smoke: phase ")]
    assert len(passed) == (4 if chips == 1 else 1), out
    assert all("passed" in l for l in passed)
