"""The path rule and the split of lib/scopes.py, the readers of the
program's counters, and what of them a rehearsal may print."""

import json
import os

import pytest

from perfbench.lib import scopes, spec

DATA = os.path.join(os.path.dirname(__file__), "data")
BN = "bn1:BatchNormalization"


@pytest.mark.parametrize("path, want", [
    ("jit(step)/jvp(forward)/bn1:BatchNormalization/div",
     ("forward", BN, "BatchNormalization")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/forward/"
     "bn1:BatchNormalization/mul", ("backward", BN, "BatchNormalization")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "forward/bn1:BatchNormalization/sqrt",
     ("recompute", BN, "BatchNormalization")),
    ("jit(step)/updater/sub", ("updater", None, "-")),
    ("jit(step)/jvp(loss)/add", ("loss", None, "-")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/loss/mul",
     ("backward", None, "-")),
    ("jit(inner)/while/body/closed_call/jvp(forward)/"
     "res2_0_a_conv:ConvolutionLayer/conv_general_dilated",
     ("forward", "res2_0_a_conv:ConvolutionLayer", "ConvolutionLayer")),
    # a layer name may hold a colon; the class is what follows the last
    ("jit(step)/jvp(forward)/a:b:DenseLayer/dot_general",
     ("forward", "a:b:DenseLayer", "DenseLayer")),
    ("jit(step)/jvp(forward)/convert_element_type", ("forward", None, "-")),
    ("jit(step)/transpose(jvp(jvp()))/remat2", ("backward", None, "-")),
    ("jit(inner)/while/body/dynamic_slice", ("unscoped", None, None)),
    ("params['fc']['W']", ("unscoped", None, None)),
    ("", ("unscoped", None, None)),
    (None, ("unscoped", None, None)),
])
def test_classify(path, want):
    assert scopes.classify(path) == want


def test_split_of_a_hand_made_step():
    table = {"fusion.1": "jit(step)/jvp(forward)/c:ConvolutionLayer/conv",
             "fusion.2": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                         "forward/b:BatchNormalization/mul",
             "fusion.3": "jit(step)/updater/sub", "copy.4": ""}
    ops = [(0, 100, "%while.9 = (s32[]) while(...)"),      # holds the rest
           (0, 40, "%fusion.1 = bf16[8] fusion(...)"),
           (40, 70, "%fusion.2 = bf16[8] fusion(...)"),
           (70, 80, "%fusion.3 = f32[8] fusion(...)"),
           (80, 90, "%copy.4 = f32[8] copy(...)"),
           (90, 100, "%fusion.77 = f32[8] fusion(...)")]    # not in the table
    sp = scopes.split(ops, table)
    assert sp["total_s"] == pytest.approx(100e-9)
    assert sp["by_phase"] == pytest.approx(
        {"forward": 40e-9, "backward": 30e-9, "updater": 10e-9,
         "unscoped": 20e-9})
    assert sp["by_kind"]["BatchNormalization"] == pytest.approx(30e-9)
    assert sp["by_phase_kind"][("forward", "ConvolutionLayer")] == \
        pytest.approx(40e-9)
    assert sp["unscoped_share"] == pytest.approx(0.2)
    assert scopes.split([], table)["unscoped_share"] == 0.0


def test_split_of_a_recorded_step():
    """Every eighth operation of one step of cell 1 on the chip with its
    rows of the program's table (tools/trace_host.py --cut)."""
    cut = json.load(open(os.path.join(DATA, "cell1_scopes_cut.json")))
    ops = [tuple(o) for o in cut["ops"]]
    sp = scopes.split(ops, cut["op_scopes"])
    assert sp["total_s"] == pytest.approx(cut["expect"]["total_s"])
    assert sp["by_phase"] == pytest.approx(cut["expect"]["by_phase"])
    assert set(sp["by_phase"]) >= {"forward", "recompute", "backward",
                                   "updater"}
    assert sp["unscoped_share"] < 0.05
    assert sp["by_kind"]["BatchNormalization"] > 0
    assert sp["by_kind"]["ConvolutionLayer"] > 0


def _read(reader, obs, args, cell=None):
    return spec.load_module("readers", reader).read(obs, {}, cell or {}, args)


def test_pipeline_stat_reader():
    obs = {"pipeline_stats": {"process_cpu_sec": 9.0, "steps": 45,
                              "bytes_staged": 45 * 155164672,
                              "in_flight_max": 18}}
    assert _read("pipeline_stat", obs, {"key": "process_cpu_sec",
                                        "per": "steps", "scale": 1000.0}) \
        == pytest.approx(200.0)
    assert _read("pipeline_stat", obs, {"key": "bytes_staged",
                                        "per": "steps", "scale": 1e-6}) \
        == pytest.approx(155.164672)
    assert _read("pipeline_stat", obs, {"key": "in_flight_max"}) == 18.0
    # the parent's program keeps no such counter: nothing, not 0
    old = {"pipeline_stats": {"wall_sec": 6.5, "wait_sec": 0.1}}
    for m in ("host_cpu_ms_per_step", "bytes_staged_per_step",
              "steps_in_flight_max", "host_loop_busy_share"):
        f = spec.metric_file(m)
        assert _read(f["reader"], old, f["args"]) is None
    assert _read("pipeline_stat", {"pipeline_stats": {"bytes_staged": 1,
                                                      "steps": 0}},
                 {"key": "bytes_staged", "per": "steps"}) is None


def test_registry_readers():
    from deeplearning4j_tpu.exec.programs import get_programs
    from deeplearning4j_tpu.monitor.metrics import get_registry
    progs = get_programs()
    saved = dict(progs._programs)
    progs.clear()
    try:
        mem = spec.metric_file("step_program_hbm_share")
        sec = spec.metric_file("train_compile_s")
        obs = {"memory_limit_bytes": 16e9}
        assert _read(mem["reader"], obs, mem["args"]) is None
        assert _read(sec["reader"], obs, sec["args"]) is None
        base = {"flops": 1.0, "bytes": 1.0, "compile_seconds": 5.0}
        progs._programs[("cg9", "train_step_b256")] = dict(
            base, caller="cg9", key="train_step_b256", memory_bytes=4e9,
            aot_seconds=2.0)
        progs._programs[("eng", "b32")] = dict(
            base, caller="eng", key="b32", memory_bytes=1e9, aot_seconds=0.5)
        assert _read(mem["reader"], obs, mem["args"]) == pytest.approx(25.0)
        assert _read(mem["reader"], {}, mem["args"]) is None
        fam = get_registry().counter(sec["args"]["counter"],
                                     labelnames=("model",))
        before = sum(c.value for _, c in fam.children())
        fam.labels(model="ComputationGraph").inc(7.0)
        assert _read(sec["reader"], obs, sec["args"]) == \
            pytest.approx(before + 7.0 + 2.5)
        # a registry of the parent's: records without aot_seconds
        for rec in progs._programs.values():
            del rec["aot_seconds"]
        assert _read(sec["reader"], obs, sec["args"]) is None
    finally:
        progs.clear()
        progs._programs.update(saved)


def test_rehearsal_line_carries_the_exact_count_and_no_reading(capsys):
    from perfbench import run
    cell = "resnet50-224.fit-b256"
    rc = run.main(["--workload", cell, "--seed", "2281701001", "--seconds",
                   "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    traffic = spec.cell(spec.load_benchmark(), cell)[2]
    b = traffic["rehearsal_batch"]
    per_step = b * 64 * 64 * 3 * 4 + b * 10 * 4    # the rehearsal's shapes
    assert line["metrics"] == {
        "programs_traced": {"value": 0.0, "unit": "count"},
        "bytes_staged_per_step": {"value": per_step / 1e6, "unit": "MB"}}
    assert line["rehearsal"] is True and line["window_s"] is None
    assert "memory_peak_bytes" not in line["device"]
