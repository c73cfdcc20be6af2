"""The DeepSeek-V2-Lite configuration: its node list is a count and has to
be the count of ``lib/counts_mla_moe_lm.py``, to the hand count; the
published widths; the new reader on hand-made observations; the
configuration, its cell and its metrics found by name; the cell's rehearsal
end to end, and planted faults read ``correct`` false."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import arch, counts_mla_moe_lm as counts, spec
from perfbench.lib import reference_mla_moe_lm as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "deepseek-v2-lite.fit-s32k-b1"
CONFIG = os.path.join(BENCH, "configs", "deepseek-v2-lite.json")
SEQ = 32768
LISTED = ("host_loop_busy_share", "host_cpu_ms_per_step",
          "bytes_staged_per_step", "steps_in_flight_max",
          "step_program_hbm_share", "train_compile_s", "moe_time_share.train",
          "expert_load_max_over_mean", "init_s", "init_programs_built",
          "setup_trace_lower_s", "setup_xla_compile_s", "setup_cache_load_s",
          "setup_cache_misses", "mla_attention_roofline")


@pytest.fixture(scope="module")
def cfg():
    return arch.load_config(CONFIG)


def test_node_list_counts_what_counts_mla_moe_lm_counts(cfg):
    mine = counts.train_flops_per_sequence(cfg, SEQ)
    nodes = arch.train_flops_per_example(cfg)
    assert abs(nodes - mine) / mine < 1e-3
    assert mine == pytest.approx(133.18e12, rel=1e-3)
    # image^2 x channels is one sequence of hidden states
    assert cfg["image"] ** 2 * cfg["channels"] == SEQ * cfg["hidden_size"]


def test_forward_macs_per_token_by_hand(cfg):
    parts = {k: v / SEQ for k, v in
             counts.forward_macs_per_sequence(cfg, SEQ).items()}
    c = 2048
    # 5 layers; q: C x 16 x 192; kv: C x 576, 512 x 16 x 256; o: 2048 x C
    assert parts["mla_proj"] == 5 * (c * 3072 + c * 576 + 512 * 4096
                                     + 2048 * c)
    assert parts["attention"] == 5 * 16 * (192 + 128) * (SEQ + 1) / 2
    assert parts["dense"] == 3 * c * 10944
    assert parts["router"] == 4 * c * 64
    assert counts.even_pairs_per_token(cfg) == 6 * 8 / 64
    assert parts["routed"] == 4 * 0.75 * 3 * c * 1408
    assert parts["shared"] == 4 * 3 * c * 2816
    assert parts["head"] == c * 12800
    # the latent attention is most of the work at 32k
    total = sum(parts.values())
    assert (parts["attention"] + parts["mla_proj"]) / total \
        == pytest.approx(0.721, abs=1e-3)


def test_parameters_and_every_published_width(cfg):
    """535.1 M parameters on this chip, no width differs from the catalog
    row's, and the entry of BENCHMARK.json says what the file says."""
    n = 0
    for _, _, shape, _ in ref.param_shapes(cfg):
        k = 1
        for s in shape:
            k *= s
        n += k
    assert n == pytest.approx(535.06e6, rel=1e-4)
    widths = {"hidden_size": 2048, "q_lora_rank": None, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "intermediate_size": 10944,
              "moe_intermediate_size": 1408, "num_experts_per_tok": 6,
              "routed_scaling_factor": 1, "n_shared_experts": 2,
              "num_attention_heads": 16, "norm_topk_prob": False,
              "seq_aux": True, "rms_norm_eps": 1e-6}
    for k, v in widths.items():
        assert cfg[k] == v and k not in cfg["reduced"]
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 12800)
    assert ref.dims(cfg)["score_scale"] == pytest.approx(1.5896, rel=1e-4)
    entry = spec.by_name(spec.load_benchmark()["configs"], cfg["name"],
                         "configuration")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert len(entry["why"]) <= 200


def test_every_catalog_number_is_in_the_file_or_in_reduced(cfg):
    """The model-configs catalog's entry for DeepSeek-V2-Lite, as kept in
    ``tests/data``: every number of its ``config`` is in the file."""
    with open(os.path.join(HERE, "data", "catalog_deepseek-v2-lite.json")) as f:
        row = json.load(f)
    assert row["source_url"] == cfg["source"]
    for k, v in row["config"].items():
        assert k in cfg, k
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k


def test_the_cell_and_its_files_are_found_by_name():
    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit-s32k-b1")
    assert len(cell["why"]) <= 200
    assert conf["file"] == "perfbench/configs/deepseek-v2-lite.json"
    assert (traffic["job"], traffic["batch"], traffic["seq"]) \
        == ("fit_mla_moe_lm", 1, 32768)
    assert "balance_loss_gap" in limits
    assert hasattr(spec.load_module("jobs", traffic["job"]), "run")
    mine = [m["name"] for m in bench["per_layer"] if spec.applies(m, CELL)]
    assert set(LISTED) < set(mine)
    f = spec.metric_file("mla_attention_roofline")
    entry = spec.by_name(bench["per_layer"], "mla_attention_roofline",
                         "metric")
    assert entry == {k: v for k, v in f.items() if k not in ("reader",
                                                             "args")}
    assert entry["workloads"] == [CELL]
    assert bench["per_layer"][-1] == entry
    # read through counts modules that cannot read this configuration
    for name in ("moe_grouped_matmul_roofline", "latent_attention_roofline",
                 "attention_time_share.train"):
        assert not spec.applies(spec.by_name(
            bench["per_layer"], name, "metric"), CELL)


def _read(name, obs, cell=None):
    f = spec.metric_file(name)
    return spec.load_module("readers", f["reader"]).read(
        obs, {}, cell or {}, f["args"])


def test_the_new_reader(cfg):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cell = {"cfg": cfg, "peaks": peaks}
    ds = {"total_s": 1.0, "by_kind": {"LatentAttention": 0.6},
          "inner": {"attend": 0.5, "experts": 0.08}}
    obs = {"device_seconds": ds, "seq": SEQ, "examples": 2}
    # 5 layers x 16 heads x (192 + 128) x the triangle, x 3 passes x 2
    flops = 2 * 6 * 5 * 16 * 320 * SEQ * (SEQ + 1) / 2
    assert _read("mla_attention_roofline", obs, cell) == pytest.approx(
        100 * flops / 197e12 / 0.5)
    # nothing under the scope (the parent), no reduction, no peaks
    none = dict(obs, device_seconds=dict(ds, inner={"experts": 0.1}))
    assert _read("mla_attention_roofline", none, cell) is None
    assert _read("mla_attention_roofline", dict(obs, device_seconds=None),
                 cell) is None
    assert _read("mla_attention_roofline", obs, {"cfg": cfg}) is None


def test_the_cells_rehearsal_through_run_py():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483777", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["steps"] > 0
    assert {c["name"] for c in line["compared"]} >= {
        "trace_norm_gap_median", "delta_norm_gap", "pairs_dropped",
        "balance_loss_gap"}
    assert "programs_traced" in line["metrics"]


def test_planted_faults_are_not_correct_at_rehearsal_size():
    """tools/readings_mla_moe_lm.py exits 1 on a wrong verdict: the sound
    run has to be correct under the rehearsal limits, the fp8 control and
    the planted faults not."""
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import readings_mla_moe_lm
    assert readings_mla_moe_lm.main([
        "--workload", CELL, "--seeds", "3", "--control-seeds", "3",
        "--rehearse"]) == 0
