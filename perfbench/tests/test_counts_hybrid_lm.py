"""The hybrid configuration: its node list is a count and has to be the
count of ``lib/counts_hybrid_lm.py``, to the hand count; the new reader on
hand-made observations; the configuration, its cell and its metrics found
by name; the cell's rehearsal end to end, and planted faults read
``correct`` false."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import arch, counts_hybrid_lm as counts, spec
from perfbench.lib import reference_hybrid_lm as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "nemotron-3-super-120b-a12b.fit-s8k-b1"
CONFIG = os.path.join(BENCH, "configs", "nemotron-3-super-120b-a12b.json")
SEQ = 8192
NEW = ("ssm_time_share.train", "ssm_scan_roofline", "latent_experts_roofline")
LISTED = ("host_loop_busy_share", "host_cpu_ms_per_step",
          "bytes_staged_per_step", "steps_in_flight_max",
          "step_program_hbm_share", "train_compile_s", "moe_time_share.train",
          "attention_time_share.train", "expert_load_max_over_mean")


@pytest.fixture(scope="module")
def cfg():
    return arch.load_config(CONFIG)


def test_node_list_counts_what_counts_hybrid_lm_counts(cfg):
    mine = counts.train_flops_per_sequence(cfg, SEQ)
    nodes = arch.train_flops_per_example(cfg)
    assert abs(nodes - mine) / mine < 1e-3
    assert mine == pytest.approx(22.31e12, rel=1e-3)
    # image^2 x channels is one sequence of hidden states
    assert cfg["image"] ** 2 * cfg["channels"] == SEQ * cfg["hidden_size"]


def test_forward_macs_per_token_by_hand(cfg):
    parts = {k: v / SEQ for k, v in
             counts.forward_macs_per_sequence(cfg, SEQ).items()}
    # 16 heads of 64, one group of state 128: [z | x B C | dt] and out
    assert parts["ssm_proj"] == 5 * (4096 * (2 * 1024 + 2 * 128 + 16)
                                     + 1024 * 4096)
    # (128 + 1024) x 129 / 2 inside a chunk, 2 x 1024 x 128 for the states
    assert counts.scan_macs_per_token(cfg) == 1152 * 64.5 + 262144 == 336448
    assert parts["ssm_scan"] == 5 * 336448
    assert parts["router"] == 5 * 4096 * 512
    assert parts["latent"] == 5 * 2 * 4096 * 1024
    assert counts.even_pairs_per_token(cfg) == 22 * 8 / 512
    assert parts["routed"] == 5 * 0.34375 * 2 * 1024 * 2688
    assert parts["shared"] == 5 * 2 * 4096 * 5376
    assert parts["attention_proj"] == 4096 * (2 * 2048 + 2 * 128)
    assert parts["attention"] == 2 * 2048 * (SEQ + 1) / 2
    assert parts["head"] == 4096 * 16384
    assert sum(parts.values()) == pytest.approx(454.0e6, rel=1e-3)
    # X, y of 1024 and B, C of 128 in two bytes, delta of 16 in four; the
    # gate z is read under gate_norm, not under scan
    assert counts.scan_bytes_per_token(cfg) == 2 * (2 * 1024 + 256) + 64


def test_parameters_and_every_published_width(cfg):
    """713.4 M parameters on this chip, no width differs from the catalog
    row's, and the entry of BENCHMARK.json says what the file says."""
    n = 0
    for _, _, shape, _ in ref.param_shapes(cfg):
        k = 1
        for s in shape:
            k *= s
        n += k
    assert n == pytest.approx(713.4e6, rel=1e-4)
    widths = {"hidden_size": 4096, "head_dim": 128, "mamba_head_dim": 64,
              "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
              "expand": 2, "moe_latent_size": 1024,
              "moe_intermediate_size": 2688, "intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376,
              "num_experts_per_tok": 22, "routed_scaling_factor": 5,
              "n_shared_experts": 1, "layer_norm_epsilon": 1e-5}
    for k, v in widths.items():
        assert cfg[k] == v and k not in cfg["reduced"]
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "mamba_num_heads": 128, "n_groups": 8, "num_attention_heads": 32,
        "num_key_value_heads": 2, "vocab_size": 131072,
        "num_nextn_predict_layers": 1,
        "hybrid_override_pattern": cfg["published"]["hybrid_override_pattern"]}
    # the layers held are layers 25-35 of the published pattern
    assert cfg["published"]["hybrid_override_pattern"][25:36] \
        == cfg["hybrid_override_pattern"] == "*EMEMEMEMEM"
    assert len(cfg["published"]["hybrid_override_pattern"]) == 88
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["mamba_num_heads"], cfg["n_groups"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (11, 8, 16, 1, 16, 1, 16384)
    entry = spec.by_name(spec.load_benchmark()["configs"], cfg["name"],
                         "configuration")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert len(entry["why"]) <= 200


def test_every_catalog_number_is_in_the_file_or_in_reduced(cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(catalog)
               if cfg["source"] in l)
    for k, v in row["config"].items():
        assert k in cfg, k
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k


def test_the_cell_and_its_files_are_found_by_name():
    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit-s8k-b1")
    assert len(cell["why"]) <= 200
    assert conf["file"] == "perfbench/configs/nemotron-3-super-120b-a12b.json"
    assert (traffic["job"], traffic["batch"], traffic["seq"]) \
        == ("fit_hybrid_lm", 1, 8192)
    assert {"loss_step1", "loss_step2", "loss_step3", "trace_norm_gap",
            "trace_norm_gap_median", "delta_norm_gap",
            "delta_norm_gap_median", "pairs_dropped", "routed_pairs_gap",
            "decay_mean_gap"} == set(limits)
    assert hasattr(spec.load_module("jobs", traffic["job"]), "run")
    mine = [m["name"] for m in bench["per_layer"] if spec.applies(m, CELL)]
    assert set(NEW) | set(LISTED) < set(mine) and len(mine) == 17
    for name in NEW:
        f = spec.metric_file(name)
        entry = spec.by_name(bench["per_layer"], name, "metric")
        assert entry == {k: v for k, v in f.items()
                         if k not in ("reader", "args")}
        assert entry["workloads"] == [CELL]
        assert hasattr(spec.load_module("readers", f["reader"]), "read")
    for name in ("moe_grouped_matmul_roofline", "window_attention_roofline",
                 "replay_time_share.train", "indexer_roofline",
                 "sparse_attention_roofline", "selected_keys_share"):
        assert not spec.applies(
            spec.by_name(bench["per_layer"], name, "metric"), CELL)


def _read(name, obs, cell=None):
    f = spec.metric_file(name)
    return spec.load_module("readers", f["reader"]).read(
        obs, {}, cell or {}, f["args"])


# two steps of 0.5 s
DS = {"total_s": 1.0, "by_kind": {"Mamba2Mixer": 0.2, "ExpertLayer": 0.5},
      "inner": {"scan": 0.05, "experts": 0.08, "in_proj": 0.1}}


def test_the_new_readers(cfg):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cell = {"cfg": cfg, "peaks": peaks}
    obs = {"device_seconds": DS, "seq": SEQ, "examples": 2,
           "moe_pairs": 2 * 5 * 2816}
    assert _read("ssm_time_share.train", obs) == pytest.approx(20.0)
    # bound by memory: 2 passes x 4672 B x 2 x 8192 x 5 tokens over 819 GB/s
    by_bytes = 2 * 4672 * 2 * SEQ * 5 / 819e9
    by_ops = 6 * 336448 * 2 * SEQ * 5 / 197e12
    assert by_bytes > by_ops
    assert _read("ssm_scan_roofline", obs, cell) \
        == pytest.approx(100 * by_bytes / 0.05)
    assert _read("latent_experts_roofline", obs, cell) == pytest.approx(
        100 * 6 * 28160 * 2 * 1024 * 2688 / 197e12 / 0.08)
    # nothing under the scope (the parent), no reduction, no peaks
    none = dict(obs, device_seconds=dict(DS, inner={"in_proj": 0.1}))
    assert _read("ssm_scan_roofline", none, cell) is None
    assert _read("latent_experts_roofline", none, cell) is None
    assert _read("ssm_scan_roofline", dict(obs, device_seconds=None),
                 cell) is None
    assert _read("ssm_scan_roofline", obs, {"cfg": cfg}) is None
    assert _read("ssm_time_share.train", {"device_seconds": dict(
        DS, by_kind={"ExpertLayer": 0.5})}) is None


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
        "trace_norm_gap", "delta_norm_gap", "routed_pairs_gap",
        "pairs_dropped", "decay_mean_gap", "loss_step1"}
    assert "programs_traced" in line["metrics"]


def test_planted_faults_are_not_correct_at_rehearsal_size():
    """tools/readings_hybrid_lm.py exits 1 on a wrong verdict: the sound
    run has to be correct under the rehearsal limits, the fp8 control and
    the planted faults not (three of the six here, for the time)."""
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import readings_hybrid_lm
    assert readings_hybrid_lm.main([
        "--workload", CELL, "--seeds", "3", "--control-seeds", "3",
        "--controls", "fp8,chunk_reset,unweighted_latent,drop_expert",
        "--rehearse"]) == 0
