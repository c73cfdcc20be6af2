"""The configuration with a learned selection of keys: its node list is a
count and has to be the count of ``lib/counts_sparse_lm.py``, to the hand
count; the new readers on hand-made observations; the configuration, its
cell and its metrics found by name."""

import json
import os

import pytest

from perfbench.lib import arch, counts_sparse_lm as counts, spec
from perfbench.lib import reference_sparse_lm as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "keye-vl-2.0-30b-a3b.fit-s16k-b1"
CONFIG = os.path.join(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")
SEQ = 16384
NEW = ("indexer_time_share.train", "selected_keys_share",
       "sparse_attention_roofline", "indexer_roofline")


@pytest.fixture(scope="module")
def cfg():
    return arch.load_config(CONFIG)


def test_node_list_counts_what_counts_sparse_lm_counts(cfg):
    mine = counts.train_flops_per_sequence(cfg, SEQ)
    nodes = arch.train_flops_per_example(cfg)
    assert abs(nodes - mine) / mine < 1e-3
    assert mine == pytest.approx(23.58e12, rel=1e-3)
    # image^2 x channels is one sequence of hidden states
    assert cfg["image"] ** 2 * cfg["channels"] == SEQ * cfg["hidden_size"]


def test_forward_macs_per_token_by_hand(cfg):
    parts = counts.forward_macs_per_sequence(cfg, SEQ)
    layer = {k: v / SEQ / 4 for k, v in parts.items() if k != "head"}
    assert layer["attention_proj"] == 2048 * (2 * 4096 + 2 * 512)
    assert layer["index_proj"] == 2048 * (1024 + 64 + 16)
    # 1024 a visible pair, (T + 1) / 2 pairs a token
    assert layer["index_scores"] == 1024 * (SEQ + 1) / 2
    # 8192 a selected pair, 31,458,304 selected pairs a sequence
    assert layer["selected_attention"] == 8192 * 31458304 / SEQ
    assert layer["router"] == 2048 * 128
    assert layer["routed"] == 1 * 3 * 2048 * 768      # one pair a token
    assert parts["head"] / SEQ == 2048 * 18992
    assert sum(parts.values()) / SEQ == pytest.approx(239.8e6, rel=1e-3)


@pytest.mark.parametrize("t,top,want", [
    (8, 3, 1 + 2 + 3 * 6), (4, 9, 10), (16384, 2048, 31458304),
    (2048, 2048, 2048 * 2049 // 2)])
def test_keys_selected_and_visible(t, top, want):
    assert counts.keys_selected(t, top) == want \
        == sum(min(i + 1, top) for i in range(t))
    assert counts.keys_visible(t) == t * (t + 1) // 2


def test_parameters_and_every_published_width(cfg):
    """465.4 M parameters on this chip, no width differs from the catalog
    row's, and the entry of BENCHMARK.json says what the file says."""
    n = 0
    for _, _, shape, _ in ref.param_shapes(cfg):
        k = 1
        for s in shape:
            k *= s
        n += k
    assert n == pytest.approx(465.4e6, rel=1e-4)
    widths = {"hidden_size": 2048, "intermediate_size": 6144, "head_dim": 128,
              "num_attention_heads": 32, "num_key_value_heads": 4,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8,
              "rope_theta": 10000000, "max_position_embeddings": 262144}
    for k, v in widths.items():
        assert cfg[k] == v and k not in cfg["reduced"]
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_local_experts": 128,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["vocab_size"]) == (4, 16, 16, 18992)
    entry = spec.by_name(spec.load_benchmark()["configs"], cfg["name"],
                         "configuration")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_cell_and_its_files_are_found_by_name():
    bench = spec.load_benchmark()
    cell, conf, traffic, limits = spec.cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "fit-s16k-b1")
    assert conf["file"] == "perfbench/configs/keye-vl-2.0-30b-a3b.json"
    assert (traffic["job"], traffic["batch"], traffic["seq"]) \
        == ("fit_sparse_lm", 1, 16384)
    assert {"trace_norm_gap", "delta_norm_gap", "pairs_dropped",
            "routed_pairs_gap", "index_loss_gap",
            "selected_keys_gap"} <= set(limits)
    assert hasattr(spec.load_module("jobs", traffic["job"]), "run")
    mine = [m["name"] for m in bench["per_layer"]
            if spec.applies(m, CELL)]
    assert set(NEW) < set(mine) and len(mine) == 18
    for name in NEW:
        f = spec.metric_file(name)
        entry = spec.by_name(bench["per_layer"], name, "metric")
        assert entry == {k: v for k, v in f.items()
                         if k not in ("reader", "args")}
        assert entry["workloads"] == [CELL]
        assert hasattr(spec.load_module("readers", f["reader"]), "read")
    # the attention layer's share of the step is one metric over both
    # decoders: its reader reads the job's seconds by layer kind
    assert "attention_time_share.train" in mine
    # what counts from Laguna's keys stays Laguna's, and so does the entry
    # whose list a test of the accepted benchmark pins (test_replay_share)
    for name in ("moe_grouped_matmul_roofline", "window_attention_roofline",
                 "replay_time_share.train"):
        assert not spec.applies(
            spec.by_name(bench["per_layer"], name, "metric"), CELL)


def _read(name, obs, cell=None):
    f = spec.metric_file(name)
    return spec.load_module("readers", f["reader"]).read(
        obs, {}, cell or {}, f["args"])


# a step of 1 s: attention 0.8 of it, by scope
DS = {"total_s": 2.0, "by_kind": {"RotaryGQAttention": 1.6, "-": 0.4},
      "inner": {"attend": 0.9, "index": 0.2, "select": 0.2,
                "index_loss": 0.3}}


@pytest.mark.parametrize("name,obs,want", [
    ("attention_time_share.train", {"device_seconds": DS}, 80.0),
    ("indexer_time_share.train", {"device_seconds": DS}, 35.0),
    # a scope under which nothing ran counts 0 beside the others
    ("indexer_time_share.train", {"device_seconds": dict(
        DS, inner={"attend": 0.9, "index": 0.2})}, 10.0),
    # a program without the scopes (the parent), a run without a trace
    ("indexer_time_share.train", {"device_seconds": dict(
        DS, inner={"attend": 0.9})}, None),
    ("indexer_time_share.train", {"device_seconds": None}, None),
    ("indexer_time_share.train", {}, None),
    ("attention_time_share.train", {"device_seconds": dict(
        DS, by_kind={"-": 2.0})}, None),
    ("selected_keys_share", {"selected_keys_share": 23.4375}, 23.4375),
    ("selected_keys_share", {}, None),
])
def test_share_readers(name, obs, want):
    got = _read(name, obs)
    assert got is None if want is None else got == pytest.approx(want)


def test_roofline_readers(cfg):
    cell = {"cfg": cfg, "peaks": {"bf16_flops_per_s": 197e12}}
    obs = {"device_seconds": DS, "seq": SEQ, "examples": 2}
    # two sequences, four layers: 6 x 8192 x 31,458,304 selected pairs
    want = 100 * 2 * 4 * 6 * 8192 * 31458304 / (0.9 * 197e12)
    assert _read("sparse_attention_roofline", obs, cell) \
        == pytest.approx(want)
    want = 100 * 2 * 4 * 6 * 1024 * (SEQ * (SEQ + 1) // 2) / (0.2 * 197e12)
    assert _read("indexer_roofline", obs, cell) == pytest.approx(want)
    # nothing under the scope, no reduction, no peaks: nothing to read
    assert _read("indexer_roofline", dict(obs, device_seconds=dict(
        DS, inner={"attend": 0.9})), cell) is None
    assert _read("sparse_attention_roofline", dict(obs, device_seconds=None),
                 cell) is None
    assert _read("sparse_attention_roofline", obs, {"cfg": cfg}) is None


def test_the_controls_and_faults_are_not_correct_at_rehearsal_size():
    """tools/readings_sparse_lm.py exits 1 on a wrong verdict: the sound
    run has to be correct under the rehearsal limits, the fp8 control and
    each of the five planted faults not."""
    import sys
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import readings_sparse_lm
    assert readings_sparse_lm.main(["--workload", CELL, "--seeds", "3",
                                    "--control-seeds", "3",
                                    "--rehearse"]) == 0
