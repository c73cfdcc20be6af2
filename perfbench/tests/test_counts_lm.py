"""The language-model configuration's node list is a count, and it has to
be the count: ``perfbench/readers/step_mfu.py`` reads it through
``lib/arch.py``, while the roofline shares and ``PERF.md`` read
``lib/counts_lm.py``. Hold the two together, and both to the hand count."""

import json
import os

import pytest

from perfbench.lib import arch, counts_lm, reference_lm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = os.path.join(BENCH, "configs", "laguna-s-2.1.json")
SEQ = 8192


@pytest.fixture(scope="module")
def cfg():
    return arch.load_config(CONFIG)


def test_node_list_counts_what_counts_lm_counts(cfg):
    mine = counts_lm.train_flops_per_sequence(cfg, SEQ)
    nodes = arch.train_flops_per_example(cfg)
    assert abs(nodes - mine) / mine < 1e-3
    # image^2 x channels is one sequence of hidden states
    assert cfg["image"] ** 2 * cfg["channels"] == SEQ * cfg["hidden_size"]


def test_forward_macs_per_token_by_hand(cfg):
    parts = counts_lm.forward_macs_per_sequence(cfg, SEQ)
    per_token = {k: v / SEQ for k, v in parts.items()}
    total = sum(per_token.values())
    assert total == pytest.approx(306e6, rel=0.01)
    # the issue's shares: attention 33 %, layer 0's MLP 37 %, the four
    # expert layers 17 %, the head 13 %
    share = lambda *ks: sum(per_token[k] for k in ks) / total
    assert share("attention_proj", "attention") == pytest.approx(0.33, abs=0.01)
    assert share("dense_mlp") == pytest.approx(0.37, abs=0.01)
    assert share("router", "routed", "shared") == pytest.approx(0.17, abs=0.01)
    assert share("head") == pytest.approx(0.13, abs=0.01)


@pytest.mark.parametrize("t,window,want", [
    (8, None, 36), (8, 3, 1 + 2 + 3 * 6), (4, 9, 10), (8192, 512, None)])
def test_keys_seen(t, window, want):
    got = counts_lm.keys_seen(t, window)
    if want is None:
        want = sum(min(i + 1, window) for i in range(t))
    assert got == want


def test_parameters_and_every_published_width(cfg):
    """602.6 M parameters on this chip, and no width differs from the
    catalog row's."""
    n = sum(_size(s) for _, _, s, _ in
            reference_lm.param_shapes(cfg))
    assert n == pytest.approx(602.6e6, rel=1e-3)
    widths = {"hidden_size": 3072, "intermediate_size": 12288,
              "head_dim": 128, "moe_intermediate_size": 1024,
              "shared_expert_intermediate_size": 1024,
              "num_experts_per_tok": 10, "sliding_window": 512}
    for k, v in widths.items():
        assert cfg[k] == v and k not in cfg["reduced"]
    bench = json.load(open(os.path.join(os.path.dirname(BENCH),
                                        "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["published"]["num_experts"] == 256


def _size(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_the_controls_and_faults_are_not_correct_at_rehearsal_size():
    """tools/readings_lm.py exits 1 on a wrong verdict: the sound run has
    to be correct under the rehearsal limits, the fp8 control and each
    planted fault not."""
    import sys
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import readings_lm
    assert readings_lm.main(["--workload", "laguna-s-2.1.fit-s8k-b2",
                             "--seeds", "3", "--control-seeds", "3",
                             "--rehearse"]) == 0
