"""The plain reference against ``net.fit`` at a tiny size on the CPU, one
step, float32, both containers; and the control, which has to fail."""

import json
import os

import pytest

from perfbench.lib import arch, compare, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = {"batch": 16, "pool_batches": 3, "steps_per_call": 1,
           "check_steps": 1}


def tiny(name):
    return arch.load_config(os.path.join(BENCH, "configs", f"{name}.json"),
                            rehearse=True)


@pytest.mark.parametrize("name", ["resnet50-224", "vgg16-224"])
def test_reference_matches_fit_one_step_float32(name):
    from deeplearning4j_tpu.data.dataset import DataSet
    fit = spec.load_module("jobs", "fit")
    cfg = tiny(name)
    assert cfg["program"]["kwargs"].get("compute_dtype") is None
    seed = 2 ** 31 + 77
    net = fit.build_net(cfg)
    fit.set_weights(cfg, net, fit.weights.make_weights(cfg, seed))
    pool = fit.make_pool(cfg, TRAFFIC, seed, 16)
    seen = fit.check_steps(cfg, TRAFFIC, net, pool, DataSet, seed)
    ref = fit.reference_steps(cfg, TRAFFIC, pool, seed, 1)
    got = compare.readings(seen, ref)
    assert got["loss_step1"] < 1e-5, got
    assert got["trace_norm_gap"] < 1e-4, got
    assert got["delta_norm_gap"] < 1e-3, got
    if "state_norm_gap" in got:
        assert got["state_norm_gap"] < 1e-4, got


@pytest.mark.parametrize("precision", ["fp8", "int8"])
@pytest.mark.parametrize(
    "cell", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_control_below_bfloat16_comes_out_not_correct(cell, precision):
    """The reference in a precision below the configuration's, put in the
    program's place, fails the cell's rehearsal limits."""
    fit = spec.load_module("jobs", "fit")
    bench = spec.load_benchmark()
    _, conf, traffic, limits = spec.cell(bench, cell, rehearse=True)
    cfg = tiny(conf["name"])
    seed = 31
    pool = fit.make_pool(cfg, traffic, seed, traffic["rehearsal_batch"])
    steps = traffic["check_steps"]
    ref = fit.reference_steps(cfg, traffic, pool, seed, steps)
    ctl = fit.reference_steps(cfg, traffic, pool, seed, steps,
                              precision=precision)
    k = traffic["steps_per_call"]
    seen = dict(ctl, losses={i + 1: l for i, l in enumerate(ctl["losses"])
                             if (i + 1) % k == 0})
    compared = compare.numbers(seen, ref, limits)
    assert compared and not compare.correct(compared), compared
    sound = dict(ref, losses={i + 1: l for i, l in enumerate(ref["losses"])
                              if (i + 1) % k == 0})
    assert compare.correct(compare.numbers(sound, ref, limits))


def test_worst_gap_is_a_gap_of_norms_against_the_larger_floor():
    import numpy as np
    ref = np.array([1.0, 2.0, 1e-6])
    gap, i = compare.worst_gap(np.array([1.1, 2.0, 2e-6]), ref)
    assert i == 0 and gap == pytest.approx(0.1)
    gap, _ = compare.worst_gap(np.array([1.0, 2.0, 0.5]), ref)
    assert gap == pytest.approx(0.5, rel=1e-4)  # against the median leaf
    assert compare.worst_gap(np.array([1.0, np.nan, 0.0]), ref)[0] == np.inf
    assert not compare.correct([])
    assert not compare.correct([{"name": "x", "value": float("nan"),
                                 "limit": 1.0}])
