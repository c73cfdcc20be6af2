"""The trace reduction on a hand-made event list and on a cut of a
recorded trace of cell 1."""

import json
import os

import pytest

from perfbench.lib import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 8)]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert tr.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def hand_made():
    # one chip, a window of 95 ns: two program calls, ops inside them (the
    # first call's inside a loop op, which holds them and is no work of its
    # own), an
    # all-reduce of which 10 ns overlap a fusion and 10 ns stand alone
    ops = [(0, 50, "%while.7 = (s32[], bf16[8]) while(...)"),
           (0, 20, "%fusion.1 = bf16[8] fusion(...)"),
           (20, 20, "%all-reduce.3 = f32[8] all-reduce(f32[8] %x)"),
           (30, 20, "%fusion.2 = bf16[8] fusion(...)"),
           (60, 30, "%fusion.1 = bf16[8] fusion(...)")]
    modules = [(0, 50, "jit_step(1)"), (60, 35, "jit_step(1)")]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {}}


def test_reduction_of_a_hand_made_trace():
    red = tr.reduce_chips(tr.chips_from_events(hand_made()))
    assert red["window_s"] == pytest.approx(95e-9)
    assert red["busy_s"] == pytest.approx(80e-9)       # 0-50 and 60-90
    assert red["idle_share"] == pytest.approx(15 / 95)
    assert red["collective_s"] == pytest.approx(20e-9)
    assert red["exposed_collective_s"] == pytest.approx(10e-9)
    assert red["device_ops"][0][0] == "fusion.1"
    assert red["device_ops"][0][1] == pytest.approx(50e-9)
    named = tr.name_gaps(red["gaps"], [(40, 70, "perfbench_next"),
                                       (0, 100, "perfbench_window")])
    assert named[0][0] == "perfbench_next"
    assert named[0][1] == pytest.approx(10e-9)


def test_no_device_events_reduce_to_nothing():
    assert tr.reduce_chips(tr.chips_from_events({"/host:CPU": {}})) == {}
    empty = {"/device:TPU:0": {"XLA Ops": [], "XLA Modules": []}}
    assert tr.reduce_chips(tr.chips_from_events(empty)) == {}


def test_four_chips_average():
    planes = hand_made()
    for i in range(1, 4):
        planes[f"/device:TPU:{i}"] = planes["/device:TPU:0"]
    red = tr.reduce_chips(tr.chips_from_events(planes))
    assert red["busy_s"] == pytest.approx(80e-9)
    assert red["idle_share"] == pytest.approx(15 / 95)


def test_recorded_cut_of_cell_1():
    path = os.path.join(DATA, "cell1_trace_cut.json")
    if not os.path.exists(path):
        pytest.skip("no recorded cut in this checkout")
    cut = json.load(open(path))
    planes = {k: {l: [tuple(e) for e in evs] for l, evs in v.items()}
              for k, v in cut["planes"].items()}
    red = tr.reduce_chips(tr.chips_from_events(planes))
    assert red["busy_s"] == pytest.approx(cut["expect"]["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(cut["expect"]["window_s"],
                                            rel=1e-9)
    assert 0.0 <= red["idle_share"] < 1.0
    assert red["collective_s"] == 0.0          # one chip: no collective
    assert len(red["device_ops"]) == 10
