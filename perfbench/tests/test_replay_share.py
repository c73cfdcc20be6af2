"""``replay_time_share.train``: its reader on hand-made observations, and
its files against BENCHMARK.json's entry."""

import pytest

from perfbench.lib import spec

NAME = "replay_time_share.train"


def _read(obs):
    f = spec.metric_file(NAME)
    return spec.load_module("readers", f["reader"]).read(obs, {}, {},
                                                         f["args"])


@pytest.mark.parametrize("obs, want", [
    # a step of 448.7 ms of which jax.checkpoint runs 78.1 again
    ({"device_seconds": {"total_s": 0.4487, "by_phase": {
        "backward": 0.2086, "forward": 0.1004, "recompute": 0.0781}}},
     100 * 0.0781 / 0.4487),
    # nothing is replayed: the share is 0, not missing
    ({"device_seconds": {"total_s": 2.0, "by_phase": {"forward": 2.0}}},
     0.0),
    # no device plane or no table of the step program (a run without
    # --trace, a job kind that makes no such reduction): nothing to read
    ({"device_seconds": None}, None),
    ({}, None),
    ({"device_seconds": {"total_s": 0.0, "by_phase": {}}}, None),
    ({"device_seconds": {"total_s": 1.0, "by_kind": {"-": 1.0}}}, None),
])
def test_reader(obs, want):
    got = _read(obs)
    assert got is None if want is None else got == pytest.approx(want)


def test_files_say_what_benchmark_json_says():
    entry = [m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == NAME]
    f = spec.metric_file(NAME)
    assert entry == [{k: v for k, v in f.items()
                      if k not in ("reader", "args")}]
    assert entry[0]["workloads"] == ["laguna-s-2.1.fit-s8k-b2"]
