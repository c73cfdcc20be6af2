"""Drive a whole run (the look for a chip aside: the CPU rehearsal) with
the timed path broken underneath, and see ``correct`` come out false, once
for each fault a training cell can have; the sound run comes out true."""

import json

import pytest

from perfbench.lib import spec
from perfbench.tools import plant


def run_cell(monkeypatch, capsys, cell, fault):
    from perfbench import run
    load = spec.load_module

    def load_broken(kind, name, *a, **kw):
        mod = load(kind, name, *a, **kw)
        if kind == "jobs" and fault:
            build = mod.build_net
            mod.build_net = lambda cfg: plant.break_net(build(cfg), fault)
        return mod

    monkeypatch.setattr(spec, "load_module", load_broken)
    rc = run.main(["--workload", cell, "--seed", "4000000007", "--seconds",
                   "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("fault", (None,) + plant.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_under_the_timed_path(monkeypatch, capsys, cell, fault):
    line = run_cell(monkeypatch, capsys, cell, fault)
    assert line["compared"], "nothing was compared"
    assert line["correct"] is (fault is None), line["compared"]
