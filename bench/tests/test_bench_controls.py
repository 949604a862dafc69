"""The comparison that decides ``correct`` fails what it must.

The control: the plain reference with every point and centre rounded to
bfloat16, one precision step below the float32 the configuration states,
put in the program's place. The same comparison that judges the program
has to turn ``correct`` false for it.

The faults: the timed path broken underneath a whole run, once for each
fault a cell can have on one chip: a fold that leaves its state
unchanged, half of every batch left out, an answer altered where it is
produced. Each has to turn ``correct`` false.
"""
import numpy as np
import pytest

import bench_tiny


def _failed(checks):
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_control_is_not_correct(cell):
    r = bench_tiny.run(cell, control=True)
    assert not r["correct"], r["checks"]
    assert _failed(r["checks"])
    assert "control_checks" not in r


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_readings_give_program_and_control_side_by_side(cell):
    r = bench_tiny.run(cell, readings=True)
    assert r["correct"] and not r["control_correct"]
    assert set(r["control_checks"]) == set(r["checks"])
    assert _failed(r["control_checks"]) and not _failed(r["checks"])
    assert list(r)[-1] == "checks"


def _cluster_faults(monkeypatch, fault):
    import repro.launch.cluster as CL
    real_absorb = CL.multisketch_absorb
    real_costs = CL.ClusterEngine.service_costs
    if fault == "unchanged":
        calls = {"n": 0}

        def absorb(state, keys, weights, active=None, **kw):
            calls["n"] += 1
            if calls["n"] > 1:
                return state
            return real_absorb(state, keys, weights, active, **kw)
        monkeypatch.setattr(CL, "multisketch_absorb", absorb)
    elif fault == "half":
        def absorb(state, keys, weights, active=None, **kw):
            act = np.asarray(active).copy()
            act[act.shape[0] // 2:] = False
            return real_absorb(state, keys, weights, act, **kw)
        monkeypatch.setattr(CL, "multisketch_absorb", absorb)
    else:
        def service_costs(self, queries):
            return np.array(real_costs(self, queries)) * 1.001
        monkeypatch.setattr(CL.ClusterEngine, "service_costs",
                            service_costs)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    _cluster_faults(monkeypatch, fault)
    r = bench_tiny.run(cell)
    assert not r["correct"], r["checks"]
    assert _failed(r["checks"])
