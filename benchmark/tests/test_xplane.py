"""The trace reduction on a small trace recorded on a TPU v5e (my chip
run, PR 23): three rounds of a jitted 2048x2048 bf16 matmul
(``jit_fixture_matmul``) under a ``fixture/step`` annotation, a 10 ms host
sleep under ``fixture/host_gap`` and a jitted add (``jit_fixture_add``),
between the window marks, with the profiler options of ``xplane.Session``.
"""
import os

import pytest

from benchmark.lib import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace(FIXTURE, chips=1)


def test_interval_arithmetic():
    merged = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert merged == [(0, 3), (5, 7)]
    assert xplane.measure(merged) == 5
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (7, 10)]
    assert xplane.subtract(merged, [(2, 5.5)]) == [(0, 2), (5.5, 7)]
    assert xplane.clip(merged, 1, 6) == [(1, 3), (5, 6)]
    assert xplane.op_name("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"


def test_programs_and_ops_are_found_by_name(trace):
    assert len(trace.devices) == 1
    # three ran; the third ends with the trace and is left out as cut
    adds = trace.program_runs("jit_fixture_add")
    assert len(adds) == 2 and all(5e-6 < d < 2e-5 for d in adds)
    assert len(trace.program_runs("jit_fixture_matmul")) >= 2
    ops = trace.op_seconds()
    assert set(ops) == {"fusion", "broadcast_add_fusion", "copy-start",
                        "copy-done"}
    assert ops["fusion"] > ops["broadcast_add_fusion"] > 0
    # the matmul is the root of a kOutput fusion, the add a kLoop fusion
    assert set(trace.op_seconds(lambda n, kind: kind == "kOutput")) == \
        {"fusion"}
    assert xplane.op_kind("%f = f32[] fusion(%p), kind=kLoop, calls=%c") \
        == "kLoop"


def test_busy_is_the_union_and_gaps_go_to_the_host_span(trace):
    assert 0 < trace.busy_s() < 1e-3 < trace.window_s
    assert trace.idle_share() > 0.99
    gaps = trace.idle_gaps()
    assert abs(sum(gaps.values()) + trace.busy_s() - trace.window_s) < 1e-9
    # the sleeps are where the device waited
    assert max(gaps, key=gaps.get) == "fixture/host_gap"
    bd = trace.breakdown()
    assert bd["device_ops"][0][0] == "fusion"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_run_the_stretch_cuts_adds_no_steps_and_no_op_time():
    """A stretch that starts inside one run of a program and ends, with the
    trace, inside another: only the whole run between them counts, and
    with ``inside`` only its ops do."""
    t = xplane.Trace.__new__(xplane.Trace)
    ops = [("fusion.1", s, s + 0.5, "kOutput") for s in (0.2, 1.2, 2.2, 3.2)]
    t.devices = [{"ops": ops + [("while.9", 0.0, 3.7, "")],
                  "modules": [("jit_kstep", -0.5, 0.9), ("jit_kstep", 1.0, 1.9),
                              ("jit_kstep", 2.0, 2.9), ("jit_kstep", 3.0, 3.7)]}]
    t.spans, t.window = [], (0.1, 3.7)     # no closing mark: the last op's end
    spans = t.program_spans("jit_kstep")
    assert [(s, e) for _, s, e in spans] == [(1.0, 1.9), (2.0, 2.9)]
    assert t.op_seconds()["fusion.1"] == 2.0
    assert t.op_seconds(inside=spans)["fusion.1"] == 1.0
    assert abs(t.idle_share() - (1 - 2.0 / 3.6)) < 1e-9
