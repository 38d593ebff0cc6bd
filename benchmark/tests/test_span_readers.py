"""The eight readers of the program's spans (``metrics/fit_*``,
``metrics/setup_*``, ``lib/spans.py``) on a hand-made event list: one
process with a first ``fit()`` that compiles, a warm one, and a window of
one-second turns in which the feed stalls once for 2.5 s. The two
``device_trace`` readers run on the recorded fixture trace with hand-made
host spans. A program that has no such spans gives every reader ``None``.
"""
import os
import types

import pytest

from benchmark.lib import manifest, spans, xplane
from deeplearning4j_tpu import monitor

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")
FIT, FEED = 11, 22          # thread ids
NEW = ("fit_idle_unattributed_share", "fit_launch_gap_ms",
       "fit_chunk_host_ms", "fit_chunk_stall_max_ms",
       "fit_queue_wait_max_ms", "fit_feed_batch_ms", "setup_first_fit_s",
       "setup_compile_s")


def ev(name, t0, t1, tid=FIT, **args):
    e = {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
         "pid": 1, "tid": tid}
    if args:
        e["args"] = args
    return e


def turn(events, t0, chunk, seq, stall=0.0, batches=10, sync=True):
    """One turn of the pipeline starting at ``t0``: 10 queue waits of
    1 ms (the first ``stall`` longer), 20 ms staging, 10 ms launch, the
    loss fetch, 30 ms of listeners, 10 ms under no leaf. Returns its
    end."""
    t = t0
    for i in range(batches):
        w = 0.001 + (stall if i == 0 else 0.0)
        events.append(ev("etl/queue_wait", t, t + w, seq=seq + i))
        # the feed made the batch just before: 4 ms pulling (the stall
        # is the source's), 6 ms staging
        made = t + w - 0.0005
        events.append(ev("etl/stage", made - 0.006, made, FEED,
                         seq=seq + i))
        events.append(ev("etl/source_next", made - 0.010 - (
            stall if i == 0 else 0.0), made - 0.006, FEED, seq=seq + i))
        t += w
    events.append(ev("train/etl", t0, t + 0.010, batches=batches))
    t += 0.010
    events.append(ev("train/stage", t, t + 0.020))
    events.append(ev("train/launch", t + 0.020, t + 0.030))
    events.append(ev("train/dispatch", t, t + 0.030, chunk=chunk))
    t += 0.030
    if sync:
        fetch = 1.0 - 0.010 * 2 - 0.030 - 0.030 - 0.010
        events.append(ev("train/loss_fetch", t, t + fetch))
        events.append(ev("train/listeners", t + fetch, t + fetch + 0.030,
                         steps=10))
        events.append(ev("train/chunk_sync", t, t + fetch + 0.030,
                         chunk=chunk - 1))
        t += fetch + 0.030
    t += 0.010
    events.append(ev("train/chunk", t0, t, chunk=chunk, batches=batches,
                     steps=10 if sync else 0, examples=256 * batches))
    return t


@pytest.fixture
def program(monkeypatch):
    """The hand-made process, put where the readers look."""
    events = [
        # the first fit(): 30 s, of which 19 s are JAX's (a trace inside
        # a trace must not count twice)
        ev("train/epoch", 0.0, 30.0, epoch=0),
        ev("xla/trace", 1.0, 5.0, fun_name="kstep"),
        ev("xla/trace", 2.0, 3.0, fun_name="conv"),
        ev("xla/lower", 5.0, 8.0, fun_name="jit(kstep)"),
        ev("xla/backend_compile", 8.0, 20.0, fun_name="jit(kstep)"),
        ev("train/epoch", 31.0, 33.0, epoch=1),
    ]
    t, ends = 100.0, []
    t = turn(events, t, 0, 0, sync=False)            # the fill
    for k in range(1, 60):
        t = turn(events, t, k, 10 * k, stall=2.5 if k == 7 else 0.0)
        ends.append(t)
    events.append(ev("train/epoch", 100.0, t, epoch=2))
    events.append(ev("xla/backend_compile", 120.0, 120.5, fun_name="late"))
    events.append(ev("xla/trace", t + 1.0, t + 9.0, fun_name="reference"))
    monkeypatch.setattr(monitor, "trace_events", lambda: list(events))
    monkeypatch.setattr(monitor, "thread_names",
                        lambda: {FIT: "MainThread", FEED: "etl-prefetch-0"},
                        raising=False)
    # the profiler came 50 s into the window: 47 whole turns before it
    return {"fit_s": {"wall": 50.0}, "trace": None}


def read(name, ctx):
    return manifest.load_module("metrics", name).read(ctx)


def test_the_manifest_names_the_eight_with_a_reader_each():
    listed = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == ["resnet50-fit-1chip"]
        assert manifest.load_module("metrics", name) is not None
    assert {listed[n]["moves"] for n in NEW if n.startswith("setup_")} == \
        {"setup_s"}


def test_window_is_the_last_fit_up_to_the_profiler(program):
    w = spans.Window(program, spans.program_spans())
    assert (w.epoch.t0, w.cut) == (100.0, 150.0)
    # turns 1..47 end before 150 s (turn 7 took 3.5 s); the fill is out
    assert [c.args["chunk"] for c in w.chunks] == list(range(1, 48))
    assert w.steady[0] == pytest.approx(100.06)


def test_fit_chunk_host_ms(program):
    # a turn's second minus 10 waits of 1 ms and the 0.91 s fetch
    assert read("fit_chunk_host_ms", program) == pytest.approx(80.0)


def test_fit_chunk_stall_max_ms_names_the_feed(program, capsys):
    assert read("fit_chunk_stall_max_ms", program) == pytest.approx(2500.0)
    line = capsys.readouterr().out
    assert line.startswith("[stall] ") and "chunk 7," in line
    assert "etl/source_next on etl-prefetch-0" in line
    # the stall, give or take what of the neighbouring batches' 4 ms
    # pulls falls into this turn
    held = float(line.split("; ")[1].split(" ms of the excess")[0])
    assert 2480.0 <= held <= 2530.0


def test_a_stall_under_no_span_is_named_so(program, monkeypatch, capsys):
    events = [e for e in monitor.trace_events()]
    slow = next(e for e in events if e["name"] == "train/chunk"
                and e["args"]["chunk"] == 20)
    slow["dur"] += 0.4e6          # overlaps turn 21's start: still found
    monkeypatch.setattr(monitor, "trace_events", lambda: events)
    events[:] = [e for e in events if not (
        e["name"] == "etl/queue_wait" and e["dur"] > 1e6)]
    assert read("fit_chunk_stall_max_ms", program) == pytest.approx(
        2500.0)          # turn 7 is still the longest: its etl is 2.5 s
    assert "train/etl on MainThread" in capsys.readouterr().out


def test_fit_queue_wait_max_ms(program):
    assert read("fit_queue_wait_max_ms", program) == pytest.approx(2501.0)


def test_fit_feed_batch_ms(program):
    assert read("fit_feed_batch_ms", program) == pytest.approx(10.0)


def test_setup_first_fit_s(program):
    assert read("setup_first_fit_s", program) == 30.0


def test_setup_compile_s(program, capsys):
    assert read("setup_compile_s", program) == pytest.approx(19.0)
    line = capsys.readouterr().out
    assert "xla/trace 4.000 s in 2" in line          # the union
    assert "xla/lower 3.000 s in 1" in line
    assert "xla/backend_compile 12.000 s in 1" in line
    assert "longest: xla/backend_compile jit(kstep) 12.000 s, " \
        "xla/trace kstep 4.000 s, xla/lower jit(kstep) 3.000 s" in line
    assert "inside the window's fit(): 1 events ['late']" in line


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(name, monkeypatch):
    """The parent's program: `train/epoch`, `train/dispatch` and
    `etl/stage` spans, no tree, no compile spans, no device trace."""
    old = [ev("train/epoch", 0.0, 9.0), ev("train/dispatch", 1.0, 1.1),
           ev("etl/stage", 1.0, 1.1, FEED)]
    ctx = {"fit_s": {"wall": 5.0}, "trace": None}
    monkeypatch.setattr(monitor, "trace_events", lambda: old)
    got = read(name, ctx)
    assert got == (9.0 if name == "setup_first_fit_s" else None)
    monkeypatch.setattr(monitor, "trace_events", lambda: [])
    assert read(name, ctx) is None


# ------------------------------------------------- the device_trace readers
@pytest.fixture
def traced():
    tr = xplane.Trace(FIXTURE, chips=1)
    return {"trace": tr, "system": types.SimpleNamespace(
        STEP_PROGRAM="jit_fixture_matmul")}


def test_fit_idle_unattributed_share_on_the_fixture(traced, program,
                                                    capsys):
    tr = traced["trace"]
    # the profiler came 7 s into the hand-made process's window, while
    # the turn that waits 2.5 s for the feed is under way: the program's
    # buffer names it whatever the profiler's host plane holds
    traced["fit_s"] = {"wall": 7.0}
    recorded = read("fit_idle_unattributed_share", traced)
    assert 0.0 < recorded < 100.0       # fixture/step, fixture/host_gap
    line = capsys.readouterr().out
    assert line.startswith("[idle] ") and "is chunk 7, 3500.000 ms" in line
    assert "etl/source_next on etl-prefetch-0" in line
    tr.spans = []
    assert read("fit_idle_unattributed_share", traced) == 100.0
    tr.spans = [("train/loss_fetch", tr.window[0] - 1, tr.window[1] + 1)]
    assert read("fit_idle_unattributed_share", traced) == 0.0
    tr.devices = []
    assert read("fit_idle_unattributed_share", traced) is None


def test_fit_launch_gap_ms_on_the_fixture(traced, capsys):
    tr = traced["trace"]
    runs = sorted((s, e) for n, s, e in tr.devices[0]["modules"]
                  if n == "jit_fixture_matmul")
    gaps = sorted(b[0] - a[1] for a, b in zip(runs, runs[1:]))
    assert len(gaps) >= 2
    # hand-made host spans: the thread stages during every gap
    tr.spans = [("train/chunk", tr.window[0], tr.window[1])] + [
        ("train/stage", a[1], b[0]) for a, b in zip(runs, runs[1:])]
    got = read("fit_launch_gap_ms", traced)
    assert got == pytest.approx(1e3 * (
        gaps[len(gaps) // 2] if len(gaps) % 2 else
        0.5 * (gaps[len(gaps) // 2 - 1] + gaps[len(gaps) // 2])))
    line = capsys.readouterr().out
    assert line.startswith("[gap] ")
    assert "lies under, ms: [('train/stage'," in line
    # the add program and the copies ran between two matmuls
    assert "other programs ran for" in line
    traced["system"].STEP_PROGRAM = "jit_kstep"
    assert read("fit_launch_gap_ms", traced) is None
