"""chip_smoke.py off the chip: the parent's contract (no TPU -> non-zero
exit and no result, a failed phase fails the run, one result line) and
each phase's control flow at its rehearsal size on the CPU. What the
phases assert about a TPU is only ever proven by `python chip_smoke.py`
on one."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_no_tpu_exits_nonzero_and_trains_nothing():
    """No chip and no explicit rehearsal request: a non-zero exit within
    seconds, nothing run on the CPU instead, no result line."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == chip_smoke.NO_TPU_RC
    assert "nothing was run" in r.stderr
    assert "fit" not in r.stdout          # no phase got past its device check
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


class _FakePhases:
    """Stands in for subprocess.Popen in the parent: scripted stdout and
    exit code per phase, and a record of which phases were started."""

    def __init__(self, script):
        self.script, self.started = script, []

    def __call__(self, cmd, **kw):
        name = cmd[cmd.index("--phase") + 1]
        self.started.append(name)
        lines, rc = self.script[name]
        fake = type("P", (), {})()
        fake.pid, fake.stdout = 0, iter(ln + "\n" for ln in lines)
        fake.wait = lambda *a: rc
        fake.poll = lambda: rc
        return fake


def _ok(name, device=_TPU):
    return ([f"[{name}] something ran",
             json.dumps({"phase": name, "ok": True, "device": device,
                         "ran": True})], 0)


@pytest.fixture
def fake(monkeypatch):
    def install(script):
        phases = _FakePhases(script)
        monkeypatch.setattr(chip_smoke.subprocess, "Popen", phases)
        return phases
    return install


def test_all_phases_pass_prints_the_result_as_the_last_line(fake, capsys):
    phases = fake({p: _ok(p) for p in chip_smoke.PHASES})
    assert chip_smoke.main([]) == 0
    assert phases.started == list(chip_smoke.PHASES)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": _TPU}


def test_no_tpu_stops_the_run_at_once(fake, capsys):
    phases = fake({"train": (["[train] device: platform=cpu"],
                             chip_smoke.NO_TPU_RC)})
    assert chip_smoke.main([]) == chip_smoke.NO_TPU_RC
    assert phases.started == ["train"]
    assert '"ok"' not in capsys.readouterr().out


def test_a_failed_phase_fails_the_run_but_the_rest_still_report(
        fake, capsys):
    script = {p: _ok(p) for p in chip_smoke.PHASES}
    script["kernel"] = (["[kernel] flash vs dense ... FAIL"], 1)
    phases = fake(script)
    assert chip_smoke.main([]) == 1
    assert phases.started == list(chip_smoke.PHASES)
    captured = capsys.readouterr()
    assert "FAILED phases: ['kernel']" in captured.err
    assert '{"ok"' not in captured.out


def test_phases_that_saw_different_devices_fail(fake, capsys):
    script = {p: _ok(p) for p in chip_smoke.PHASES}
    script["serve"] = _ok("serve", {"platform": "cpu", "kind": "cpu",
                                    "count": 1})
    fake(script)
    assert chip_smoke.main([]) == 1
    assert '{"ok"' not in capsys.readouterr().out


def test_serve_alone_is_preceded_by_a_device_probe(fake):
    phases = fake({"probe": _ok("probe"), "serve": _ok("serve")})
    assert chip_smoke.main(["--phases", "serve"]) == 0
    assert phases.started == ["probe", "serve"]


def test_metric_sum_adds_every_label_set_of_one_family():
    text = ('# HELP serving_decode_compiles_total x\n'
            'serving_decode_compiles_total{model="a",program="p"} 3\n'
            'serving_decode_compiles_total{model="b",program="q"} 4\n'
            'serving_decode_compiles_total_other 100\n')
    assert chip_smoke._metric_sum(
        text, "serving_decode_compiles_total") == 7.0


def test_kernel_phase_rehearsal():
    """Interpreted kernel vs the dense path, then the flash layer in a
    tiny LM fit()."""
    report = chip_smoke.phase_kernel(chip_smoke.REHEARSAL["kernel"],
                                     rehearse=True)
    assert report["ran"] and report["device"]["platform"] == "cpu"


@pytest.fixture
def ledger_off_after(monkeypatch):
    """The train phase turns the process-wide XLA ledger on (and, on the
    CPU, needs a nominal peak for the MFU gauge): leave neither behind."""
    from deeplearning4j_tpu import monitor
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
    yield
    monitor.xla.disable_ledger()
    monitor.xla.clear_ledger()


@pytest.mark.slow
@pytest.mark.parametrize("mesh", [False, True])
def test_train_phase_rehearsal(ledger_off_after, mesh):
    """ResNet-50 at a tiny input through both fit() programs and the
    checkpoint round trip; again over the virtual-device mesh."""
    report = chip_smoke.phase_train(chip_smoke.REHEARSAL["train"],
                                    rehearse=True, mesh=mesh)
    assert report["ran"]
    if mesh:
        assert report["shard_shape"][0] == 1     # batch 8 over 8 devices


@pytest.mark.slow
def test_serve_phase_rehearsal():
    """The serving CLI as a child (a 2-layer LM), the JAX-free client,
    compiles == warm-up runs, SIGTERM -> exit 0."""
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse-cpu", "--phases",
         "serve"], cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] and last["rehearsal"]
    assert "SIGTERM -> drained, exit 0" in r.stdout
