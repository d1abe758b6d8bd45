"""chip_smoke.py rehearsed on the CPU: the script's own contract.

The smoke is what says "the system still starts on the chip", so what
must never happen is a failure that exits 0: no TPU and no rehearsal
flag, a phase that raises, a comparison that disagrees — each must end
non-zero with no ``"ok": true`` line. The rehearsal (tiny presets,
children pinned to the CPU, asked for explicitly) must pass and name
the CPU.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (the parent half imports no jax)


def _run(tmp_path, *args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"), **env)
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          env=full, capture_output=True, text=True,
                          timeout=600)


def test_cpu_rehearsal_passes_and_names_the_cpu(tmp_path):
    r = _run(tmp_path, "--cpu-rehearsal")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    # the earlier lines carry the evidence, the last line nothing more
    assert "train: losses" in r.stdout
    assert "serve: leak_check" in r.stdout


def test_without_a_tpu_and_without_the_flag_it_fails(tmp_path):
    r = _run(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "UnavailableError" in r.stderr


def test_a_failing_serve_phase_fails_the_script(tmp_path):
    """Every request meets an injected fault: the server answers typed
    errors, the smoke must not count replies as success."""
    r = _run(tmp_path, "--cpu-rehearsal",
             PT_FAULT_INJECT="serving.request:p=1.0")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "train: done" in r.stdout  # the phase before it had passed


class TestParentLogic:
    """The driver half, with its phases replaced: what it does with a
    phase that raises or a comparison that disagrees."""

    DEVICE = {"platform": "cpu", "kind": "cpu", "count": 4}

    def _phases(self, monkeypatch, train4_losses, serve4_tokens,
                serve_raises=False):
        def run_child(phase, args, timeout_s):
            return {"losses": [7.0, 6.9, 6.8] if phase == "train"
                    else train4_losses, "device": self.DEVICE}

        def run_server(tag, args, mesh=0):
            if serve_raises:
                raise RuntimeError("serve phase failed")
            return {"tokens": serve4_tokens if mesh else [[1, 2]],
                    "platform": "cpu"}
        monkeypatch.setattr(chip_smoke, "run_child", run_child)
        monkeypatch.setattr(chip_smoke, "run_server", run_server)

    def _main(self, capsys):
        try:
            chip_smoke.main(["--chips", "4", "--cpu-rehearsal"])
        finally:
            self.out = capsys.readouterr().out

    def test_agreeing_phases_print_the_device_with_count_4(
            self, monkeypatch, capsys):
        self._phases(monkeypatch, [7.0, 6.9, 6.8], [[1, 2]])
        self._main(capsys)
        assert json.loads(self.out.strip().splitlines()[-1]) == \
            {"ok": True, "device": self.DEVICE}

    @pytest.mark.parametrize("kw", [
        dict(train4_losses=[7.0, 6.9, 6.5], serve4_tokens=[[1, 2]]),
        dict(train4_losses=[7.0, 6.9, 6.8], serve4_tokens=[[1, 3]]),
        dict(train4_losses=[7.0, 6.9, 6.8], serve4_tokens=[[1, 2]],
             serve_raises=True),
    ], ids=["losses-differ", "tokens-differ", "phase-raises"])
    def test_a_disagreement_or_a_raise_ends_without_ok(
            self, monkeypatch, capsys, kw):
        self._phases(monkeypatch, **kw)
        with pytest.raises(RuntimeError):
            self._main(capsys)
        assert '"ok"' not in self.out
