"""chip_smoke.py off the chip: it rehearses every phase at a tiny size and
never reports success without a TPU."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"
PHASES = ("fit.step:", "fit:", "predict:", "fit.check:", "stream:", "serve:")


def _run(script, *args, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "REPRO_PALLAS_INTERPRET")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, str(script), *args], env=full,
                          capture_output=True, text=True, timeout=600)


def test_tiny_rehearsal_runs_every_phase_and_never_succeeds(tmp_path):
    out = _run(SCRIPT, "--tiny", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.returncode == 3, out.stderr[-2000:]
    for phase in PHASES:
        assert phase in out.stdout, (phase, out.stdout[-2000:])
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("case,rc", [("no-tpu", 1), ("interpret-env", 2),
                                     ("script-alone", 2)])
def test_refuses_without_a_compiled_tpu_run(case, rc, tmp_path):
    script, env = SCRIPT, {}
    if case == "interpret-env":
        env["REPRO_PALLAS_INTERPRET"] = "1"
    elif case == "script-alone":
        script = Path(shutil.copy(SCRIPT, tmp_path))
    out = _run(script, **env)
    assert out.returncode == rc, out.stderr[-2000:]
    assert '"ok"' not in out.stdout
