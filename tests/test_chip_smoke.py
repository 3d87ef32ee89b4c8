"""``chip_smoke.py`` can only pass on a chip: without an accelerator it
exits non-zero and its last line reports ``"ok": false`` — from the
checkout, and from a directory that holds nothing else of the repo."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(script: str, cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the lone copy must not find the package
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_fails_without_a_chip(tmp_path, alone):
    script = SCRIPT
    if alone:
        script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(str(script), str(tmp_path))
    assert proc.returncode != 0, proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["error"]
    assert '"ok": true' not in proc.stdout
