"""chip_smoke.py refuses to report a result off the chip."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """On the CPU it exits non-zero before any phase, and never prints the
    ok line, also when copied away from the rest of the repo."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert '"platform": "cpu"' in out.stdout
