"""chip_smoke.py where there is no chip (ISSUE 21): it must fail, say
why, pass nothing, and leave its own process off JAX — the parent of the
phases may never hold the device its children need."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# main(), then what this (parent) process itself did to JAX.
_RUN = """
import sys
import chip_smoke
rc = chip_smoke.main()
bridge = sys.modules.get("jax._src.xla_bridge")
print("PARENT_BACKENDS", 0 if bridge is None else len(bridge._backends))
sys.exit(rc)
"""


def test_no_tpu_fails_every_phase_and_parent_stays_off_jax():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    out = subprocess.run([sys.executable, "-c", _RUN], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert "passed []" in out.stdout and "failed ['probe']" in out.stdout
    assert '"ok"' not in out.stdout
    assert "PARENT_BACKENDS 0" in out.stdout


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert os.listdir(tmp_path) == ["chip_smoke.py"]
