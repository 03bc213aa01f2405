"""Sharded estimator execution: 8-fake-device parity for every registry
entry plus the data-parallel serving smoke (ISSUE 3 acceptance). Runs in a
subprocess so the test process keeps seeing 1 device (see dryrun.py's
device-count note)."""
import os
import subprocess
import sys
from pathlib import Path

SCRIPTS = Path(__file__).parent / "dist_scripts"
SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(script: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, str(SCRIPTS / script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"{script} failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n"
            f"{proc.stderr[-4000:]}"
        )
    return proc.stdout


def test_sharded_estimators_and_dp_serving():
    out = _run("run_sharded_estimators.py")
    assert "SHARDED ESTIMATORS OK" in out
    assert "DP decode matches single-device generations" in out


def test_dp_decode_split_lanes():
    out = _run("run_dp_decode_lanes.py")
    assert "split-lane DP decode matches single-device generations" in out
