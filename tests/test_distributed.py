"""Multi-device integration: runs tests/_distributed_main.py in a
subprocess with 8 forced host devices (keeps the main pytest process on
1 device, per the dry-run isolation rule)."""
import os
import subprocess
import sys

import pytest


@pytest.mark.timeout(1200)
def test_distributed_integration():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"    # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "_distributed_main.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "distributed checks failed"
    assert "ALL DISTRIBUTED CHECKS PASSED" in proc.stdout
