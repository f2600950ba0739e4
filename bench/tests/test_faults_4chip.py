"""flight-exact-4chip on four CPU devices: correct when sound, and not
correct with the cross-chip exchange left out.  Runs in a child process
that asks for four host devices (the test process keeps one)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = textwrap.dedent("""
    import sys
    sys.path[:0] = [{root!r}, {src!r}]
    import repro
    import pytest
    from bench.tests import _tiny
    mp = pytest.MonkeyPatch()
    if {fault!r}:
        _tiny.plant(mp, {fault!r})
    out = _tiny.run("flight-exact-4chip")
    print("CORRECT", out["correct"], out["checks"])
""")


@pytest.mark.parametrize("fault,want", [("", True), ("no_exchange", False),
                                        ("half_batch", False)])
def test_four_devices(fault, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"), fault=fault)
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [x for x in res.stdout.splitlines() if x.startswith("CORRECT")]
    assert line and line[-1].split()[1] == str(want), res.stdout[-2000:]
