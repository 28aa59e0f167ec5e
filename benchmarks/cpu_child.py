"""Run a fake-mesh benchmark child on host CPU devices.

The mesh benches build their meshes from ``--xla_force_host_platform_
device_count`` CPU devices in a child process, so the flag never leaks into
the parent.  The child's environment pins ``JAX_PLATFORMS=cpu``: a parent
that has already touched JAX holds the accelerator, and a child reaching
for it would fail or hang — and a mesh of one chip's worth of fake devices
would be no chip measurement anyway.  Rows built from a child's numbers
carry ``platform=cpu``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLATFORM = "cpu"


def run_cpu_child(code: str, *args) -> dict:
    """Run ``code`` (which prints one JSON line last) and return that object.

    A child that exits non-zero or prints no JSON raises ``RuntimeError``
    with the end of its stderr, so the failure reaches the bench harness.
    """
    r = subprocess.run(
        [sys.executable, "-c", code, *map(str, args)],
        capture_output=True,
        text=True,
        env={
            **os.environ,
            "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": PLATFORM,
        },
        cwd=ROOT,
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    if r.returncode != 0 or not lines:
        raise RuntimeError(
            f"benchmark child exited {r.returncode}: {r.stderr[-2000:]}"
        )
    return json.loads(lines[-1])
