"""CPU subprocesses with a forced XLA host-device count.

A process's jax backend is initialized once, so anything that needs N fake
CPU devices (multi-device tests, the dist-scaling benchmark) must run in a
child process with its own ``XLA_FLAGS``.  This is the one place the child
environment is built — tests and benchmarks share it so the two can't
drift.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

# Directory containing the ``repro`` package (the repo's src/), handed to
# the child as PYTHONPATH so it resolves the same checkout as the parent.
_SRC = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str, devices: int = 8, *,
                      timeout: int = 420) -> subprocess.CompletedProcess:
    """Run ``code`` in a fresh interpreter with ``devices`` fake devices."""
    # JAX_PLATFORMS=cpu: the children exist for fake host devices, and must
    # never reach for an accelerator the parent process may hold
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=_SRC)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def check_in_subprocess(code: str, devices: int = 8, *,
                        timeout: int = 420) -> str:
    """Like :func:`run_in_subprocess` but raises on failure; -> stdout."""
    out = run_in_subprocess(code, devices, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-3000:])
    return out.stdout
