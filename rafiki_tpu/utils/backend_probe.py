"""The accelerator's device count, read in a child under a timeout.

A process that starts chip-holding children (the admin in process/hosts
mode, a host agent, the doctor) must never initialise a JAX backend
itself: a chip belongs to one process at a time, and the parent would
keep it from every worker it starts. :func:`probe_device_count` asks a
short-lived child interpreter instead; the child is killed when the
timeout runs out.

The probe child TAKES THE CHIP for its lifetime. It therefore cannot run
while a worker of the same host holds the chip — callers probe before
they start any worker (admin boot, agent boot), or accept that a probe
against a busy host reports 0 devices with the backend's own error.
"""

from __future__ import annotations

import os
import subprocess
import sys

PROBE_TIMEOUT_S = float(os.environ.get("RAFIKI_BACKEND_PROBE_TIMEOUT_S", 75))

_PROBE_CODE = (
    "import jax; print('DEVICE_COUNT=%d' % len(jax.devices()))"
)


def probe_device_count(
    timeout_s: float = PROBE_TIMEOUT_S,
) -> tuple[int, str | None]:
    """(device_count, error) for the backend this environment selects.
    ``device_count`` is 0 on any failure; ``error`` carries the reason
    (None on success). The child has exited — or been killed — by the
    time this returns, so the chip is free for the caller's workers."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_CODE], env=dict(os.environ),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return 0, f"backend probe killed after {timeout_s:.0f}s"
    except OSError as e:
        return 0, f"backend probe failed to launch: {e!r}"
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICE_COUNT="):
            return int(line.split("=", 1)[1]), None
    tail = proc.stdout.strip().splitlines()
    return 0, (f"backend probe rc={proc.returncode}: "
               + (tail[-1] if tail else "no output"))
