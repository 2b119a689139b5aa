"""Host-device count for CPU rehearsals of multi-device paths.

``force_host_device_count`` is the one sanctioned way to request N host
platform devices. It must run before the jax backend initializes
(importing jax is fine; the flag is read at first device query), and it
APPENDS to ``XLA_FLAGS`` — user-set flags survive, and an existing
device-count flag is replaced rather than duplicated.
"""
from __future__ import annotations

import os

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count(n: int) -> None:
    """Ask the CPU platform for ``n`` devices by amending ``XLA_FLAGS``
    in place (replace our flag if present, keep everything else)."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_DEVICE_COUNT_FLAG)]
    flags.append(f"{_DEVICE_COUNT_FLAG}={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
