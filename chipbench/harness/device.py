"""The chip the run holds: found, described, and its peak memory."""
from __future__ import annotations

import sys
from typing import Dict


def require_accelerator(jax, chips: int):
    """Exit non-zero, printing no result, unless JAX's devices are TPUs
    and there are as many as the cell asks for. No CPU fallback."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU found (JAX's first device is "
                 f"{devs[0].platform!r}); the benchmark runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX finds "
                 f"{len(devs)}")
    return devs[:chips]


def describe(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no statistics)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks)
