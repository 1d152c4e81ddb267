"""The look for a chip, the device's identity and memory, and the table of
peaks. The only module besides the drivers that imports jax."""

from __future__ import annotations

import json
import os
import sys


def require_tpu(chips: int) -> dict:
    """jax's view of the machine, or exit non-zero: a measurement path that
    finds no TPU, or fewer chips than the cell asks for, fails."""
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    try:
        import jax

        devs = jax.devices()
    except Exception as e:  # jax raises RuntimeError subclasses of many kinds
        print(f"benchmark: jax found no accelerator: {e!r}", file=sys.stderr)
        raise SystemExit(3)
    info = describe(devs)
    if info["platform"] != "tpu" or info["count"] < chips:
        print(
            f"benchmark: need {chips} TPU chip(s), jax reports {info}; "
            "nothing was run",
            file=sys.stderr,
        )
        raise SystemExit(3)
    return info


def describe(devs) -> dict:
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peaks(device_kind: str) -> dict:
    """Published peaks of this device. A device not in the table is an
    error, not a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {path}"
        )
    return table[device_kind]
