"""Process-wide accelerator-backend probe.

One answer to "is jax's default backend an accelerator?", shared by
every auto-mode gate (the verify coalescer's device windows, the node's
coalescer boot decision, the adaptive host/device crossover) so the
gates can never disagree within a process and a new platform string is
added in exactly one place.

``jax.default_backend()`` initializes an XLA backend, which a host-only
node may otherwise never pay for (seconds of import + backend init).
When ``JAX_PLATFORMS`` pins a host-only platform set — every CPU test
run does — the probe answers False without importing jax at all; only
an unpinned environment (where a device may genuinely exist) pays the
probe, once per process.

A chip belongs to ONE process. A launcher that starts several nodes on
one host pins ``JAX_PLATFORMS=cpu`` on every child but the chip's owner
(e2e/runner.py does); a node that was asked for the device and cannot
open it fails at boot rather than running host-only in silence.
"""

from __future__ import annotations

import os
import sys

ACCELERATOR_BACKENDS = ("tpu",)

_probe: bool | None = None


def _pinned_platforms() -> list[str]:
    plats = os.environ.get("JAX_PLATFORMS", "")
    return [p.strip().lower() for p in plats.split(",") if p.strip()]


def _host_only_pinned() -> bool:
    """True when JAX_PLATFORMS pins a platform set with no accelerator
    in it — the one parse both probes share."""
    plats = _pinned_platforms()
    return bool(plats) and not any(p in ACCELERATOR_BACKENDS for p in plats)


def device_requested() -> bool:
    """True when the operator named an accelerator in JAX_PLATFORMS."""
    return any(p in ACCELERATOR_BACKENDS for p in _pinned_platforms())


def accelerator_backend(required: bool = False) -> bool:
    """True when jax's default backend is an accelerator (cached).

    A backend that fails to initialize raises when the operator asked
    for the device — ``JAX_PLATFORMS`` names it, or ``required`` (a
    plane forced on) — so a chip that is missing or held by another
    process stops the boot. Only an environment that named nothing gets
    the logged "no accelerator" answer.
    """
    global _probe
    if _probe is None:
        if _host_only_pinned():
            _probe = False
        else:
            import jax

            try:
                _probe = jax.default_backend() in ACCELERATOR_BACKENDS
            except RuntimeError:
                if required or device_requested():
                    raise
                import logging

                logging.getLogger(__name__).warning(
                    "jax backend init failed and no accelerator was "
                    "requested; running host-only",
                    exc_info=True,
                )
                _probe = False
    return _probe


def plane_wanted(mode: str) -> bool:
    """Whether a booting node starts a device plane (verify coalescer,
    hash plane) whose knob reads ``mode``: "off" never, "auto" only on
    an accelerator backend, "on" always — host windows on a host-only
    pin, but a device that fails to OPEN stops the boot: the operator
    asked for the plane, not for a silent host-only node."""
    if mode == "off":
        return False
    probe = accelerator_backend(required=(mode == "on"))
    return mode == "on" or probe


def accelerator_backend_live() -> bool:
    """True when an accelerator backend is ALREADY initialized in this
    process. NEVER triggers backend init, so it is safe on hot paths.
    Steady-state gates (the adaptive crossover, the coalescer's
    per-window device check) use this: a process that never initialized
    an accelerator has, by construction, no device work to route or
    calibrate — the node's boot-time :func:`accelerator_backend` probe
    is what brings the backend up on accelerator deployments.
    """
    if _host_only_pinned():
        return False
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    # initialized backends only — xla_bridge populates _backends as
    # platforms come up; reading it never inits one
    return any(
        name in ACCELERATOR_BACKENDS for name in jax._src.xla_bridge._backends
    )
