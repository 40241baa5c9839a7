"""Lazy jax platform selection.

``ServiceSettings.backend`` ("auto" | "cpu" | "tpu") selects the accelerator
platform, but importing jax costs seconds of cold-start and hundreds of MB of
RSS — a parser or reader service must never pay that. So the Service records
the request here without importing jax, and jax-using components (the scorer's
``_ensure_scorer``) resolve it right before their first jax op.

``backend: cpu`` / ``backend: tpu`` is a requirement, not a hint: a platform
that cannot be had raises :class:`BackendUnavailable` and the component fails
to boot. ``auto`` takes what jax finds; either way the resolved platform is
logged once and reported by ``GET /admin/xla``.
"""
from __future__ import annotations

import logging
from typing import Optional

_requested: Optional[str] = None
_logged = False


class BackendUnavailable(RuntimeError):
    """The platform ``backend:`` names cannot be initialised in this process."""


def request_platform(name: Optional[str]) -> None:
    """Record the platform choice (no jax import). "auto"/None = whatever
    jax picks."""
    global _requested
    _requested = name if name in ("cpu", "tpu") else None


def requested_platform() -> str:
    return _requested or "auto"


def apply_platform_pin() -> str:
    """Pin jax to the requested platform, initialise the backend and return
    the platform it resolved to. Call before the first jax op."""
    global _logged
    import jax

    name = _requested
    if name is not None:
        # a listed platform that cannot initialise is an error and the first
        # entry is the default backend; "cpu" stays listed behind "tpu" so
        # the scorer's host twin can still reach the CPU backend
        jax.config.update("jax_platforms",
                          "tpu,cpu" if name == "tpu" else name)
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise BackendUnavailable(
            f"backend {requested_platform()!r}: jax cannot initialise it "
            f"({exc})") from exc
    platform = devices[0].platform
    if name is not None and platform != name:
        # jax_platforms only takes effect before the first backend
        # initialisation; something in this process got there first
        raise BackendUnavailable(
            f"backend {name!r} requested but jax is already running on "
            f"{platform!r} in this process")
    if not _logged:
        _logged = True
        logging.getLogger(__name__).info(
            "jax backend resolved: requested=%s platform=%s device_kind=%s "
            "devices=%d", requested_platform(), platform,
            devices[0].device_kind, len(devices))
    return platform
