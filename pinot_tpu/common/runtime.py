"""Which backend this process runs on, chosen per role and reported as is.

A chip belongs to one process at a time, and the installed JAX carries on on
the CPU with a warning when the platform it would have preferred cannot
initialise. So each role states its platform before anything can touch a
device: the server is the one process that owns a chip and fails to start
without one, broker and controller stay on the CPU. `describe()` is what the
roles log at start-up and serve under `GET /health/ready` (`runtime`), so a
launcher can check from outside which process held what.
"""

from __future__ import annotations

import os
import threading

import jax

import pinot_tpu

_cache_lock = threading.Lock()
_cache_events = {"requests": 0, "hits": 0, "misses": 0}
_CACHE_EVENT_KEYS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    # recorded when a freshly compiled program is written to the cache
    "/jax/compilation_cache/cache_misses": "misses",
}


def _on_jax_event(event: str, **_kw) -> None:
    key = _CACHE_EVENT_KEYS.get(event)
    if key is not None:
        with _cache_lock:
            _cache_events[key] += 1


jax.monitoring.register_event_listener(_on_jax_event)


def compile_requests() -> int:
    """Compile requests this process has made so far (jax's own event; a
    request answered from the persistent cache counts too)."""
    with _cache_lock:
        return _cache_events["requests"]


def pin_cpu() -> dict:
    """Broker / controller: the CPU is the only platform this process may
    initialise, whatever accelerator the host has."""
    pinot_tpu.select_cpu()
    return describe()


def require_device() -> dict:
    """Server: with JAX_PLATFORMS unset the platform is `tpu` and nothing
    else, so a chip this process cannot get is a start-up error, not a CPU
    server. An explicit JAX_PLATFORMS (tests, CI, rehearsal: `cpu`) is taken
    as given and reported."""
    if not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", "tpu")
    return describe()


def describe() -> dict:
    """The backend as JAX reports it (initialises it on first call), the
    compile cache's place and counters, and the native library's state."""
    from pinot_tpu import native
    from pinot_tpu.ops import groupby_pallas

    devices = jax.local_devices()
    with _cache_lock:
        cache = dict(_cache_events)
    return {
        "platform": devices[0].platform,
        "deviceKind": devices[0].device_kind,
        "deviceCount": len(devices),
        "devices": [
            {"id": d.id, "coords": list(getattr(d, "coords", None) or []) or None}
            for d in devices
        ],
        # the launcher's per-process chip pin, when it set one
        "visibleChips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "pallasInterpret": groupby_pallas.interpret_mode(),
        "compileCache": {
            "dir": pinot_tpu.COMPILE_CACHE_DIR,
            "enabled": bool(jax.config.jax_enable_compilation_cache),
            **cache,
        },
        "native": native.status(),
    }
