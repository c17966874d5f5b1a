"""The device a run measures: the accelerator check, compile counting and
the memory peak."""
from __future__ import annotations

from typing import Dict


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_accelerator(chips: int) -> Dict[str, object]:
    """The device description, or :class:`NoAccelerator` when JAX's
    default backend is the CPU or holds fewer than ``chips`` devices.
    There is no fallback: a CPU number is never a device metric."""
    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    if backend == "cpu":
        raise NoAccelerator("JAX found no accelerator (default backend is "
                            "the CPU); this benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)} {backend} devices")
    return describe(chips)


def describe(chips: int) -> Dict[str, object]:
    """Platform, ``device_kind`` and count of the devices a cell uses."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": min(chips, len(jax.devices()))}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's devices (0 where the
    backend keeps no statistics)."""
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class CompileCounter:
    """Counts executables JAX lowers (each new one is lowered before it is
    compiled or loaded from the persistent cache) and persistent-cache
    hits."""

    def __init__(self):
        import jax
        self.lowered = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
