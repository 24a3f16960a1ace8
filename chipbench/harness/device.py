"""The chip under the run: which one, its compile cache, its memory, its clock."""
from __future__ import annotations

import contextlib
import os

from harness.peaks import peaks_for

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(SystemExit):
    pass


def check_devices(chips: int) -> dict:
    """Platform, kind and count of the devices JAX sees. Anything but
    ``chips`` TPUs of a kind in the peaks table stops the run with a
    non-zero exit: there is no CPU fallback."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX sees {info['count']} {info['platform']} device(s)")
    if info["count"] < chips:
        raise NoChip(f"{chips} chips asked for, {info['count']} found")
    peaks_for(info["kind"])
    return info


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept, so
    only a cell's first run in a checkout compiles."""
    import jax

    path = os.environ.get(CACHE_ENV) or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(n: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def sync() -> None:
    """Wait until every array JAX holds is computed."""
    import jax

    jax.block_until_ready(jax.live_arrays())


class CompileCounter:
    """Counts programs lowered (compiled, or fetched from the persistent
    cache) and compiled by the backend while it is active."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if not self.active:
            return
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1

    @contextlib.contextmanager
    def counting(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False
