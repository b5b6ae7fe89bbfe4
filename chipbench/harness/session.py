"""One benchmark process: compile cache, devices, compile counting, spans,
trace capture and peak memory.

Import order matters: nothing here imports JAX at module level, so
``run.py`` can refuse a missing chip before any device is touched.
"""
from __future__ import annotations

import contextlib
import glob
import os
import sys
import time
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Fixed, inside the checkout: the path is part of the cache's key.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "chipbench", ".trace")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def add_program_to_path() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program's sources are not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    every executable cached (the small surgery programs too)."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size cap (an inherited one evicts entries as they are written)
    jax.config.update("jax_compilation_cache_max_size", -1)


def devices(chips: int, allow_cpu: bool = False):
    """The first ``chips`` accelerators; NoChip when there are none."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable backend: {e}") from None
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s), "
                     "and the benchmark runs on a TPU only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    d0 = devs[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devs:
        try:
            st = d.memory_stats()
        except Exception:        # noqa: BLE001 - backends without stats
            st = None
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs built (backend compiles and persistent-cache loads)
    from ``jax.monitoring`` events, so compiles inside a window show."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.on = False

        def listener(event, duration_secs, **kw):
            if self.on and event in self.EVENTS:
                self.count += 1
        self._listener = listener
        monitoring.register_event_duration_secs_listener(listener)

    @contextlib.contextmanager
    def counting(self):
        self.on = True
        try:
            yield self
        finally:
            self.on = False

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._listener)


def span(name: str, **kw):
    """A host span in the profiler's trace (a no-op when none is live)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


class Capture:
    """A ``jax.profiler`` capture of part of a window, read back as
    ``ProfileData`` once stopped.  Python tracing is off and host tracing
    keeps only annotations (level 1): either would slow every call of the
    host loop it is meant to observe, and fill the trace."""

    def __init__(self, directory: str = TRACE_DIR):
        self.directory = directory

    def start(self) -> None:
        import shutil
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        return jax.profiler.ProfileData.from_file(found[-1])

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.directory, ignore_errors=True)


def now() -> float:
    return time.perf_counter()
