"""What the entry points that run on the chip share (``chip_smoke.py``
and the serve and train launchers): the device check, JAX's persistent
compilation cache, the seconds spent compiling and a compiled program's
device bytes.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``:
a fixed path, because the directory is part of each entry's key, so a
later run from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO / ".jax_cache"


def require_tpu() -> list:
    """The devices JAX holds.  Exits non-zero when they are not TPUs, so
    a run meant for the chip never goes on on the CPU in its place."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX holds {len(devices)} {devices[0].platform} "
            f"device(s) ({devices[0].device_kind}); this runs on the chip")
    return devices


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def program_bytes(memory) -> int:
    """Device bytes a compiled program holds while it runs, from its
    ``memory_analysis()``: arguments, outputs and temporaries, with a
    donated argument that becomes an output counted once."""
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes)


class CompileTimer:
    """Sums the seconds JAX spends tracing, lowering and compiling while
    the ``with`` block runs, from JAX's own ``/jax/core/compile/*``
    duration events."""

    def __init__(self):
        self.seconds = 0.0

    def _on_event(self, event: str, seconds: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += seconds

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)
