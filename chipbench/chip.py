"""Device helpers kept with the benchmark, so that no change to the program
can move them: the chip check, JAX's persistent compilation cache, a
compiled program's device bytes and the seconds JAX spends compiling.

Copied from ``src/repro/launch/chip.py``; the cache here always lives in
the checkout (``.chipbench/jax_cache``), never where the environment
points, so that two checkouts measured on one machine share nothing.
"""
from __future__ import annotations

import collections
import json
import pathlib

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".chipbench" / "jax_cache"
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def require_chips(n: int) -> list:
    """The devices JAX holds.  Exits non-zero unless they are TPUs, at
    least ``n`` of them, so that a run meant for the chip never goes on
    on the CPU in its place."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise SystemExit(
            f"no chip for this cell: JAX holds {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind}); "
            f"the cell needs {n} TPU chip(s)")
    return devices[:n]


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, not a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on, for every program however
    small or quick to compile, so that only a checkout's first run of a
    cell compiles.  Returns the directory."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def program_bytes(memory) -> int:
    """Device bytes a compiled program holds while it runs, from its
    ``memory_analysis()``: arguments, outputs and temporaries, with a
    donated argument that becomes an output counted once."""
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes)


class CompileTimer:
    """Sums the seconds JAX spends tracing, lowering, compiling or loading
    from the persistent cache while the ``with`` block runs, from JAX's own
    ``/jax/core/compile/*`` duration events, and counts the programs that
    missed the persistent cache (compiled afresh)."""

    def __init__(self):
        self.seconds = 0.0
        self.events = collections.Counter()

    def _on_duration(self, event: str, seconds: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += seconds

    def _on_event(self, event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[-1]] += 1

    @property
    def cache_misses(self) -> int:
        return self.events["cache_misses"]

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
