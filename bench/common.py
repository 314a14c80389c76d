"""Pieces every cell of the benchmark shares: finding files by name, the
seeded generators, the compile counter, host spans and tail arithmetic."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
clock = time.perf_counter

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by its path (file names may hold '-' and '.')."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng(seed: int, stream: int):
    """A NumPy generator for one use of the seed (data, arrivals, ...)."""
    import numpy as np
    return np.random.default_rng([int(seed), int(stream)])


def projections(seed: int, shape: tuple):
    """The SRP projection matrix, made on the device from the seed in one
    jitted call, in float32 as the program serves it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    words = np.random.SeedSequence([int(seed), 7]).generate_state(
        2, dtype=np.uint32)
    make = jax.jit(lambda k: jax.random.normal(
        jax.random.wrap_key_data(k), shape, jnp.float32))
    return make(jnp.asarray(words))


class CompileCounter:
    """Counts executables built or loaded (JAX's backend-compile event),
    so that a compile inside a measured window shows."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Spans:
    """Host spans in the profiler's own trace, named ``bench:<what>``.  The
    trace reduction labels device idle time by the span open during it."""

    on: bool = False

    def __call__(self, what: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench:" + what)


def nearest_rank(values, q: float) -> float:
    """The q-quantile (0 < q <= 1) by nearest rank: the smallest value with
    at least a share q of the sample at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    return v[max(math.ceil(q * len(v)) - 1, 0)]


def median(values) -> float:
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    m = len(v) // 2
    return v[m] if len(v) % 2 else 0.5 * (v[m - 1] + v[m])
