"""Shared on-device timing protocol (the bench.py fence).

Every benchmark in the repo (bench.py, flash_bench, the per-op harness)
uses THIS helper so protocol fixes land once:

* async dispatch: `step(i)` must enqueue without blocking
  (``return_numpy=False`` / raw jitted calls);
* one host read of the last step's scalar at the end is the fence: a
  device runs one process's dispatches in order, so the read returns
  only after all of them ran;
* the fence's own round trip is measured on a fresh device scalar from a
  PRE-COMPILED probe (timing the first call would fold its compile time
  into the round trip and over-subtract) and subtracted.
"""

import time

import numpy as np


def timed_steps(step, steps, warmup=2, fetch=None):
    """Run ``steps`` async steps of ``step(i)``; returns (seconds, last).

    ``fetch(out) -> float`` materializes one scalar from a step's result
    (the fence); default reads element 0 of out[0].
    """
    import jax
    import jax.numpy as jnp

    if fetch is None:
        def fetch(out):
            return float(np.asarray(out[0]).reshape(-1)[0])
    out = None
    for i in range(warmup):
        out = step(i)
    _ = fetch(out)                                  # drain pipeline
    probe_fn = jax.jit(lambda x: x + 1)
    _ = float(np.asarray(probe_fn(jnp.float32(0))))  # compile + run once
    probe = probe_fn(jnp.float32(1))                 # fresh, no host cache
    t = time.perf_counter()
    _ = float(np.asarray(probe))
    rtt = time.perf_counter() - t
    t0 = time.perf_counter()
    for i in range(steps):
        out = step(warmup + i)
    last = fetch(out)                               # fences the chain
    wall = time.perf_counter() - t0
    dt = wall - rtt
    if dt <= 0:
        raise RuntimeError(
            "timed window (%.1f ms) did not exceed the fence RTT "
            "(%.1f ms): raise the step count"
            % (wall * 1e3, rtt * 1e3))
    return dt, last
