"""Shared bench timing/sync helpers for bench.py and bench_pieces.py.

 - **Sync is ``block_until_ready``.**  JAX dispatch is asynchronous, so a
   timed region ends with it.  ``chip_smoke.py`` checks the semantics on
   the chip every run: it times one large matmul to ``block_until_ready``
   and to a one-element device->host fetch and prints both.
 - **Kernel-level timings are amortized.**  ``timed_amortized`` runs REPS
   dependent invocations inside ONE jit (the carry feeds back into an
   operand so XLA cannot CSE or reorder the calls) and divides, so the
   per-dispatch host cost does not sit in a per-kernel number.
"""

import time

import jax
import jax.numpy as jnp


def device_sync(x):
    """Block until ``x`` is computed."""
    jax.block_until_ready(x)


def sync_frame(frame):
    """Force completion of a frame's device work (async dispatch barrier)."""
    jax.block_until_ready([v.data for v in frame.vecs if v.data is not None])


def timed_amortized(fn_build, *args, reps: int = 20) -> float:
    """Milliseconds per invocation of ``fn_build(acc, *args) -> new acc``,
    timed as ``reps`` dependent iterations inside one jit: a compile +
    warm-up pass, then the timed pass.
    """
    @jax.jit
    def _reps(*a):
        def body(i, acc):
            return fn_build(acc, *a)
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

    device_sync(_reps(*args))     # compile + warmup
    t0 = time.perf_counter()
    device_sync(_reps(*args))
    return (time.perf_counter() - t0) / reps * 1e3
