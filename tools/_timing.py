"""Device timing by fetch-sync: chain the work on the device, fetch one
scalar of the result, subtract one host↔device round trip.

A device→host fetch of result bytes is a valid execution barrier on every
backend — the bytes cannot arrive before the program that makes them has
run. So is ``jax.block_until_ready`` on an attached chip; this module
predates measuring on one and uses the fetch, and the round trip it
subtracts is microseconds there (it was tens of milliseconds on the backend
these helpers were first written against). Replacing the protocol with a
plain ``block_until_ready`` around the timed region belongs to the benchmark
PR (ROADMAP S0), which owns every timed number; until then the study tools
(tpu_attn_tune, tpu_kernel_check, tree_study, decode_study, time_to_acc,
lm_time_to_loss) share this one implementation so their numbers stay
comparable. It lives beside them: no module of the package imports it.

Protocol:

  1. measure the host round-trip latency on an already-ready buffer,
  2. enqueue all reps (dependency-free launches back-pressure fine; for
     per-step numbers of a training loop, fold the steps into ONE jitted
     ``lax.scan`` so Python dispatch is off the timed path entirely),
  3. synchronise by fetching one scalar of the final output,
  4. subtract the round-trip latency.

Last checked against hardware on a TPU v5e, 2026-08-02: a bf16 4096³ matmul
timed at 187 TFLOP/s (95 % of the 197 TFLOP/s peak) under this protocol.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def fetch_scalar(out) -> float:
    """Device→host fetch of one element of the first array leaf — an
    execution barrier on every backend (module docstring)."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.ravel(leaf)[0])


def measure_rtt(reps: int = 10) -> float:
    """Seconds of pure host↔device round-trip on an already-ready buffer
    (median of ``reps`` samples — a host clock has outliers)."""
    tiny = jnp.zeros((1,), jnp.float32)
    fetch_scalar(tiny)  # materialise + first-fetch path
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fetch_scalar(tiny)
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def timeit_chained(step, carry, consts=(), reps: int = 20,
                   target_s: float = 1.5) -> float:
    """Per-iteration seconds of ``step(carry, *consts)`` chained inside ONE
    jitted fori_loop, synchronised by a device→host fetch minus RTT.

    The protocol for sub-ms ops (per-call Python dispatch stays off the
    timed path); shared by tools/tpu_kernel_check.py and tools/decode_study.py.
    Requirements on ``step`` (violations produce fantasy numbers):

      * big operands enter via ``consts`` (jit arguments) — a closed-over
        concrete array bakes into the HLO as a constant (the
        constant_bloat lint rule, PERF_HISTORY.md §6);
      * the carry must depend on every output of the op under test through
        a NON-LINEAR function (e.g. ``jnp.sum(out**2)``) or by carrying the
        full output. A slice feedback lets XLA dead-code-eliminate the rest
        of the op; a *linear* reduction (plain ``sum``) of a linear op lets
        XLA reassociate (``sum(R@f) == colsum(R)·f``) and hoist the O(n·d)
        work out of the loop — observed as 0.0 ms readings.

    The trip count is a traced argument (fori_loop lowers to while_loop),
    so adaptively scaling reps until the loop body is ~``target_s`` of
    device time costs no recompile.
    """
    @jax.jit
    def loop(c, consts, n_iters):
        return jax.lax.fori_loop(0, n_iters, lambda i, c: step(c, *consts), c)

    n0 = jnp.asarray(reps, jnp.int32)
    out = loop(carry, consts, n0)
    fetch_scalar(out)
    rtt = measure_rtt()
    t0 = time.perf_counter()
    out = loop(carry, consts, n0)
    fetch_scalar(out)
    total = time.perf_counter() - t0 - rtt
    if total < target_s:
        scale = min(int(target_s / max(total, 0.01)) + 1, 200)
        n1 = jnp.asarray(reps * scale, jnp.int32)
        t0 = time.perf_counter()
        out = loop(carry, consts, n1)
        fetch_scalar(out)
        return max(time.perf_counter() - t0 - rtt, 0.0) / (reps * scale)
    return max(total, 0.0) / reps


def timeit_device(fn, *args, reps: int = 30, rtt: float | None = None) -> float:
    """Average seconds per ``fn(*args)`` call with fetch-sync.

    Warms up (compile + first run), enqueues ``reps`` launches, fetches one
    scalar of the last output, subtracts the measured round trip. For
    multi-step training loops prefer folding steps into one jitted scan and
    timing that single call.
    """
    if rtt is None:
        rtt = measure_rtt()
    out = fn(*args)
    fetch_scalar(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    fetch_scalar(out)
    return max((time.perf_counter() - t0 - rtt) / reps, 0.0)
