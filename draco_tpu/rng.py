"""Deterministic randomness discipline.

The reference makes every process agree on "who is adversarial at step t" and
"which group shuffles with which seed" by seeding numpy's global RNG with
SEED_=428 on every rank (reference: src/util.py:17,79-103). We keep the
*property* (every participant derives the identical schedule) with
``jax.random`` keys folded from the experiment seed — no global RNG state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def adversary_schedule(seed: int, max_steps: int, num_workers: int, num_fail: int) -> np.ndarray:
    """Boolean mask of shape (max_steps + 1, num_workers).

    ``mask[t, i]`` is True iff logical worker i behaves Byzantine at step t.
    Exactly ``num_fail`` workers per step, sampled without replacement, from a
    schedule every participant can recompute (reference semantics:
    src/util.py:100-103 pre-generates per-step adversary index lists from a
    fixed seed so all ranks agree).
    """
    mask = np.zeros((max_steps + 1, num_workers), dtype=bool)
    if num_fail == 0:
        return mask
    rng = np.random.RandomState(seed)
    for t in range(max_steps + 1):
        idx = rng.choice(num_workers, size=num_fail, replace=False)
        mask[t, idx] = True
    return mask


def straggler_schedule(seed: int, max_steps: int, num_workers: int,
                       num_straggle: int) -> np.ndarray:
    """Boolean mask (max_steps + 1, num_workers): True = worker misses the
    step's deadline (its gradient never arrives).

    The reference only sketched straggler handling (the unreferenced tag-77
    kill switch, resnet_split.py:625-737); here missing workers are
    first-class *erasures* — known positions, unlike Byzantine rows — and the
    schedule is deterministic for the same every-participant-agrees reason as
    :func:`adversary_schedule`. Salted so adversary and straggler draws are
    independent streams.
    """
    mask = np.zeros((max_steps + 1, num_workers), dtype=bool)
    if num_straggle == 0:
        return mask
    rng = np.random.RandomState(seed ^ 0x5A5A5A)
    for t in range(max_steps + 1):
        idx = rng.choice(num_workers, size=num_straggle, replace=False)
        mask[t, idx] = True
    return mask


def group_seeds(seed: int, num_groups: int) -> np.ndarray:
    """Per-group shuffle seeds, identical on every participant.

    Mirrors util.py:79-87: members of a repetition group share a shuffle seed
    so they draw identical batches (that is what makes the bitwise majority
    vote sound, reference: rep_worker.py:89, rep_master.py:162).
    """
    rng = np.random.RandomState(seed)
    return rng.randint(0, 20000, size=num_groups)


def epoch_permutation(seed: int, epoch: int, n: int) -> np.ndarray:
    """Shuffle of ``n`` sample indices for a given epoch from a shared seed.

    Reference re-seeds torch at every epoch with seed+factor*epoch
    (rep_worker.py:89, cyclic_worker.py:88); we fold (seed, epoch) into one
    stream the same agreed-upon way.
    """
    rng = np.random.RandomState((seed * 100003 + epoch * 23) % (2**31 - 1))
    return rng.permutation(n)


def fold(key: jax.Array, *data: int) -> jax.Array:
    """Fold a sequence of ints into a key (step ids, batch ids, worker ids)."""
    for d in data:
        key = jax.random.fold_in(key, d)
    return key


def random_projection_factors_in_graph(seed: int, dim: int) -> jnp.ndarray:
    """The decode-side random projection vector (reference:
    cyclic_master.py:58-61, np.random.normal(loc=1.0) per layer) — same
    distribution (normal, loc=1), deterministic in ``seed``, generated
    from a scalar key INSIDE the jitted step instead of being closed over
    as a d-length host constant.

    Why it exists: a closed-over (d,) float32 array is serialized into the
    XLA program — at the d≈159M LM flagship that is a 638 MB module
    (baselines_out/tpu_lm_scan_lowering.json), which failed to compile in
    four straight attempts (PERF_HISTORY.md §4). Generated in-graph, the program carries only the scalar seed and
    regenerates the identical vector each step (~one HBM pass over d —
    noise vs the step cost). Values differ from the numpy stream (jax
    PRNG, not MT19937); decode is projection-value-agnostic (exact
    recovery for ≤s corruptions regardless of the projection draw), and
    every participant still derives the identical vector, which is the
    property the reference pins (cyclic_master.py:58-61).
    """
    key = jax.random.fold_in(jax.random.key(seed), 7919)
    return 1.0 + jax.random.normal(key, (dim,), jnp.float32)
