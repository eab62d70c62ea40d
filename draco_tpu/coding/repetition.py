"""Repetition code ("maj_vote") — grouping + on-device majority vote.

Reference semantics: workers are partitioned into groups of size r; members of
a group share a shuffle seed and therefore compute *identical* batches
(rep_worker.py:89); the PS takes, per group, the value held by a strict
majority of members — implemented there as a Boyer–Moore pass with bitwise
np.array_equal (rep_master.py:154-168) — then averages the group winners.

TPU-native formulation: per-worker gradients form (n, d); reshape to
(G, r, d); the vote is an argmax over per-member "agreement counts". Equality
testing is sound here for the same reason it is in the reference: group
members run the identical deterministic computation on identical inputs (a
vmap lane under XLA), so honest replicas agree bitwise while an attacked row
differs.

Cost: the vote is O(r·d) per group, not O(r²·d) — each row is folded to two
position-sensitive 32-bit hashes of its raw bits (one O(d) pass per row) and
the (r, r) agreement matrix is built from those 64-bit fingerprints instead
of materialising the (G, r, r, d) elementwise-equality tensor. Honest
replicas are bit-identical, so hash-equality <=> bit-equality up to a ~2^-64
accidental collision. Note the fingerprint compares raw BITS where the old
elementwise `==` compared values: -0.0 vs +0.0 now count as a disagreement
(stricter) and a NaN row now agrees with its own bit-identical replicas (the
reference's np.array_equal treats NaN as always-unequal,
rep_master.py:154-168 — either way a lone NaN row loses the vote to an
honest majority).

Adversarial collision resistance — the honest threat-model ladder:

1. *Oblivious corruption* (the in-scope simulated error modes: rev_grad /
   constant / random / alie / ipm, attacks.py): any ~2^-64 pair of hashes
   suffices; collisions are accidental only.
2. *Adaptive adversary who does NOT know the salt*: the per-position mixing
   must be nonlinear and position-asymmetric. A linear hash
   h = Σ bits_j·w_j mod 2^32 — even with secret odd weights — is
   constructibly collidable (flip the top bit of any two positions: the
   difference 2^31·(w_i + w_j) vanishes because w_i + w_j is even). An
   XOR-symmetric salted avalanche sum Σ mix(bits_j ^ pos_j ^ s) is ALSO
   collidable salt-independently (swap the ``bits ^ pos`` values between
   two positions: the salt XORs out and the term multiset is unchanged).
   Here position therefore enters by *wrapping addition between two
   avalanche rounds* — Σ mix(mix(bits_j ^ s) + posmix_j) — so a
   salt-oblivious forgery would need a differential pair of the avalanche
   with constant output difference across all salts, which splitmix32 does
   not admit; only swapping bit-identical elements "collides", and that is
   the identity. (Regression-tested against both constructions' attacks.)
3. *Adversary who knows the salt*: each term is an invertible function of
   the element, so a colliding row is constructible by inverting the
   avalanche — NO seed-derived fingerprint can beat this. The training
   step derives its per-step key from ``cfg.seed`` (step.py), and the
   reference's whole discipline is that every participant shares that seed
   (rng.py, reference src/util.py:17), so an in-protocol white-box
   adversary is in this tier. For real mutually-untrusting deployments
   either source the key from PS-private entropy (pass your own ``key``)
   or set ``vote_check="exact"`` — bitwise np.array_equal semantics, the
   reference's exact-recovery guarantee (rep_master.py:162) at O(r²·d)
   memory traffic.

With ``key=None`` the salts are fixed public constants: bit-exact
deterministic, fine for tiers 1 and (heuristically) 2, direct-call/test use.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _splitmix32(z: jnp.ndarray) -> jnp.ndarray:
    """splitmix32 finaliser: a bijective xor-shift/multiply avalanche. Every
    output bit depends nonlinearly on every input bit — the property the
    collision argument in the module docstring rests on."""
    z = (z ^ (z >> 16)) * jnp.uint32(0x85EBCA6B)
    z = (z ^ (z >> 13)) * jnp.uint32(0xC2B2AE35)
    return z ^ (z >> 16)


def _row_bits(rows: jnp.ndarray) -> jnp.ndarray:
    """Validate the element dtype and bitcast to the matching uint — the one
    place the vote's bit-compare domain (2/4-byte elements) is defined."""
    if rows.dtype.itemsize not in (2, 4):
        raise ValueError(
            f"majority_vote supports 2/4-byte element dtypes "
            f"(bf16/f16/f32/i32 — what the gradient stack ever holds), got "
            f"{rows.dtype}"
        )
    uint = {2: jnp.uint16, 4: jnp.uint32}[rows.dtype.itemsize]
    return jax.lax.bitcast_convert_type(rows, uint)


def _row_fingerprints(rows: jnp.ndarray, key=None, read=None):
    """(G, r, d) — or (G, r, ...), a row laid out in several axes — -> two
    (G, r) uint32 mix-then-sum hashes of each row's bits and, from the same
    sweep, (G, r) bool: the row as stored holds a non-finite element.

    ``read``: optional map of a block of the rows, (G, r, block...), as
    stored to the block the hashes see (:func:`majority_vote`'s
    ``row_map``): the mapped rows are hashed without ever being stored.

    Per position j: keyed avalanche of the element's bits, wrapping-ADD the
    avalanched position, avalanche again, then wrapping-sum over j. The
    shape of the construction is load-bearing (module docstring tier 2): the
    outer avalanche over (inner ^-keyed mix + position) is what kills both
    the linear top-bit-pair attack and the salt-independent position-swap
    attack — position must NOT enter by XOR next to the salt, or the salt
    commutes out of a swap. Two salts give two hashes whose joint accidental
    collision odds are ~2^-64; with ``key`` they are drawn from the PRNG,
    with ``key=None`` they are fixed public constants (deterministic
    direct-call/test path). All elementwise uint32 ops: one O(d) pass per
    row either way.
    """
    if key is None:
        s1 = jnp.uint32(0x9E3779B1)
        s2 = jnp.uint32(0xC2B2AE35)
    else:
        salts = jax.random.bits(key, (2,), jnp.uint32)
        s1, s2 = salts[0], salts[1]
    s2 = s2 ^ jnp.uint32(0x7F4A7C15)

    def terms(block, j):
        """The two hashes' per-position terms of rows[..., j]."""
        bits = _row_bits(block).astype(jnp.uint32)
        posmix = _splitmix32(j * jnp.uint32(2654435761)
                             + jnp.uint32(0x9E3779B9))
        return (_splitmix32(_splitmix32(bits ^ s1) + posmix),
                _splitmix32(_splitmix32(bits ^ s2) + posmix))

    # a row — (d,), or laid out in several axes, positions counting
    # row-major — a block of its first axis at a time (one block where the
    # row is no longer than FINGERPRINT_BLOCK): as one pass over a long row
    # the bit view of the whole stack and the d-length position mix were
    # materialised (6.3 + 1.6 GB at three rows of d = 425 M, on a chip the
    # stack itself fills). The wrapping sum does not care in which order it
    # is taken: same bits. The last block is clamped back inside the row
    # and the positions an earlier block already counted are left out.
    trail = rows.shape[2:]
    d = int(np.prod(trail))
    m, inner = trail[0], d // trail[0]
    mb = min(m, max(FINGERPRINT_BLOCK // inner, 1))
    ones = (1,) * (len(trail) - 1)
    within = jnp.arange(inner, dtype=jnp.int32).reshape(trail[1:])
    axes = tuple(range(2, rows.ndim))

    def body(acc, i):
        start = jnp.minimum(i * mb, m - mb)
        lead = (start + jax.lax.iota(jnp.int32, mb)).reshape((mb,) + ones)
        block = jax.lax.dynamic_slice_in_dim(rows, start, mb, axis=2)
        # the non-finite elements of the block as stored, counted: a third
        # wrapping sum of the hashes' own shape, so that the three are one
        # pass over the block (a row has fewer than 2^32 positions)
        t0 = (~jnp.isfinite(block)).astype(jnp.uint32)
        t1, t2 = terms(block if read is None else read(block),
                       (lead * inner + within).astype(jnp.uint32))
        new = lead >= i * mb
        return tuple(a + jnp.sum(jnp.where(new, t, 0), axis=axes,
                                 dtype=jnp.uint32)
                     for a, t in zip(acc, (t1, t2, t0))), None

    zero = jnp.zeros(rows.shape[:2], jnp.uint32)
    (h1, h2, bad), _ = jax.lax.scan(body, (zero, zero, zero),
                                    jnp.arange(-(-m // mb), dtype=jnp.int32))
    return h1, h2, bad > 0


# positions of a row fingerprinted at a time. A shorter row is one block
# (every CNN and LM the other tests vote on); several blocks with a clamped
# last one: tests/test_lm_maj_vote.py's several-axes and lanes-in-turn
# cases, and the d = 425 M cell
FINGERPRINT_BLOCK = 2**22


@dataclasses.dataclass(frozen=True)
class RepetitionCode:
    n: int
    r: int  # group size

    @property
    def num_groups(self) -> int:
        return self.n // self.r

    def group_of(self, worker: int) -> int:
        return worker // self.r


def build_repetition_code(n: int, r: int) -> RepetitionCode:
    """Byzantine tolerance is (r-1)//2 per group: with r < 3 a single
    adversary ties the vote and the tie-break is arbitrary — config.validate
    enforces r >= 2s+1 whenever worker_fail > 0."""
    if n % r != 0:
        raise ValueError(f"num_workers {n} must be divisible by group_size {r}")
    return RepetitionCode(n=n, r=r)


def majority_vote(code: RepetitionCode, grads: jnp.ndarray,
                  present=None, key=None,
                  method: str = "fingerprint",
                  with_health: bool = False, row_map=None):
    """grads: (n, d) -> (d,) mean over groups of each group's majority row
    ((n, ...) -> (...): a stack whose rows are laid out in several axes, as
    a large one is so that the chip's tiling neither pads n nor makes writing
    one row cost the whole stack; positions count row-major).

    ``present``: optional (n,) bool — absent members (stragglers) neither
    vote nor can win; a group with no present member contributes nothing and
    the group mean renormalises. (The reference PS blocks forever on a
    missing member, rep_master.py:104-116.)

    ``key``: optional PRNG key salting the row fingerprints; pass a per-step
    key (the training step does) so a salt-oblivious adaptive adversary
    cannot construct a fingerprint collision — see module docstring.

    ``method``: ``"fingerprint"`` (default, O(r·d) memory traffic) or
    ``"exact"`` — full pairwise bit-equality at O(r²·d), no collision
    surface at all; the right choice when adversaries may know the
    experiment seed (module docstring tier 3; reference exact-recovery
    semantics, rep_master.py:162).

    ``row_map``: optional ``(fn, mask)`` — the vote is over the stack in
    which every row of ``mask`` ((n,) bool) is ``fn(row)``, ``fn``
    elementwise, and that stack is never stored: ``fn`` is applied to each
    block as the fingerprint sweep reads it and to the winner's row on its
    way out. Same bits as voting over ``where(mask, fn(grads), grads)``;
    the stack is read once (the step's simulated adversary,
    parallel/common.aggregate_flat_grads).

    ``with_health=True`` returns ``(voted, health)`` — the vote's own
    detection record, computed from the agreement matrix the vote already
    built and the sweep that fingerprinted the rows (telemetry metric
    columns; no extra O(d) pass):

      * ``vote_agree``: fraction of present members whose row bitwise
        matches their group's winner — 1.0 is the all-honest state, each
        live corrupted row subtracts 1/|present|;
      * ``flagged_groups``: number of groups containing ≥ 1 dissenting
        present member (the reference PS would have rejected exactly these
        groups' minority rows, rep_master.py:154-168);
      * ``flagged``: (n,) bool — present members out-voted by their group
        (the per-row located-adversary set; absent stragglers are
        known-missing, never "detected");
      * ``bad_rows``: (n,) bool — rows of ``grads`` as handed in (before
        ``row_map``) that hold a non-finite element.
    """
    g, r = code.num_groups, code.r
    rows = grads.reshape((g, r) + grads.shape[1:])
    trail = tuple(range(3, rows.ndim + 1))  # a row's axes, after (G, r, r)

    def mapped(x, which=lambda mask: mask):
        """``x`` as the vote sees it: ``fn`` on the rows that ``which`` of
        the (G, r) mask marks (bool over x's leading axes)."""
        if row_map is None:
            return x
        fn, mask = row_map
        marks = which(mask.reshape(g, r))
        return jnp.where(marks.reshape(marks.shape + (1,) * (
            x.ndim - marks.ndim)), fn(x), x)

    # pairwise-equality counts, (G, r): agree[g, i] = #{j : row_i == row_j}
    if method == "exact":
        bits = _row_bits(mapped(rows))
        eq = jnp.all(bits[:, :, None] == bits[:, None, :], axis=trail)
        bad = ~jnp.all(jnp.isfinite(rows), axis=tuple(range(2, rows.ndim)))
    elif method == "fingerprint":
        # 64-bit row fingerprints (O(r·d)) — see module docstring
        h1, h2, bad = _row_fingerprints(rows, key=key, read=mapped)
        eq = ((h1[:, :, None] == h1[:, None, :])
              & (h2[:, :, None] == h2[:, None, :]))
    else:
        raise ValueError(
            f"method must be 'fingerprint' or 'exact', got {method!r}"
        )
    if present is None:
        pres = jnp.ones((g, r), bool)
        agree = jnp.sum(eq, axis=-1)
    else:
        pres = present.reshape(g, r)
        agree = jnp.sum(eq & pres[:, None, :], axis=-1)  # only present members vote
        agree = jnp.where(pres, agree, -1)  # absent members cannot win
    winner = jnp.argmax(agree, axis=-1)  # (G,)
    if g == 1 and present is None:
        # the one group's row where it lies: a slice, no gather's select,
        # and the mean over one group is the row
        voted = mapped(jax.lax.dynamic_index_in_dim(
            rows[0], winner[0], axis=0, keepdims=False),
            lambda mask: mask[0, winner[0]])
    else:
        picked = mapped(jnp.take_along_axis(
            rows, winner.reshape((g,) + (1,) * (rows.ndim - 1)), axis=1)[:, 0],
            lambda mask: jnp.take_along_axis(
                mask, winner[:, None], axis=1)[:, 0])
        if present is None:
            voted = jnp.mean(picked, axis=0)
        else:
            group_alive = jnp.any(pres, axis=1).astype(grads.dtype)  # (G,)
            voted = (jnp.tensordot(group_alive, picked, axes=1)
                     / jnp.maximum(jnp.sum(group_alive), 1.0))
    if not with_health:
        return voted
    # member i agrees with its group's winner iff eq[g, i, winner_g]
    winner_agree = jnp.take_along_axis(
        eq, winner[:, None, None], axis=2)[:, :, 0]  # (G, r) bool
    flagged = pres & ~winner_agree
    n_pres = jnp.maximum(jnp.sum(pres.astype(jnp.float32)), 1.0)
    health = {
        "vote_agree": jnp.sum((winner_agree & pres).astype(jnp.float32))
        / n_pres,
        "flagged_groups": jnp.sum(jnp.any(flagged, axis=1)
                                  .astype(jnp.int32)),
        "flagged": flagged.reshape(code.n),
        "bad_rows": bad.reshape(code.n),
    }
    return voted, health
