"""Cyclic (DFT) gradient code — construction, encode, decode.

Re-derivation of the reference's cyclic code (src/coding.py, decode in
src/master/cyclic_master.py:146-197 with the native error-locator solve in
src/c_coding.cpp:15-84), designed for XLA: fixed shapes, no data-dependent
control flow, complex arithmetic carried as (real, imag) pairs because the
heavy products run on the MXU as real matmuls.

The math (n workers, s Byzantine, ŝ = 2s+1):

  * C = DFT(n)/√n, symmetric unitary. C1 = first n−2s columns, C2 = last 2s.
  * Encoding matrix W (n×n): column k lies in span(C1) and row i is supported
    on the cyclic window {i, …, i+ŝ−1 (mod n)}; W = C1·Q with Q[0,:] = 1.
    Worker i evaluates the ŝ batch-gradients in its window and ships the
    complex combination Σ_k W[i,k]·g_k.
  * Received matrix R (n×d) = W·G + ε where ε has ≤ s nonzero rows.
  * Decode: project R to a vector with a random factor (catch corruption in
    any coordinate), form the syndrome E2 = C2ᴴ·(R·f) — zero iff ε = 0,
    since C2ᴴC1 = 0 — solve the s×s Hankel system for the error-locator
    polynomial, evaluate it on the DFT grid to locate honest rows, then find
    v supported on honest rows with vᵀC1 = e1ᵀ, which gives
    vᵀW = 1ᵀ  ⇒  vᵀR = Σ_k g_k exactly.

Everything below the construction is jit-compatible and shape-static: the
data-dependent "err_indices" selection of the reference
(cyclic_master.py:162-169) becomes `jnp.nonzero(..., size=n-2s)`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from draco_tpu.coding import linalg as linalg_mod
from draco_tpu.ops import coded as ops_coded

PREC = jax.lax.Precision.HIGHEST

# Ridge for the error-locator Hankel solve, shared by the jit decode below and
# the native oracle (native/coding.cpp locator_alpha) so borderline
# rank-deficient cases (< s actually-corrupt rows) rank rows identically on
# both paths. Must sit well above float32 epsilon — see the normalisation
# comment in decode().
# Relative singular-value cutoff for the locator least-squares (σ below
# rcond·σmax truncated). Shared with the native decoder (native/coding.cpp
# locator_alpha, which applies the equivalent rcond² eigenvalue cutoff on its
# float64 gram) so jit and host decodes rank borderline rank-deficient rows
# identically. Sits well above f32 σ noise (~1e-7·σmax) and well below the
# locator system's genuine σmin (cond(A) is O(1e3) for corrupt-row spreads
# seen at n≤32).
LOCATOR_RCOND = 1e-5

# Decode-health row-flagging threshold (relative amplitude): a received row
# whose deviation from the fitted codeword exceeds HEALTH_REL_TOL × the
# RMS row magnitude counts as a located error. Honest-row deviations are
# pure f32 solve noise (~1e-6 relative, even through the m×m fit); the
# in-scope attack payloads sit at O(100×) the honest magnitude (attacks.py
# ADVERSARY=-100) — five orders of margin either side.
HEALTH_REL_TOL = 1e-3

# Golden-ratio Weyl constant for the λ-regularized locator's honest-subset
# bias (ISSUE 15): rows ranked by frac(r·φ) form a maximally-spread subset
# (three-distance theorem), whose DFT extrapolation amplification is O(1)
# (measured 2–9× across study shapes) where the index-contiguous first
# n−2s rows amplify ~4e4× at n=32 — the mechanism behind the PR 10
# quant-noise blowup: with no live adversary the locator magnitudes are
# noise, the chosen subset is noise-driven (or contiguous under the index
# bias), and the exact codeword fit extrapolates the excluded rows with
# that amplification. The spread bias only engages on the λ path; the
# exact λ=0 decode keeps the historical index bias bit-for-bit.
SPREAD_PHI = 0.6180339887498949


def _spread_rank(n: int) -> np.ndarray:
    """Host-side (n,) f32 spread ranks: rank of frac(r·φ) — the λ-path
    tie-break ordering (SPREAD_PHI docstring)."""
    key = (np.arange(n) * SPREAD_PHI) % 1.0
    return np.argsort(np.argsort(key)).astype(np.float32)


# Loud-row forensics threshold (relative ENERGY vs the median present row):
# a present row whose projected energy exceeds LOUD_REL_TOL × the median is
# "loud". A forensic-only accusation signal (obs/forensics.py) — it feeds
# the per-worker accusation columns, never the decode, the located_errors
# count, or the step guard. Rationale: beyond the locator budget (> s
# corrupt rows) exact location is information-theoretically impossible and
# the fitted-codeword deviations above say nothing (any n−2s rows define an
# exact codeword), but the in-scope attack payloads are magnitude outliers
# (O(100×) amplitude ⇒ O(1e4×) energy) while honest encoded rows sit within
# ~6× of their median energy (measured, PERF_HISTORY.md §10) — 30× energy splits the
# two with more than an order of margin either side. The median (not the
# mean) keeps the baseline honest with up to s+1 corrupt rows present, and
# absent rows are excluded from both sides (a zero-filled erasure is
# known-missing, not quiet).
LOUD_REL_TOL = 30.0


# --------------------------------------------------------------------------
# Construction (host-side numpy, run identically by every participant at
# setup — reference: search_w called on all ranks, util.py:185)
# --------------------------------------------------------------------------

def _dft_c(n: int) -> np.ndarray:
    """Symmetric scaled DFT matrix C[p,q] = exp(-2πi·pq/n)/√n."""
    p, q = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return np.exp(-2j * np.pi * p * q / n) / np.sqrt(n)


def _cyclic_support(n: int, hat_s: int) -> np.ndarray:
    """0/1 mask, row i supported on the cyclic window [i, i+hat_s)."""
    mask = np.zeros((n, n))
    for i in range(n):
        mask[i, (np.arange(i, i + hat_s) % n)] = 1.0
    return mask


def _solve_w(c1: np.ndarray, support: np.ndarray) -> np.ndarray:
    """W with columns in span(C1), support matching ``support``, Q[0,:]=1.

    For column k: W[:,k] = C1 @ q with q[0] = 1 and W[j,k] = 0 for all j
    outside the column's support — a small complex least-squares per column.
    """
    n, m = c1.shape
    w = np.zeros((n, n), dtype=complex)
    for k in range(n):
        zero_rows = np.where(support[:, k] == 0)[0]
        a = c1[zero_rows, 1:]
        b = -c1[zero_rows, 0]
        q_tail, *_ = np.linalg.lstsq(a, b, rcond=None)
        q = np.concatenate([[1.0 + 0j], q_tail])
        w[:, k] = c1 @ q
    return w


@dataclasses.dataclass(frozen=True)
class CyclicCode:
    """All constants the encode/decode kernels need, as device-ready arrays."""

    n: int
    s: int
    # encoding matrix entries gathered at each worker's support:
    # w_sel[i, k] = W[i, batch_ids[i, k]], shape (n, hat_s), as re/im pairs
    w_sel_re: np.ndarray
    w_sel_im: np.ndarray
    batch_ids: np.ndarray  # (n, hat_s) int32 — which batches worker i computes
    # syndrome operator C2^H, shape (2s, n)
    c2h_re: np.ndarray
    c2h_im: np.ndarray
    # C1, shape (n, n-2s) — decode's recombination basis
    c1_re: np.ndarray
    c1_im: np.ndarray
    # locator evaluation grid: est[t, j] = exp(+2πi t/n)^j, shape (n, s+1)
    est_re: np.ndarray
    est_im: np.ndarray
    # support-masked full W for the shared-compute encode path, (n, n)
    w_masked_re: np.ndarray
    w_masked_im: np.ndarray
    # full matrices kept for tests / host tooling
    w_full: np.ndarray  # complex (n, n)
    support: np.ndarray  # (n, n) 0/1

    @property
    def hat_s(self) -> int:
        return 2 * self.s + 1


def build_cyclic_code(n: int, s: int) -> CyclicCode:
    if n <= 4 * s:
        raise ValueError(f"cyclic code needs n > 4s, got n={n}, s={s}")
    hat_s = 2 * s + 1
    c = _dft_c(n)
    c1 = c[:, : n - hat_s + 1]  # n-2s columns
    support = _cyclic_support(n, hat_s)
    w = _solve_w(c1, support)
    c2 = c[:, n - hat_s + 1 :]
    c2h = c2.conj().T  # (2s, n)
    batch_ids = np.stack([np.where(support[i] != 0)[0] for i in range(n)]).astype(np.int32)
    w_sel = np.take_along_axis(w, batch_ids, axis=1)  # (n, hat_s)
    t = np.arange(n)
    z = np.exp(2j * np.pi * t / n)
    est = np.stack([z**j for j in range(s + 1)], axis=1)  # (n, s+1)
    f32 = lambda x: np.ascontiguousarray(x, dtype=np.float32)
    return CyclicCode(
        n=n,
        s=s,
        w_sel_re=f32(w_sel.real),
        w_sel_im=f32(w_sel.imag),
        batch_ids=batch_ids,
        c2h_re=f32(c2h.real),
        c2h_im=f32(c2h.imag),
        c1_re=f32(c1.real),
        c1_im=f32(c1.imag),
        est_re=f32(est.real),
        est_im=f32(est.imag),
        w_masked_re=f32(w.real * support),
        w_masked_im=f32(w.imag * support),
        w_full=w,
        support=support,
    )


# --------------------------------------------------------------------------
# Encode (on-device, per worker-shard; reference: cyclic_worker.py:165-194)
# --------------------------------------------------------------------------

def encode(code: CyclicCode, grads: jnp.ndarray):
    """Encode per-batch gradients into per-worker complex rows.

    grads: (n, hat_s, d) — grads[i, k] is the gradient of the batch_ids[i, k]-th
    batch, computed by worker i. Returns (enc_re, enc_im), each (n, d):
    row i = Σ_k W[i, batch_ids[i,k]] · grads[i, k].
    """
    enc_re = jnp.einsum("nk,nkd->nd", jnp.asarray(code.w_sel_re), grads, precision=PREC)
    enc_im = jnp.einsum("nk,nkd->nd", jnp.asarray(code.w_sel_im), grads, precision=PREC)
    return enc_re, enc_im


def encode_shared(code: CyclicCode, batch_grads: jnp.ndarray):
    """Encode from one-copy batch gradients (TPU-native fast path).

    batch_grads: (n, d) — gradient of batch k at row k, each computed once.
    Equivalent to :func:`encode` when redundant computations of the same batch
    agree bitwise (they do: per-batch gradients are deterministic functions of
    (params, batch) under XLA). One fused complex matmul (Pallas on TPU —
    draco_tpu.ops.coded — streaming the (n, d) gradient matrix once).
    """
    return ops_coded.complex_matmul(
        jnp.asarray(code.w_masked_re), jnp.asarray(code.w_masked_im), batch_grads
    )


def encode_segment(code: CyclicCode, batch_grads: jnp.ndarray, a: int,
                   b: int):
    """Per-segment encode for the streaming segmented wire (ISSUE 16):
    the encode is a d-column-separable matmul, so the [a, b) slice of the
    full encode equals encoding the [a, b) gradient columns —
    ``encode_shared(code, g)[..][:, a:b] == encode_segment(code, g, a, b)``
    bitwise (identical contractions over the same operand columns). This
    is what lets workers emit per-segment codeword messages without any
    new encode weights: the segment-sliced weights ARE the full weights.
    """
    return ops_coded.complex_matmul(
        jnp.asarray(code.w_masked_re), jnp.asarray(code.w_masked_im),
        batch_grads[..., a:b]
    )


# --------------------------------------------------------------------------
# Decode (replicated phase; reference: cyclic_master.py:152-173 +
# c_coding.cpp:15-84)
# --------------------------------------------------------------------------

# The stacked-real-embedding complex solve moved to coding/linalg.py
# (ISSUE 12 satellite: one shared home for the hand-rolled solvers, used
# by both code families and the fused decode kernels' reference path).
# Bit-identical ops — the XLA decode path stays bitwise.
_complex_solve = linalg_mod.complex_solve


def _locate_v(code: CyclicCode, e_re: jnp.ndarray, e_im: jnp.ndarray,
              present: Optional[jnp.ndarray] = None,
              rel_tol: float = HEALTH_REL_TOL, lam: float = 0.0):
    """Locator + recombination vector from one projected column e (n,).

    ``lam`` (ISSUE 15): Tikhonov λ for the LOCATOR solve only — the Hankel
    system is the one that goes rank-deficient with fewer than s corrupt
    rows and amplifies a narrow wire's quantization noise
    (obs/numerics.WIRE_LOCATOR_LAMBDA scales λ to the dtype's noise floor
    on the scale-normalized system). The recombination and health-fit
    solves stay exact: their honest-row DFT submatrices are full-rank by
    construction. λ=0 (every f32-wire caller) is bitwise the historical
    path.

    Steps 2–5 of the decode: syndrome → error-locator solve → honest-row
    top-k → recombination vector v with vᵀC1 = e1ᵀ supported on those rows.
    Shape-static and vmap-able (layer-granularity decode maps this over the
    per-layer projected columns). Returns (v_re, v_im, honest, health) —
    the first three (n,), ``health`` the decode-health dict (below).

    Decode health (in-graph, no host traffic): the paper's exactness
    guarantee — the decoder *exactly* removes ≤ s corruptions — made
    observable. After choosing the honest set, fit the codeword those rows
    imply (the m×m solve ``C1[idx] q̂ = e[idx]``) and measure every row's
    deviation ``|e − C1 q̂|``:

      * honest rows deviate by f32 solve noise only (≈1e-6 relative);
      * a corrupt row deviates by its injected error magnitude;
      * rows above ``rel_tol`` × RMS(e) are ``flagged`` (present rows
        only — a zero-filled straggler erasure is known-missing, not a
        detected adversary). ``rel_tol`` defaults to HEALTH_REL_TOL (the
        f32 wire's solve-noise margin); the shadow-quantized decode
        (obs/numerics.py, ISSUE 10) passes a wider quantization-aware
        threshold because honest rows on a bf16/int8 wire deviate by
        rounding noise, not f32 noise;
      * ``residual`` is the *unflagged* present rows' deviation energy as
        a fraction of total received energy — ≈ 0 whenever the decode is
        self-consistent (the located-honest codeword explains every row it
        claims is honest), and the fault signal when it is not: with more
        corruption than the locator budget the honest set is mislocated,
        the fitted codeword is poisoned, and genuinely honest rows deviate
        loudly (they then also over-flag, so ``located > s`` is the
        companion budget-exceeded signal).
    """
    n, s = code.n, code.s
    c2h_re = jnp.asarray(code.c2h_re)
    c2h_im = jnp.asarray(code.c2h_im)

    # presence + received-energy statistics (the λ path's signal scale and
    # the health normalisation both read these)
    pres_f = (jnp.ones((n,), jnp.float32) if present is None
              else present.astype(jnp.float32))
    energy = e_re**2 + e_im**2
    msq = jnp.sum(energy * pres_f) / jnp.maximum(jnp.sum(pres_f), 1.0)

    # 2. syndrome E2 = C2^H e, shape (2s,)
    e2_re = jnp.matmul(c2h_re, e_re, precision=PREC) - jnp.matmul(c2h_im, e_im, precision=PREC)
    e2_im = jnp.matmul(c2h_re, e_im, precision=PREC) + jnp.matmul(c2h_im, e_re, precision=PREC)

    if s > 0:
        # 3. Hankel system A α = b from syndrome entries
        #    (c_coding.cpp:74-79: A[i,:] = E2[s-i-1 : 2s-i-1], b[i] = E2[2s-i-1])
        rows = jnp.arange(s)
        cols = jnp.arange(s)
        idx = (s - rows[:, None] - 1) + cols[None, :]
        a_re, a_im = e2_re[idx], e2_im[idx]
        b_idx = 2 * s - rows - 1
        b_re, b_im = e2_re[b_idx], e2_im[b_idx]
        # α is invariant to a common scaling of (A, b); normalising by the
        # syndrome magnitude makes the truncation threshold scale-free. With
        # fewer than s corrupt rows the Hankel system is genuinely
        # rank-deficient (geometric syndromes); the truncated pseudoinverse
        # keeps the solve NaN-free there while staying exact (f32 exact) on
        # full-rank systems, so corrupt-row locator magnitudes sit ~1e-5 vs
        # honest ~1.
        syn = jnp.maximum(jnp.max(e2_re**2 + e2_im**2) ** 0.5, 1e-30)
        if lam == 0.0:
            scale = syn
        else:
            # λ path (ISSUE 15): normalise by the SIGNAL scale (present-row
            # RMS of e) instead of the syndrome's own magnitude. A pure-
            # quantization syndrome is then ~the dtype noise floor λ is
            # calibrated to — self-normalisation would blow it up to O(1)
            # and hand the solve pure noise, the PR 10 amplification.
            scale = jnp.maximum(jnp.sqrt(msq), 1e-30)
        alpha_re, alpha_im = _complex_solve(
            a_re / scale, a_im / scale, b_re / scale, b_im / scale,
            rcond=LOCATOR_RCOND, lam=lam,
        )

        # 4. locator polynomial p(z) = z^s - Σ α_j z^j, roots at corrupt rows
        #    (cyclic_master.py:159-162)
        poly_re = jnp.concatenate([-alpha_re, jnp.ones((1,), a_re.dtype)])
        poly_im = jnp.concatenate([-alpha_im, jnp.zeros((1,), a_re.dtype)])
        est_re = jnp.asarray(code.est_re)
        est_im = jnp.asarray(code.est_im)
        val_re = jnp.matmul(est_re, poly_re, precision=PREC) - jnp.matmul(est_im, poly_im, precision=PREC)
        val_im = jnp.matmul(est_re, poly_im, precision=PREC) + jnp.matmul(est_im, poly_re, precision=PREC)
        mag = val_re**2 + val_im**2
        if lam > 0.0:
            # syndrome significance gate (branchless): a syndrome at the
            # quantization noise floor certifies NO corruption — the
            # locator output is pure amplified noise there, so the row
            # magnitudes collapse to uniform and the spread bias below
            # picks the deterministic well-conditioned subset. A real
            # corruption (O(100×) payloads) puts the relative syndrome
            # orders of magnitude above λ and the gate is transparent.
            # gate at 2λ: the gate must clear the dtype's measured
            # noise-floor maximum with margin, while the SOLVE cutoff
            # (σ ≤ λ dropped, coding/linalg) must not eat the genuine
            # locator directions — one λ cannot serve both (measured:
            # int8 at n=32 s=3 mislocates live adversaries when the
            # cutoff rides at the gate's 2^-5, locates exactly at 2^-6)
            live = (syn / scale) > 2.0 * lam
            mag = jnp.where(live, mag, jnp.ones_like(mag))
    else:
        mag = jnp.ones((n,), jnp.float32)

    # Deterministic tie-break: honest rows equidistant from a locator root
    # tie exactly (DFT-grid symmetry), and float noise would break the tie
    # differently per projection — per-layer decodes would then pick
    # different (all equally valid) honest sets. An index-monotone bias far
    # above float noise (~1e-7·mean) and far below any honest magnitude
    # (≳5e-2·mean) pins the choice, identically in the jit and native
    # decoders (native/coding.cpp draco_cyclic_decode). The λ path biases
    # by SPREAD rank instead (SPREAD_PHI docstring): the subset it pins in
    # the gated no-corruption state extrapolates at O(1) amplification.
    order = (jnp.arange(n, dtype=mag.dtype) if lam == 0.0
             else jnp.asarray(_spread_rank(n)))
    mag = mag + order * ((1e-3 / n) * jnp.mean(mag))

    # 5. recombination vector v supported on n-2s located-honest rows,
    #    v^T C1[idx] = e1^T  (fixed-shape stand-in for the reference's
    #    dynamic err_indices + scipy lsq_linear, cyclic_master.py:164-171).
    #    Rows are chosen as the top n-2s by locator magnitude — corrupt rows
    #    are locator roots, so they sit in the bottom s — which stays
    #    full-rank (any n-2s distinct rows of the DFT Vandermonde C1 are
    #    independent) even when fewer than s rows are actually corrupt and a
    #    thresholded mask would under- or over-fill. The returned mask marks
    #    exactly the rows the recombination used.
    if present is not None:
        # absent rows are never eligible, whatever the locator thinks; in the
        # erasure-only regime the locator may be overwhelmed (e > s), but any
        # n-2s present rows are honest and exactness holds regardless of mag
        mag = jnp.where(present, mag, -1.0)
    m = n - 2 * s
    idx = jnp.sort(jax.lax.top_k(mag, m)[1])
    honest = jnp.zeros((n,), dtype=bool).at[idx].set(True)
    rec_re = jnp.asarray(code.c1_re)[idx]  # (m, m)
    rec_im = jnp.asarray(code.c1_im)[idx]
    e1 = jnp.zeros((m,), rec_re.dtype).at[0].set(1.0)
    v_re, v_im = _complex_solve(rec_re.T, rec_im.T, e1, jnp.zeros_like(e1))

    v_full_re = jnp.zeros((n,), rec_re.dtype).at[idx].set(v_re)
    v_full_im = jnp.zeros((n,), rec_re.dtype).at[idx].set(v_im)

    # ---- decode health (docstring above): codeword fit + per-row deviation
    # (pres_f / energy / msq computed at the top alongside the λ path's
    # signal scale)
    q_re, q_im = _complex_solve(rec_re, rec_im, e_re[idx], e_im[idx])
    c1_re = jnp.asarray(code.c1_re)
    c1_im = jnp.asarray(code.c1_im)
    fit_re = jnp.matmul(c1_re, q_re, precision=PREC) - jnp.matmul(
        c1_im, q_im, precision=PREC)
    fit_im = jnp.matmul(c1_re, q_im, precision=PREC) + jnp.matmul(
        c1_im, q_re, precision=PREC)
    dev = (e_re - fit_re) ** 2 + (e_im - fit_im) ** 2  # (n,) |e - C1 q̂|²
    flagged = (dev > (rel_tol**2) * msq) & (pres_f > 0)
    resid_sq = jnp.sum(jnp.where(flagged, 0.0, dev) * pres_f) / jnp.maximum(
        jnp.sum(energy * pres_f), 1e-30)
    # loud-row outlier mask (LOUD_REL_TOL docstring): forensic-only — the
    # accusation signal that survives the beyond-budget regime, where the
    # fitted-codeword deviations above are blind (the chosen-row fit is a
    # square solve, exact on whatever rows it picked). NaN energies (a
    # non-finite wire) compare False on both sides, so a NaN-poisoned
    # column accuses nobody here — the ingest-row check
    # (obs/forensics.nonfinite_rows) owns that attribution.
    med = jnp.nanmedian(jnp.where(pres_f > 0, energy, jnp.nan))
    loud = (energy > LOUD_REL_TOL * med) & (pres_f > 0)
    health = {"residual": jnp.sqrt(resid_sq), "flagged": flagged,
              "loud": loud,
              # per-row relative deviation sqrt(dev/msq) — the quantity
              # rel_tol thresholds. Not a metric column: tools/wire_study
              # reads it to DERIVE the per-(n, s, dtype) narrow-wire
              # threshold table (honest-max vs adversary-min margins)
              "dev_rel": jnp.sqrt(dev / jnp.maximum(msq, 1e-30))}
    return v_full_re, v_full_im, honest, health


def locator_core(e_re, e_im, c2h_re, c2h_im, c1_re, c1_im, est_re, est_im,
                 pres_f, s: int, rel_tol: float = HEALTH_REL_TOL,
                 lam: float = 0.0):
    """Steps 2–5 of the decode + health, batched over projected columns —
    the fused counterpart of :func:`_locate_v` (ISSUE 12 tentpole).

    Identical selection and health semantics, restructured so that ONE
    function serves both lowerings: the Pallas kernel
    (``ops/decode_kernels.cyclic_locator``) calls it on its VMEM blocks and
    ``impl="fused"`` jits it on the full stack, so the two cannot drift
    algorithmically. It is **batch-last** — the projected columns ride the
    trailing (lane) axis and every value is an (n, B) block, a (1, B) row
    or an (n, 1) column — and uses only what the TPU's Pallas compiler
    lowers on such values (coding/linalg.py, fused tier):

      * the locator least squares is one-sided Jacobi
        (``linalg.jacobi_lstsq``) on the 2s×2s embedded Hankel system;
      * honest-row ``top_k`` and the loud-row median are pairwise ranks
        (``linalg.topk_mask`` / ``masked_median``);
      * the two honest-row solves of ``_locate_v`` have closed forms,
        because the honest-row submatrix of C1 is a Vandermonde matrix in
        the DFT nodes z_t = e^{−2πi t/n}: with H the honest set and
        Q_i = Π_{j∈H, j≠i} (z_i − z_j), the recombination vector is the
        Lagrange basis at 0, v_i = √n · Π_{j∈H, j≠i} z_j / (z_j − z_i)
        = (−1)^{m+1} √n (Π_{j∈H} z_j) z̄_i / Q_i, and the codeword the
        honest rows imply is their degree-<m interpolant,
        fit_t = Q_t · Σ_{i∈H} (e_i / Q_i) / (z_t − z_i) for t ∉ H
        (barycentric form; fit_t = e_t on H). One masked running product
        over the n nodes yields every Q at once — no gather of the honest
        rows and no (m, m) solve.

    Against the XLA path the results are bounded-err with identical
    flag/honest sets (the selection and flag margins are orders of
    magnitude above the solver differences; the equivalence suite pins
    both). Honest rows deviate from their own interpolant by exactly 0
    here where ``_locate_v`` reports f32 solve noise.

    e_re, e_im: (n, B) projected columns, one per lane. pres_f: (n, 1) f32
    presence (all-ones when every row arrived). Returns
    ``(v_re, v_im, honest, flagged, loud, residual)`` — the first five
    (n, B) with the v pair already carrying the 1/1 scale of
    ``_locate_v`` (callers fold /n into it), ``residual`` (1, B).
    """
    n = e_re.shape[0]
    m = n - 2 * s
    # presence-weighted received energy (the λ path's signal scale and the
    # health normalisation below)
    energy = e_re ** 2 + e_im ** 2
    # widened as f32 and compared at full shape: the kernel compiler has no
    # broadcast of a boolean column
    present = jnp.broadcast_to(pres_f, energy.shape) > 0
    msq = (jnp.sum(energy * pres_f, axis=0, keepdims=True)
           / jnp.maximum(jnp.sum(pres_f, axis=0, keepdims=True), 1.0))

    if s > 0:
        # 2. syndrome (2s, B): one complex matmul pair, then one (1, B)
        #    row per syndrome entry
        e2_re = (jnp.matmul(c2h_re, e_re, precision=PREC)
                 - jnp.matmul(c2h_im, e_im, precision=PREC))
        e2_im = (jnp.matmul(c2h_re, e_im, precision=PREC)
                 + jnp.matmul(c2h_im, e_re, precision=PREC))
        # same scale-free normalisation as _locate_v; the λ path divides
        # by the SIGNAL scale instead and gates on syndrome significance
        # (_locate_v's λ-branch comments — identical semantics here)
        syn = jnp.sqrt(jnp.maximum(
            jnp.max(e2_re ** 2 + e2_im ** 2, axis=0, keepdims=True), 1e-60))
        if lam == 0.0:
            scale = syn
        else:
            scale = jnp.maximum(jnp.sqrt(msq), 1e-30)
        sr = [e2_re[k:k + 1] / scale for k in range(2 * s)]
        si = [e2_im[k:k + 1] / scale for k in range(2 * s)]
        # 3. Hankel system (A[i, j] = E2[s-1-i+j], b[i] = E2[2s-1-i]) in
        #    its real 2s×2s embedding [[Ar, −Ai], [Ai, Ar]] — plain python
        #    indexing of the per-entry rows
        big = ([[sr[s - 1 - i + j] for j in range(s)]
                + [-si[s - 1 - i + j] for j in range(s)] for i in range(s)]
               + [[si[s - 1 - i + j] for j in range(s)]
                  + [sr[s - 1 - i + j] for j in range(s)] for i in range(s)])
        rhs = ([sr[2 * s - 1 - i] for i in range(s)]
               + [si[2 * s - 1 - i] for i in range(s)])
        al = linalg_mod.jacobi_lstsq(big, rhs, LOCATOR_RCOND, lam=lam)
        # 4. locator polynomial p(z) = z^s − Σ α_j z^j on the DFT grid:
        #    (n, 1) grid columns × (1, B) coefficient rows
        poly_re = [-x for x in al[:s]] + [jnp.ones_like(syn)]
        poly_im = [-x for x in al[s:]] + [jnp.zeros_like(syn)]
        val_re = sum(est_re[:, j:j + 1] * poly_re[j]
                     - est_im[:, j:j + 1] * poly_im[j] for j in range(s + 1))
        val_im = sum(est_re[:, j:j + 1] * poly_im[j]
                     + est_im[:, j:j + 1] * poly_re[j] for j in range(s + 1))
        mag = val_re ** 2 + val_im ** 2
        if lam > 0.0:
            # syndrome significance gate at 2λ (_locate_v λ-branch comment)
            mag = jnp.where((syn / scale) > 2.0 * lam, mag, 1.0)
    else:
        mag = jnp.ones_like(energy)

    # deterministic tie-break (see _locate_v) + absent rows never eligible;
    # the λ path biases by SPREAD rank (SPREAD_PHI) — computed from iota
    # pairwise comparisons, no host constant (kernel body)
    iota = linalg_mod.iota
    if lam == 0.0:
        bias = iota(mag.shape, 0).astype(jnp.float32)
    else:
        ki = iota((n, n), 0).astype(jnp.float32) * SPREAD_PHI
        kj = iota((n, n), 1).astype(jnp.float32) * SPREAD_PHI
        ki = ki - jnp.floor(ki)
        kj = kj - jnp.floor(kj)
        bias = jnp.sum(jnp.where(kj < ki, 1.0, 0.0), axis=1,
                       keepdims=True)  # (n, 1)
    mag = mag + bias * ((1e-3 / n) * jnp.mean(mag, axis=0, keepdims=True))
    mag = jnp.where(present, mag, -1.0)

    # 5. honest set, then recombination vector and codeword fit in closed
    #    form over the DFT nodes (docstring). z_t = √n · C1[t, 1].
    honest = linalg_mod.topk_mask(mag, m)  # (n, B) bool
    h = jnp.where(honest, 1.0, 0.0)
    root_n = float(np.sqrt(n))
    z_re, z_im = c1_re[:, 1:2] * root_n, c1_im[:, 1:2] * root_n  # (n, 1)
    node = iota((n, 1), 0)
    nodes = np.exp(-2j * np.pi * np.arange(n) / n)

    # diffs[j]: the (n, 1) column z_t − z_j, its t == j entry set to 1 + 0i
    diffs = [(jnp.where(node == j, 1.0, z_re - float(nodes[j].real)),
              jnp.where(node == j, 0.0, z_im - float(nodes[j].imag)))
             for j in range(n)]

    # Q_t = Π_{j∈H, j≠t} (z_t − z_j) for every t at once, and the honest
    # node product Π_{j∈H} z_j per column — masked running products: a
    # dishonest j contributes the factor 1
    q_re, q_im = jnp.ones_like(energy), jnp.zeros_like(energy)
    zp_re, zp_im = jnp.ones_like(msq), jnp.zeros_like(msq)
    for j in range(n):
        hj = h[j:j + 1]  # (1, B)
        d_re, d_im = diffs[j]
        f_re, f_im = 1.0 + hj * (d_re - 1.0), hj * d_im
        q_re, q_im = q_re * f_re - q_im * f_im, q_re * f_im + q_im * f_re
        g_re = 1.0 + hj * (float(nodes[j].real) - 1.0)
        g_im = hj * float(nodes[j].imag)
        zp_re, zp_im = zp_re * g_re - zp_im * g_im, zp_re * g_im + zp_im * g_re
    q_abs2 = jnp.maximum(q_re ** 2 + q_im ** 2, 1e-30)
    qi_re, qi_im = q_re / q_abs2, -q_im / q_abs2  # 1 / Q
    # v_i = (−1)^{m+1} √n (Π_H z_j) z̄_i / Q_i on H, 0 elsewhere
    sign = root_n if (m + 1) % 2 == 0 else -root_n
    w_re = qi_re * z_re + qi_im * z_im  # z̄ / Q
    w_im = qi_im * z_re - qi_re * z_im
    v_re = h * sign * (zp_re * w_re - zp_im * w_im)
    v_im = h * sign * (zp_re * w_im + zp_im * w_re)
    # health fit: fit_t = Q_t Σ_{i∈H} (e_i / Q_i) / (z_t − z_i) off H, e_t
    # on H. A dishonest i has u_i = 0; where(…) and not h·(…) so a
    # non-finite dishonest row cannot leak in through 0·NaN
    u_re = jnp.where(honest, e_re * qi_re - e_im * qi_im, 0.0)
    u_im = jnp.where(honest, e_re * qi_im + e_im * qi_re, 0.0)
    acc_re, acc_im = jnp.zeros_like(energy), jnp.zeros_like(energy)
    for i in range(n):
        d_re, d_im = diffs[i]
        d_abs2 = d_re ** 2 + d_im ** 2
        r_re, r_im = d_re / d_abs2, -d_im / d_abs2  # 1 / (z_t − z_i)
        ui_re, ui_im = u_re[i:i + 1], u_im[i:i + 1]
        acc_re = acc_re + ui_re * r_re - ui_im * r_im
        acc_im = acc_im + ui_re * r_im + ui_im * r_re
    fit_re = jnp.where(honest, e_re, q_re * acc_re - q_im * acc_im)
    fit_im = jnp.where(honest, e_im, q_re * acc_im + q_im * acc_re)
    dev = (e_re - fit_re) ** 2 + (e_im - fit_im) ** 2
    # energy / msq computed at the top (the λ path's signal scale)
    flagged = (dev > (rel_tol ** 2) * msq) & present
    resid_sq = (jnp.sum(jnp.where(flagged, 0.0, dev) * pres_f, axis=0,
                        keepdims=True)
                / jnp.maximum(jnp.sum(energy * pres_f, axis=0,
                                      keepdims=True), 1e-30))
    # loud-row forensics (LOUD_REL_TOL docstring): rank-selection median
    # over present∧non-NaN rows matches _locate_v's nanmedian exactly
    med = linalg_mod.masked_median(energy, present & (energy == energy))
    loud = (energy > LOUD_REL_TOL * med) & present
    return v_re, v_im, honest, flagged, loud, jnp.sqrt(resid_sq)


def _run_locator(code: CyclicCode, e_re_l, e_im_l, present, rel_tol,
                 impl: str, lam: float = 0.0):
    """Dispatch the batched locator on an (L, n) projected-column stack:
    ``fused`` = :func:`locator_core` lowered through XLA (the kernels'
    reference), ``pallas``/``pallas_interpret`` = the hand-tiled kernel
    (ops/decode_kernels.cyclic_locator) running the same function on VMEM
    blocks. Both take the stack batch-last, (n, L); the transposes of a few
    kilobytes live here so the callers keep their (L, n) rows."""
    n = code.n
    pres_f = (jnp.ones((n, 1), jnp.float32) if present is None
              else jnp.asarray(present).astype(jnp.float32)[:, None])
    if impl in ("pallas", "pallas_interpret"):
        from draco_tpu.ops import decode_kernels

        out = decode_kernels.cyclic_locator(
            code, e_re_l.T, e_im_l.T, pres_f, rel_tol,
            interpret=(impl == "pallas_interpret"), lam=lam)
    else:
        out = locator_core(
            e_re_l.T, e_im_l.T,
            jnp.asarray(code.c2h_re), jnp.asarray(code.c2h_im),
            jnp.asarray(code.c1_re), jnp.asarray(code.c1_im),
            jnp.asarray(code.est_re), jnp.asarray(code.est_im),
            pres_f, code.s, rel_tol, lam=lam)
    *masks, resid = out
    return (*(x.T for x in masks), resid[0])


def decode(code: CyclicCode, r_re: jnp.ndarray, r_im: jnp.ndarray, rand_factor: jnp.ndarray,
           present: Optional[jnp.ndarray] = None, with_health: bool = False,
           rel_tol: float = HEALTH_REL_TOL, impl: str = "xla",
           lam: float = 0.0, wire=None):
    """Recover the exact sum of the n batch gradients from corrupt rows.

    r_re, r_im: (n, d) received encoded rows (≤ s rows arbitrarily corrupt).
    rand_factor: (d,) random projection (reference: cyclic_master.py:58-61).
    present: optional (n,) bool — False rows never arrived (stragglers /
    crashed workers; they must be zero-filled by the caller). Known-missing
    rows are *erasures*: they cost one redundancy unit instead of two, so the
    decode is exact when either (a) no adversary is live and ≤ 2s rows are
    missing, or (b) adversaries + missing ≤ s (the locator treats each
    zero-filled row as one located error). No reference counterpart — the
    reference PS simply blocks forever on a missing worker
    (baseline_master.py:112-116).

    Returns (n·mean-gradient, honest_mask): the (d,) real decoded sum / n and
    the (n,) mask of rows the recombination actually used (True = treated as
    honest; exactly n-2s rows are True, every located adversary and every
    absent row is False). ``with_health=True`` appends the decode-health
    dict (``_locate_v`` docstring: scalar ``residual`` ≈ 0 iff the decode is
    self-consistent, (n,) bool ``flagged`` marking present rows whose
    received value deviates from the fitted codeword, (n,) bool ``loud``
    marking magnitude-outlier present rows — the forensic-only accusation
    signal, LOUD_REL_TOL) — in-graph values for the telemetry metric
    columns, backward-compatible 2-tuple otherwise.

    ``impl`` selects the locator implementation (ISSUE 12): ``"xla"`` is
    the historical lowering, bit-for-bit unchanged (the K∈{1,4} bitwise
    suites run it); ``"fused"`` runs the batched :func:`locator_core`
    through XLA (the kernels' reference lowering — bounded-err vs
    xla, identical honest/flag sets); ``"pallas"`` runs the hand-tiled
    kernel (ops/decode_kernels, TPU backends). Both non-xla paths fold
    the 1/n into the recombination vector.
    """
    n = code.n
    # 1. project to one column: e = R @ f  (the only O(n·d) work besides the
    #    final recombination — one fused pass over (R_re, R_im))
    e_re, e_im = ops_coded.complex_project(r_re, r_im, rand_factor)
    if impl == "xla":
        v_full_re, v_full_im, honest, health = _locate_v(code, e_re, e_im,
                                                         present, rel_tol,
                                                         lam=lam)
        # 6. recombine: Re(v^T R) / n — the second O(n·d) pass, fused
        decoded = ops_coded.complex_recombine(v_full_re, v_full_im,
                                              r_re, r_im) / n
    else:
        v_re, v_im, honest_l, flagged_l, loud_l, resid_l = _run_locator(
            code, e_re[None, :], e_im[None, :], present, rel_tol, impl,
            lam=lam)
        honest = honest_l[0]
        health = {"residual": resid_l[0], "flagged": flagged_l[0],
                  "loud": loud_l[0]}
        from draco_tpu.ops import decode_kernels

        if (impl in ("pallas", "pallas_interpret")
                and decode_kernels.narrow_kernel_ok(wire)):
            # narrow-ingest recombination (ISSUE 15): the kernel streams
            # the REAL narrow wire buffers and dequantizes in-tile — the
            # widened f32 (n, d) matrix never round-trips HBM
            decoded = decode_kernels.cyclic_narrow_recombine(
                v_re[0] / n, v_im[0] / n, wire,
                interpret=(impl == "pallas_interpret"))
        else:
            decoded = ops_coded.complex_recombine(v_re[0] / n, v_im[0] / n,
                                                  r_re, r_im)
    if with_health:
        return decoded, honest, health
    return decoded, honest


def _recombine_layers_fused(n: int, v_re_l, v_im_l, bounds, r_re, r_im):
    """Per-layer recombination of the fused decode path (PERF_HISTORY.md §14):
    same per-segment complex matvecs as the XLA path, but assembled by
    dynamic_update_slice writes into one preallocated (d,) output instead
    of a concatenate, and with the 1/n already folded into the v pair —
    measured fastest of the in-jit assembly variants on XLA:CPU (the
    gather- and broadcast-materialized (n, d) weight-matrix forms win as
    standalone microbenches but fuse pathologically inside the full step
    program). On TPU the same structure lets consecutive segment writes
    land in place."""
    del n  # shape-independent assembly (n rides in the operands)
    segs = list(zip(bounds[:-1], bounds[1:]))
    out = jnp.zeros((r_re.shape[1],), jnp.float32)
    for i, (a, b) in enumerate(segs):
        seg = ops_coded.complex_recombine(v_re_l[i], v_im_l[i],
                                          r_re[:, a:b], r_im[:, a:b])
        out = jax.lax.dynamic_update_slice(out, seg, (a,))
    return out


def decode_layers(code: CyclicCode, r_re: jnp.ndarray, r_im: jnp.ndarray,
                  rand_factor: jnp.ndarray, offsets,
                  present: Optional[jnp.ndarray] = None,
                  with_health: bool = False,
                  rel_tol: float = HEALTH_REL_TOL, impl: str = "xla",
                  lam: float = 0.0, wire=None):
    """Layer-granularity decode — one locator per parameter tensor.

    The reference decodes each layer independently with its own random
    projection factor (cyclic_master.py:125-129 loops layers, :58-61 draws a
    factor per layer); this is that semantics on the flattened (n, d) matrix:
    ``offsets`` are the static leaf boundaries (len L+1), segment ℓ =
    [offsets[ℓ], offsets[ℓ+1]). Each segment gets its own projection (a slice
    of the same (d,) factor vector), its own locator solve and its own
    recombination vector; the tiny per-layer solves run batched under one
    vmap. When corruption is per-worker (a whole row is attacked — the only
    kind the wire protocol admits) every layer locates the same set, and this
    agrees with the global decode; the per-layer locators additionally catch
    corruption confined to a single layer's coordinates, which a single
    global projection could only see through that layer's contribution.

    Returns (decoded (d,), honest (L, n)); ``with_health=True`` appends the
    combined decode-health dict — residual is the worst layer's (a single
    inconsistent layer is a fault), flagged is the union over layers (a row
    corrupted in any layer's coordinates is a located error).

    ``impl`` as in :func:`decode`. This is the fused kernel's home regime
    (ISSUE 12): the per-layer locators run as ONE batched
    :func:`locator_core` call over the (L, n) projected-column stack —
    a hand-tiled Pallas grid on TPU, one XLA program on CPU — instead of
    L vmapped solver chains, and the per-layer recombination is re-tiled
    per worker count (:func:`_recombine_layers_fused`).

    ``wire`` (ISSUE 15) is accepted for signature parity with
    :func:`decode` but the layer-granularity recombination keeps the
    widened f32 rows: the per-layer segment boundaries do not align with
    the narrow wire's per-block scale tiling, so the in-tile dequant
    kernel applies to the GLOBAL decode only (PERF_HISTORY.md §17).
    """
    del wire
    n = code.n
    bounds = [int(o) for o in offsets]
    e_res, e_ims = [], []
    for a, b in zip(bounds[:-1], bounds[1:]):
        e_re, e_im = ops_coded.complex_project(
            r_re[:, a:b], r_im[:, a:b], rand_factor[a:b]
        )
        e_res.append(e_re)
        e_ims.append(e_im)
    e_re_l = jnp.stack(e_res)  # (L, n)
    e_im_l = jnp.stack(e_ims)
    if impl == "xla":
        v_re_l, v_im_l, honest_l, health_l = jax.vmap(
            lambda er, ei: _locate_v(code, er, ei, present, rel_tol, lam)
        )(e_re_l, e_im_l)
        parts = [
            ops_coded.complex_recombine(v_re_l[i], v_im_l[i], r_re[:, a:b], r_im[:, a:b])
            for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:]))
        ]
        decoded = jnp.concatenate(parts) / n
        if with_health:
            health = {"residual": jnp.max(health_l["residual"]),
                      "flagged": jnp.any(health_l["flagged"], axis=0),
                      "loud": jnp.any(health_l["loud"], axis=0),
                      "dev_rel": jnp.max(health_l["dev_rel"], axis=0)}
            return decoded, honest_l, health
        return decoded, honest_l
    v_re_l, v_im_l, honest_l, flagged_l, loud_l, resid_l = _run_locator(
        code, e_re_l, e_im_l, present, rel_tol, impl, lam=lam)
    decoded = _recombine_layers_fused(n, v_re_l / n, v_im_l / n, bounds,
                                      r_re, r_im)
    if with_health:
        health = {"residual": jnp.max(resid_l),
                  "flagged": jnp.any(flagged_l, axis=0),
                  "loud": jnp.any(loud_l, axis=0)}
        return decoded, honest_l, health
    return decoded, honest_l


def decode_segments(code: CyclicCode, r_re: jnp.ndarray, r_im: jnp.ndarray,
                    rand_factor: jnp.ndarray, bounds,
                    present: Optional[jnp.ndarray] = None,
                    with_health: bool = False,
                    rel_tol: float = HEALTH_REL_TOL, impl: str = "xla",
                    lam: float = 0.0, wire=None):
    """Streaming segmented decode (ISSUE 16; arXiv:1903.01974's
    multi-message communication): one locator per WIRE SEGMENT instead of
    one per layer — ``bounds`` are the quantum-aligned segment cuts
    (obs/numerics.wire_segment_bounds; len S+1), segment j =
    [bounds[j], bounds[j+1]).

    Segment algebra: each segment gets its own projection column (a slice
    of the same (d,) factor), its own syndrome + Hankel locator solve and
    its own recombination vector — exactly the layer-granularity decode's
    structure (:func:`decode_layers`), so the same correctness argument
    applies: the wire protocol corrupts whole ROWS, so every segment of a
    corrupt row carries that row's error and every segment's locator sees
    it; a straggler erasure zero-fills all its segments under the same
    present mask. The per-step accusation/health verdict is the FOLD
    across segments — residual = worst segment (a single inconsistent
    segment is a fault), flagged/loud = union (a row corrupt in any
    segment's coordinates is a located error) — so detection P/R, guards,
    incidents and the autopilot keep seeing one verdict per step.

    Unlike :func:`decode_layers`, segment cuts ARE aligned to the narrow
    wire's per-block scale tiling (the bounds contract), so the
    narrow-ingest recombination applies per segment: on the kernel path
    each segment streams its own slice of the REAL narrow buffers and
    dequantizes in-tile (ops/decode_kernels.wire_slice_pair — the
    segment-offset entry point, no new kernels).

    Returns ``(decoded (d,), honest (S', n)[, health])`` — callers fold
    honest with ``jnp.all(axis=0)`` like the layer path. S'=len(bounds)-1.
    """
    n = code.n
    bounds = [int(o) for o in bounds]
    segs = list(zip(bounds[:-1], bounds[1:]))
    e_res, e_ims = [], []
    for a, b in segs:
        e_re, e_im = ops_coded.complex_project(
            r_re[:, a:b], r_im[:, a:b], rand_factor[a:b]
        )
        e_res.append(e_re)
        e_ims.append(e_im)
    e_re_l = jnp.stack(e_res)  # (S', n)
    e_im_l = jnp.stack(e_ims)
    if impl == "xla":
        v_re_l, v_im_l, honest_l, health_l = jax.vmap(
            lambda er, ei: _locate_v(code, er, ei, present, rel_tol, lam)
        )(e_re_l, e_im_l)
        decoded = _recombine_layers_fused(n, v_re_l / n, v_im_l / n,
                                          bounds, r_re, r_im)
        if with_health:
            health = {"residual": jnp.max(health_l["residual"]),
                      "flagged": jnp.any(health_l["flagged"], axis=0),
                      "loud": jnp.any(health_l["loud"], axis=0),
                      "dev_rel": jnp.max(health_l["dev_rel"], axis=0)}
            return decoded, honest_l, health
        return decoded, honest_l
    v_re_l, v_im_l, honest_l, flagged_l, loud_l, resid_l = _run_locator(
        code, e_re_l, e_im_l, present, rel_tol, impl, lam=lam)
    from draco_tpu.ops import decode_kernels

    if (impl in ("pallas", "pallas_interpret")
            and decode_kernels.narrow_kernel_ok(wire)):
        # per-segment narrow ingest: each segment's recombination streams
        # its own slice of the narrow buffers (decode-on-arrival unit)
        out = jnp.zeros((r_re.shape[1],), jnp.float32)
        for i, (a, b) in enumerate(segs):
            seg = decode_kernels.cyclic_narrow_recombine_segment(
                v_re_l[i] / n, v_im_l[i] / n, wire, a, b,
                interpret=(impl == "pallas_interpret"))
            out = jax.lax.dynamic_update_slice(out, seg, (a,))
        decoded = out
    else:
        decoded = _recombine_layers_fused(n, v_re_l / n, v_im_l / n,
                                          bounds, r_re, r_im)
    if with_health:
        health = {"residual": jnp.max(resid_l),
                  "flagged": jnp.any(flagged_l, axis=0),
                  "loud": jnp.any(loud_l, axis=0)}
        return decoded, honest_l, health
    return decoded, honest_l
