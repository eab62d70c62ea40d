"""Shared small-dense linear algebra for the coded decode paths.

Two tiers, one home (ISSUE 12):

**XLA tier** — the exact solvers the production ``decode_impl="xla"`` paths
have always used, deduplicated here from their former copies:
:func:`complex_solve` (cyclic's stacked-real-embedding solve, previously
``coding/cyclic._complex_solve``) and :func:`truncated_lstsq` (the
rcond-truncated SVD least squares both that embedding and the approx
family's where-masked optimal-decoding solve call). Bit-for-bit the ops the
callers inlined before; the K∈{1,4} bitwise equivalence suites pin that.

**Fused tier** — the same math re-derived for the fused cyclic locator
(``coding/cyclic.locator_core``, which ``ops/decode_kernels.cyclic_locator``
runs on VMEM blocks and the ``impl="fused"`` reference path jits on full
arrays — one function, two lowerings). Everything here is **batch-last**:
the batch of independent problems (the per-layer projected columns) rides
the LAST axis — the TPU lane axis — and every value is a 2-D array, an
``(n, B)`` block with the code's worker axis on sublanes or a ``(1, B)``
row holding one scalar per problem. That restriction is what the TPU's
Pallas compiler (Mosaic) lowers without relayouts: elementwise algebra,
row/column broadcasts, static row slices, axis-0 reductions, int32
``broadcasted_iota`` masks and ``fori_loop``-carried 2-D values — no
``lax.linalg`` custom calls, no ``sort``/``top_k``/``gather``/``scatter``,
no rank-3 values, no reshapes. (The batch-FIRST rank-3 formulation this
replaces passed interpret mode and was refused by the chip's compiler —
PERF.md, chip bring-up.)

  truncated least squares   :func:`jacobi_lstsq` — one-sided Jacobi SVD,
                            fixed sweep count (quadratic convergence; the
                            systems are ≤ 2s×2s ≤ 10×10), on matrices
                            held as nested lists of ``(1, B)`` entries.
                            Works on A directly, NOT its gram: the gram
                            squares the condition number and f32 gram
                            eigenvalues below ~1e-7·λmax are noise, which
                            would put the rcond=1e-5 locator cutoff (λ
                            cutoff 1e-10) under the noise floor — the exact
                            failure the XLA tier's docstring warns about.
  honest-row top-k          :func:`topk_mask` — pairwise-comparison ranks
                            accumulated row by row; ties break toward the
                            lower index, matching ``lax.top_k``.
  masked median             :func:`masked_median` — rank-selection median
                            over a masked axis, matching ``jnp.nanmedian``
                            over present∧finite entries (the cyclic loud-row
                            threshold's statistic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# XLA tier — the exact production solvers, deduplicated (ISSUE 12 satellite)
# ---------------------------------------------------------------------------


def truncated_lstsq(a: jnp.ndarray, b: jnp.ndarray, rcond: float,
                    lam: float = 0.0):
    """rcond-truncated SVD least squares (singular values below
    ``rcond·σmax`` zeroed), the shared primitive of the cyclic locator
    solve (via :func:`complex_solve`) and the approx family's where-masked
    optimal-decoding solve (coding/approx.decode_weights). Unlike a fixed
    ridge, truncation leaves full-rank systems f32-exact while keeping
    genuinely rank-deficient ones NaN-free — both call sites depend on
    exactly that (cyclic's < s-corrupt locator, approx's whole-cluster
    absences).

    ``lam`` > 0 (ISSUE 15) switches to the noise-floor-regularized solve:
    singular directions with σ ≤ λ are dropped OUTRIGHT on top of the
    relative rcond cutoff (keep σ > max(rcond·σmax, λ)). On the
    signal-scale-normalized locator system a direction at or below λ
    carries only quantization noise — the relative rcond alone keeps it
    whenever σmax is large (a live adversary), which is the PR 10
    finding: the cyclic locator amplifies bf16/int8 rounding past any
    usable flag threshold at n=32 s=3. λ is the hard-truncation limit of
    the Tikhonov family — ridge-DAMPING the kept directions
    (σ/(σ²+λ²)) was measured to distort the locator polynomial enough
    to mislocate live adversaries at int8's noise floor (the σ ≈ λ
    boundary pays up to 50% coefficient shrinkage; PERF_HISTORY.md §17), so
    kept directions solve exactly. ``lam == 0.0`` takes the historical
    path bit-for-bit (a static python branch — the compiled program is
    unchanged)."""
    if lam == 0.0:
        x, _, _, _ = jnp.linalg.lstsq(a, b, rcond=rcond)
        return x
    u, s, vt = jnp.linalg.svd(a, full_matrices=False)
    smax = jnp.max(s)
    keep = s > jnp.maximum(rcond * smax, lam)
    utb = jnp.matmul(u.T, b)
    coef = jnp.where(keep, 1.0 / jnp.maximum(s, lam * 1e-6), 0.0)
    return jnp.matmul(vt.T, coef * utb)


def complex_solve(a_re, a_im, b_re, b_im, rcond: float = 0.0,
                  lam: float = 0.0):
    """Solve complex A x = b via the real 2m×2m block embedding.

    [[Ar, -Ai], [Ai, Ar]] [xr; xi] = [br; bi]. LU-based jnp.linalg.solve is
    supported on TPU; the systems here are at most (n-2s) × (n-2s).

    rcond > 0 switches to the SVD-truncated least squares
    (:func:`truncated_lstsq`), for systems that can be genuinely
    rank-deficient — the error-locator Hankel system loses rank when fewer
    than s rows are actually corrupt; the reference used an SVD
    least-squares there for the same reason (c_coding.cpp:81). SVD on the
    embedded system (not its gram) keeps the threshold meaningful in f32:
    the gram squares the condition number. ``lam`` > 0 additionally drops
    singular directions at or below the noise floor λ OUTRIGHT — kept
    directions still solve exactly, deliberately NOT ridge-damped
    (narrow-wire locator solves, truncated_lstsq docstring); λ=0 is the
    historical path bit-for-bit.

    (Moved verbatim from ``coding/cyclic._complex_solve`` — the XLA decode
    path must stay bitwise.)
    """
    m = a_re.shape[0]
    top = jnp.concatenate([a_re, -a_im], axis=1)
    bot = jnp.concatenate([a_im, a_re], axis=1)
    big = jnp.concatenate([top, bot], axis=0)
    rhs = jnp.concatenate([b_re, b_im], axis=0)
    if rcond > 0.0:
        x = truncated_lstsq(big, rhs, rcond, lam=lam)
    else:
        x = jnp.linalg.solve(big, rhs)
    return x[:m], x[m:]


# ---------------------------------------------------------------------------
# Fused tier — Mosaic-lowerable batch-LAST primitives (trailing axis = batch)
# ---------------------------------------------------------------------------

# One-sided Jacobi sweep count. Convergence is quadratic in sweeps; the
# largest system any caller builds is the 2s×2s embedded locator (2s ≤ 10
# at the n=32 s=5 construction ceiling), where 12 cyclic sweeps leave
# off-diagonal mass below f32 noise with a wide margin. Fixed (never
# data-dependent) so the op graph is shape-static.
JACOBI_SWEEPS = 12

# Guard against 0/0 in rotation/normalization algebra on exactly-zero
# columns (an all-absent syndrome block is legitimately the zero matrix).
_TINY = 1e-30


def iota(shape, dim):
    """int32 iota — the only iota dtype the TPU's Pallas compiler has."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _row_at(x: jnp.ndarray, j):
    """Row j of an (n, B) block as (1, B), for a TRACED j: a masked axis-0
    reduction (there is no dynamic slice in a kernel body). A static j
    slices instead (``x[j:j + 1]``)."""
    return jnp.sum(jnp.where(iota(x.shape, 0) == j, x, 0.0), axis=0,
                   keepdims=True)


def jacobi_lstsq(a, b, rcond: float, sweeps: int = JACOBI_SWEEPS,
                 lam: float = 0.0):
    """Truncated least squares ``min ‖A x − b‖`` via one-sided Jacobi SVD,
    batched over the lanes.

    ``a``: m×m nested sequence, ``a[r][c]`` a (1, B) row (entry (r, c) of
    each of the B systems); ``b``: m-sequence of (1, B) rows. Returns x as
    a list of m (1, B) rows with singular directions below ``rcond·σmax``
    dropped — the fused-tier counterpart of :func:`truncated_lstsq` (same
    cutoff semantics; σ come out of the rotations at high relative
    accuracy because the gram is never formed). ``lam`` > 0 drops
    directions with σ ≤ λ outright, exactly like the XLA tier
    (truncated_lstsq's noise-floor cutoff — keep
    σ² > max((rcond·σmax)², λ²)); kept directions solve exactly.

    One-sided Jacobi: rotate column pairs of A (accumulating the rotations
    in V) until columns are mutually orthogonal — then A·V = W with
    ``WᵀW = diag(σ²)``, and x = V Σ⁻² Wᵀ b restricted to kept σ. The pair
    loop is a static python loop (m ≤ 10) over per-entry (1, B) rows:
    every update is elementwise, nothing is indexed.
    """
    m = len(b)
    one, zero = jnp.ones_like(b[0]), jnp.zeros_like(b[0])
    w0 = [[a[r][c] for c in range(m)] for r in range(m)]
    v0 = [[one if r == c else zero for c in range(m)] for r in range(m)]

    def sweep(_, carry):
        w, v = ([list(row) for row in t] for t in carry)
        for p in range(m - 1):
            for q in range(p + 1, m):
                alpha = sum(w[r][p] * w[r][p] for r in range(m))
                beta = sum(w[r][q] * w[r][q] for r in range(m))
                gamma = sum(w[r][p] * w[r][q] for r in range(m))
                # rotation annihilating the (p, q) off-diagonal of WᵀW:
                # branchless — |γ| ≈ 0 degrades to the identity rotation
                live = jnp.abs(gamma) > _TINY
                g_safe = jnp.where(live, gamma, 1.0)
                zeta = (beta - alpha) / (2.0 * g_safe)
                # NB not jnp.sign: equal column norms give ζ = 0 where the
                # optimal rotation is 45° (t = 1) — sign(0) = 0 would skip it
                sgn = jnp.where(zeta >= 0.0, 1.0, -1.0)
                t = sgn / (jnp.abs(zeta) + jnp.sqrt(1.0 + zeta * zeta))
                t = jnp.where(live, t, 0.0)
                c = 1.0 / jnp.sqrt(1.0 + t * t)
                s = c * t
                for mat in (w, v):
                    for r in range(m):
                        mp, mq = mat[r][p], mat[r][q]
                        mat[r][p] = c * mp - s * mq
                        mat[r][q] = s * mp + c * mq
        return w, v

    # sweeps under ONE fori_loop: the pair loop must stay unrolled (static
    # entries) but the sweep body is identical each pass — carrying it
    # keeps the op graph sweeps× smaller, which is the difference between a
    # seconds and a minutes XLA:CPU compile at n=32
    w, v = jax.lax.fori_loop(0, sweeps, sweep, (w0, v0))
    sig2 = [sum(w[r][c] * w[r][c] for r in range(m)) for c in range(m)]
    sig2max = functools.reduce(jnp.maximum, sig2)
    coef = []
    for c in range(m):
        keep = sig2[c] > (rcond * rcond) * sig2max
        if lam > 0.0:
            keep = keep & (sig2[c] > lam * lam)
        wtb = sum(w[r][c] * b[r] for r in range(m))  # (Wᵀ b)[c]
        coef.append(jnp.where(keep, wtb / jnp.maximum(sig2[c], _TINY), 0.0))
    return [sum(v[r][c] * coef[c] for c in range(m)) for r in range(m)]


def _count_ahead(x: jnp.ndarray, eligible=None):
    """(n, B) f32 count, per entry i, of the entries j of its column that
    sort AHEAD of it ascending (``x[j] < x[i]``, equal values by lower
    index) — restricted to ``eligible`` j's when given ((n, B) f32 0/1).
    One row per ``fori_loop`` step: the (n, n) pairwise block never exists,
    and the op graph does not grow with n. f32 counts are exact (n ≤ 64)."""
    n = x.shape[0]
    idx = iota(x.shape, 0)

    def step(j, count):
        xj = _row_at(x, j)
        ahead = (xj < x) | ((xj == x) & (idx > j))
        if eligible is not None:
            ahead = ahead & (_row_at(eligible, j) > 0.5)
        return count + jnp.where(ahead, 1.0, 0.0)

    return jax.lax.fori_loop(0, n, step, jnp.zeros(x.shape, jnp.float32))


def topk_mask(mag: jnp.ndarray, m: int):
    """Bool mask of the top-m entries per COLUMN of mag (n, B), by
    pairwise-comparison rank — no sort, no top_k (Mosaic constraint). Ties
    break toward the lower index (``lax.top_k``'s preference), though the
    cyclic locator's index-monotone bias makes exact ties unreachable."""
    return _count_ahead(-mag) < float(m)


def masked_median(x: jnp.ndarray, mask: jnp.ndarray):
    """Median of each column of x (n, B) over the rows where mask (n, B)
    is True, as a (1, B) row — rank-selection (average of the two middle
    order statistics for even counts), matching ``jnp.nanmedian`` over the
    masked entries. All-False columns return NaN, like nanmedian of an
    all-NaN slice. Non-finite x entries must be excluded by the caller's
    mask; masked-out entries are value-sanitized so a NaN there cannot
    leak through the 0·NaN trap."""
    mf = jnp.where(mask, 1.0, 0.0)
    xs = jnp.where(mask, x, 0.0)
    rank = _count_ahead(xs, mf)  # masked rank of every entry
    p = jnp.sum(mf, axis=0, keepdims=True)  # (1, B)
    k1 = jnp.floor((p - 1.0) * 0.5)
    k2 = jnp.floor(p * 0.5)

    def at_rank(k):
        return jnp.sum(jnp.where((rank == k) & mask, xs, 0.0), axis=0,
                       keepdims=True)

    med = 0.5 * (at_rank(k1) + at_rank(k2))
    return jnp.where(p > 0, med, jnp.nan)
