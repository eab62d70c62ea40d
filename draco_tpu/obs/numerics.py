"""Wire & numerics observatory: dynamic-range telemetry and the
shadow-quantized coded wire (ISSUE 10).

ROADMAP item 4 wants the worker→aggregator wire narrowed to bf16/int8 (the
reference shipped blosc-compressed gradients, ``compress_gradient.py``; the
communication-efficient coding line — PAPERS.md arXiv:1802.03475,
CodedReduce arXiv:1902.01981 — makes wire bytes the scaling bottleneck at
large n). Before any dtype change lands, this module MEASURES it, under the
telemetry spine's standing invariant: zero extra device fetches, zero
retraces, and the f32 training path bit-for-bit untouched.

Three instruments, all riding the existing (K, m) metric block:

**Numerics columns** (``cfg.numerics_watch == "on"``) — per-step dynamic-
range statistics of three pipeline stages: the pre-encode per-worker
gradients (``grad``), the post-encode codewords that would cross the wire
(``wire``), and the decoded aggregate (``agg``). Per stage: absmax, rms,
underflow fraction at the bf16-subnormal threshold (values a bf16 wire
would flush to zero), underflow fraction at the int8-per-block-scale
threshold (values a per-block-scaled int8 wire would round to zero),
overflow fraction past bf16 max, the non-finite fraction, and a coarse
base-2 exponent histogram (EXP_EDGES bins, as fractions — fractions rather
than raw counts because an f32-carried count loses integer exactness past
2^24 elements, which d·n already exceeds at LM scale). Every statistic is
computed over the FINITE elements only, so an injected NaN/Inf fault
(resilience/faults.py) yields finite sentinel values plus a loud
``nonfinite`` fraction instead of poisoning the metric block — the
chaos-matrix NaN-safety contract.

**Shadow-quantized wire** (``cfg.shadow_wire ∈ {bf16, int8}``) — inside the
same step body the codewords are rounded to the narrow dtype (int8 with
per-block scales over ``cfg.shadow_block``-element blocks; optional
stochastic rounding via ``cfg.shadow_round``) and decoded ALONGSIDE the f32
path. Only the f32 decode updates parameters, so the K∈{1,4} bitwise
equivalence suites hold with the shadow enabled; the shadow emits:

  shadow_err          relative L2 error of the shadow aggregate vs the f32
                      aggregate — the end-to-end cost of the narrow wire
  shadow_residual     the shadow decode's own health residual (cyclic:
                      fitted-codeword self-consistency at a quantization-
                      aware flag threshold, SHADOW_REL_TOL; approx:
                      measured residual vs the true mean; maj_vote:
                      1 − shadow vote agreement)
  shadow_flag_agree   fraction of present workers whose shadow detection
                      flag equals the f32 flag (1.0 = quantization changed
                      no accusation)
  shadow_det_flagged / shadow_det_tp
                      the shadow flag set scored against the seeded
                      schedules, so detection precision/recall *under
                      quantization* is measured, not assumed

All shadow columns are NaN-sentineled (``SHADOW_SENTINEL``): a fault-
poisoned comparison lands at −1.0, never NaN, so the block stays finite.

**Wire ledger** (:func:`wire_ledger`, jax-free) — logical wire bytes per
worker per step from the program's registered shapes (cyclic ships re+im,
everything else one row of d f32s), with the bf16/int8 candidate sizes, for
``status.json``'s ``wire`` block, callers that report wire bytes, and
``tools/wire_study.py``.

The int8 shadow stores its levels in f32 (every int8 value is exact in
f32): the shadow never leaves the chip, so only the LOGICAL bytes matter —
the ledger tracks those; the program needs no narrow buffer. The bf16
shadow uses real bf16 converts (whitelisted promotion sites under the dtype
lint rule; shadow-watch programs register with ``BF16_DTYPES``).

Like the rest of draco_tpu/obs this module imports WITHOUT jax (in-graph
functions import it lazily), so jax-free tools can use the ledger and the
column-name helpers.
"""

from __future__ import annotations

from typing import Optional

# ---- thresholds (jax-free constants) --------------------------------------

# smallest positive bfloat16 subnormal (2^-126 · 2^-7): an f32 value below
# this flushes to zero when a bf16 wire carries it
BF16_TINY = 2.0 ** -133
# largest finite bfloat16 (0x7F7F): an f32 value above this rounds to inf
# on a bf16 wire
BF16_MAX = 3.3895313892515355e38
# int8 quantization levels per sign (symmetric per-block scale absmax/127)
INT8_LEVELS = 127.0
# default per-block scale granularity (elements per block along the last
# axis) — cfg.shadow_block overrides
DEFAULT_BLOCK = 256

# coarse exponent histogram: bin edges in floor(log2 |x|) over finite
# nonzero elements. Bin i covers [EXP_EDGES[i-1], EXP_EDGES[i]) with the
# open ends below the first and at/above the last edge, i.e.
# (-inf,-32) [-32,-16) [-16,-8) [-8,0) [0,8) [8,+inf) — six bins bracketing
# where bf16/int8 rounding decisions happen for gradient-scale data
EXP_EDGES = (-32, -16, -8, 0, 8)
NUM_EXP_BINS = len(EXP_EDGES) + 1

NUMERICS_STAGES = ("grad", "wire", "agg")
STAT_NAMES = ("absmax", "rms", "uf_bf16", "uf_int8", "of_bf16",
              "nonfinite") + tuple(f"exp{i}" for i in range(NUM_EXP_BINS))
NUMERICS_PREFIX = "nx_"

SHADOW_NAMES = ("shadow_err", "shadow_residual", "shadow_flag_agree",
                "shadow_det_flagged", "shadow_det_tp")
# finite sentinel for a fault-poisoned shadow comparison (real values of
# every shadow column are >= 0, so -1 is unambiguous)
SHADOW_SENTINEL = -1.0

# ---- the REAL narrow wire (ISSUE 15) --------------------------------------
# cfg.wire_dtype picks what the worker→aggregator wire PHYSICALLY carries:
WIRE_DTYPES = ("f32", "bf16", "int8")

# Regularization λ for the cyclic locator solve per wire dtype, scaled to
# the dtype's quantization noise floor on the SIGNAL-normalized Hankel
# system (the λ path divides the syndrome by the received rows' RMS, so a
# pure-quantization syndrome sits at the dtype's relative noise — measured
# ≤ 4.6e-3 bf16 / ≤ 1.6e-2 int8 at n=32 s=3, tools/wire_study.py locator
# cells). λ sits ~2× above each measured floor and acts twice, both
# branchless: (1) the syndrome-significance GATE — relative syndrome below
# λ certifies no corruption, collapsing the locator magnitudes to uniform
# so the spread-rank bias (coding/cyclic.SPREAD_PHI) pins the
# well-conditioned honest subset, instead of the noise-driven subset whose
# exact codeword fit extrapolates quantization noise ~4e4× (the PR 10
# n=32 s=3 blocker); (2) the solve's noise-floor cutoff — singular
# directions with σ ≤ λ are dropped outright (coding/linalg.truncated_lstsq
# λ semantics). λ=0 (the f32 wire) is the exact historical path, bitwise.
WIRE_LOCATOR_LAMBDA = {"f32": 0.0, "bf16": 2.0 ** -8, "int8": 2.0 ** -6}

# Per-(n, s, dtype) cyclic flag thresholds for the REAL narrow wire,
# DERIVED by tools/wire_study.py's locator-margin cells (committed in
# wire_study.json's threshold_table and re-verified by --check): each
# entry sits between the measured worst honest-row deviation (quantization
# noise through the λ-regularized locator/fit solves) and the measured
# smallest adversary-row deviation at the in-scope attack magnitudes.
# Shapes not in the table fall back to the per-dtype SHADOW_REL_TOL
# calibration band — run wire_study at the target shape before shipping a
# narrow wire there (wire_rel_tol docstring).
WIRE_REL_TOL_TABLE = {
    # study shapes (n=8): the PR 10 shadow calibration band holds
    (8, 1, "bf16"): 5e-2, (8, 1, "int8"): 1.5e-1,
    # the PR 10 blocker shape: UNUSABLE unregularized (no-adversary honest
    # deviations amplified to 29–137× the row RMS — past any threshold);
    # usable with the λ-regularized locator, whose measured no-adversary
    # honest deviations sit under 0.047/0.24 vs adversary deviations above
    # 0.33 (wire_study.py locator cells, re-verified by --check). Measured
    # limit: WITH live adversaries at this shape, honest rows extrapolated
    # through the locator fit deviate up to 0.79/7.5 — past these
    # thresholds — so detection recall holds but flag precision degrades
    # in the adversary regime (honest_dev_max_adv in the committed cells;
    # PERF_HISTORY.md §17). The certificate these entries carry is the
    # no-adversary one the PR 10 blocker was about.
    (32, 3, "bf16"): 2e-1, (32, 3, "int8"): 2.8e-1,
}

# Guard/incident residual slack per wire dtype: on a narrow wire the
# UNFLAGGED honest rows deviate from the fitted codeword by rounding noise
# (not f32 noise), and the approx family's measured residual carries the
# end-to-end quantization error on top of its analytic bound (which prices
# drops only). guards.assess and the decode_residual incident detector add
# this to their tolerances so a clean narrow-wire step is not a trip —
# sized ~3× the committed shadow-study maxima (bf16 err ≤0.6%, int8 ≤3.5%).
WIRE_RESIDUAL_SLACK = {"f32": 0.0, "bf16": 2e-2, "int8": 1e-1}

# f32-ward widening ladder (the autopilot's wire_widen remediation walks
# it one step at a time; wire_narrow walks back toward the configured
# dtype): int8 -> bf16 -> f32
WIRE_WIDEN = {"int8": "bf16", "bf16": "f32", "f32": "f32"}


def wire_rel_tol(n: int, s: int, dtype: str) -> float:
    """The cyclic flag threshold a REAL narrow wire decodes with at
    (n, s): the committed per-shape table entry, else — inside the
    s ≤ 2 band PR 10 measured — the per-dtype calibration default
    (SHADOW_REL_TOL). Outside both, ``inf``: no usable threshold is
    KNOWN, and config.validate routes such shapes to the approx family
    (whose decode has no locator to amplify the quantization noise,
    arXiv:1802.03475) until tools/wire_study.py measures them. f32 keeps
    HEALTH_REL_TOL — resolved by the caller, not here."""
    key = (int(n), int(s), dtype)
    if key in WIRE_REL_TOL_TABLE:
        return WIRE_REL_TOL_TABLE[key]
    if int(s) <= 2:
        return SHADOW_REL_TOL[dtype]
    return float("inf")


def wire_locator_lambda(dtype: str) -> float:
    return WIRE_LOCATOR_LAMBDA[dtype]


def wire_residual_slack(dtype: str) -> float:
    return WIRE_RESIDUAL_SLACK.get(dtype, 0.0)


def narrow_toward(current: str, target: str) -> str:
    """One narrowing step from ``current`` toward ``target`` (the
    autopilot's wire_narrow ladder): f32 -> bf16 -> int8, never past the
    configured target."""
    order = ("f32", "bf16", "int8")
    ci, ti = order.index(current), order.index(target)
    return order[min(ci + 1, ti)] if ci < ti else current


# quantization-aware flag threshold for the SHADOW cyclic decode (relative
# amplitude, same role as coding/cyclic.HEALTH_REL_TOL = 1e-3): honest rows
# on a quantized wire deviate from the fitted codeword by the rounding
# noise (~2^-9 relative for bf16, ~1/254 of the block absmax for int8)
# AMPLIFIED through the locator/fit solves — loudest in the no-live-
# adversary regime, where the locator system is rank-deficient and the
# truncated solve spreads the noise (measured worst honest deviation at
# n≤9, s≤2: 0.03 relative for bf16, 0.1 for int8 — vs f32's ~1e-6).
# These thresholds cover that band with ~2× margin while sitting two
# orders under the in-scope attack payloads (O(100×) amplitude). They are
# the thresholds a REAL narrow wire would ship with at these shapes;
# at larger (n, s) the amplification grows further — run
# tools/wire_study.py at the target shape before narrowing the wire
# (ROADMAP item 4), that measurement being this module's whole point.
SHADOW_REL_TOL = {"bf16": 5e-2, "int8": 1.5e-1}


def watch_enabled(cfg) -> bool:
    """True when the step bodies should compute any observatory columns."""
    return cfg.numerics_watch == "on" or cfg.shadow_wire != "off"


def numerics_metric_names() -> tuple:
    """Column order of the numerics block: 3 stages × STAT_NAMES."""
    return tuple(f"{NUMERICS_PREFIX}{stage}_{stat}"
                 for stage in NUMERICS_STAGES for stat in STAT_NAMES)


def watch_metric_names(cfg) -> tuple:
    """The observatory's contribution to a route's metric schema — the one
    name source for step bodies and the host flush (same contract as
    forensics.mask_metric_names)."""
    names = ()
    if cfg.numerics_watch == "on":
        names += numerics_metric_names()
    if cfg.shadow_wire != "off":
        names += SHADOW_NAMES
    return names


# --------------------------------------------------------------------------
# wire ledger (jax-free)
# --------------------------------------------------------------------------


def wire_rows(approach: str) -> int:
    """f32 words per gradient element on the wire: the cyclic code ships a
    complex codeword (re + im row pair); every other family ships one real
    row per worker."""
    return 2 if approach == "cyclic" else 1


# Segment quantum for the streaming segmented wire (ISSUE 16): cuts land
# on multiples of this so every segment is a whole number of kernel d-tiles.
# Mirrors ops/coded.TILE_D — pinned equal by tests/test_segments.py; kept a
# literal here so the ledger (and wire_study --check) stays jax-free.
SEGMENT_QUANTUM = 4096


def wire_segment_bounds(d: int, segments: int, block: int = 1) -> tuple:
    """Jax-free cut points for the streaming segmented wire: ``(b_0=0 <
    b_1 < ... < b_S=d)`` splitting the d axis into at most ``segments``
    pieces, every interior cut a multiple of the segment quantum
    (SEGMENT_QUANTUM when ``block`` divides it, else ``block`` itself).

    Quantum alignment is the bitwise-invariance contract: the int8
    per-block scales (one per ``block`` elements) and the (d,)-shaped
    shared stochastic-rounding draws never straddle an interior cut, so
    quantize-the-full-row-then-slice equals quantize-per-segment
    bit-for-bit — the narrow buffers are segment-invariant and only the
    decode is segmented. A ``d`` smaller than ``segments`` quanta yields
    fewer (possibly one) segments rather than sub-quantum slivers."""
    d = int(d)
    segments = max(int(segments), 1)
    block = max(int(block), 1)
    if d <= 0:
        return (0, 0)
    quantum = SEGMENT_QUANTUM if SEGMENT_QUANTUM % block == 0 else block
    units = -(-d // quantum)  # whole quanta covering d
    s_eff = max(min(segments, units), 1)
    per, rem = divmod(units, s_eff)
    bounds = [0]
    for i in range(s_eff):
        step = (per + (1 if i < rem else 0)) * quantum
        bounds.append(min(bounds[-1] + step, d))
    bounds[-1] = d
    # dedupe (clamping can only collapse trailing cuts onto d)
    out = [bounds[0]]
    for b in bounds[1:]:
        if b > out[-1]:
            out.append(b)
    return tuple(out)


def cfg_segment_bounds(cfg, dim: int) -> tuple:
    """The segment bounds a config induces at flat-gradient size ``dim``
    — THE one bounds source for the in-graph decode seams, the ledger and
    the tools, so they cannot drift. int8 wires align cuts to the
    per-block scale granularity; f32/bf16 only to the kernel d-tile."""
    block = (int(getattr(cfg, "shadow_block", DEFAULT_BLOCK))
             if getattr(cfg, "wire_dtype", "f32") == "int8" else 1)
    return wire_segment_bounds(dim, getattr(cfg, "wire_segments", 1),
                               block)


def _segment_bytes(bounds: tuple, rows: int, dtype: str,
                   block: int) -> list:
    """Per-segment wire bytes for one worker at ``dtype`` — the physical
    bytes of each [a, b) slice of the narrow buffers. Because interior
    cuts are block-aligned, per-segment int8 scale counts sum exactly to
    the unsegmented ledger's count (no padding hidden at the seams)."""
    out = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        w = rows * (b - a)
        if dtype == "f32":
            out.append(4 * w)
        elif dtype == "bf16":
            out.append(2 * w)
        else:  # int8: 1 byte/elem + f32 per-block scales
            out.append(w + 4 * rows * (-(-(b - a) // block)))
    return out


def wire_ledger(cfg, dim: int) -> dict:
    """Logical worker→aggregator wire bytes per step at the program's
    registered shapes, per dtype candidate — and, since ISSUE 15, the
    MATERIALIZED wire: ``wire_dtype`` names the dtype the step body
    actually rounds the codewords into (real bf16/int8 buffers crossing
    the sharding boundary) and ``physical_bytes_per_worker`` /
    ``physical_bytes_per_step`` are that candidate's bytes — equal BY
    CONSTRUCTION to the logical candidate row (the narrow buffers carry
    exactly 1 byte/elem + f32 per-block scales for int8, 2 bytes/elem for
    bf16), which is what wire_study --check re-verifies. int8 adds one
    f32 scale per ``cfg.shadow_block`` elements (per row)."""
    n = int(cfg.num_workers)
    rows = wire_rows(cfg.approach)
    words = rows * int(dim)
    block = max(int(getattr(cfg, "shadow_block", DEFAULT_BLOCK)), 1)
    blocks = rows * ((int(dim) + block - 1) // block)
    per_worker = {
        "f32": 4 * words,
        "bf16": 2 * words,
        "int8": words + 4 * blocks,  # 1 byte/elem + f32 per-block scales
    }
    wire_dtype = getattr(cfg, "wire_dtype", "f32")
    bounds = cfg_segment_bounds(cfg, dim)
    seg_worker = _segment_bytes(bounds, rows, wire_dtype, block)
    ledger = {
        "family": cfg.approach,
        "dim": int(dim),
        "num_workers": n,
        "wire_words_per_worker": words,
        "bytes_per_worker": per_worker,
        "bytes_per_step": {k: v * n for k, v in per_worker.items()},
        "wire_dtype": wire_dtype,
        "physical_bytes_per_worker": per_worker[wire_dtype],
        "physical_bytes_per_step": per_worker[wire_dtype] * n,
        "shadow_wire": cfg.shadow_wire,
        "shadow_block": block,
        # streaming segmented wire (ISSUE 16): the per-segment physical
        # bytes MUST sum to the per-worker/per-step rows above — block-
        # aligned cuts hide no padding at the seams (wire_study --check
        # re-verifies the sum on the committed matrix)
        "segments": {
            "count": len(bounds) - 1,
            "bounds": list(bounds),
            "physical_bytes_per_worker": seg_worker,
            "physical_bytes_per_step": [v * n for v in seg_worker],
        },
    }
    # hierarchical tree wire (ISSUE 17): per-level ingest bytes. Level 0
    # (leaves) carries the same n physical codewords as the flat wire —
    # level_bytes_per_step[0] == physical_bytes_per_step EXACTLY — and
    # each parent level carries one f32 decoded partial per child group
    # (perf_watch pins the sum identity on the committed study).
    if getattr(cfg, "topology", "flat") == "tree":
        from draco_tpu.coding.topology import tree_ledger_block

        ledger["tree"] = tree_ledger_block(
            n, int(cfg.tree_fanout), int(getattr(cfg, "tree_levels", 0)),
            int(dim), per_worker[wire_dtype])
    return ledger


# --------------------------------------------------------------------------
# in-graph numerics statistics (lazy jax imports)
# --------------------------------------------------------------------------


def _block_absmax(af, block: int):
    """Per-block absmax along the last axis (blocks pad with 0), broadcast
    back to ``af``'s shape — the int8 per-block scale basis. ``af`` must
    already be the finite-masked |x|."""
    import jax.numpy as jnp

    d = af.shape[-1]
    nb = (d + block - 1) // block
    pad = nb * block - d
    if pad:
        padding = [(0, 0)] * (af.ndim - 1) + [(0, pad)]
        af = jnp.pad(af, padding)
    blocked = af.reshape(af.shape[:-1] + (nb, block))
    bmax = jnp.max(blocked, axis=-1, keepdims=True)
    out = jnp.broadcast_to(bmax, blocked.shape)
    out = out.reshape(af.shape[:-1] + (nb * block,))
    return out[..., :d]


def _part_counts(x, block: int) -> dict:
    """Raw accumulators for one tensor (any shape): everything needed to
    combine multiple wire parts (cyclic re+im) without materializing their
    concatenation. All values are finite by construction."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    total = float(x.size)  # static
    a = jnp.abs(x)
    finite = jnp.isfinite(x)
    af = jnp.where(finite, a, 0.0)
    nonzero = finite & (a > 0)
    counts = {
        "total": total,
        "n_finite": jnp.sum(finite.astype(jnp.float32)),
        "sumsq": jnp.sum(jnp.where(finite, x * x, 0.0)),
        "absmax": jnp.max(af) if x.size else jnp.float32(0.0),
        "uf_bf16": jnp.sum((nonzero & (a < BF16_TINY)).astype(jnp.float32)),
        "of_bf16": jnp.sum((finite & (a > BF16_MAX)).astype(jnp.float32)),
    }
    thr = _block_absmax(af, block) / (2.0 * INT8_LEVELS)
    counts["uf_int8"] = jnp.sum((nonzero & (af < thr)).astype(jnp.float32))
    # exponent histogram over finite nonzero elements (log2 of the masked
    # |x| with zeros excluded by the nonzero gate)
    e = jnp.where(nonzero, jnp.log2(jnp.where(nonzero, af, 1.0)), 0.0)
    edges = (-float("inf"),) + tuple(float(v) for v in EXP_EDGES) \
        + (float("inf"),)
    counts["exp"] = [
        jnp.sum((nonzero & (e >= lo) & (e < hi)).astype(jnp.float32))
        for lo, hi in zip(edges[:-1], edges[1:])
    ]
    return counts


def stage_columns(stage: str, parts, block: int = DEFAULT_BLOCK) -> dict:
    """The ``nx_{stage}_*`` columns for one pipeline stage, combined over
    ``parts`` (a list of arrays — the cyclic wire is its (re, im) pair).
    Fractions are over ALL elements; absmax/rms over the finite ones, so a
    NaN/Inf fault yields finite sentinels plus a loud ``nonfinite``."""
    import jax.numpy as jnp

    acc = [_part_counts(p, block) for p in parts]
    total = sum(c["total"] for c in acc)
    n_finite = sum(c["n_finite"] for c in acc)
    sumsq = sum(c["sumsq"] for c in acc)
    absmax = acc[0]["absmax"]
    for c in acc[1:]:
        absmax = jnp.maximum(absmax, c["absmax"])
    denom = max(total, 1.0)
    cols = {
        f"{NUMERICS_PREFIX}{stage}_absmax": absmax,
        f"{NUMERICS_PREFIX}{stage}_rms": jnp.sqrt(
            sumsq / jnp.maximum(n_finite, 1.0)),
        f"{NUMERICS_PREFIX}{stage}_uf_bf16": sum(
            c["uf_bf16"] for c in acc) / denom,
        f"{NUMERICS_PREFIX}{stage}_uf_int8": sum(
            c["uf_int8"] for c in acc) / denom,
        f"{NUMERICS_PREFIX}{stage}_of_bf16": sum(
            c["of_bf16"] for c in acc) / denom,
        f"{NUMERICS_PREFIX}{stage}_nonfinite": (total - n_finite) / denom,
    }
    for i in range(NUM_EXP_BINS):
        cols[f"{NUMERICS_PREFIX}{stage}_exp{i}"] = sum(
            c["exp"][i] for c in acc) / denom
    return cols


def numerics_columns(cfg, grad_parts, wire_parts, agg) -> dict:
    """All three stages' columns (numerics_metric_names order)."""
    block = max(int(cfg.shadow_block), 1)
    cols = {}
    cols.update(stage_columns("grad", list(grad_parts), block))
    cols.update(stage_columns("wire", list(wire_parts), block))
    cols.update(stage_columns("agg", [agg], block))
    return cols


# --------------------------------------------------------------------------
# shadow quantizers (lazy jax imports)
# --------------------------------------------------------------------------


def _round_step_key(cfg, step, offset: int):
    """Per-step PRNG key for stochastic rounding — None under nearest
    rounding (the default), so the deterministic path adds no PRNG ops.
    Folded from (seed, step) like every other schedule; the noise draw is
    shared across wire rows (shape (d,)), so bitwise-identical rows
    (maj_vote's soundness condition) quantize bitwise-identically.
    ``offset`` separates the shadow and real-wire streams."""
    if cfg.shadow_round != "stochastic":
        return None
    import jax

    s = 0 if step is None else step
    return jax.random.fold_in(jax.random.key(cfg.seed + offset), s)


def shadow_step_key(cfg, step=None):
    """The shadow quantizer's stochastic-rounding key (_round_step_key)."""
    return _round_step_key(cfg, step, 11)


def _bf16_stochastic(x, key):
    """Stochastic bf16 rounding via the +rand16-truncate bit trick: f32 in,
    the exactly-bf16-representable f32 values out. ONE implementation for
    the shadow quantizer and the real wire — the calibration transfers
    because the arithmetic cannot drift (pinned bitwise in
    tests/test_wire.py)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    r = jax.random.bits(key, (x.shape[-1],), jnp.uint32) \
        & jnp.uint32(0xFFFF)
    bits = (bits + r) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _int8_levels_and_scale(x, block: int, key):
    """Symmetric per-block int8 quantization core — f32 rows in,
    ``(q, scale)`` out with ``q`` the integer levels in [-127, 127] held
    in f32 (exact) and ``scale`` the per-ELEMENT f32 scale (absmax/127
    over ``block``-element blocks along the last axis, constant within a
    block). Round-to-nearest, or floor(x/s + u) stochastic under ``key``;
    non-finite inputs map to 0 — a narrow integer wire has no NaN
    encoding, and non-finite attribution belongs to the ingest-row
    forensics (obs/forensics.nonfinite_rows), not the wire. ONE
    implementation for the shadow quantizer and the real wire."""
    import jax
    import jax.numpy as jnp

    block = max(int(block), 1)
    d = x.shape[-1]
    finite = jnp.isfinite(x)
    af = jnp.where(finite, jnp.abs(x), 0.0)
    bmax = _block_absmax(af, block)
    scale = jnp.where(bmax > 0, bmax / INT8_LEVELS, 1.0)
    y = jnp.where(finite, x, 0.0) / scale
    if key is None:
        q = jnp.round(y)
    else:
        u = jax.random.uniform(key, (d,), jnp.float32)
        q = jnp.floor(y + u)
    return jnp.clip(q, -INT8_LEVELS, INT8_LEVELS), scale


def quantize_rows(x, mode: str, block: int = DEFAULT_BLOCK, key=None):
    """Round wire rows to the narrow dtype, returning the DEQUANTIZED f32
    tensor the shadow decode consumes.

    ``bf16``: round-to-nearest-even via real bf16 converts (or stochastic
    via :func:`_bf16_stochastic` when ``key`` is set). ``int8``:
    :func:`_int8_levels_and_scale` — the SAME cores the real wire
    (narrow_wire_rows) quantizes with, so the shadow calibration
    transfers by construction."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    if mode == "bf16":
        if key is None:
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return _bf16_stochastic(x, key)
    if mode != "int8":
        raise ValueError(f"unknown shadow wire dtype: {mode!r}")
    q, scale = _int8_levels_and_scale(x, block, key)
    # int8 levels are exact in f32 — the shadow never leaves the chip, so
    # no narrow buffer is materialized (module docstring); the LOGICAL
    # bytes live in wire_ledger
    return q * scale


# --------------------------------------------------------------------------
# the REAL narrow wire (ISSUE 15): actual bf16/int8 buffers cross the
# sharding boundary; f32 exists again only inside the decode
# --------------------------------------------------------------------------


def wire_step_key(cfg, step=None):
    """Per-step PRNG key for the REAL wire's stochastic rounding
    (``cfg.shadow_round`` doubles as the wire rounding mode — the
    observatory knob it was calibrated with). Distinct stream from the
    shadow's (seed + 17 vs + 11, _round_step_key)."""
    return _round_step_key(cfg, step, 17)


def narrow_wire_rows(x, mode: str, block: int = DEFAULT_BLOCK, key=None):
    """Round (..., d) f32 wire rows into REAL narrow buffers — the arrays
    that physically cross the worker→aggregator sharding boundary.

    Returns a dict of narrow arrays:
      bf16: {"q": bfloat16 (..., d)}
      int8: {"q": int8 (..., d), "scale": f32 (..., ceil(d/block))}
            symmetric per-block scales (absmax/127 over ``block``-element
            blocks along the last axis, per row); non-finite inputs map
            to 0 (an integer wire has no NaN encoding — non-finite
            attribution belongs to the pre-encode ingest forensics).
    Rounding: nearest by default; ``key`` enables the shared-draw
    stochastic rounding (wire_step_key). The quantization cores
    (:func:`_bf16_stochastic`, :func:`_int8_levels_and_scale`) are THE
    SAME ones the shadow quantizer runs — the calibration transfers by
    construction, pinned bitwise in tests/test_wire.py."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    if mode == "bf16":
        if key is None:
            return {"q": x.astype(jnp.bfloat16)}
        # the stochastic-rounded value is exactly bf16-representable: the
        # narrowing cast is exact
        return {"q": _bf16_stochastic(x, key).astype(jnp.bfloat16)}
    if mode != "int8":
        raise ValueError(f"unknown wire dtype: {mode!r}")
    q, scale = _int8_levels_and_scale(x, block, key)
    # blocked scale buffer: within-block values are identical, so strided
    # slicing at the block starts yields the (..., nb) per-block scales
    return {"q": q.astype(jnp.int8),
            "scale": scale[..., ::max(int(block), 1)]}


def widen_wire_rows(buf: dict, mode: str, block: int = DEFAULT_BLOCK):
    """Narrow wire buffers -> the f32 rows the decode consumes (f32
    accumulation throughout). This is the ONLY widening site: on the XLA
    path the convert fuses into the consuming matmul; on TPU the
    narrow-ingest Pallas kernels (ops/decode_kernels) run the same
    arithmetic in-tile on VMEM blocks, so the widened (n, d) f32 matrix
    never round-trips HBM."""
    import jax.numpy as jnp

    q = jnp.asarray(buf["q"])
    if mode == "bf16":
        return q.astype(jnp.float32)
    if mode != "int8":
        raise ValueError(f"unknown wire dtype: {mode!r}")
    block = max(int(block), 1)
    d = q.shape[-1]
    scale = jnp.asarray(buf["scale"])
    wide = jnp.repeat(scale, block, axis=-1)[..., :d]
    return q.astype(jnp.float32) * wide


def wire_decode_params(cfg, n=None, s=None):
    """(rel_tol, lam) the cyclic decode runs with at ``cfg``'s wire dtype:
    (None, 0.0) on the f32 wire — the caller keeps HEALTH_REL_TOL and the
    exact λ=0 solve bitwise — else the committed per-(n, s, dtype)
    threshold and the dtype's locator λ. ``n``/``s`` override the flat
    (num_workers, worker_fail) shape — the tree route decodes each leaf
    group at the GROUP shape (fanout, s_g), so its thresholds come from
    that row of the table, not the flat one."""
    dtype = getattr(cfg, "wire_dtype", "f32")
    if dtype == "f32":
        return None, 0.0
    n = cfg.num_workers if n is None else n
    s = cfg.worker_fail if s is None else s
    return wire_rel_tol(n, s, dtype), wire_locator_lambda(dtype)


def narrow_wire_pair(cfg, enc_re, enc_im, step=None, constrain=None):
    """Apply the REAL narrow wire to a cyclic (re, im) codeword pair:
    quantize into narrow buffers — THE arrays that cross the sharding
    boundary (``constrain`` pins each to the worker axis) — then widen to
    f32 for the decode. Returns ``(enc_re, enc_im, wire)`` where ``wire``
    is ``(mode, buf_re, buf_im, block)`` for the narrow-ingest decode
    kernels, or None on the f32 wire (identity — no ops added)."""
    dtype = getattr(cfg, "wire_dtype", "f32")
    if dtype == "f32":
        return enc_re, enc_im, None
    import jax

    key = wire_step_key(cfg, step)
    k_im = None if key is None else jax.random.fold_in(key, 1)
    buf_re = narrow_wire_rows(enc_re, dtype, cfg.shadow_block, key)
    buf_im = narrow_wire_rows(enc_im, dtype, cfg.shadow_block, k_im)
    if constrain is not None:
        buf_re = {k: constrain(v) for k, v in buf_re.items()}
        buf_im = {k: constrain(v) for k, v in buf_im.items()}
    return (widen_wire_rows(buf_re, dtype, cfg.shadow_block),
            widen_wire_rows(buf_im, dtype, cfg.shadow_block),
            (dtype, buf_re, buf_im, int(cfg.shadow_block)))


def narrow_wire_single(cfg, rows, step=None, constrain=None):
    """The single-row-block variant (approx partial sums / maj_vote raw
    gradient rows): returns ``(rows_f32, wire)`` with ``wire`` =
    ``(mode, buf, block)`` or None on the f32 wire."""
    dtype = getattr(cfg, "wire_dtype", "f32")
    if dtype == "f32":
        return rows, None
    buf = narrow_wire_rows(rows, dtype, cfg.shadow_block,
                           wire_step_key(cfg, step))
    if constrain is not None:
        buf = {k: constrain(v) for k, v in buf.items()}
    return (widen_wire_rows(buf, dtype, cfg.shadow_block),
            (dtype, buf, int(cfg.shadow_block)))


# --------------------------------------------------------------------------
# shadow comparison columns
# --------------------------------------------------------------------------


def _finite_or(v, sentinel: float = SHADOW_SENTINEL):
    import jax.numpy as jnp

    v = jnp.asarray(v, jnp.float32)
    return jnp.where(jnp.isfinite(v), v, jnp.float32(sentinel))


def shadow_columns(agg, shadow_agg, shadow_residual, flags, shadow_flags,
                   adv_mask, present) -> dict:
    """The SHADOW_NAMES columns from one step's f32 + shadow decode pair
    (module docstring). The detection counts reimplement the present-gated
    scoring of parallel/common.decode_health_metrics on the SHADOW flag set
    (a straggling adversary is neither detectable nor ground truth)."""
    import jax.numpy as jnp

    agg = jnp.asarray(agg, jnp.float32)
    shadow_agg = jnp.asarray(shadow_agg, jnp.float32)
    n = int(jnp.asarray(flags).shape[0])
    pres = (jnp.ones((n,), bool) if present is None
            else jnp.asarray(present, bool))
    f = jnp.asarray(flags, bool) & pres
    sf = jnp.asarray(shadow_flags, bool) & pres
    adv = jnp.asarray(adv_mask, bool)
    err = jnp.sqrt(jnp.sum((shadow_agg - agg) ** 2)) / jnp.maximum(
        jnp.sqrt(jnp.sum(agg ** 2)), 1e-30)
    agree = jnp.sum(((f == sf) & pres).astype(jnp.float32)) / jnp.maximum(
        jnp.sum(pres.astype(jnp.float32)), 1.0)
    return {
        "shadow_err": _finite_or(err),
        "shadow_residual": _finite_or(shadow_residual),
        "shadow_flag_agree": _finite_or(agree),
        "shadow_det_flagged": jnp.sum(sf.astype(jnp.int32)),
        "shadow_det_tp": jnp.sum((sf & adv & pres).astype(jnp.int32)),
    }


# --------------------------------------------------------------------------
# per-family shadow drivers (one place, so the CNN bodies and the LM tail
# cannot drift on quantize/decode/compare semantics)
# --------------------------------------------------------------------------


def cyclic_shadow(cfg, code, enc_re, enc_im, agg, health, rand_factor,
                  leaf_offsets, present, adv_mask, step=None) -> dict:
    """Shadow decode of the quantized cyclic wire (both complex halves
    rounded), at the quantization-aware flag threshold SHADOW_REL_TOL.
    Decode granularity follows the live f32 decode so the flag sets
    compare apples to apples."""
    import jax

    from draco_tpu.coding import cyclic as cyclic_mod

    key = shadow_step_key(cfg, step)
    k_im = None if key is None else jax.random.fold_in(key, 1)
    q_re = quantize_rows(enc_re, cfg.shadow_wire, cfg.shadow_block, key)
    q_im = quantize_rows(enc_im, cfg.shadow_wire, cfg.shadow_block, k_im)
    rel_tol = SHADOW_REL_TOL[cfg.shadow_wire]
    if cfg.decode_granularity == "layer":
        sagg, _honest, sh = cyclic_mod.decode_layers(
            code, q_re, q_im, rand_factor, leaf_offsets, present=present,
            with_health=True, rel_tol=rel_tol)
    else:
        sagg, _honest, sh = cyclic_mod.decode(
            code, q_re, q_im, rand_factor, present=present,
            with_health=True, rel_tol=rel_tol)
    return shadow_columns(agg, sagg, sh["residual"], health["flagged"],
                          sh["flagged"], adv_mask, present)


def majvote_shadow(cfg, rep_code, grads, voted, vhealth, vkey, present,
                   adv_mask, step=None) -> dict:
    """Shadow vote over the quantized gradient rows (the repetition code's
    wire IS the raw rows). Deterministic quantization preserves within-
    group bitwise equality, so the vote's soundness condition holds on the
    shadow wire by construction; the columns verify it per step. The
    residual slot carries 1 − shadow vote agreement (the family's decode-
    health analogue)."""
    from draco_tpu.coding import repetition as rep_mod

    key = shadow_step_key(cfg, step)
    qg = quantize_rows(grads, cfg.shadow_wire, cfg.shadow_block, key)
    voted_s, sh = rep_mod.majority_vote(rep_code, qg, present=present,
                                        key=vkey, method=cfg.vote_check,
                                        with_health=True)
    return shadow_columns(voted, voted_s, 1.0 - sh["vote_agree"],
                          vhealth["flagged"], sh["flagged"], adv_mask,
                          present)


def approx_shadow(cfg, code, rows, grads, decoded, present,
                  adv_mask, step=None) -> dict:
    """Shadow partial-recovery decode of the quantized approx wire. This
    family has no located-error set (no Byzantine certificate), so the
    flag comparison is over the non-finite WIRE rows — meaningful under
    fault injection, identically empty on clean runs. The residual slot is
    the shadow decode's measured relative error vs the true batch-gradient
    mean (same units as the family's decode_residual column)."""
    from draco_tpu.coding import approx as approx_mod
    from draco_tpu.obs.forensics import nonfinite_rows

    key = shadow_step_key(cfg, step)
    q = quantize_rows(rows, cfg.shadow_wire, cfg.shadow_block, key)
    dec_s, _v, sh = approx_mod.decode(code, q, present=present,
                                      with_health=True, batch_grads=grads)
    return shadow_columns(decoded, dec_s, sh["residual"],
                          nonfinite_rows(rows), nonfinite_rows(q),
                          adv_mask, present)
