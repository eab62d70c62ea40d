"""Fused coded-decode kernels — the second and third Pallas TPU kernels
(ISSUE 12, ROADMAP item 4).

PR 9's committed device ledger puts the coded decode at 17–25% of LM
device time (4.5–13.5% CNN) at CI shapes — the largest non-matmul phase,
and the one that grows with n as the flat aggregation point ingests more
codewords. Two kernels attack it:

``cyclic_locator``
    Steps 2–5 of the cyclic decode — syndrome matmuls → Hankel locator
    solve → honest-row top-k → recombination vector → fitted-codeword
    health residual — fused into one kernel over the per-layer projected
    columns, batch-last: each grid step loads an (n, 128) block of the
    (n, L) projected-column stack into VMEM — 128 columns on the lanes —
    and runs the whole locator chain on it (``coding/cyclic.locator_core``
    — the SAME function the reference path jits, so the two lowerings
    cannot drift), instead of round-tripping ~6 solver ops per layer
    through HBM. The in-graph health/forensics columns (residual,
    flagged, loud, honest) are KERNEL OUTPUTS — observability is part of
    the contract, not a casualty of fusion.

``approx_decode``
    The approx family's partial-recovery decode tail: where-mask →
    combine-matvec → true-mean → residual-vs-bound norms, fused into ONE
    pass over the (n, d) wire and batch-gradient blocks (the XLA path
    pays ≥ 4 separate HBM sweeps for the same numbers). The d axis is the
    grid; per-row presence masking (true zeros — a NaN payload survives
    multiplicative masking), the decode matvec, the true-mean matvec and
    both squared-norm accumulators live in VMEM, with 128-lane partial
    sums accumulated across sequential grid steps (the
    ``ops/coded._project_kernel`` accumulator pattern).

Dispatch (``resolve_decode_impl``): ``cfg.decode_impl = "auto"`` selects
the kernels on a TPU backend when the step's mesh is ONE device, and the XLA
lowering on a mesh that spans devices (GSPMD cannot partition a Mosaic
kernel, and the decode sits in the replicated part of the step) and off-TPU;
``"pallas"`` selects the kernels on a one-device TPU mesh, raises on a TPU
mesh that spans devices, and off-TPU says, on stderr, that it runs their
reference lowering instead (the same functions through XLA —
coding/cyclic.locator_core / coding/approx._decode_fused); ``"xla"`` pins
the historical path bit-for-bit. The choice is made once per setup from the
backend and the mesh — never per call, never by catching a failure.
Interpret mode covers the kernel bodies in CI without a TPU, and
tests/test_chip_compile.py compiles every kernel for a described v5e with
the chip's own compiler (interpret mode and the cross-platform export both
accept programs that compiler refuses — PERF.md, chip bring-up).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from draco_tpu.ops.coded import TILE_D, _pad_d, use_pallas

# Projected columns (layers / wire segments) per cyclic-locator grid step:
# the lane width. The (n, L) batch-last stack is padded up to a multiple of
# this; padded columns run the locator on zeros (harmlessly — the truncated
# solves are zero-safe) and the wrapper slices them away.
LAYER_BLOCK = 128


def resolve_decode_impl(value: str, mesh=None, backend_pallas=None) -> str:
    """cfg.decode_impl -> the coding-layer ``impl`` tag. Static per setup:
    it depends only on the attached backend and on ``mesh`` — the mesh the
    step program is built for — so the jitted step closes over the result;
    no retraces, and nothing gives way to anything else at run time.

    The kernels are a ONE-DEVICE lowering: inside a program that GSPMD
    partitions over several devices a Mosaic kernel is refused at compile
    time ("cannot be automatically partitioned"), and the decode runs in
    exactly that replicated-after-the-gather part of the step. So:

      auto    the kernels on a TPU backend when the mesh is one device;
              xla on a mesh that spans devices, and off-TPU
      xla     the historical lowering, everywhere
      pallas  the kernels on a one-device TPU mesh; on a TPU mesh that
              spans devices a ValueError — the request cannot be met and
              is not reinterpreted. Off-TPU the kernels cannot be built;
              the request resolves — LOUDLY, on stderr — to ``fused``,
              the kernels' reference lowering (the same functions through
              XLA), which is what the CPU tests, the lint registry and
              the committed CPU artifacts drive
    """
    if backend_pallas is None:
        backend_pallas = use_pallas()
    n_dev = 1 if mesh is None else mesh.devices.size
    if value == "xla":
        return "xla"
    if value == "auto":
        return "pallas" if backend_pallas and n_dev == 1 else "xla"
    if value == "pallas":
        if backend_pallas and n_dev > 1:
            raise ValueError(
                f"decode_impl=pallas: the decode kernels are a one-device "
                f"lowering and this mesh spans {n_dev} devices (a Mosaic "
                f"kernel cannot be partitioned by GSPMD); use "
                f"decode_impl=auto or xla")
        if backend_pallas:
            return "pallas"
        print(f"decode_impl=pallas: backend is {jax.default_backend()!r}, "
              f"not a TPU — the kernels cannot run here; using their XLA "
              f"reference lowering (impl=fused)", file=sys.stderr, flush=True)
        return "fused"
    raise ValueError(f"decode_impl must be auto|xla|pallas, got {value!r}")


# ---------------------------------------------------------------------------
# cyclic: fused locator (steps 2-5), grid over per-layer projected columns
# ---------------------------------------------------------------------------


def _cyclic_locator_kernel(s, rel_tol, lam, e_re_ref, e_im_ref, c2h_re_ref,
                           c2h_im_ref, c1_re_ref, c1_im_ref, est_re_ref,
                           est_im_ref, pres_ref, v_re_ref, v_im_ref,
                           honest_ref, flagged_ref, loud_ref, resid_ref):
    from draco_tpu.coding import cyclic as cyclic_mod

    v_re, v_im, honest, flagged, loud, resid = cyclic_mod.locator_core(
        e_re_ref[...], e_im_ref[...], c2h_re_ref[...], c2h_im_ref[...],
        c1_re_ref[...], c1_im_ref[...], est_re_ref[...], est_im_ref[...],
        pres_ref[...], s, rel_tol, lam=lam)
    v_re_ref[...] = v_re
    v_im_ref[...] = v_im
    honest_ref[...] = jnp.where(honest, 1.0, 0.0)
    flagged_ref[...] = jnp.where(flagged, 1.0, 0.0)
    loud_ref[...] = jnp.where(loud, 1.0, 0.0)
    resid_ref[...] = resid


@functools.partial(jax.jit,
                   static_argnames=("s", "rel_tol", "lam", "interpret"))
def _cyclic_locator_pallas(e_re, e_im, c2h_re, c2h_im, c1_re, c1_im,
                           est_re, est_im, pres_f, s, rel_tol, lam,
                           interpret):
    n, L = e_re.shape
    lp = -(-L // LAYER_BLOCK) * LAYER_BLOCK
    if lp != L:
        pad = [(0, 0), (0, lp - L)]
        e_re = jnp.pad(e_re, pad)
        e_im = jnp.pad(e_im, pad)
    if s == 0:  # no syndrome: a placeholder row stands in for the (0, n) C2ᴴ
        c2h_re = c2h_im = jnp.zeros((1, n), jnp.float32)
    col = lambda i: (0, i)  # noqa: E731
    whole = lambda i: (0, 0)  # noqa: E731
    blk = (n, LAYER_BLOCK)
    consts = (c2h_re, c2h_im, c1_re, c1_im, est_re, est_im, pres_f)
    out = pl.pallas_call(
        functools.partial(_cyclic_locator_kernel, s, rel_tol, lam),
        grid=(lp // LAYER_BLOCK,),
        in_specs=[pl.BlockSpec(blk, col)] * 2
        + [pl.BlockSpec(c.shape, whole) for c in consts],
        out_specs=[pl.BlockSpec(blk, col)] * 5
        + [pl.BlockSpec((1, LAYER_BLOCK), col)],
        out_shape=[jax.ShapeDtypeStruct((n, lp), jnp.float32)] * 5
        + [jax.ShapeDtypeStruct((1, lp), jnp.float32)],
        interpret=interpret,
    )(e_re, e_im, *consts)
    v_re, v_im, honest, flagged, loud, resid = out
    return (v_re[:, :L], v_im[:, :L], honest[:, :L] > 0.5,
            flagged[:, :L] > 0.5, loud[:, :L] > 0.5, resid[:, :L])


def cyclic_locator(code, e_re, e_im, pres_f, rel_tol,
                   interpret: bool = False, lam: float = 0.0):
    """Kernel entry used by ``coding/cyclic._run_locator``: batch-last
    (n, L) projected-column stack -> the locator outputs of
    ``coding/cyclic.locator_core`` in the same layout (v pair and
    honest/flagged/loud masks (n, L), residual (1, L)). ``pres_f``: (n, 1)
    f32 presence column shared by every layer. ``lam``: static Tikhonov λ
    of the locator solve (narrow-wire regularization, ISSUE 15; 0.0 =
    exact path)."""
    return _cyclic_locator_pallas(
        e_re, e_im,
        jnp.asarray(code.c2h_re), jnp.asarray(code.c2h_im),
        jnp.asarray(code.c1_re), jnp.asarray(code.c1_im),
        jnp.asarray(code.est_re), jnp.asarray(code.est_im),
        jnp.asarray(pres_f), code.s, float(rel_tol), float(lam), interpret)


# ---------------------------------------------------------------------------
# narrow-ingest dequantization (ISSUE 15): widen bf16/int8 wire tiles to
# f32 INSIDE the kernel body, so the widened (n, d) f32 matrix never
# round-trips HBM — the dequant the XLA lowering pays as a separate
# convert/multiply pass happens on the VMEM-resident tile instead
# ---------------------------------------------------------------------------


def _dequant_tile(q, scale, block):
    """(n, T) narrow tile -> f32. ``q`` bf16 (scale None) or int8 with
    ``scale`` the (n, T/block) per-block f32 scales. The block broadcast
    is a matmul against an iota-built 0/1 expansion matrix — Mosaic has
    no gather/repeat, but (nb, T) one-hot times (n, nb) is MXU work."""
    if scale is None:
        return q.astype(jnp.float32)
    n, t = q.shape
    nb = scale.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (nb, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nb, t), 1)
    expand = ((col >= row * block)
              & (col < (row + 1) * block)).astype(jnp.float32)
    wide = jnp.dot(scale, expand, preferred_element_type=jnp.float32)
    return q.astype(jnp.float32) * wide


def _scale_tiles(scale, n_tiles: int, sb: int):
    """(n, nb) per-block int8 scales -> (n_tiles, n, sb), one (n, sb) slab
    per d tile, padded with 1.0 (padded q lanes are 0, so 0·1 stays 0).
    The slab is a block whose last two dims EQUAL the array's — the only
    way a block sb = TILE_D/block (16) lanes wide is legal on TPU once
    there is more than one tile; an (n, sb) window into the (n, nb) array
    is refused by the compiler at any real d."""
    n, nb = scale.shape
    if n_tiles * sb != nb:
        scale = jnp.pad(scale, [(0, 0), (0, n_tiles * sb - nb)],
                        constant_values=1.0)
    return jnp.moveaxis(scale.reshape(n, n_tiles, sb), 1, 0)


def _scale_spec(n: int, sb: int):
    return pl.BlockSpec((None, n, sb), lambda j: (j, 0, 0))


# ---------------------------------------------------------------------------
# approx: fused partial-recovery decode tail, grid over d tiles
# ---------------------------------------------------------------------------


def _approx_decode_body(d, n, block, rows_ref, scale_ref, bg_ref, vn_ref,
                        pres_ref, dec_ref, sqd_ref, sqg_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        sqd_ref[...] = jnp.zeros_like(sqd_ref)
        sqg_ref[...] = jnp.zeros_like(sqg_ref)

    base = j * TILE_D
    cols = base + jax.lax.broadcasted_iota(jnp.int32, (1, TILE_D), 1)
    live = (cols < d).astype(jnp.float32)  # ragged edge tile mask
    pres = pres_ref[...][:, :1]  # (n, 1) — lane 0 of the broadcast block
    raw = _dequant_tile(
        rows_ref[...], None if scale_ref is None else scale_ref[...], block)
    # true zero-fill of absent rows (0·NaN = NaN through the matvec —
    # multiplicative masking alone would pass a NaN payload)
    rows = jnp.where(pres > 0, raw, 0.0) * live
    bg = bg_ref[...] * live
    decoded = jnp.dot(vn_ref[...], rows,
                      preferred_element_type=jnp.float32)  # (1, T), Σv/n·row
    true_mean = jnp.dot(jnp.full((1, n), 1.0 / n, jnp.float32), bg,
                        preferred_element_type=jnp.float32)
    dec_ref[...] = decoded
    diff2 = (decoded - true_mean) ** 2
    sqd_ref[...] += diff2.reshape(TILE_D // 128, 128).sum(
        axis=0, keepdims=True)
    sqg_ref[...] += (bg * bg).reshape(n, TILE_D // 128, 128).sum(
        axis=(0, 1))[None, :]


def _approx_decode_kernel(d, n, rows_ref, bg_ref, vn_ref, pres_ref,
                          dec_ref, sqd_ref, sqg_ref):
    _approx_decode_body(d, n, 0, rows_ref, None, bg_ref, vn_ref, pres_ref,
                        dec_ref, sqd_ref, sqg_ref)


def _approx_decode_kernel_narrow(d, n, block, rows_ref, scale_ref, bg_ref,
                                 vn_ref, pres_ref, dec_ref, sqd_ref,
                                 sqg_ref):
    _approx_decode_body(d, n, block, rows_ref, scale_ref, bg_ref, vn_ref,
                        pres_ref, dec_ref, sqd_ref, sqg_ref)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _approx_decode_pallas(rows, bg, v_over_n, pres_wide, scale=None,
                          block=0, interpret=False):
    n, d = rows.shape
    rows_p = _pad_d(rows, TILE_D)
    bg_p = _pad_d(bg, TILE_D)
    dp = rows_p.shape[-1]
    grid = (dp // TILE_D,)
    whole = lambda j: (0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
        pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
        pl.BlockSpec((1, n), whole),
        pl.BlockSpec((n, 128), whole),
    ]
    operands = [rows_p, bg_p, v_over_n, pres_wide]
    if scale is None:
        kernel = functools.partial(_approx_decode_kernel, d, n)
    else:
        # per-block int8 scales ride their own (n, TILE_D/block) slabs
        sb = TILE_D // block
        kernel = functools.partial(_approx_decode_kernel_narrow, d, n,
                                   block)
        in_specs.insert(1, _scale_spec(n, sb))
        operands.insert(1, _scale_tiles(scale, dp // TILE_D, sb))
    decoded, sqd, sqg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((1, 128), whole),
            pl.BlockSpec((1, 128), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return decoded[0, :d], jnp.sum(sqd), jnp.sum(sqg)


def approx_decode(rows, batch_grads, v, pres_b, interpret: bool = False,
                  wire=None):
    """Kernel entry used by ``coding/approx._decode_fused``: one fused
    pass over the (n, d) wire + gradient blocks. Returns
    ``(decoded (d,), Σ(decoded − true_mean)², Σ batch_grads²)`` — the
    caller folds the two scalars into the residual-vs-bound health.

    ``wire`` (ISSUE 15): the narrow-ingest variant. ``(mode, buf)`` with
    ``buf`` the real narrow buffers (obs/numerics.narrow_wire_rows —
    bf16 ``{"q"}`` or int8 ``{"q", "scale"}`` at ``block`` granularity,
    passed as ``(mode, buf, block)`` for int8): the kernel loads the
    NARROW tiles and dequantizes in VMEM (_dequant_tile), so the widened
    f32 wire matrix never exists in HBM. ``rows`` is ignored then (the
    narrow buffers ARE the wire). int8 requires ``TILE_D % block == 0``
    (the per-tile scale columns must align; callers fall back to the
    pre-widened path otherwise)."""
    n = batch_grads.shape[0]
    pres_wide = jnp.broadcast_to(
        jnp.asarray(pres_b).astype(jnp.float32)[:, None], (n, 128))
    if wire is not None:
        mode, buf = wire[0], wire[1]
        if mode == "bf16":
            return _approx_decode_pallas(
                jnp.asarray(buf["q"]), batch_grads, (v / n)[None, :],
                pres_wide, interpret=interpret)
        block = int(wire[2])
        return _approx_decode_pallas(
            jnp.asarray(buf["q"]), batch_grads, (v / n)[None, :],
            pres_wide, scale=jnp.asarray(buf["scale"]), block=block,
            interpret=interpret)
    return _approx_decode_pallas(rows, batch_grads, (v / n)[None, :],
                                 pres_wide, interpret=interpret)


# ---------------------------------------------------------------------------
# cyclic: narrow-ingest recombination (ISSUE 15) — Re[vᵀ(R_re + i·R_im)]
# with R supplied as the REAL narrow wire buffers, dequantized in-tile
# ---------------------------------------------------------------------------


def _cyclic_recombine_body(block, vr_ref, vi_ref, qr_ref, qi_ref, sr_ref,
                           si_ref, out_ref):
    rr = _dequant_tile(qr_ref[...],
                       None if sr_ref is None else sr_ref[...], block)
    ri = _dequant_tile(qi_ref[...],
                       None if si_ref is None else si_ref[...], block)
    out_ref[...] = (jnp.dot(vr_ref[...], rr,
                            preferred_element_type=jnp.float32)
                    - jnp.dot(vi_ref[...], ri,
                              preferred_element_type=jnp.float32))


def _cyclic_recombine_kernel_bf16(vr_ref, vi_ref, qr_ref, qi_ref, out_ref):
    _cyclic_recombine_body(0, vr_ref, vi_ref, qr_ref, qi_ref, None, None,
                           out_ref)


def _cyclic_recombine_kernel_int8(block, vr_ref, vi_ref, qr_ref, qi_ref,
                                  sr_ref, si_ref, out_ref):
    _cyclic_recombine_body(block, vr_ref, vi_ref, qr_ref, qi_ref, sr_ref,
                           si_ref, out_ref)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _cyclic_recombine_pallas(v_re, v_im, q_re, q_im, s_re=None, s_im=None,
                             block=0, interpret=False):
    n, d = q_re.shape
    qr_p = _pad_d(q_re, TILE_D)
    qi_p = _pad_d(q_im, TILE_D)
    dp = qr_p.shape[-1]
    grid = (dp // TILE_D,)
    whole = lambda j: (0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, n), whole), pl.BlockSpec((1, n), whole),
                pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
                pl.BlockSpec((n, TILE_D), lambda j: (0, j))]
    operands = [v_re[None, :], v_im[None, :], qr_p, qi_p]
    if s_re is None:
        kernel = _cyclic_recombine_kernel_bf16
    else:
        sb = TILE_D // block
        kernel = functools.partial(_cyclic_recombine_kernel_int8, block)
        in_specs += [_scale_spec(n, sb)] * 2
        operands += [_scale_tiles(s_re, dp // TILE_D, sb),
                     _scale_tiles(s_im, dp // TILE_D, sb)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TILE_D), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[0, :d]


def cyclic_narrow_recombine(v_re, v_im, wire, interpret: bool = False):
    """Narrow-ingest cyclic recombination: ``wire`` is the
    ``(mode, buf_re, buf_im, block)`` tuple of
    obs/numerics.narrow_wire_pair — the REAL bf16/int8 buffers that
    crossed the sharding boundary. The kernel streams the narrow tiles
    once and dequantizes in VMEM (_dequant_tile), so the widened f32
    (n, d) matrix never round-trips HBM — the narrow wire's HBM half of
    the ISSUE 15 win. int8 requires ``TILE_D % block == 0``."""
    mode, buf_re, buf_im, block = wire
    if mode == "bf16":
        return _cyclic_recombine_pallas(
            v_re, v_im, jnp.asarray(buf_re["q"]), jnp.asarray(buf_im["q"]),
            interpret=interpret)
    return _cyclic_recombine_pallas(
        v_re, v_im, jnp.asarray(buf_re["q"]), jnp.asarray(buf_im["q"]),
        s_re=jnp.asarray(buf_re["scale"]), s_im=jnp.asarray(buf_im["scale"]),
        block=int(block), interpret=interpret)


# ---------------------------------------------------------------------------
# streaming segmented wire (ISSUE 16): segment-offset entry points — the
# existing kernels already tile over d, so a segment is just a [a, b) slice
# of the operands (and, for the narrow wire, of the q/scale buffers); no new
# kernels, only sliced dispatch
# ---------------------------------------------------------------------------


def _slice_narrow_buf(buf, a, b, block):
    """[a, b) d-slice of one narrow buffer dict ({"q"[, "scale"]}) — the
    int8 per-block scale columns slice at block granularity, which is why
    interior segment cuts MUST be block-aligned
    (obs/numerics.wire_segment_bounds guarantees it)."""
    out = {"q": buf["q"][:, a:b]}
    if "scale" in buf:
        blk = max(int(block), 1)
        if a % blk:
            raise ValueError(
                f"segment cut {a} not aligned to int8 scale block {blk}")
        out["scale"] = buf["scale"][:, a // blk:-(-b // blk)]
    return out


def wire_slice_pair(wire, a: int, b: int):
    """Segment [a, b) view of a narrow_wire_pair tuple
    ``(mode, buf_re, buf_im, block)`` — same tuple shape, sliced buffers,
    so the unsegmented narrow-ingest kernels consume it unchanged."""
    if wire is None:
        return None
    mode, buf_re, buf_im, block = wire
    return (mode, _slice_narrow_buf(buf_re, a, b, block),
            _slice_narrow_buf(buf_im, a, b, block), block)


def wire_slice_single(wire, a: int, b: int):
    """Segment [a, b) view of a narrow_wire_single tuple
    ``(mode, buf, block)`` (the approx family's real wire)."""
    if wire is None:
        return None
    mode, buf, block = wire
    return (mode, _slice_narrow_buf(buf, a, b, block), block)


def cyclic_narrow_recombine_segment(v_re, v_im, wire, a: int, b: int,
                                    interpret: bool = False):
    """Per-segment narrow-ingest recombination: the [a, b) slice of
    ``cyclic_narrow_recombine`` with this segment's own recombination
    vector — the decode-on-arrival unit of the cyclic streaming wire."""
    return cyclic_narrow_recombine(v_re, v_im, wire_slice_pair(wire, a, b),
                                   interpret=interpret)


def approx_decode_segment(rows, batch_grads, v, pres_b, a: int, b: int,
                          interpret: bool = False, wire=None):
    """Per-segment approx decode tail: the [a, b) slice of
    ``approx_decode`` — returns this segment's ``(decoded (b-a,),
    Σ(decoded − true_mean)², Σ batch_grads²)``; the caller folds the
    scalar accumulators across segments BEFORE the final residual sqrt so
    the health verdict stays per-step."""
    w_seg = None if wire is None else wire_slice_single(wire, a, b)
    return approx_decode(rows[:, a:b], batch_grads[:, a:b], v, pres_b,
                         interpret=interpret, wire=w_seg)


def narrow_kernel_ok(wire) -> bool:
    """Static feasibility of the narrow-ingest kernels for this wire:
    int8 per-block scales must tile evenly into the TILE_D grid."""
    if wire is None:
        return False
    if wire[0] == "bf16":
        return True
    block = int(wire[-1])
    return block >= 1 and TILE_D % block == 0


# ---------------------------------------------------------------------------
# program-lint registration (draco_tpu/analysis) — the kernel-bearing rows
# ---------------------------------------------------------------------------


def lint_programs():
    """The pallas_call-bearing decode programs, linted like the flash
    kernel's rows (tools/tpu_attn_lowering_check.py): exported for the TPU
    platform on the CPU host — so the Python-side Mosaic lowering of both
    kernels runs on every CI lint sweep — with the memory-capture opt-out
    (tpu_custom_call cannot compile for the CPU backend). No state carry
    to donate, no collectives; constant-bloat, dtype and host-traffic
    still apply (a kernel baking a d-sized table or upcasting to f64 must
    fail here, not on chip)."""
    from draco_tpu.analysis.registry import (
        BuiltProgram, LintProgram, Manifest,
    )

    from draco_tpu.analysis.registry import BF16_DTYPES

    kernel_manifest = Manifest(require_donated=None, collectives=None)
    bf16_kernel_manifest = Manifest(require_donated=None, collectives=None,
                                    allowed_dtypes=BF16_DTYPES,
                                    required_dtypes=frozenset({"bf16"}))

    def build_cyclic():
        from draco_tpu.coding import cyclic as cyclic_mod

        code = cyclic_mod.build_cyclic_code(8, 1)
        L, n = 16, 8

        def fn(e_re, e_im, pres_f):
            return cyclic_locator(code, e_re, e_im, pres_f,
                                  cyclic_mod.HEALTH_REL_TOL)

        args = (jnp.zeros((n, L), jnp.float32),
                jnp.zeros((n, L), jnp.float32),
                jnp.ones((n, 1), jnp.float32))
        return BuiltProgram("kernel_cyclic_locator", jax.jit(fn), args,
                            None, kernel_manifest,
                            extra={"layers": L, "n": n, "s": code.s},
                            capture_memory=False)

    def build_approx():
        n, d = 8, 4096

        def fn(rows, bg, v, pres):
            return approx_decode(rows, bg, v, pres)

        args = (jnp.zeros((n, d), jnp.float32),
                jnp.zeros((n, d), jnp.float32),
                jnp.ones((n,), jnp.float32) / n,
                jnp.ones((n,), bool))
        return BuiltProgram("kernel_approx_decode", jax.jit(fn), args,
                            None, kernel_manifest,
                            extra={"n": n, "d": d},
                            capture_memory=False)

    def build_cyclic_narrow():
        n, d, block = 8, 4096, 256

        def fn(v_re, v_im, q_re, q_im, s_re, s_im):
            wire = ("int8", {"q": q_re, "scale": s_re},
                    {"q": q_im, "scale": s_im}, block)
            return cyclic_narrow_recombine(v_re, v_im, wire)

        nb = d // block
        args = (jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
                jnp.zeros((n, d), jnp.int8), jnp.zeros((n, d), jnp.int8),
                jnp.ones((n, nb), jnp.float32),
                jnp.ones((n, nb), jnp.float32))
        return BuiltProgram("kernel_cyclic_narrow_recombine", jax.jit(fn),
                            args, None, kernel_manifest,
                            extra={"n": n, "d": d, "block": block},
                            capture_memory=False)

    def build_approx_narrow():
        n, d, block = 8, 4096, 256

        def fn(q, s, bg, v, pres):
            return approx_decode(q, bg, v, pres,
                                 wire=("int8", {"q": q, "scale": s}, block))

        args = (jnp.zeros((n, d), jnp.int8),
                jnp.ones((n, d // block), jnp.float32),
                jnp.zeros((n, d), jnp.float32),
                jnp.ones((n,), jnp.float32) / n,
                jnp.ones((n,), bool))
        return BuiltProgram("kernel_approx_decode_narrow", jax.jit(fn),
                            args, None, kernel_manifest,
                            extra={"n": n, "d": d, "block": block},
                            capture_memory=False)

    def build_cyclic_narrow_bf16():
        n, d = 8, 4096

        def fn(v_re, v_im, q_re, q_im):
            wire = ("bf16", {"q": q_re}, {"q": q_im}, 256)
            return cyclic_narrow_recombine(v_re, v_im, wire)

        args = (jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
                jnp.zeros((n, d), jnp.bfloat16),
                jnp.zeros((n, d), jnp.bfloat16))
        return BuiltProgram("kernel_cyclic_narrow_recombine_bf16",
                            jax.jit(fn), args, None, bf16_kernel_manifest,
                            extra={"n": n, "d": d},
                            capture_memory=False)

    def build_approx_narrow_bf16():
        n, d = 8, 4096

        def fn(q, bg, v, pres):
            return approx_decode(q, bg, v, pres,
                                 wire=("bf16", {"q": q}, 256))

        args = (jnp.zeros((n, d), jnp.bfloat16),
                jnp.zeros((n, d), jnp.float32),
                jnp.ones((n,), jnp.float32) / n,
                jnp.ones((n,), bool))
        return BuiltProgram("kernel_approx_decode_narrow_bf16",
                            jax.jit(fn), args, None, bf16_kernel_manifest,
                            extra={"n": n, "d": d},
                            capture_memory=False)

    return [
        LintProgram(name="kernel_cyclic_locator", build=build_cyclic,
                    route="decode_kernel"),
        LintProgram(name="kernel_approx_decode", build=build_approx,
                    route="decode_kernel"),
        # narrow-ingest variants (ISSUE 15), BOTH wire dtypes: the int8
        # tiles + per-block scales and the bf16 tiles (which hit bf16's
        # stricter sublane tiling) are dequantized in VMEM (_dequant_tile)
        # — the TPU-platform export below runs their Python-side Mosaic
        # lowering on every CI lint sweep, like the other kernel rows
        LintProgram(name="kernel_cyclic_narrow_recombine",
                    build=build_cyclic_narrow, route="decode_kernel"),
        LintProgram(name="kernel_approx_decode_narrow",
                    build=build_approx_narrow, route="decode_kernel"),
        LintProgram(name="kernel_cyclic_narrow_recombine_bf16",
                    build=build_cyclic_narrow_bf16, route="decode_kernel"),
        LintProgram(name="kernel_approx_decode_narrow_bf16",
                    build=build_approx_narrow_bf16, route="decode_kernel"),
    ]
