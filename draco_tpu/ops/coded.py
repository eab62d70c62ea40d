"""Fused complex-arithmetic kernels for the cyclic gradient code.

Three ops cover the O(n·d) work of a cyclic encode/decode step (reference:
the einsum encode in src/worker/cyclic_worker.py:172-175 and the R-matvecs in
src/master/cyclic_master.py:154,171 around the native s×s solve of
src/c_coding.cpp):

  * ``complex_matmul``    — encode:      (Wr + i·Wi) @ G          for real G
  * ``complex_project``   — decode in:   (Rr + i·Ri) @ f          for real f
  * ``complex_recombine`` — decode out:  Re[(vr + i·vi)ᵀ (Rr + i·Ri)]

All three stream the big (n, d) operand exactly once; the complex pairing is
done in VMEM.

Dispatch: **jnp/XLA by default, everywhere** — measured on a real TPU v5e
(tools/tpu_kernel_check.py, baselines_out/tpu_kernels.json): at ResNet-18
gradient size (n=8, d≈11.2M) XLA's own lowering of the unfused matmul pairs
runs at near HBM-bound speed (encode 2.36 ms, project 0.74 ms, recombine
1.40 ms) while the hand-tiled Pallas kernels are 2.8–4.5× slower (encode
6.6 ms, project 3.3 ms, recombine 4.4 ms): with only n=8 sublanes per block
the sequential 1-D grid cannot saturate HBM, and XLA already fuses the
neighbouring elementwise work. The Pallas paths remain available via
``force=True`` (and run in interpret mode in CI) as regression references
and for future re-tuning on other topologies; production code takes the XLA
path, which is the north-star-sanctioned lowering ("XLA/Pallas").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PREC = jax.lax.Precision.HIGHEST

# d-axis tile: 8 MXU lanes' worth of f32 per row block; (n≤64, 4096)·f32
# blocks keep well under VMEM even with two inputs + two outputs resident.
TILE_D = 4096


def use_pallas() -> bool:
    """True when the attached backend compiles the Pallas kernels: a TPU.
    Drives the decode-kernel and flash-attention dispatch
    (ops/decode_kernels.resolve_decode_impl, ops/flash_attention); the three
    kernels in THIS module stay behind ``force=True`` after hardware
    measurement (module docstring)."""
    return jax.default_backend() == "tpu"


def _pad_d(x: jnp.ndarray, tile: int) -> jnp.ndarray:
    d = x.shape[-1]
    pad = (-d) % tile
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


# --------------------------------------------------------------------------
# encode: (Wr + i Wi) @ G, G real (n, d) -> two (n, d) outputs, one read of G
# --------------------------------------------------------------------------

def _matmul_kernel(wr_ref, wi_ref, g_ref, or_ref, oi_ref):
    g = g_ref[:]
    or_ref[:] = jnp.dot(wr_ref[:], g, preferred_element_type=jnp.float32, precision=PREC)
    oi_ref[:] = jnp.dot(wi_ref[:], g, preferred_element_type=jnp.float32, precision=PREC)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _matmul_pallas(w_re, w_im, g, interpret=False):
    n, d = g.shape
    gp = _pad_d(g, TILE_D)
    dp = gp.shape[-1]
    grid = (dp // TILE_D,)
    out_re, out_im = pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((w_re.shape[0], n), lambda j: (0, 0)),
            pl.BlockSpec((w_im.shape[0], n), lambda j: (0, 0)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((w_re.shape[0], TILE_D), lambda j: (0, j)),
            pl.BlockSpec((w_re.shape[0], TILE_D), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w_re.shape[0], dp), jnp.float32),
            jax.ShapeDtypeStruct((w_re.shape[0], dp), jnp.float32),
        ],
        interpret=interpret,
    )(w_re, w_im, gp)
    return out_re[:, :d], out_im[:, :d]


def complex_matmul(w_re, w_im, g, *, force=None, interpret=False):
    """(Wr + i·Wi) @ G for real G: returns (re, im).

    force: None = XLA (measured faster on TPU, see module docstring);
    True = Pallas kernel.
    """
    w_re, w_im, g = jnp.asarray(w_re), jnp.asarray(w_im), jnp.asarray(g)
    if force is True or interpret:
        return _matmul_pallas(w_re, w_im, g, interpret=interpret)
    return (
        jnp.matmul(w_re, g, precision=PREC),
        jnp.matmul(w_im, g, precision=PREC),
    )


# --------------------------------------------------------------------------
# project: (Rr + i Ri) @ f, f real (d,) -> two (n,) outputs; reduction over d
# accumulated per 128-wide lane group across sequential grid steps, both R's
# read once. The (n, 128) output block is a native f32 tile — an (n, 1)
# accumulator block (previous design) made Mosaic allocate scoped-vmem stack
# per grid step, which OOMed at ResNet-18 size (d≈11.2M, 2730 steps) on a
# real v5e; lane partials keep scoped vmem flat in d. Final 128-lane sum
# happens in XLA outside the kernel.
# --------------------------------------------------------------------------

def _project_kernel(d, rr_ref, ri_ref, f_ref, er_ref, ei_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        er_ref[:] = jnp.zeros_like(er_ref)
        ei_ref[:] = jnp.zeros_like(ei_ref)

    n = rr_ref.shape[0]
    base = j * TILE_D
    cols = base + jax.lax.broadcasted_iota(jnp.int32, (1, TILE_D), 1)
    f = jnp.where(cols < d, f_ref[:], 0.0)  # mask the ragged edge tile
    er_ref[:] += (rr_ref[:] * f).reshape(n, TILE_D // 128, 128).sum(axis=1)
    ei_ref[:] += (ri_ref[:] * f).reshape(n, TILE_D // 128, 128).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _project_pallas(r_re, r_im, f, interpret=False):
    n, d = r_re.shape
    rrp = _pad_d(r_re, TILE_D)
    rip = _pad_d(r_im, TILE_D)
    fp = _pad_d(f[None, :], TILE_D)
    dp = rrp.shape[-1]
    grid = (dp // TILE_D,)
    e_re, e_im = pl.pallas_call(
        functools.partial(_project_kernel, d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((1, TILE_D), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((n, 128), lambda j: (0, 0)),
            pl.BlockSpec((n, 128), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
        ],
        interpret=interpret,
    )(rrp, rip, fp)
    return e_re.sum(axis=1), e_im.sum(axis=1)


def complex_project(r_re, r_im, f, *, force=None, interpret=False):
    """(Rr + i·Ri) @ f for real f (d,): returns (re, im) of shape (n,)."""
    r_re, r_im, f = jnp.asarray(r_re), jnp.asarray(r_im), jnp.asarray(f)
    if force is True or interpret:
        return _project_pallas(r_re, r_im, f, interpret=interpret)
    return (
        jnp.matmul(r_re, f, precision=PREC),
        jnp.matmul(r_im, f, precision=PREC),
    )


# --------------------------------------------------------------------------
# recombine: Re[(vr + i vi)^T (Rr + i Ri)] = vr^T Rr - vi^T Ri, one pass
# --------------------------------------------------------------------------

def _recombine_kernel(vr_ref, vi_ref, rr_ref, ri_ref, out_ref):
    out_ref[:] = jnp.dot(vr_ref[:], rr_ref[:], preferred_element_type=jnp.float32, precision=PREC) - jnp.dot(
        vi_ref[:], ri_ref[:], preferred_element_type=jnp.float32, precision=PREC
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _recombine_pallas(v_re, v_im, r_re, r_im, interpret=False):
    n, d = r_re.shape
    rrp = _pad_d(r_re, TILE_D)
    rip = _pad_d(r_im, TILE_D)
    dp = rrp.shape[-1]
    grid = (dp // TILE_D,)
    out = pl.pallas_call(
        _recombine_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n), lambda j: (0, 0)),
            pl.BlockSpec((1, n), lambda j: (0, 0)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(v_re[None, :], v_im[None, :], rrp, rip)
    return out[0, :d]


def complex_recombine(v_re, v_im, r_re, r_im, *, force=None, interpret=False):
    """Re[(vr + i·vi)ᵀ (Rr + i·Ri)]: returns real (d,)."""
    v_re, v_im = jnp.asarray(v_re), jnp.asarray(v_im)
    r_re, r_im = jnp.asarray(r_re), jnp.asarray(r_im)
    if force is True or interpret:
        return _recombine_pallas(v_re, v_im, r_re, r_im, interpret=interpret)
    return jnp.matmul(v_re, r_re, precision=PREC) - jnp.matmul(v_im, r_im, precision=PREC)
