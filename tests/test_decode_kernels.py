"""Fused decode kernels (ISSUE 12): the shared linalg primitives, the
kernel-vs-XLA equivalence contract (bounded-err decode, IDENTICAL
honest/flag/loud sets), interpret-mode kernel bodies, the Mosaic TPU
lowering of the registered kernel programs, and the dispatch switch.

Equivalence tolerances follow the code's own accuracy against ground
truth: at the CI shapes both lowerings sit at f32 solve noise
(~1e-6 relative) and at the n=32 s=3 erasure shapes both drift to ~5e-3
(the honest-row DFT submatrix conditioning — measured equal for the two
solvers), so the suite pins fused-vs-xla within the same envelope the
existing xla-vs-truth tests use, and pins the discrete outputs (honest /
flagged / loud) bit-identical.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu.attacks import inject_cyclic
from draco_tpu.coding import approx as approx_mod
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.coding import linalg as linalg_mod
from draco_tpu.ops import decode_kernels


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _mesh(n_dev):
    from draco_tpu.runtime import make_mesh

    return make_mesh(n_dev, devices=jax.devices()[:n_dev])


@pytest.mark.parametrize("value,backend,n_dev,want", [
    ("auto", False, 1, "xla"),
    ("auto", True, 1, "pallas"),
    ("auto", True, 4, "xla"),  # the kernels are a one-device lowering
    ("xla", True, 1, "xla"),
    ("xla", False, 1, "xla"),
    ("pallas", True, 1, "pallas"),
    ("pallas", False, 1, "fused"),  # the reference lowering, said on stderr
    ("pallas", False, 4, "fused"),
])
def test_resolve_decode_impl(value, backend, n_dev, want, capfd):
    assert decode_kernels.resolve_decode_impl(
        value, _mesh(n_dev), backend_pallas=backend) == want
    # only the one substitution speaks, and it speaks every time
    assert ("not a TPU" in capfd.readouterr().err) == (want == "fused")


def test_resolve_decode_impl_rejects_what_it_cannot_meet():
    with pytest.raises(ValueError, match="auto|xla|pallas"):
        decode_kernels.resolve_decode_impl("mosaic", backend_pallas=True)
    # an explicit pallas on a TPU mesh that spans devices is an error, not
    # a quiet xla: GSPMD cannot partition a Mosaic kernel
    with pytest.raises(ValueError, match="one-device lowering"):
        decode_kernels.resolve_decode_impl("pallas", _mesh(4),
                                           backend_pallas=True)


def test_config_validates_decode_impl():
    from draco_tpu.config import TrainConfig

    cfg = TrainConfig(network="LeNet", dataset="synthetic-mnist",
                      approach="cyclic", num_workers=8, worker_fail=1,
                      decode_impl="mosaic")
    with pytest.raises(ValueError, match="decode_impl"):
        cfg.validate()


# ---------------------------------------------------------------------------
# shared linalg primitives (coding/linalg.py)
# ---------------------------------------------------------------------------

def _entry_rows(a):
    """(B, ...) batch-first numpy -> the fused tier's batch-LAST nested
    lists of (1, B) rows (coding/linalg.py): entry [r][c] of every system."""
    a = np.asarray(a)
    if a.ndim == 2:
        return [jnp.asarray(a[:, r])[None, :] for r in range(a.shape[1])]
    return [[jnp.asarray(a[:, r, c])[None, :] for c in range(a.shape[2])]
            for r in range(a.shape[1])]


def _jacobi(a, b, rcond, **kw):
    x = linalg_mod.jacobi_lstsq(_entry_rows(a), _entry_rows(b), rcond, **kw)
    return np.concatenate([np.asarray(r) for r in x], axis=0).T  # (B, m)


@pytest.mark.parametrize("m", [2, 4, 6, 8])  # 2s ≤ 8 covers s ≤ 4; the
# m=10 (s=5 ceiling) case pays ~20 s of eager pair-loop dispatch for no
# new code path, so it stays out of the tier-1 budget
def test_jacobi_lstsq_matches_truncated_svd(m, rng):
    a = rng.randn(3, m, m).astype(np.float32)
    a[1, :, -1] = a[1, :, 0]  # batch 1 genuinely rank-deficient
    b = rng.randn(3, m).astype(np.float32)
    x = _jacobi(a, b, 1e-5)
    for i in range(3):
        want, *_ = np.linalg.lstsq(a[i].astype(np.float64),
                                   b[i].astype(np.float64), rcond=1e-5)
        err = np.abs(x[i] - want).max() / max(1.0, np.abs(want).max())
        assert err < 2e-3, (i, err)


def test_jacobi_lstsq_zero_system_is_zero_and_finite():
    x = _jacobi(np.zeros((1, 4, 4), np.float32), np.ones((1, 4), np.float32),
                1e-5)
    assert (x == 0).all()


def _locate_on_clean_columns(code, cols, pres=None):
    """locator_core on B clean projected columns (n, B) — the recombination
    vector and health fit of whatever honest set the locator picks."""
    n = code.n
    pres_f = (np.ones((n, 1), np.float32) if pres is None
              else pres.astype(np.float32)[:, None])
    return jax.jit(cyclic_mod.locator_core, static_argnums=9)(
        jnp.asarray(cols.real.astype(np.float32)),
        jnp.asarray(cols.imag.astype(np.float32)),
        *(jnp.asarray(getattr(code, k)) for k in (
            "c2h_re", "c2h_im", "c1_re", "c1_im", "est_re", "est_im")),
        jnp.asarray(pres_f), code.s)


@pytest.mark.parametrize("n,s", [(8, 1), (9, 2), (32, 3)])
def test_locator_closed_form_recombination_solves_the_system(n, s, rng):
    """The Lagrange closed form IS the solve it replaced: on every column
    v is supported on exactly n−2s rows and vᵀC1 = e1ᵀ (the system
    ``_locate_v`` hands to an LU solve), whichever rows the erasures leave."""
    code = cyclic_mod.build_cyclic_code(n, s)
    m = n - 2 * s
    c1 = code.c1_re.astype(np.float64) + 1j * code.c1_im
    cols = c1 @ (rng.randn(m, 4) + 1j * rng.randn(m, 4))  # clean codewords
    pres = np.ones(n, bool)
    pres[rng.choice(n, size=s, replace=False)] = False
    cols[~pres] = 0.0
    v_re, v_im, honest, flagged, _, resid = _locate_on_clean_columns(
        code, cols, pres)
    v = np.asarray(v_re) + 1j * np.asarray(v_im)  # (n, B)
    honest = np.asarray(honest)
    assert (honest.sum(axis=0) == m).all() and not honest[~pres].any()
    assert (v[~honest] == 0).all()
    e1 = np.zeros(m)
    e1[0] = 1.0
    for col in range(4):
        np.testing.assert_allclose(v[:, col] @ c1, e1, atol=2e-4 * n)
    assert not np.asarray(flagged).any()
    # n=32 extrapolates the excluded rows at ~1e-4 (module docstring)
    assert float(np.asarray(resid).max()) < cyclic_mod.HEALTH_REL_TOL


def test_topk_mask_matches_lax_topk(rng):
    for n, m in ((8, 6), (16, 10), (32, 26)):
        mag = rng.rand(5, n).astype(np.float32)
        mask = np.asarray(linalg_mod.topk_mask(jnp.asarray(mag.T), m)).T
        for i in range(5):
            idx = np.asarray(jax.lax.top_k(jnp.asarray(mag[i]), m)[1])
            want = np.zeros(n, bool)
            want[np.sort(idx)] = True
            np.testing.assert_array_equal(mask[i], want)


def test_locator_fit_flags_the_row_off_the_interpolant(rng):
    """Health fit = the honest rows' degree-<m interpolant evaluated at
    the excluded rows: a clean excluded row sits on it (not flagged), a
    corrupted one is off it by its error (flagged) — the per-row deviation
    ``_locate_v`` reads off an (m, m) solve."""
    code = cyclic_mod.build_cyclic_code(9, 1)
    c1 = code.c1_re.astype(np.float64) + 1j * code.c1_im
    cols = c1 @ (rng.randn(7, 3) + 1j * rng.randn(7, 3))
    cols[4, 1] += 50.0  # one corrupt row in column 1 only
    _, _, honest, flagged, loud, _ = _locate_on_clean_columns(code, cols)
    want = np.zeros((9, 3), bool)
    want[4, 1] = True
    np.testing.assert_array_equal(np.asarray(flagged), want)
    np.testing.assert_array_equal(np.asarray(loud), want)
    assert not np.asarray(honest)[4, 1]


def test_masked_median_matches_nanmedian(rng):
    x = rng.randn(6, 11).astype(np.float32)
    mask = rng.rand(6, 11) > 0.3
    mask[5] = False  # all-masked row -> NaN, like nanmedian of all-NaN
    x[0, 0] = np.nan
    mask[0, 0] = False  # NaN outside the mask must not leak (0·NaN trap)
    got = np.asarray(linalg_mod.masked_median(jnp.asarray(x.T),
                                              jnp.asarray(mask.T)))[0]
    for i in range(6):
        if not mask[i].any():
            assert np.isnan(got[i])
            continue
        want = np.nanmedian(np.where(mask[i], x[i], np.nan))
        np.testing.assert_allclose(got[i], want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# cyclic: fused locator vs the XLA path (the equivalence contract)
# ---------------------------------------------------------------------------

def _attacked_wire(code, rng, d, t, e):
    """Encoded wire with t live adversaries + e zero-filled stragglers."""
    n = code.n
    bg = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic_mod.encode(code, jnp.asarray(bg[code.batch_ids]))
    picks = rng.choice(n, size=t + e, replace=False)
    adv = np.zeros(n, bool)
    adv[picks[:t]] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv),
                                   "rev_grad")
    present = np.ones(n, bool)
    present[picks[t:]] = False
    enc_re = enc_re * jnp.asarray(present)[:, None]
    enc_im = enc_im * jnp.asarray(present)[:, None]
    rf = jnp.asarray(rng.normal(loc=1.0, size=d).astype(np.float32))
    pres = jnp.asarray(present) if e else None
    return bg, enc_re, enc_im, rf, adv, pres


@pytest.mark.parametrize("n,s,t,e,tol", [
    (8, 1, 1, 0, 1e-4), (11, 2, 2, 0, 1e-4), (11, 2, 1, 1, 1e-4),
    (16, 3, 3, 0, 1e-3), (32, 3, 2, 1, 2e-2), (32, 3, 3, 0, 2e-2),
])
def test_cyclic_fused_matches_xla(n, s, t, e, tol, rng):
    """Decoded bounded-err vs xla AND vs truth at the xla path's own
    accuracy envelope; honest/flagged/loud bit-identical."""
    code = cyclic_mod.build_cyclic_code(n, s)
    d = 192
    bg, er, ei, rf, adv, pres = _attacked_wire(code, rng, d, t, e)
    def decode(impl):  # each path ONE compiled program (tests/parity.py)
        return jax.jit(lambda er, ei, rf, pres: cyclic_mod.decode(
            code, er, ei, rf, present=pres, with_health=True, impl=impl))(
                er, ei, rf, pres)

    dx, hx, hlx = decode("xla")
    df, hf, hlf = decode("fused")
    np.testing.assert_array_equal(np.asarray(hx), np.asarray(hf))
    np.testing.assert_array_equal(np.asarray(hlx["flagged"]),
                                  np.asarray(hlf["flagged"]))
    np.testing.assert_array_equal(np.asarray(hlx["loud"]),
                                  np.asarray(hlf["loud"]))
    want = bg.sum(axis=0) / n
    scale = np.abs(want).max()
    assert np.abs(np.asarray(df) - want).max() / scale < tol
    assert np.abs(np.asarray(df) - np.asarray(dx)).max() / scale < tol
    assert not np.asarray(hf)[adv].any()
    assert float(hlf["residual"]) < 1e-3  # clean decode: solve noise only


@pytest.mark.parametrize("n,s", [(8, 1), (11, 2)])
def test_cyclic_fused_layer_matches_xla(n, s, rng):
    code = cyclic_mod.build_cyclic_code(n, s)
    d = 192
    bg, er, ei, rf, adv, _ = _attacked_wire(code, rng, d, s, 0)
    offs = [0, 40, 100, d]
    def decode(impl):
        return jax.jit(lambda er, ei, rf: cyclic_mod.decode_layers(
            code, er, ei, rf, offs, with_health=True, impl=impl))(er, ei, rf)

    dx, hx, hlx = decode("xla")
    df, hf, hlf = decode("fused")
    np.testing.assert_array_equal(np.asarray(hx), np.asarray(hf))
    np.testing.assert_array_equal(np.asarray(hlx["flagged"]),
                                  np.asarray(hlf["flagged"]))
    np.testing.assert_array_equal(np.asarray(hlx["loud"]),
                                  np.asarray(hlf["loud"]))
    want = bg.sum(axis=0) / n
    scale = np.abs(want).max()
    assert np.abs(np.asarray(df) - want).max() / scale < 1e-4
    assert np.abs(np.asarray(df) - np.asarray(dx)).max() / scale < 1e-4


def test_cyclic_fused_beyond_budget_keeps_fault_signals(rng):
    """s+1 corruptions: the fused path keeps the budget-exceeded guard
    signal (flagged rows > s — coding/cyclic._locate_v docstring) and the
    loud forensic mask still names the magnitude outliers, identically to
    the xla impl."""
    code = cyclic_mod.build_cyclic_code(8, 1)
    d = 128
    bg = rng.randn(8, d).astype(np.float32)
    er, ei = cyclic_mod.encode(code, jnp.asarray(bg[code.batch_ids]))
    adv = np.zeros(8, bool)
    adv[[2, 5]] = True  # 2 > s = 1
    er, ei = inject_cyclic(er, ei, jnp.asarray(adv), "rev_grad")
    rf = jnp.asarray(rng.normal(loc=1.0, size=d).astype(np.float32))
    flags = {}
    for impl in ("xla", "fused"):
        _, _, hl = parity.run_jitted(
            cyclic_mod.decode, code, er, ei, rf, with_health=True, impl=impl)
        assert int(np.asarray(hl["flagged"]).sum()) > code.s, impl
        # the loud forensic mask still names the magnitude outliers
        assert np.asarray(hl["loud"])[adv].all(), impl
        flags[impl] = (np.asarray(hl["flagged"]), np.asarray(hl["loud"]))
    np.testing.assert_array_equal(flags["xla"][0], flags["fused"][0])
    np.testing.assert_array_equal(flags["xla"][1], flags["fused"][1])


def test_cyclic_fused_nan_wire_accuses_nobody(rng):
    """NaN wire: decode non-finite (guard territory), flag/loud sets
    empty — same attribution discipline as the xla path."""
    code = cyclic_mod.build_cyclic_code(8, 1)
    d = 64
    er = jnp.asarray(np.full((8, d), np.nan, np.float32))
    ei = jnp.zeros((8, d), jnp.float32)
    rf = jnp.ones((d,), jnp.float32)
    for impl in ("xla", "fused"):
        dec, _, hl = parity.run_jitted(
            cyclic_mod.decode, code, er, ei, rf, with_health=True, impl=impl)
        assert not np.isfinite(np.asarray(dec)).all(), impl
        assert not np.asarray(hl["flagged"]).any(), impl
        assert not np.asarray(hl["loud"]).any(), impl


# ---------------------------------------------------------------------------
# approx: fused decode vs the XLA path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,r,drops", [(8, 1.5, 0), (8, 1.5, 2),
                                       (12, 2.0, 3)])
def test_approx_fused_matches_xla(n, r, drops, rng):
    code = approx_mod.build_approx_code(n, r)
    d = 257
    bg = rng.randn(n, d).astype(np.float32)
    rows = approx_mod.encode_shared(code, jnp.asarray(bg))
    present = np.ones(n, bool)
    if drops:
        present[rng.choice(n, size=drops, replace=False)] = False
    pres = jnp.asarray(present)
    dx, vx, hlx = approx_mod.decode(code, rows, present=pres,
                                    with_health=True,
                                    batch_grads=jnp.asarray(bg), impl="xla")
    df, vf, hlf = approx_mod.decode(code, rows, present=pres,
                                    with_health=True,
                                    batch_grads=jnp.asarray(bg),
                                    impl="fused")
    # identical weight solve (shared prologue): v bitwise
    np.testing.assert_array_equal(np.asarray(vx), np.asarray(vf))
    np.testing.assert_array_equal(np.asarray(hlx["bound"]),
                                  np.asarray(hlf["bound"]))
    np.testing.assert_array_equal(np.asarray(hlx["recovered_fraction"]),
                                  np.asarray(hlf["recovered_fraction"]))
    scale = max(1e-9, np.abs(np.asarray(dx)).max())
    assert np.abs(np.asarray(df) - np.asarray(dx)).max() / scale < 1e-5
    # the certificate holds on the fused path's own numbers
    assert float(hlf["residual"]) <= float(hlf["bound"]) + 1e-4
    assert abs(float(hlf["residual"]) - float(hlx["residual"])) < 1e-4


# ---------------------------------------------------------------------------
# the kernels themselves: interpret mode (CI covers the kernel body
# without a TPU) + the Mosaic TPU lowering of the registered programs
# ---------------------------------------------------------------------------

def test_cyclic_kernel_interpret_bitwise_vs_reference(rng):
    """pallas_call(interpret=True) runs the SAME locator_core the fused
    reference jits — block plumbing (grid, lane padding, output slicing) is
    the only difference, so every discrete output (honest / flagged / loud)
    is bit-identical. The decoded vector agrees to f32 rounding, not to
    the bit: XLA:CPU vectorizes a 3-lane stack and a 128-lane block
    differently (measured 3.6e-7 absolute here)."""
    code = cyclic_mod.build_cyclic_code(8, 1)
    d = 300
    bg = rng.randn(8, d).astype(np.float32)
    er, ei = cyclic_mod.encode(code, jnp.asarray(bg[code.batch_ids]))
    adv = np.zeros(8, bool)
    adv[3] = True
    er, ei = inject_cyclic(er, ei, jnp.asarray(adv), "rev_grad")
    rf = jnp.asarray(rng.normal(loc=1.0, size=d).astype(np.float32))
    offs = [0, 50, 128, d]  # 3 layers: exercises the L % LAYER_BLOCK pad
    out_f = cyclic_mod.decode_layers(code, er, ei, rf, offs,
                                     with_health=True, impl="fused")
    out_k = cyclic_mod.decode_layers(code, er, ei, rf, offs,
                                     with_health=True,
                                     impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out_f[0]), np.asarray(out_k[0]),
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out_f[1]), np.asarray(out_k[1]))
    for key in ("flagged", "loud"):
        np.testing.assert_array_equal(np.asarray(out_f[2][key]),
                                      np.asarray(out_k[2][key]))
    # both residuals ARE rounding noise (~1e-8 against a 1e-3 trip level)
    np.testing.assert_allclose(float(out_f[2]["residual"]),
                               float(out_k[2]["residual"]), rtol=0,
                               atol=1e-6)
    assert not np.asarray(out_k[1])[:, adv].any()


def test_approx_kernel_interpret_matches_reference(rng):
    """Ragged d (not a TILE_D multiple) + a NaN payload in an absent row:
    the kernel's where-mask must drop it (0·NaN = NaN through the matvec
    otherwise) and the accumulated health scalars must match the
    reference sweep to accumulation-order noise."""
    n, d = 8, 5000
    code = approx_mod.build_approx_code(n, 1.5)
    bg = rng.randn(n, d).astype(np.float32)
    rows = np.array(approx_mod.encode_shared(code, jnp.asarray(bg)))
    present = np.ones(n, bool)
    present[2] = False
    rows[2] = np.nan
    args = dict(present=jnp.asarray(present), with_health=True,
                batch_grads=jnp.asarray(bg))
    o_f = approx_mod.decode(code, jnp.asarray(rows), impl="fused", **args)
    o_k = approx_mod.decode(code, jnp.asarray(rows),
                            impl="pallas_interpret", **args)
    assert np.isfinite(np.asarray(o_k[0])).all()
    scale = max(1e-9, np.abs(np.asarray(o_f[0])).max())
    assert np.abs(np.asarray(o_f[0]) - np.asarray(o_k[0])).max() / scale \
        < 1e-5
    assert abs(float(o_f[2]["residual"]) - float(o_k[2]["residual"])) < 1e-4
    assert float(o_k[2]["residual"]) <= float(o_k[2]["bound"]) + 1e-4


def test_kernel_programs_export_for_tpu():
    """The registered kernel-bearing lint programs pass the Python-side
    Mosaic TPU lowering via cross-platform export on this CPU host — the
    tpu_attn_lowering_check methodology, here as a plain test so a kernel
    edit that breaks the TPU lowering fails CI, not a chip window."""
    from jax import export as jexport

    progs = decode_kernels.lint_programs()
    assert {p.name for p in progs} == {"kernel_cyclic_locator",
                                       "kernel_approx_decode",
                                       "kernel_cyclic_narrow_recombine",
                                       "kernel_approx_decode_narrow",
                                       "kernel_cyclic_narrow_recombine_bf16",
                                       "kernel_approx_decode_narrow_bf16"}
    for prog in progs:
        bp = prog.build()
        exp = jexport.export(bp.fn, platforms=["tpu"])(*[
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in bp.args])
        assert len(exp.mlir_module_serialized) > 0
        assert not bp.capture_memory  # tpu_custom_call can't compile on CPU


def test_kernel_programs_registered():
    """registry.collect() carries the kernel rows (the committed
    program_lint.json must cover them — test_program_lint pins that)."""
    from draco_tpu.analysis.registry import collect

    names = {p.name for p in collect()}
    assert {"kernel_cyclic_locator", "kernel_approx_decode",
            "cnn_cyclic_layer_step", "cnn_cyclic_layer_pallas_step",
            "cnn_approx_pallas_step",
            "lm_sp_ring_approx_pallas_many_k2"} <= names


# ---------------------------------------------------------------------------
# production step bodies on the fused path: eager-vs-chunked bitwise
# WITHIN the impl, bounded-err + identical flag columns vs the xla impl
# ---------------------------------------------------------------------------

def _mini_cfg(**overrides):
    from draco_tpu.config import TrainConfig

    kw = dict(network="LeNet", dataset="synthetic-mnist", approach="cyclic",
              batch_size=2, num_workers=8, worker_fail=1,
              err_mode="rev_grad", lr=0.01, momentum=0.9, max_steps=4,
              eval_freq=0, train_dir="", log_every=10 ** 9)
    kw.update(overrides)
    return TrainConfig(**kw)


@pytest.mark.slow  # two full train-setup builds + K=4 scan compiles
# (~40 s); the decode semantics are pinned by the fast coding-level
# equivalence tests above — this is the end-to-end integration layer
@pytest.mark.parametrize("overrides", [
    dict(decode_granularity="layer"),
    dict(approach="approx", worker_fail=0, redundancy="shared",
         code_redundancy=1.5),
])
def test_train_step_fused_decode_equivalence(overrides, rng):
    """The fused decode through the REAL step body: per-step losses and
    decoded updates bounded-err vs the xla impl, every discrete telemetry
    column (flag counts, detection counts, packed forensics masks)
    bit-identical, zero retraces across the 4 eager dispatches, and the
    K=4 chunk agreeing with the 4 eager steps WITHIN each impl at
    scan-vs-eager fusion noise (the strict bitwise K∈{1,4} contract lives
    at the Trainer level — tests/test_chunked_trainer.py — and stays on
    the xla path this suite leaves untouched; raw train_step-vs-train_many
    already differs at ~3e-8 on the unmodified xla impl)."""
    import numpy as np

    from draco_tpu import rng as drng
    from draco_tpu.models import input_shape
    from draco_tpu.runtime import make_mesh
    from draco_tpu.training.step import build_train_setup

    k = 4
    mesh = make_mesh(8)
    shape = input_shape("synthetic-mnist")
    xs = rng.randn(k, 8, 2, *shape).astype(np.float32)
    ys = rng.randint(0, 10, size=(k, 8, 2)).astype(np.int32)
    adv = drng.adversary_schedule(428, k + 1, 8, 1)
    masks = jnp.asarray(np.asarray(adv[1:k + 1]))

    discrete = {"located_errors", "det_tp", "det_adv", "honest_located",
                "recovered_fraction"}
    results = {}
    for impl in ("xla", "pallas"):  # pallas resolves to fused on CPU
        setup = build_train_setup(_mini_cfg(**overrides,
                                            decode_impl=impl), mesh)
        st = setup.state
        rows = []
        for i in range(k):
            st, m = setup.train_step(st, jnp.asarray(xs[i]),
                                     jnp.asarray(ys[i]), masks[i])
            rows.append({kk: np.asarray(v) for kk, v in m.items()})
        # compile-once contract: 4 dispatches, one executable (the fused
        # dispatch tag is static — a retrace here would be the silent
        # steady-state recompile the PR 5 sentinel guards against)
        assert setup.train_step._cache_size() == 1, impl
        # K=4 chunk vs the 4 eager steps, same impl
        setup2 = build_train_setup(_mini_cfg(**overrides,
                                             decode_impl=impl), mesh)
        st_many, block = setup2.train_many(
            setup2.state, jnp.asarray(xs), jnp.asarray(ys), masks, None)
        for li, (a, b) in enumerate(zip(jax.tree.leaves(st.params),
                                        jax.tree.leaves(st_many.params))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=f"{impl} leaf {li}")
        block = np.asarray(block)
        for i, name in enumerate(setup2.metric_names):
            col = np.asarray([r[name] for r in rows], np.float32)
            if name in discrete or name.startswith("wmask_"):
                np.testing.assert_array_equal(block[:, i], col,
                                              err_msg=f"{impl} {name}")
            else:
                np.testing.assert_allclose(block[:, i], col, rtol=1e-4,
                                           atol=1e-5,
                                           err_msg=f"{impl} {name}")
        results[impl] = (rows, st)

    rows_x, st_x = results["xla"]
    rows_f, st_f = results["pallas"]
    for i in range(k):
        for name in rows_x[i]:
            a, b = rows_x[i][name], rows_f[i][name]
            if name in discrete or name.startswith("wmask_"):
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"step {i} {name}")
            else:
                np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4,
                                           err_msg=f"step {i} {name}")
    for a, b in zip(jax.tree.leaves(st_x.params),
                    jax.tree.leaves(st_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
