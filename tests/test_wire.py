"""The REAL narrow coded wire (ISSUE 15): bf16/int8 codewords end-to-end.

What this file pins, layer by layer:

  * λ=0 exact-path bitwise equality — the regularized-solver plumbing
    (coding/linalg, coding/cyclic) must leave the f32 wire's solves
    bit-for-bit untouched, and an explicit ``wire_dtype="f32"`` config
    must train bit-identically to the default.
  * The narrow buffers are REALLY narrow (bf16 / int8 element types, not
    dequantized f32 copies), roundtrip within the dtype's noise, and the
    int8 shared-draw stochastic rounding quantizes bitwise-identical rows
    bitwise-identically — maj_vote's soundness condition on the wire.
  * Narrow-mode training: bounded end-to-end error vs the f32 twin,
    detection P/R unchanged under a live adversary, zero guard trips —
    eager (K=1) vs chunked (K=4) bitwise-equal WITHIN a wire dtype, on
    the CNN loop and the LM routes including the real w×tp GSPMD mesh
    under compile_guard="raise".
  * The PR 10 blocker: at n=32 s=3 the UNREGULARIZED locator amplifies
    quantization noise past any usable threshold; the λ-regularized
    locator (signal-scale normalisation + syndrome-significance gate +
    spread-rank subset + noise-floor cutoff) restores the margin while
    still locating live adversaries exactly.
  * Narrow-ingest kernel parity: the Pallas in-tile dequant variants
    (ops/decode_kernels) match the widened-XLA path bitwise in interpret
    mode.
  * The autopilot wire dial: numerics_drift evidence emits a
    ``wire_widen`` remediation, sustained clean evidence a
    ``wire_narrow`` back toward the configured dtype.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu import rng as drng, runtime
from draco_tpu.coding import approx as approx_mod
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.coding import linalg as linalg_mod
from draco_tpu.config import TrainConfig
from draco_tpu.obs import numerics as nx
from draco_tpu.training.step import build_train_setup

NW = 8


# --------------------------------------------------------------------------
# λ plumbing: exact path bitwise, regularized path well-defined
# --------------------------------------------------------------------------


@pytest.mark.core
def test_lam_zero_paths_bitwise():
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.randn(6, 6).astype(np.float32))
    b = jnp.asarray(rs.randn(6).astype(np.float32))
    x0 = linalg_mod.truncated_lstsq(a, b, 1e-5)
    x1 = linalg_mod.truncated_lstsq(a, b, 1e-5, lam=0.0)
    assert np.array_equal(np.asarray(x0), np.asarray(x1))
    # fused tier: batch-last nested (1, B) entry rows (coding/linalg.py)
    ab = [[jnp.asarray(rs.randn(1, 4).astype(np.float32)) for _ in range(6)]
          for _ in range(6)]
    bb = [jnp.asarray(rs.randn(1, 4).astype(np.float32)) for _ in range(6)]
    j0 = linalg_mod.jacobi_lstsq(ab, bb, 1e-5)
    j1 = linalg_mod.jacobi_lstsq(ab, bb, 1e-5, lam=0.0)
    assert all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(j0, j1))
    ar, ai = (jnp.asarray(rs.randn(5, 5).astype(np.float32))
              for _ in range(2))
    br, bi = (jnp.asarray(rs.randn(5).astype(np.float32)) for _ in range(2))
    c0 = linalg_mod.complex_solve(ar, ai, br, bi, rcond=1e-5)
    c1 = linalg_mod.complex_solve(ar, ai, br, bi, rcond=1e-5, lam=0.0)
    assert all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(c0, c1))


@pytest.mark.core
def test_lam_drops_noise_floor_directions():
    """The λ path keeps directions above λ exact and zeroes those below
    (the truncated_lstsq noise-floor semantics)."""
    u = np.linalg.qr(np.random.RandomState(1).randn(4, 4))[0]
    a = jnp.asarray((u @ np.diag([1.0, 0.5, 1e-3, 1e-6]) @ u.T
                     ).astype(np.float32))
    b = jnp.asarray(np.ones(4, np.float32))
    # λ between the two small σ: the 1e-6 direction must vanish, the rest
    # solve exactly (compare against numpy pinv with the same cutoff)
    x = np.asarray(linalg_mod.truncated_lstsq(a, b, 1e-8, lam=1e-4))
    ainv = u @ np.diag([1.0, 2.0, 1e3, 0.0]) @ u.T
    assert np.allclose(x, ainv @ np.ones(4), rtol=1e-3)


# --------------------------------------------------------------------------
# narrow buffers
# --------------------------------------------------------------------------


@pytest.mark.core
def test_narrow_buffers_are_really_narrow():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 1000)
                    .astype(np.float32))
    b16 = nx.narrow_wire_rows(x, "bf16", 256)
    assert b16["q"].dtype == jnp.bfloat16
    w = nx.widen_wire_rows(b16, "bf16", 256)
    assert w.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(w - x) / (jnp.abs(x) + 1e-9))) < 2 ** -8
    i8 = nx.narrow_wire_rows(x, "int8", 256)
    assert i8["q"].dtype == jnp.int8
    assert i8["scale"].shape == (4, 4)  # ceil(1000/256) blocks per row
    w8 = nx.widen_wire_rows(i8, "int8", 256)
    # per-block absmax/127 scale: error bounded by half a level per block
    bmax = np.asarray(nx._block_absmax(jnp.abs(x), 256))
    assert np.all(np.abs(np.asarray(w8) - np.asarray(x))
                  <= bmax / 127.0 * 0.51 + 1e-9)


@pytest.mark.core
def test_int8_shared_draw_row_identical():
    """Stochastic rounding with the shared (d,) draw quantizes identical
    rows identically — the maj_vote soundness condition on the wire."""
    base = np.random.RandomState(0).randn(1000).astype(np.float32)
    g = jnp.asarray(np.stack([base, base, base * 2, base * 2]))
    key = jax.random.key(7)
    for mode in ("bf16", "int8"):
        buf = nx.narrow_wire_rows(g, mode, 256, key)
        w = np.asarray(nx.widen_wire_rows(buf, mode, 256))
        assert np.array_equal(w[0], w[1])
        assert np.array_equal(w[2], w[3])
        assert not np.array_equal(w[0], w[2])


@pytest.mark.core
def test_real_wire_matches_shadow_quantizer_bitwise():
    """The REAL wire's narrow-then-widen pipeline is BITWISE the shadow
    quantizer (obs/numerics.quantize_rows) under every mode — nearest and
    shared-draw stochastic, bf16 and int8, ragged block tail included.
    This is the 'calibration transfers' contract: the committed shadow
    study (PERF_HISTORY.md §13) priced exactly the arithmetic the real wire ships,
    so the two implementations may never drift apart."""
    x = np.random.RandomState(3).randn(5, 1000).astype(np.float32)
    x[0, 7] = np.inf
    x[2, 11] = np.nan  # non-finite maps to 0 in BOTH paths
    g = jnp.asarray(x)
    for mode in ("bf16", "int8"):
        for key in (None, jax.random.key(13)):
            shadow = np.asarray(nx.quantize_rows(g, mode, 192, key))
            real = np.asarray(nx.widen_wire_rows(
                nx.narrow_wire_rows(g, mode, 192, key), mode, 192))
            np.testing.assert_array_equal(shadow, real)


@pytest.mark.core
def test_wire_ledger_reports_materialized_dtype():
    cfg = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                      wire_dtype="int8", redundancy="shared")
    led = nx.wire_ledger(cfg, 1000)
    assert led["wire_dtype"] == "int8"
    assert led["physical_bytes_per_worker"] == led["bytes_per_worker"]["int8"]
    assert led["physical_bytes_per_step"] \
        == led["bytes_per_worker"]["int8"] * 8
    # the narrow ratios the acceptance pins: bf16 exactly 0.5, int8
    # 0.25 + the per-block scale overhead
    per = led["bytes_per_worker"]
    assert per["bf16"] * 2 == per["f32"]
    assert per["int8"] / per["f32"] <= 0.26


@pytest.mark.core
def test_wire_dtype_validation():
    ok = TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                     wire_dtype="bf16", redundancy="shared")
    ok.validate()
    with pytest.raises(ValueError, match="coded approach"):
        TrainConfig(approach="baseline", wire_dtype="bf16").validate()
    with pytest.raises(ValueError, match="mutually exclusive"):
        TrainConfig(approach="cyclic", worker_fail=1, num_workers=8,
                    wire_dtype="bf16", shadow_wire="bf16",
                    redundancy="shared").validate()
    # an unmeasured large-s shape routes to the approx family
    with pytest.raises(ValueError, match="approach=approx"):
        TrainConfig(approach="cyclic", worker_fail=3, num_workers=16,
                    wire_dtype="int8", redundancy="shared").validate()
    # ... which accepts the narrow wire (no locator to amplify noise)
    TrainConfig(approach="approx", worker_fail=0, num_workers=16,
                wire_dtype="int8", redundancy="shared",
                code_redundancy=1.5).validate()
    # the measured blocker shape is in the committed table
    TrainConfig(approach="cyclic", worker_fail=3, num_workers=32,
                wire_dtype="int8", redundancy="shared").validate()


# --------------------------------------------------------------------------
# the PR 10 blocker: n=32 s=3
# --------------------------------------------------------------------------


def _encode_quantized(code, dtype, adv_rows, seed=100, d=4096):
    rs = np.random.RandomState(seed)
    g = rs.randn(code.n, d).astype(np.float32) * 0.05
    enc_re, enc_im = cyclic_mod.encode_shared(code, jnp.asarray(g))
    adv = np.zeros(code.n, bool)
    if adv_rows:
        adv[rs.choice(code.n, adv_rows, replace=False)] = True
        m = jnp.asarray(adv)[:, None]
        enc_re = jnp.where(m, -100.0 * enc_re, enc_re)
        enc_im = jnp.where(m, -100.0 * enc_im, enc_im)
    buf_re = nx.narrow_wire_rows(enc_re, dtype, 256)
    buf_im = nx.narrow_wire_rows(enc_im, dtype, 256)
    return (nx.widen_wire_rows(buf_re, dtype, 256),
            nx.widen_wire_rows(buf_im, dtype, 256), adv,
            jnp.asarray(rs.randn(d).astype(np.float32)))


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_regularized_locator_solves_n32_s3_blocker(dtype):
    """λ=0 reproduces the PR 10 finding (no-adversary honest deviations
    amplified past ANY usable threshold); the committed λ restores the
    margin under the committed threshold while still locating and
    flagging live adversaries exactly."""
    code = cyclic_mod.build_cyclic_code(32, 3)
    lam = nx.wire_locator_lambda(dtype)
    tol = nx.wire_rel_tol(32, 3, dtype)
    assert 0.0 < tol < 1.0

    # no adversary: the rank-deficient regime. The amplification is
    # subset-conditioning dependent, so the blocker is a worst-case over
    # trials (exactly how the study measures it)
    hmax0 = hmax1 = 0.0
    blocked = parity.jitted(cyclic_mod.decode, code, with_health=True,
                            rel_tol=1e9, lam=0.0)
    regularized = parity.jitted(cyclic_mod.decode, code, with_health=True,
                                rel_tol=tol, lam=lam)
    for seed in range(100, 108):
        enc_re, enc_im, _, f = _encode_quantized(code, dtype, 0,
                                                 seed=seed)
        _, _, h0 = blocked(enc_re, enc_im, f)
        _, _, h1 = regularized(enc_re, enc_im, f)
        hmax0 = max(hmax0, float(jnp.max(h0["dev_rel"])))
        hmax1 = max(hmax1, float(jnp.max(h1["dev_rel"])))
        # regularized: nothing flagged on any clean trial
        assert int(jnp.sum(h1["flagged"])) == 0
    # the blocker (unregularized): honest deviations past any usable
    # threshold; regularized: every honest row under the committed one
    assert hmax0 > 1.0 > tol > hmax1

    # s live adversaries: located exactly, flagged above the threshold
    enc_re, enc_im, adv, f = _encode_quantized(code, dtype, 3)
    _, honest, h2 = regularized(enc_re, enc_im, f)
    honest = np.asarray(honest)
    assert not np.any(honest & adv)  # no adversary in the honest subset
    flagged = np.asarray(h2["flagged"])
    assert np.all(flagged[adv])  # every adversary flagged


# --------------------------------------------------------------------------
# narrow-mode training: CNN loop, eager vs chunked, det P/R, guard
# --------------------------------------------------------------------------


def _mk_cfg(**kw):
    base = dict(network="FC", dataset="synthetic-mnist", batch_size=4,
                num_workers=NW, lr=0.05, momentum=0.9, max_steps=8,
                eval_freq=0, train_dir="", log_every=1,
                approach="cyclic", worker_fail=1, err_mode="rev_grad",
                redundancy="shared")
    base.update(kw)
    return TrainConfig(**base)


def _run_eager(cfg, mesh, steps=4):
    setup = build_train_setup(cfg, mesh)
    adv = drng.adversary_schedule(cfg.seed, steps + 1, NW,
                                  cfg.num_adversaries)
    st = setup.state
    rows = []
    for s in range(1, steps + 1):
        x = jnp.asarray(np.random.RandomState(s)
                        .randn(NW, cfg.batch_size, 28, 28, 1)
                        .astype(np.float32))
        y = jnp.zeros((NW, cfg.batch_size), jnp.int32)
        st, m = setup.train_step(st, x, y, jnp.asarray(np.asarray(adv[s])))
        rows.append({k: np.asarray(v) for k, v in m.items()})
    pv = np.concatenate([np.ravel(t) for t in
                         jax.tree.leaves(jax.device_get(st.params))])
    return pv, rows


def _run_chunked(cfg, mesh, steps=4):
    setup = build_train_setup(cfg, mesh)
    adv = drng.adversary_schedule(cfg.seed, steps + 1, NW,
                                  cfg.num_adversaries)
    xs = jnp.asarray(np.stack([
        np.random.RandomState(s).randn(NW, cfg.batch_size, 28, 28, 1)
        .astype(np.float32) for s in range(1, steps + 1)]))
    ys = jnp.zeros((steps, NW, cfg.batch_size), jnp.int32)
    masks = jnp.asarray(np.asarray(adv[1:steps + 1]))
    st, block = setup.train_many(setup.state, xs, ys, masks, None)
    pv = np.concatenate([np.ravel(t) for t in
                         jax.tree.leaves(jax.device_get(st.params))])
    return pv, np.asarray(block), setup.metric_names


def test_f32_wire_mode_bitwise():
    """wire_dtype="f32" is the identity: bit-for-bit the default program's
    result on both execution shapes."""
    mesh = runtime.make_mesh(NW)
    p0, _ = _run_eager(_mk_cfg(), mesh)
    p1, _ = _run_eager(_mk_cfg(wire_dtype="f32"), mesh)
    assert np.array_equal(p0, p1)
    c0, b0, _ = _run_chunked(_mk_cfg(steps_per_call=4), mesh)
    c1, b1, _ = _run_chunked(_mk_cfg(steps_per_call=4, wire_dtype="f32"),
                             mesh)
    assert np.array_equal(c0, c1) and np.array_equal(b0, b1)
    assert np.array_equal(p0, c0)  # eager == chunked, unchanged


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_cnn_narrow_wire_bounded_err_det_preserved(dtype):
    """Narrow mode: eager == chunked bitwise WITHIN the dtype; bounded
    end-to-end error vs the f32 twin; detection P/R 1.0 under the live
    adversary; zero guard trips."""
    mesh = runtime.make_mesh(NW)
    kw = dict(wire_dtype=dtype, numerics_watch="on", step_guard="on")
    p_f32, _ = _run_eager(_mk_cfg(step_guard="on"), mesh)
    p_e, rows = _run_eager(_mk_cfg(**kw), mesh)
    p_c, block, names = _run_chunked(_mk_cfg(steps_per_call=4, **kw), mesh)
    assert np.array_equal(p_e, p_c)  # K∈{1,4} bitwise within the dtype
    err = np.linalg.norm(p_e - p_f32) / np.linalg.norm(p_f32)
    assert err < (2e-2 if dtype == "bf16" else 1e-1)
    assert err > 0.0  # the narrow wire is really there
    for r in rows:
        assert r["det_tp"] == r["det_adv"] == 1  # recall 1.0
        assert r["located_errors"] == 1  # precision 1.0
        assert r["guard_trips"] == 0
    # the chunked block agrees column-for-column with the eager rows
    for j, name in enumerate(names):
        eager_col = np.asarray([r[name] for r in rows], np.float32)
        assert np.array_equal(eager_col, block[:, j]), name


def test_majvote_narrow_wire_soundness():
    """maj_vote on an int8 stochastic wire: within-group agreement and
    detection identical to the f32 wire (the shared-draw row-identity
    carried through a real training step)."""
    mesh = runtime.make_mesh(NW)
    kw = dict(approach="maj_vote", group_size=4, worker_fail=1)

    def run(wire):
        cfg = _mk_cfg(wire_dtype=wire, shadow_round="stochastic",
                      step_guard="on", **kw)
        setup = build_train_setup(cfg, mesh)
        adv = drng.adversary_schedule(cfg.seed, 4, NW, cfg.num_adversaries)
        st = setup.state
        out = []
        gids = np.arange(NW) // 4
        for s in range(1, 4):
            xg = np.random.RandomState(s).randn(2, cfg.batch_size, 28, 28, 1
                                                ).astype(np.float32)
            x = jnp.asarray(xg[gids])  # group-replicated batches
            y = jnp.zeros((NW, cfg.batch_size), jnp.int32)
            st, m = setup.train_step(st, x, y,
                                     jnp.asarray(np.asarray(adv[s])))
            out.append({k: np.asarray(v) for k, v in m.items()})
        return out

    rows_f32 = run("f32")
    rows_i8 = run("int8")
    for a, b in zip(rows_f32, rows_i8):
        assert a["vote_agree"] == b["vote_agree"]
        assert b["det_tp"] == b["det_adv"] == 1
        assert b["guard_trips"] == 0


def test_approx_narrow_wire_within_bound_slack():
    """approx on a narrow wire: the measured residual carries the
    quantization error, the guard's wire slack absorbs it (zero trips),
    and the decode stays bounded."""
    mesh = runtime.make_mesh(NW)
    kw = dict(approach="approx", worker_fail=0, code_redundancy=1.5)
    p0, _ = _run_eager(_mk_cfg(step_guard="on", **kw), mesh)
    p8, rows = _run_eager(_mk_cfg(wire_dtype="int8", step_guard="on", **kw),
                          mesh)
    err = np.linalg.norm(p8 - p0) / np.linalg.norm(p0)
    assert 0.0 < err < 1e-1
    for r in rows:
        assert r["guard_trips"] == 0
        assert r["decode_residual"] > 0.0  # the quantization is visible


# --------------------------------------------------------------------------
# narrow-ingest kernels: interpret-mode parity with the widened XLA path
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_narrow_kernel_parity(dtype):
    from draco_tpu.ops import decode_kernels as dk

    rs = np.random.RandomState(0)
    n, d = 8, 5000  # ragged vs TILE_D
    code = cyclic_mod.build_cyclic_code(n, 1)
    g = rs.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic_mod.encode_shared(code, jnp.asarray(g))
    buf_re = nx.narrow_wire_rows(enc_re, dtype, 256)
    buf_im = nx.narrow_wire_rows(enc_im, dtype, 256)
    wre = nx.widen_wire_rows(buf_re, dtype, 256)
    wim = nx.widen_wire_rows(buf_im, dtype, 256)
    v_re = jnp.asarray(rs.randn(n).astype(np.float32))
    v_im = jnp.asarray(rs.randn(n).astype(np.float32))
    ref = np.asarray(jnp.matmul(v_re, wre) - jnp.matmul(v_im, wim))
    out = np.asarray(dk.cyclic_narrow_recombine(
        v_re, v_im, (dtype, buf_re, buf_im, 256), interpret=True))
    assert np.array_equal(out, ref)

    acode = approx_mod.build_approx_code(n, 1.5)
    rows = approx_mod.encode_shared(acode, jnp.asarray(g))
    pres = np.ones(n, bool)
    pres[3] = False
    rows = rows * jnp.asarray(pres)[:, None]
    buf = nx.narrow_wire_rows(rows, dtype, 256)
    wrows = nx.widen_wire_rows(buf, dtype, 256)
    dec_x, _, h_x = approx_mod.decode(
        acode, wrows, present=jnp.asarray(pres), with_health=True,
        batch_grads=jnp.asarray(g), impl="fused")
    dec_k, _, h_k = approx_mod.decode(
        acode, wrows, present=jnp.asarray(pres), with_health=True,
        batch_grads=jnp.asarray(g), impl="pallas_interpret",
        wire=(dtype, buf, 256))
    # the decode is a per-column reduction over n rows — bitwise under
    # any d-tiling; the residual's d-length sum accumulates in tile order
    # (128-lane partials) so it is bounded-equal, not bitwise
    assert np.array_equal(np.asarray(dec_k), np.asarray(dec_x))
    np.testing.assert_allclose(np.asarray(h_k["residual"]),
                               np.asarray(h_x["residual"]), rtol=1e-5)


def test_narrow_kernel_infeasible_block_falls_back():
    """A block size that does not tile TILE_D falls back to the widened
    path instead of mis-tiling the scale grid."""
    from draco_tpu.ops import decode_kernels as dk

    assert not dk.narrow_kernel_ok(("int8", {}, {}, 300))
    assert dk.narrow_kernel_ok(("int8", {}, {}, 256))
    assert dk.narrow_kernel_ok(("bf16", {}, {}, 300))
    assert not dk.narrow_kernel_ok(None)


# --------------------------------------------------------------------------
# the LM routes: shared tail + the real w×tp mesh
# --------------------------------------------------------------------------


def test_lm_tp_mesh_narrow_wire_clean():
    """The real w×tp GSPMD mesh on a bf16 wire: K=4 chunked run completes
    under compile_guard="raise" (0 steady retraces), finite, detection
    preserved. The f32-mode bitwise contract on this mesh is pinned by the
    existing K∈{1,4} suites — this cell pins the NARROW mode."""
    from draco_tpu.parallel.mesh import make_mesh_wtp
    from draco_tpu.parallel.tp_step import train_tp

    cfg = TrainConfig(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=NW, approach="cyclic", worker_fail=1,
        err_mode="rev_grad", redundancy="shared", seq_len=16, vocab=32,
        model_dim=32, model_heads=2, model_layers=1, max_steps=8,
        eval_freq=0, train_dir="", log_every=1, steps_per_call=4,
        tensor_shards=2, wire_dtype="bf16", step_guard="on",
        compile_guard="raise")
    state, metrics = train_tp(cfg, make_mesh_wtp(4, 2), quiet=True)
    pv = np.concatenate([np.ravel(t) for t in
                         jax.tree.leaves(jax.device_get(state.params))])
    assert np.all(np.isfinite(pv))
    assert np.isfinite(metrics["loss"])


# --------------------------------------------------------------------------
# the autopilot wire dial (unit: no training)
# --------------------------------------------------------------------------


class _StubIncidents:
    def __init__(self):
        self._open = []
        self.episodes = []
        self.ledger = None
        self.current_masks = None
        self.quarantined = set()
        self.remediations = []

    def open_episodes(self):
        return list(self._open)

    def remediation(self, rem):
        self.remediations.append(rem)


class _StubHeartbeat:
    def __init__(self):
        self.incidents = _StubIncidents()
        self.wire = None
        self.control = None

    def set_control(self, block):
        self.control = block

    def set_wire(self, ledger):
        self.wire = ledger


class _StubClient:
    BASE_LABEL = "train_many"
    can_swap = True

    def __init__(self):
        self.setup = None
        self.switched = []

    def build_setup(self, cfg):
        return ("setup", cfg.approach, cfg.wire_dtype)

    def switch_regime(self, setup, label):
        self.switched.append((setup, label))


class _StubEngine:
    def __init__(self, client):
        self.client = client


def test_autopilot_wire_widen_and_narrow():
    from draco_tpu.control.autopilot import Autopilot

    cfg = TrainConfig(
        network="FC", dataset="synthetic-mnist", approach="cyclic",
        worker_fail=1, num_workers=NW, redundancy="shared",
        steps_per_call=4, wire_dtype="int8", incident_watch="on",
        autopilot="on", train_dir="/tmp/x").validate()
    hb = _StubHeartbeat()
    pilot = Autopilot(cfg, hb, policy={"wire_narrow_boundaries": 2.0})
    client = _StubClient()
    engine = _StubEngine(client)
    assert pilot.regime.wire_dtype == "int8"

    # a numerics_drift episode opens → the next boundary widens one step
    hb.incidents._open = [{"type": "numerics_drift", "severity": "warn",
                           "onset_step": 5, "workers": []}]
    pilot.act(8, engine)
    assert pilot.regime.wire_dtype == "bf16"
    rem = pilot.remediations[-1]
    assert rem["action"] == "wire_widen"
    assert rem["trigger"]["type"] == "numerics_drift"
    assert rem["evidence"]["wire_dtype_before"] == "int8"
    assert rem["evidence"]["wire_dtype_after"] == "bf16"
    assert client.switched and "wirebf16" in client.switched[-1][1]
    # the re-stamped wire ledger reports the WIDENED materialized dtype
    assert hb.wire is None or hb.wire["wire_dtype"] == "bf16"

    # decode_residual drift widens again, f32-ward
    hb.incidents._open = [{"type": "decode_residual", "severity": "warn",
                           "onset_step": 9, "workers": []}]
    pilot.act(12, engine)
    assert pilot.regime.wire_dtype == "f32"
    assert pilot.remediations[-1]["action"] == "wire_widen"

    # sustained clean evidence narrows back toward the CONFIGURED dtype,
    # one step per decision
    hb.incidents._open = []
    pilot.act(16, engine)
    assert pilot.regime.wire_dtype == "f32"  # hysteresis: not yet
    pilot.act(20, engine)
    assert pilot.regime.wire_dtype == "bf16"
    assert pilot.remediations[-1]["action"] == "wire_narrow"
    pilot.act(24, engine)
    pilot.act(28, engine)
    assert pilot.regime.wire_dtype == "int8"  # back at base, never past
    pilot.act(32, engine)
    pilot.act(36, engine)
    assert pilot.regime.wire_dtype == "int8"
    # warm cache: returning to the int8 regime reused the cached setup
    tags = [lbl for _, lbl in client.switched]
    assert any("wirebf16" in t for t in tags)


def test_drift_grad_fault_is_finite_and_windowed():
    """The drift_grad in-graph fault: finite scaling inside the window,
    identity outside, no victim worker required."""
    from draco_tpu.resilience import faults

    cfg = _mk_cfg(fault_spec="drift_grad@3-5")
    g = jnp.ones((NW, 16), jnp.float32)
    out2 = np.asarray(faults.corrupt_grads(g, cfg, jnp.asarray(2)))
    out4 = np.asarray(faults.corrupt_grads(g, cfg, jnp.asarray(4)))
    assert np.array_equal(out2, np.ones((NW, 16), np.float32))
    assert np.allclose(out4, faults.DRIFT_GRAD_SCALE)
    assert np.all(np.isfinite(out4))


def test_regime_carries_wire_dtype():
    from draco_tpu.control import autopilot as ap

    cfg = TrainConfig(
        approach="cyclic", worker_fail=1, num_workers=NW,
        redundancy="shared", steps_per_call=4, wire_dtype="bf16",
        incident_watch="on", autopilot="on", train_dir="/tmp/x").validate()
    base = ap.base_regime(cfg)
    assert base.wire_dtype == "bf16" and "wirebf16" in base.tag
    cfg2 = ap.regime_cfg(cfg, dataclasses.replace(base, wire_dtype="f32"))
    assert cfg2.wire_dtype == "f32"
    # the family dial carries the current wire dtype along
    tgt = ap.Regime("approx", 1.5, "off", "bf16")
    cfg3 = ap.regime_cfg(cfg, tgt)
    assert cfg3.approach == "approx" and cfg3.wire_dtype == "bf16"
