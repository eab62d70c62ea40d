"""Property tests for the cyclic code — the tests the reference never had
(SURVEY.md §4): parity-check annihilation, exact decode∘encode recovery,
recovery under ≤ s Byzantine rows, agreement with an independent numpy oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from draco_tpu.coding import cyclic


def numpy_oracle_decode(code, R, rand_factor):
    """Independent float64 complex decode following the published algorithm
    (syndrome -> error locator -> honest-set recombination)."""
    n, s = code.n, code.s
    c = cyclic._dft_c(n)
    c1 = c[:, : n - 2 * s]
    c2 = c[:, n - 2 * s :]
    e = R @ rand_factor
    e2 = c2.conj().T @ e
    if s > 0:
        A = np.empty((s, s), dtype=complex)
        b = np.empty((s,), dtype=complex)
        for i in range(s):
            A[i] = e2[s - i - 1 : 2 * s - i - 1]
            b[i] = e2[2 * s - i - 1]
        alpha, *_ = np.linalg.lstsq(A, b, rcond=None)
        poly = np.concatenate([-alpha, [1.0]])
        z = np.exp(2j * np.pi * np.arange(n) / n)
        vals = np.stack([z**j for j in range(s + 1)], axis=1) @ poly
        mags = np.abs(vals)
    else:
        mags = np.ones(n)
    # top n-2s rows by locator magnitude (corrupt rows are roots -> bottom s);
    # mask marks exactly the rows used — same policy as cyclic.decode
    idx = np.sort(np.argsort(-mags, kind="stable")[: n - 2 * s])
    honest = np.zeros(n, dtype=bool)
    honest[idx] = True
    rec = c1[idx]
    e1 = np.zeros(n - 2 * s)
    e1[0] = 1.0
    v, *_ = np.linalg.lstsq(rec.T, e1, rcond=None)
    v_full = np.zeros(n, dtype=complex)
    v_full[idx] = v
    return np.real(v_full @ R) / n, honest


@pytest.mark.parametrize("n,s", [(7, 1), (8, 1), (11, 2), (15, 3)])
def test_construction_properties(n, s):
    code = cyclic.build_cyclic_code(n, s)
    # support: each row has exactly 2s+1 nonzeros on its cyclic window
    assert (code.support.sum(axis=1) == 2 * s + 1).all()
    # W respects the support up to least-squares residual
    off = code.w_full * (1 - code.support)
    assert np.abs(off).max() < 1e-7
    # parity check: C2^H annihilates the code space (coding.py:80-85's
    # manual check, automated)
    c2h = code.c2h_re + 1j * code.c2h_im
    assert np.abs(c2h @ code.w_full).max() < 1e-5
    # decodability: ones^T lies in the row space of W restricted to any
    # (n-2s)-subset of honest rows — checked via v from C1
    assert code.batch_ids.shape == (n, 2 * s + 1)


@pytest.mark.parametrize("n,s", [(7, 1), (11, 2), (15, 3)])
def test_exact_recovery_no_adversary(n, s, rng):
    code = cyclic.build_cyclic_code(n, s)
    d = 64
    batch_grads = rng.randn(n, d).astype(np.float32)
    # every worker honestly encodes its window
    g = batch_grads[code.batch_ids]  # (n, hat_s, d)
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(g))
    rf = np.ones(d, dtype=np.float32)
    dec, honest = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=2e-4, atol=2e-4)
    # mask reports the n-2s rows used for recombination
    assert np.asarray(honest).sum() == n - 2 * s


@pytest.mark.parametrize("n,s", [(7, 1), (11, 2), (15, 3)])
@pytest.mark.parametrize("attack", ["rev_grad", "constant"])
def test_exact_recovery_under_attack(n, s, attack, rng):
    from draco_tpu.attacks import inject_cyclic

    code = cyclic.build_cyclic_code(n, s)
    d = 128
    batch_grads = rng.randn(n, d).astype(np.float32)
    g = batch_grads[code.batch_ids]
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(g))
    adv = np.zeros(n, dtype=bool)
    adv[rng.choice(n, size=s, replace=False)] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv), attack)
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    dec, honest = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=5e-3, atol=5e-3)
    # located honest set must exclude every adversary
    assert not np.asarray(honest)[adv].any()


def test_matches_numpy_oracle(rng):
    n, s, d = 11, 2, 96
    code = cyclic.build_cyclic_code(n, s)
    batch_grads = rng.randn(n, d).astype(np.float32)
    g = batch_grads[code.batch_ids]
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(g))
    R = np.asarray(enc_re) + 1j * np.asarray(enc_im)
    adv = rng.choice(n, size=s, replace=False)
    R[adv] += -100.0 * R[adv]
    rf = rng.normal(loc=1.0, size=d)
    want, honest_np = numpy_oracle_decode(code, R, rf)
    dec, honest = parity.run_jitted(
        cyclic.decode, code, jnp.asarray(R.real.astype(np.float32)),
        jnp.asarray(R.imag.astype(np.float32)),
        jnp.asarray(rf.astype(np.float32)),
    )
    np.testing.assert_allclose(np.asarray(dec), want, rtol=5e-3, atol=5e-3)
    np.testing.assert_array_equal(np.asarray(honest), honest_np)


def test_encode_shared_equals_encode(rng):
    n, s, d = 9, 2, 32
    code = cyclic.build_cyclic_code(n, s)
    batch_grads = rng.randn(n, d).astype(np.float32)
    g = batch_grads[code.batch_ids]
    re1, im1 = cyclic.encode(code, jnp.asarray(g))
    re2, im2 = cyclic.encode_shared(code, jnp.asarray(batch_grads))
    np.testing.assert_allclose(np.asarray(re1), np.asarray(re2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(im1), np.asarray(im2), rtol=1e-5, atol=1e-5)


def test_decode_is_jittable():
    code = cyclic.build_cyclic_code(7, 1)
    d = 16
    r_re = jnp.zeros((7, d))
    r_im = jnp.zeros((7, d))
    rf = jnp.ones((d,))
    jitted = jax.jit(lambda a, b, c: cyclic.decode(code, a, b, c))
    dec, honest = jitted(r_re, r_im, rf)
    assert dec.shape == (d,)


@pytest.mark.parametrize("n,s", [(7, 1), (11, 2)])
def test_decode_layers_matches_global(n, s, rng):
    """Per-layer locators (reference: cyclic_master.py:125-129) agree with the
    global decode when corruption is per-worker — whole rows attacked, the
    only corruption the wire protocol admits."""
    from draco_tpu.attacks import inject_cyclic

    d = 96
    code = cyclic.build_cyclic_code(n, s)
    batch_grads = rng.randn(n, d).astype(np.float32)
    g = batch_grads[code.batch_ids]
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(g))
    adv = np.zeros(n, dtype=bool)
    adv[rng.choice(n, size=s, replace=False)] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv), "rev_grad")
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    offsets = [0, 17, 40, d]  # three unequal "layers"
    dec_g, honest_g = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf))
    dec_l, honest_l = parity.run_jitted(
        cyclic.decode_layers, code, enc_re, enc_im, jnp.asarray(rf),
        offsets=offsets)
    np.testing.assert_allclose(np.asarray(dec_l), np.asarray(dec_g),
                               rtol=5e-3, atol=5e-3)
    # every layer locates the same honest set, and none admits an adversary
    assert (np.asarray(honest_l) == np.asarray(honest_g)[None, :]).all()
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec_l), want, rtol=5e-3, atol=5e-3)


def test_decode_layers_erasures(rng):
    """Layer decode honours the present mask (stragglers) per layer."""
    n, s, d = 9, 2, 64
    code = cyclic.build_cyclic_code(n, s)
    batch_grads = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(batch_grads[code.batch_ids]))
    present = np.ones(n, dtype=bool)
    present[[2, 6]] = False
    enc_re = jnp.asarray(np.asarray(enc_re) * present[:, None])
    enc_im = jnp.asarray(np.asarray(enc_im) * present[:, None])
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    dec, honest_l = parity.run_jitted(cyclic.decode_layers, code, enc_re, enc_im,
                              jnp.asarray(rf), offsets=[0, 20, d],
                              present=jnp.asarray(present))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=2e-3, atol=2e-3)
    assert not np.asarray(honest_l)[:, [2, 6]].any()


def test_decode_layers_jittable():
    code = cyclic.build_cyclic_code(7, 1)
    d = 24
    jitted = jax.jit(
        lambda a, b, c: cyclic.decode_layers(code, a, b, c, [0, 10, 24])
    )
    dec, honest_l = jitted(jnp.zeros((7, d)), jnp.zeros((7, d)), jnp.ones((d,)))
    assert dec.shape == (d,)
    assert honest_l.shape == (2, 7)


# ---------------------------------------------------------------------------
# scale envelope: larger n and s than the reference cluster ever ran
# (reference: 8 workers, README.md:39-47)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,s", [(16, 3), (21, 3), (32, 3), (32, 5)])
def test_construction_at_scale(n, s):
    code = cyclic.build_cyclic_code(n, s)
    assert (code.support.sum(axis=1) == 2 * s + 1).all()
    c2h = code.c2h_re + 1j * code.c2h_im
    assert np.abs(c2h @ code.w_full).max() < 1e-4


@pytest.mark.parametrize("n,s", [(16, 3), (32, 3)])
@pytest.mark.parametrize("attack", ["rev_grad", "constant"])
def test_exact_recovery_under_attack_at_scale(n, s, attack, rng):
    from draco_tpu.attacks import inject_cyclic

    code = cyclic.build_cyclic_code(n, s)
    d = 128
    batch_grads = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(batch_grads[code.batch_ids]))
    adv = np.zeros(n, dtype=bool)
    adv[rng.choice(n, size=s, replace=False)] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv), attack)
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    dec, honest = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=1e-2, atol=1e-2)
    assert not np.asarray(honest)[adv].any()
    assert np.asarray(honest).sum() == n - 2 * s


@pytest.mark.parametrize("n,s,t,e", [(16, 3, 2, 1), (16, 3, 1, 2), (32, 3, 2, 1)])
def test_joint_adversary_and_erasure_at_scale(n, s, t, e, rng):
    """t live adversaries + e stragglers, t + e <= s, at n the reference
    never reached."""
    from draco_tpu.attacks import inject_cyclic

    code = cyclic.build_cyclic_code(n, s)
    d = 128
    batch_grads = rng.randn(n, d).astype(np.float32)
    enc_re, enc_im = cyclic.encode(code, jnp.asarray(batch_grads[code.batch_ids]))
    picks = rng.choice(n, size=t + e, replace=False)
    adv, missing = picks[:t], picks[t:]
    adv_mask = np.zeros(n, dtype=bool)
    adv_mask[adv] = True
    enc_re, enc_im = inject_cyclic(enc_re, enc_im, jnp.asarray(adv_mask), "rev_grad")
    present = np.ones(n, dtype=bool)
    present[missing] = False
    enc_re = jnp.asarray(np.asarray(enc_re) * present[:, None])
    enc_im = jnp.asarray(np.asarray(enc_im) * present[:, None])
    rf = rng.normal(loc=1.0, size=d).astype(np.float32)
    dec, used = parity.run_jitted(
        cyclic.decode, code, enc_re, enc_im, jnp.asarray(rf),
        present=jnp.asarray(present))
    want = batch_grads.sum(axis=0) / n
    np.testing.assert_allclose(np.asarray(dec), want, rtol=1e-2, atol=1e-2)
    used = np.asarray(used)
    assert not used[adv].any() and not used[missing].any()
