"""Flash-attention kernel parity (interpret mode in CI; real lowering is
exercised by chip_smoke.py's ``kernels`` phase on hardware).

Oracle: parallel/ring_attention.dense_attention — the streaming-softmax
reference the ring path is tested against. Forward values AND input
gradients must match: the backward pass is a hand-written custom VJP —
one kernel that builds each probability block once and takes dq, dk and dv
from it — the most bug-prone part."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import draco_tpu.ops.flash_attention as fa
import parity
from draco_tpu.ops.flash_attention import flash_attention
from draco_tpu.parallel.ring_attention import dense_attention


def _qkv(rng, b=2, t=256, h=2, dh=64):
    shape = (b, t, h, dh)
    return (jnp.asarray(rng.normal(size=shape).astype(np.float32)),
            jnp.asarray(rng.normal(size=shape).astype(np.float32)),
            jnp.asarray(rng.normal(size=shape).astype(np.float32)))


@pytest.mark.parametrize("dh", [64, 128])
def test_forward_matches_dense(rng, dh):
    q, k, v = _qkv(rng, dh=dh)
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, force=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (64, 32)])
def test_forward_uneven_blocks(rng, bq, bk):
    """T spanning several q/k blocks with bq != bk — both directions: the
    block-skip predicate must compare positions, not block indices (bq > bk
    regressed to dropping valid past keys)."""
    q, k, v = _qkv(rng, t=512, dh=64)
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=bq, block_k=bk, force=True,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _grads(attn, loss_of, args):
    return jax.jit(jax.grad(lambda *a: loss_of(attn(*a)),
                            argnums=(0, 1, 2)))(*args)


def _assert_grads_close(got, want, atol):
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(np.abs(b).max(), 1e-8)
        np.testing.assert_allclose(a / scale, b / scale, atol=atol,
                                   err_msg=f"d{name} mismatch")


def test_grads_uneven_blocks(rng):
    """bq > bk through the custom VJP (the backward kernel's predicate)."""
    q, k, v = _qkv(rng, t=256, dh=64)
    tgt = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def loss_of(o):
        return jnp.sum((o - tgt) ** 2)

    flash = lambda q, k, v: flash_attention(q, k, v, block_q=128, block_k=64,
                                            force=True, interpret=True)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)
    _assert_grads_close(_grads(flash, loss_of, (q, k, v)),
                        _grads(dense, loss_of, (q, k, v)), atol=5e-5)


# (T, query heads, key/value heads, q/k head size, v head size, bq, bk):
# several query blocks cross every key block's sweep and several key blocks
# add into every query block's dq
GRAD_SHAPES = {
    "square": (256, 2, 2, 64, 64, None, 1024),
    "latent_qk192_v128": (256, 2, 2, 192, 128, 64, 128),  # kanana2's MLA
    "grouped_query_32_on_4": (128, 32, 4, 16, 16, 32, 64),  # mellum2's heads
    "grouped_query_d256": (256, 4, 1, 256, 256, 128, 64),  # qwen3next, bq > bk
}


@pytest.mark.parametrize("shape", sorted(GRAD_SHAPES))
def test_grads_match_dense(rng, shape):
    """dq, dk and dv of the fused backward against the dense reference at
    the head layouts the cells run: padded q/k heads beside narrower v heads,
    and key/value heads shared by several query heads."""
    t, h, kv, dh, dv, bq, bk = GRAD_SHAPES[shape]
    q, k, v = (jnp.asarray(rng.normal(size=s).astype(np.float32))
               for s in [(1, t, h, dh), (1, t, kv, dh), (1, t, kv, dv)])
    tgt = jnp.asarray(rng.normal(size=(1, t, h, dv)).astype(np.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=bq, block_k=bk, force=True,
                               interpret=True)

    def dense(q, k, v):
        return dense_attention(q, *fa.spread_kv_heads(h, k, v), causal=True)

    def loss_of(o):
        return jnp.sum((o - tgt) ** 2)

    _assert_grads_close(_grads(flash, loss_of, (q, k, v)),
                        _grads(dense, loss_of, (q, k, v)), atol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_with_a_live_lse_match_dense(rng, causal):
    """``flash_attention_with_lse`` under a loss that reads BOTH outputs:
    the ring's merge differentiates the log-sum-exp, so the fused kernel
    takes the dlse stream (d lse / d s = softmax) — causal (the self hop)
    and fully visible (a past owner's hop)."""
    from draco_tpu.parallel.ring_attention import dense_attention_lse

    q, k, v = _qkv(rng, b=1, t=256, dh=64)

    def loss(attn):
        def f(q, k, v):
            o, lse = attn(q, k, v)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))
        return f

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, causal=causal, block_q=64,
                                           block_k=128, interpret=True)

    def dense(q, k, v):
        return dense_attention_lse(q, k, v, causal=causal)

    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    _assert_grads_close(got, want, atol=5e-5)


def _pallas_calls(jaxpr) -> int:
    """pallas_call equations of a jaxpr, those of nested jaxprs (pjit,
    custom_vjp, cond) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub)
    return n


@pytest.mark.parametrize("window", [None, 40])
def test_backward_is_one_kernel(rng, window):
    """One ``flash_attention`` call under ``jax.grad`` holds the forward
    kernel and exactly ONE backward kernel: the probability blocks are
    rebuilt once, not once for dq and once more for dk / dv."""
    q, k, v = _qkv(rng, b=1, t=128, dh=64)

    def attn(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=32,
                               block_k=64, interpret=True)

    forward = _pallas_calls(jax.make_jaxpr(attn)(q, k, v).jaxpr)
    both = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(attn(*a) ** 2), argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert forward == 1
    assert both - forward == 1


def test_three_evaluations_give_the_same_bits(rng):
    """The vote's premise: three lanes that compute the same row hold the
    same gradient, bit for bit — also through the dq sum that stays in the
    kernel's scratch across a head's key blocks."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(rng, b=1, t=256, dh=64))
    grads = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, block_q=64, block_k=128, interpret=True
        ).astype(jnp.float32))), argnums=(0, 1, 2)))
    first = grads(q, k, v)
    for _ in range(2):
        for a, b in zip(first, grads(q, k, v)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    # three lanes of one program (the step's own form: a map over lanes)
    lanes = jax.jit(jax.vmap(grads))(*(jnp.stack([x] * 3) for x in (q, k, v)))
    for a in lanes:
        np.testing.assert_array_equal(np.asarray(a[0], np.float32),
                                      np.asarray(a[1], np.float32))
        np.testing.assert_array_equal(np.asarray(a[0], np.float32),
                                      np.asarray(a[2], np.float32))


def test_backward_refuses_a_head_that_cannot_stay_in_vector_memory():
    """A head's dq sum lives in VMEM whole: a row too long for that raises
    at trace time, naming the way out, instead of failing in the chip's
    compiler."""
    g, t, d = 1, 2 ** 17, 256
    x = jax.ShapeDtypeStruct((g, t, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((g, t), jnp.float32)
    with pytest.raises(ValueError, match="vector memory"):
        jax.eval_shape(lambda q, k, v, o, lse, do: fa._flash_bwd(
            q, k, v, o, lse, do, None, 0.0625, 512, 1024, True, None, False),
            x, x, x, x, lse, x)
    assert fa._bwd_vmem_bytes(4096, 256, 128, 512, 1024, 2, 2) < 2 ** 25
    assert fa._bwd_vmem_bytes(8192, 128, 128, 1024, 1024, 2, 2) < 2 ** 26


def test_flash_through_model_matches_dense(rng, monkeypatch):
    """attn_impl=flash through the full sp-path train step (interpret-mode
    kernel forced) reproduces the dense step's loss and update — the kernel's
    custom VJP is exercised inside jax.grad of the whole model."""
    import functools

    from draco_tpu import ops
    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import build_sp_train_setup, synthetic_text

    import draco_tpu.ops.flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention",
        functools.partial(fa.flash_attention.__wrapped__
                          if hasattr(fa.flash_attention, "__wrapped__")
                          else fa.flash_attention, force=True, interpret=True),
    )

    def cfg(attn):
        return TrainConfig(
            network="TransformerLM", dataset="synthetic-text", batch_size=2,
            num_workers=2, approach="baseline", mode="normal", worker_fail=0,
            seq_len=256, vocab=32, model_dim=32, model_heads=2, model_layers=1,
            attn_impl=attn, max_steps=1, eval_freq=0, train_dir="",
            log_every=1000,
        )

    mesh = make_mesh_2d(2, 1)
    toks = jnp.asarray(synthetic_text(428, 1, 2, 2, 256, 32))
    adv = np.zeros(2, dtype=bool)
    s_d = build_sp_train_setup(cfg("dense"), mesh)
    s_f = build_sp_train_setup(cfg("flash"), mesh)
    st_d, m_d = s_d.train_step(s_d.state, toks, adv)
    st_f, m_f = s_f.train_step(s_f.state, toks, adv)
    assert float(m_d["loss"]) == pytest.approx(float(m_f["loss"]), rel=1e-5)
    a = np.asarray(jax.device_get(st_d.params["embed"]["embedding"]))
    b = np.asarray(jax.device_get(st_f.params["embed"]["embedding"]))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_a2a_flash_inner_matches_dense(rng, monkeypatch):
    """Ulysses + flash: sp=4 head-scatter with the interpret-mode kernel as
    the inner attention reproduces the dense a2a step exactly."""
    import functools

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import build_sp_train_setup, synthetic_text

    import draco_tpu.ops.flash_attention as fa

    orig = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        functools.partial(orig, force=True, interpret=True),
    )

    def cfg(attn):
        return TrainConfig(
            network="TransformerLM", dataset="synthetic-text", batch_size=2,
            num_workers=2, approach="baseline", mode="normal", worker_fail=0,
            seq_shards=4, sp_attn="a2a", seq_len=256, vocab=32, model_dim=32,
            model_heads=4, model_layers=1, attn_impl=attn, max_steps=1,
            eval_freq=0, train_dir="", log_every=1000,
        )

    mesh = make_mesh_2d(2, 4)
    toks = jnp.asarray(synthetic_text(428, 1, 2, 2, 256, 32))
    adv = np.zeros(2, dtype=bool)
    s_d = build_sp_train_setup(cfg("dense"), mesh)
    s_f = build_sp_train_setup(cfg("flash"), mesh)
    st_d, m_d = s_d.train_step(s_d.state, toks, adv)
    st_f, m_f = s_f.train_step(s_f.state, toks, adv)
    assert float(m_d["loss"]) == pytest.approx(float(m_f["loss"]), rel=1e-5)
    a = np.asarray(jax.device_get(st_d.params["embed"]["embedding"]))
    b = np.asarray(jax.device_get(st_f.params["embed"]["embedding"]))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_flash_ring_trains(rng):
    """sp_attn=ring + attn_impl=flash is a supported composition
    (ring_flash_attention): the sp training step runs and learns."""
    import numpy as np

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import train_sp

    cfg = TrainConfig(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=4, approach="baseline", mode="normal", worker_fail=0,
        seq_len=16, vocab=32, model_dim=32, model_heads=2, model_layers=1,
        seq_shards=2, sp_attn="ring", attn_impl="flash", max_steps=30,
        eval_freq=0, train_dir="", log_every=1000,
    )
    cfg.validate()  # previously rejected; now a first-class path
    mesh = make_mesh_2d(4, 2)
    state, metrics = train_sp(cfg, mesh, steps=30, quiet=True)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < 3.0  # learned; uniform would be ln(32)=3.47


def test_fallback_off_tpu(rng):
    """Without force, off-TPU the kernel is not selected at all: the dense
    path is the lowering, whatever the shape, and it is correct causal
    attention."""
    q, k, v = _qkv(rng, t=100, dh=48)  # 100 doesn't tile, 48 < lane
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_force_true_raises_on_non_tiling_shape(rng):
    """force=True demands the O(T·Dh) kernel; a shape that cannot tile must
    raise instead of silently running the dense O(T²) path (advisor r2)."""
    q, k, v = _qkv(rng, t=100, dh=48)  # t=100 doesn't tile
    with pytest.raises(ValueError, match="does not tile"):
        flash_attention(q, k, v, force=True)


def test_selected_kernel_raises_on_non_tiling_shape(rng, monkeypatch):
    """Wherever the kernel is selected — interpret mode here, a TPU backend
    on the chip (use_pallas) — a non-tiling shape raises: no quiet O(T²)
    dense path behind a caller who asked for flash."""
    q, k, v = _qkv(rng, t=100, dh=48)
    with pytest.raises(ValueError, match="does not tile"):
        flash_attention(q, k, v, interpret=True)
    monkeypatch.setattr(fa, "use_pallas", lambda: True)  # "on the chip"
    with pytest.raises(ValueError, match="does not tile"):
        flash_attention(q, k, v)
    with pytest.raises(ValueError, match="does not tile"):
        fa.flash_attention_with_lse(q, k, v)


def test_fit_block_keeps_non_default_lengths_eligible():
    """The tuned defaults (bq=512, bk=1024) must not demote lengths that
    tiled under the old 128-block defaults: _fit_block shrinks to the
    largest block that divides t (sublane- and lane-tile legal), so e.g.
    t=768/1536/2560 stay kernel-eligible instead of silently riding the
    dense fallback (r5 review finding)."""
    for t, want_bq, want_bk in [(768, 384, 768), (1536, 512, 768),
                                (2560, 512, 640), (2048, 512, 1024),
                                (256, 256, 256)]:
        bq = fa._fit_block(512, t, lane_rule=False)
        bk = fa._fit_block(1024, t, lane_rule=True)
        assert (bq, bk) == (want_bq, want_bk), (t, bq, bk)
        assert fa._kernel_eligible(t, bq, bk, 64, True, False)
    # no legal block => 0, and eligibility raises instead of dividing by 0
    assert fa._fit_block(512, 12, lane_rule=False) == 0
    with pytest.raises(ValueError, match="does not tile"):
        flash_attention(*_qkv(np.random.RandomState(0), t=12, dh=64)[:3],
                        force=True)


def test_default_blocks_parity_t768(rng):
    """Interpret-mode parity at t=768 with DEFAULT blocks — the length the
    plain min() clamp would have broken (768 % 1024 != 0): exercises the
    divisor-aware shrink end-to-end through the public entry."""
    q, k, v = _qkv(rng, b=1, t=768, h=1, dh=64)
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, force=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_chip_study_shape_parity_interpret(rng):
    """Interpret-mode parity at the exact shape the hardware study runs
    first (T=1024, dh=64) — catches shape-dependent kernel logic bugs
    before chip time is spent on them."""
    q, k, v = _qkv(rng, b=1, t=1024, h=1, dh=64)
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, force=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs_match_dense_f32(rng):
    """bf16 q/k/v ride the MXU fast pass (matmuls in input dtype, f32
    accumulate); values must still track the f32 dense oracle to bf16
    precision, fwd and grads."""
    q, k, v = _qkv(rng, t=256, dh=64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(qb, kb, vb, force=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.05)

    def loss(attn, *xs):
        return jnp.sum(jnp.sin(attn(*xs).astype(jnp.float32)))

    g_f = jax.jit(jax.grad(
        lambda q, k, v: loss(
            lambda *a: flash_attention(*a, force=True, interpret=True),
            q, k, v),
        argnums=(0, 1, 2)))(qb, kb, vb)
    g_d = jax.jit(jax.grad(
        lambda q, k, v: loss(
            lambda *a: dense_attention(*a, causal=True), q, k, v),
        argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", g_f, g_d):
        a = np.asarray(a, np.float32)
        b = np.asarray(b)
        scale = max(np.abs(b).max(), 1e-8)
        np.testing.assert_allclose(a / scale, b / scale, atol=0.06,
                                   err_msg=f"d{name} mismatch")


def test_tpu_lowering_clean_and_control():
    """The kernel must pass the Pallas TPU *lowering* — the stage every
    recorded hardware failure came from ((8,128)-tiling errors,
    PERF_HISTORY.md) — via cross-platform export on the CPU host, and a
    deliberately mis-tiled pallas_call must still raise there (negative
    control: proves the check is exercised, not skipped). Full shape
    matrix: tools/tpu_attn_lowering_check.py."""
    import jax.export
    from jax.experimental import pallas as pl

    q = jnp.zeros((2, 256, 4, 64), jnp.float32)
    f = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, force=True))
    )(q, k, v))
    jax.export.export(f, platforms=["tpu"])(q, q, q)  # raises on regression

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((4, 12), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 12), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 48), jnp.float32),
        )(x)

    with pytest.raises(ValueError, match="Pallas TPU lowering"):
        jax.export.export(jax.jit(bad), platforms=["tpu"])(
            jnp.zeros((16, 48), jnp.float32))


# ---------------------------------------------------------------------------
# the sub-tiles (PR 43): a computed block pair multiplies only the strips of
# sub-tiles that hold a seen pair. Oracle: the parent's kernels, whose
# bodies multiply the whole (bq, bk) rectangle and mask it elementwise.
# ---------------------------------------------------------------------------

def _parent_fwd_kernel(scale, nk, bq, bk, causal, window, q_ref, k_ref, v_ref,
                       o_ref, lse_ref, acc_ref, m_ref, l_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, fa.NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when(fa._computed(i, j, bq, bk, causal, window))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = fa._masked(s, i, j, causal, window)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - fa._cols(m_cur, bk))
        corr = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * fa._cols(corr, acc_ref.shape[1]) + \
            jax.lax.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / fa._cols(l, o_ref.shape[2])).astype(
            o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _parent_bwd_kernel(scale, nq, nk, bq, bk, causal, window, has_dlse,
                       *refs):
    from jax.experimental import pallas as pl

    if has_dlse:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dlse_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs
        dlse_ref = None
    j = pl.program_id(1)
    i = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(i * bq, bq), bq)

    @pl.when(j == 0)
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((bq, dq_acc.shape[1]), jnp.float32)

    @pl.when(i == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(fa._computed(i, j, bq, bk, causal, window))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = fa._masked(s, i, j, causal, window)
        p = jnp.exp(s - fa._cols(lse_ref[0], bk))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dsum = dp - fa._cols(dcap_ref[0], bk)
        if dlse_ref is not None:
            dsum = dsum + fa._cols(dlse_ref[0], bk)
        ds = (p * dsum).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dq_acc[rows, :] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(i == nq - 1)
    def _flush_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(j == nk - 1)
    def _flush_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _folded_operands(case, dtype):
    """q, k, v, do (and dlse where the case reads the log-sum-exp) as the
    kernels take them: heads folded, head sizes whole lane tiles."""
    t, dh, dv = case["t"], case["dh"], case["dv"]
    key = jax.random.key(t + dh)
    q, k, v, do = (
        jax.random.normal(jax.random.fold_in(key, n), (1, t, d)).astype(dtype)
        for n, d in enumerate([dh, dh, dv, dv]))
    dlse = (jax.random.normal(jax.random.fold_in(key, 9), (1, t))
            if case.get("dlse") else None)
    return q, k, v, do, dlse


def _o_lse_and_gradients(case, q, k, v, do, dlse):
    """o, lse, dq, dk, dv of the module's kernels as they stand (their
    un-jitted forms, so a patched kernel body is the one traced when the
    caller compiles this anew)."""
    bq, bk, window = case["bq"], case["bk"], case.get("window")
    causal = case.get("causal", True)
    scale = q.shape[-1] ** -0.5
    o, lse = fa._flash_fwd.__wrapped__(q, k, v, scale, bq, bk, causal,
                                       window, True)
    return (o, lse) + tuple(fa._flash_bwd.__wrapped__(
        q, k, v, o, lse, do, dlse, scale, bq, bk, causal, window, True))


# blocks as the cells run them (1024 x 1024, causal and windowed at
# W = 1024) and as the ring's hops and the parent's causal kernel do
# (512 x 1024); ``bit_equal``: the rectangle rule's one body is kept (a
# window of no whole sub-tiles, no mask at all, blocks below a sub-tile), so
# the parent's bits come back
PARENT_CASES = {
    "causal_t1024_d128": dict(t=1024, bq=512, bk=1024, dh=128, dv=128),
    "causal_t2048_d256": dict(t=2048, bq=512, bk=1024, dh=256, dv=256),
    "causal_t4096_qk256_v128": dict(t=4096, bq=1024, bk=1024, dh=256, dv=128),
    "causal_t2048_bq1024": dict(t=2048, bq=1024, bk=1024, dh=128, dv=128),
    "causal_t1024_dlse": dict(t=1024, bq=512, bk=1024, dh=128, dv=128,
                              dlse=True),
    "window_1024_t3072": dict(t=3072, bq=1024, bk=1024, dh=128, dv=128,
                              window=1024),
    "window_512_t2048": dict(t=2048, bq=1024, bk=1024, dh=128, dv=128,
                             window=512),
    "window_1000_t2048": dict(t=2048, bq=1024, bk=1024, dh=128, dv=128,
                              window=1000, bit_equal=True),
    "fully_visible_t1024_dlse": dict(t=1024, bq=512, bk=1024, dh=128, dv=128,
                                     causal=False, dlse=True, bit_equal=True),
    "small_blocks_t256": dict(t=256, bq=64, bk=128, dh=128, dv=128,
                              bit_equal=True),
}


# bfloat16 as the models hand the operands over, at one case of each kind
BFLOAT16_CASES = ["causal_t1024_d128", "causal_t2048_bq1024",
                  "window_1024_t3072", "fully_visible_t1024_dlse",
                  "small_blocks_t256"]


@pytest.mark.parametrize("name,dtype", [
    *[(name, "float32") for name in sorted(PARENT_CASES)],
    *[(name, "bfloat16") for name in BFLOAT16_CASES]])
def test_sub_tiled_bodies_stay_within_ulps_of_the_rectangle_bodies(
        monkeypatch, name, dtype):
    """o, lse, dq, dk, dv of the sub-tiled kernels against the parent's
    bodies on the same operands: the terms left out are exact zeros, so only
    the grouping of a shorter contraction or row sum can differ — a few
    float32 ulps of the array's scale, one bfloat16 ulp on bfloat16
    results; and a second evaluation gives the first one's bits."""
    case = PARENT_CASES[name]
    dtype = jnp.dtype(dtype)
    ops = _folded_operands(case, dtype)
    sub_tiled_program = jax.jit(functools.partial(_o_lse_and_gradients, case))
    got = sub_tiled_program(*ops)
    again = sub_tiled_program(*ops)
    sub_tiled = fa._bodies(case["t"], case["bq"], case["bk"],
                           case.get("causal", True), case.get("window"),
                           fa.SUB_TILE) is not None
    assert sub_tiled == (not case.get("bit_equal", False))
    monkeypatch.setattr(fa, "_fwd_kernel", _parent_fwd_kernel)
    monkeypatch.setattr(fa, "_bwd_kernel", _parent_bwd_kernel)
    want = jax.jit(functools.partial(_o_lse_and_gradients, case))(*ops)
    for label, a, b, c in zip(["o", "lse", "dq", "dk", "dv"], got, want,
                              again):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(c, np.float32), label)
        eps = float(jnp.finfo(a.dtype).eps)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if case.get("bit_equal"):
            np.testing.assert_array_equal(a, b, label)
            continue
        ulps = 1 if eps > 1e-3 else 8
        np.testing.assert_allclose(a, b, rtol=0, err_msg=label,
                                   atol=ulps * eps * np.abs(b).max())


# (T, query heads, key/value heads, q/k head size, v head size, window)
# through the public entry at the blocks it chooses itself
SUB_TILED_SHAPES = {
    "causal_t1024_d64": (1024, 1, 1, 64, 64, None),
    "causal_t2048_d128_grouped": (2048, 2, 1, 128, 128, None),
    "causal_t4096_qk192_v128": (4096, 1, 1, 192, 128, None),
    "window_1024_t2048_grouped": (2048, 2, 1, 128, 128, 1024),
    "window_1000_t2048": (2048, 1, 1, 64, 64, 1000),  # the rectangle rule
}


@pytest.mark.parametrize("shape", sorted(SUB_TILED_SHAPES))
def test_sub_tiled_kernels_match_dense(shape):
    """Output and all three gradients against the dense reference at blocks
    of whole sub-tiles (the defaults: 1024 x 1024, causal and windowed), at
    the standing tolerances."""
    t, h, kv, dh, dv, window = SUB_TILED_SHAPES[shape]
    key = jax.random.key(t + dh)
    q, k, v, tgt = (jax.random.normal(jax.random.fold_in(key, n), s)
                    for n, s in enumerate([(1, t, h, dh), (1, t, kv, dh),
                                           (1, t, kv, dv), (1, t, h, dv)]))

    def flash(q, k, v):
        return flash_attention(q, k, v, window=window, interpret=True)

    def dense(q, k, v):
        return dense_attention(q, *fa.spread_kv_heads(h, k, v), causal=True,
                               window=window)

    def loss_of(o):
        return jnp.sum((o - tgt) ** 2)

    out, got = parity.with_gradients(flash, loss_of, (0, 1, 2))(q, k, v)
    want_out, want = parity.with_gradients(dense, loss_of, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)
    _assert_grads_close(got, want, atol=5e-5)


def test_sub_tiled_grads_with_a_live_lse_match_dense():
    """The ring's own-shard hop (``causal=True``, both outputs read) at
    blocks of whole sub-tiles: the dlse stream through the strips."""
    from draco_tpu.parallel.ring_attention import dense_attention_lse

    key = jax.random.key(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, n), (1, 1024, 2, 64))
               for n in range(3))

    def loss(attn):
        def f(q, k, v):
            o, lse = attn(q, k, v)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))
        return f

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, causal=True,
                                           interpret=True)

    def dense(q, k, v):
        return dense_attention_lse(q, k, v, causal=True)

    assert fa._bodies(1024, 512, 1024, True, None, fa.SUB_TILE) is not None
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    _assert_grads_close(got, want, atol=5e-5)
