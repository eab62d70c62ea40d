"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached — with the chip's own compiler, at the real widths.

Interpret mode and the cross-platform export (``jax.export`` with
``platforms=["tpu"]``, tests/test_decode_kernels.py) both accept programs the
chip's compiler refuses: the batch-first cyclic locator passed both for ten
PRs and could not be built for the chip at all (PERF.md, chip bring-up). These
cases run the real thing — Mosaic + the TPU backend of XLA, for the
``v5e:2x2`` topology — in a second or two each and at no chip time. Nothing
executes, so they say nothing about results; chip_smoke.py does that on the
chip.

Skipped where the topology cannot be described (no TPU compiler installed).
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest

from draco_tpu.coding import approx as approx_mod
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.ops import decode_kernels as dk
from draco_tpu.ops.flash_attention import flash_attention

RESNET18_D = 11_173_962  # the cyclic-resnet18 preset's gradient length
RESNET18_LEAVES = 62  # its parameter tensors: the per-layer locator batch
FLASH_SHAPE = (2, 1024, 12, 64)
BLOCK = 256  # cfg.shadow_block default: the int8 wire's scale granularity


@pytest.fixture(scope="module")
def one_chip():
    """A ``SingleDeviceSharding`` on the first chip of a described v5e host,
    with the persistent compilation cache off around the module: such a
    compile is written to the cache but cannot be read back without a chip,
    and the next one would warn."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _locator(n, s, lam=0.0):
    code = cyclic_mod.build_cyclic_code(n, s)

    def fn(e_re, e_im, pres):
        return dk.cyclic_locator(code, e_re, e_im, pres,
                                 cyclic_mod.HEALTH_REL_TOL, lam=lam)

    col = ((n, RESNET18_LEAVES), jnp.float32)
    return fn, [col, col, ((n, 1), jnp.float32)]


def _approx(mode, n=9, d=RESNET18_D):
    code = approx_mod.build_approx_code(n, 1.5)
    nb = -(-d // BLOCK)

    def fn(rows, bg, present, scale):
        wire = {"f32": None, "bf16": ("bf16", {"q": rows}, BLOCK),
                "int8": ("int8", {"q": rows, "scale": scale}, BLOCK)}[mode]
        dec, _, health = approx_mod.decode(
            code, rows.astype(jnp.float32), present=present,
            with_health=True, batch_grads=bg, impl="pallas", wire=wire)
        return dec, health["residual"]

    wire_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                  "int8": jnp.int8}[mode]
    return fn, [((n, d), wire_dtype), ((n, d), jnp.float32), ((n,), bool),
                ((n, nb), jnp.float32)]


def _recombine(mode, n=9, d=RESNET18_D):
    nb = -(-d // BLOCK)

    def fn(v_re, v_im, q_re, q_im, s_re, s_im):
        bufs = (({"q": q_re}, {"q": q_im}) if mode == "bf16" else
                ({"q": q_re, "scale": s_re}, {"q": q_im, "scale": s_im}))
        return dk.cyclic_narrow_recombine(v_re, v_im, (mode, *bufs, BLOCK))

    wire_dtype = jnp.bfloat16 if mode == "bf16" else jnp.int8
    return fn, [((n,), jnp.float32)] * 2 + [((n, d), wire_dtype)] * 2 + [
        ((n, nb), jnp.float32)] * 2


def _flash(grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, force=True)

    def bwd(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fwd(q, k, v))),
                        argnums=(0, 1, 2))(q, k, v)

    return (bwd if grad else fwd), [(FLASH_SHAPE, jnp.float32)] * 3


def _flash_lse(causal=False):
    """A ring hop's pair: fully visible (a past owner's shard) or causal
    (the own shard: the sub-tiled bodies), both outputs read, so the
    backward kernel takes the log-sum-exp's cotangent as a third row
    statistic."""
    from draco_tpu.ops.flash_attention import flash_attention_with_lse

    def fn(q, k, v):
        def loss(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                              force=True)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return fn, [(FLASH_SHAPE, jnp.float32)] * 3


# kanana2.maj_vote_r3: 32 heads of q/k 192 against v 128 over 4 096 tokens
MLA_QK, MLA_V = (1, 4096, 32, 192), (1, 4096, 32, 128)
# its routed experts: a dispatch buffer of C = 6 144 of the T*6 = 24 576
# (token, choice) pairs (LatentMoeLM.dispatch_rows), 128 groups, 8 held here
GMM_ROWS, GMM_K, GMM_N, GMM_GROUPS, GMM_HELD = 6144, 2048, 768, 128, 8


def _flash_latent():
    """Forward and backward with q/k and v of different head sizes, handed
    bfloat16 as the token model hands them."""
    def fn(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, force=True).astype(jnp.float32))), argnums=(0, 1, 2))(
                q, k, v)

    return fn, [(MLA_QK, jnp.bfloat16)] * 2 + [(MLA_V, jnp.bfloat16)]


# ouro.maj_vote_r3: 16 heads of 128, as many key/value heads, 4 096 tokens
MHA = (1, 4096, 16, 128)


def _flash_equal_heads():
    """Forward and backward at the looped model's shape: equal head sizes,
    one key/value head a query head, bfloat16 as the model hands them."""
    def fn(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, force=True).astype(jnp.float32))), argnums=(0, 1, 2))(
                q, k, v)

    return fn, [(MHA, jnp.bfloat16)] * 3


# qwen3next.maj_vote_r3: 16 query heads of 256 on 2 key/value heads
GQA_Q, GQA_KV = (1, 4096, 16, 256), (1, 4096, 2, 256)


def _flash_grouped_query():
    """Forward and backward at the widest head the kernel takes (256), the
    key/value heads spread over the query heads they serve."""
    def fn(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, force=True).astype(jnp.float32))), argnums=(0, 1, 2))(
                q, k, v)

    return fn, [(GQA_Q, jnp.bfloat16)] + [(GQA_KV, jnp.bfloat16)] * 2


# mellum2.maj_vote_r3: 32 query heads of 128 on 4 key/value heads over
# 8 192 tokens, a window of 1 024
SWA_Q, SWA_KV, SWA_WINDOW = (1, 8192, 32, 128), (1, 8192, 4, 128), 1024


def _flash_windowed(window=SWA_WINDOW):
    """Forward and backward of a sliding layer's core as the model runs it:
    the window's block skipping and two-sided residency maps, grouped-query
    heads, under the model's scope (``window=None``: the model's full layer,
    the longest head the backward keeps in vector memory)."""
    def fn(q, k, v):
        def core(q, k, v):
            with jax.named_scope("draco_window"):
                return flash_attention(q, k, v, window=window, force=True)

        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(core(
            q, k, v).astype(jnp.float32))), argnums=(0, 1, 2))(q, k, v)

    return fn, [(SWA_Q, jnp.bfloat16)] + [(SWA_KV, jnp.bfloat16)] * 2


# lfm2.maj_vote_r3: 32 query heads of 64 on 8 key/value heads, 4 096 tokens
# (each head padded to a lane tile of 128 inside the kernels' folding)
GQA64_Q, GQA64_KV = (1, 4096, 32, 64), (1, 4096, 8, 64)


def _flash_grouped_query_d64():
    """Forward and backward at heads of 64, half a lane tile, the key/value
    heads spread over the four query heads each serves."""
    def fn(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, force=True).astype(jnp.float32))), argnums=(0, 1, 2))(
                q, k, v)

    return fn, [(GQA64_Q, jnp.bfloat16)] + [(GQA64_KV, jnp.bfloat16)] * 2


def _grouped_dot():
    """models/latent_moe.grouped_dot's kernel (jax's megablox) at the
    cell's shapes, forward and backward, with only the held groups'
    matrices."""
    from unittest import mock

    from draco_tpu.models import latent_moe, spec_lm

    def fn(xs, kernels, sizes):
        # (the operand rule, ``_operand``, asks spec_lm's name)
        with mock.patch.object(latent_moe, "use_pallas", lambda: True), \
                mock.patch.object(spec_lm, "use_pallas", lambda: True):
            return jax.grad(lambda xs, kernels: jnp.sum(
                latent_moe.grouped_dot(xs, kernels, sizes, GMM_HELD) ** 2),
                argnums=(0, 1))(xs, kernels)

    return fn, [((GMM_ROWS, GMM_K), jnp.float32),
                ((GMM_HELD, GMM_K, GMM_N), jnp.float32),
                ((GMM_GROUPS,), jnp.int32)]


# qwen3next.maj_vote_r3: one lane's row through a Gated DeltaNet layer's rule
RULE_QK, RULE_V, RULE_G = (1, 4096, 16, 128), (1, 4096, 32, 128), (1, 4096, 32)


def _delta_rule(grad):
    """ops/delta_rule.py's kernels under the model's scope: the solve and
    the pass forward; with ``grad`` also the backward."""
    from draco_tpu.ops.delta_rule import chunked_gated_delta_rule

    def fwd(q, k, v, g, beta):
        with jax.named_scope("draco_deltarule"):
            return chunked_gated_delta_rule(q, k, v, g, beta, force=True)

    def bwd(*args):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fwd(*a)[0])),
                        argnums=(0, 1, 2, 3, 4))(*args)

    return (bwd if grad else fwd), (
        [(RULE_QK, jnp.float32)] * 2 + [(RULE_V, jnp.float32)]
        + [(RULE_G, jnp.float32)] * 2)


CASES = {
    # auto selects these on the chip: the three presets' codes + the narrow
    # wire's regularized locator (n=9 s=2 is cyclic-vgg11, n=8 the LM runs)
    "cyclic_locator_n9_s1": lambda: _locator(9, 1),
    "cyclic_locator_n9_s2": lambda: _locator(9, 2),
    "cyclic_locator_n8_s1": lambda: _locator(8, 1),
    "cyclic_locator_n9_s1_lam": lambda: _locator(9, 1, lam=2.0 ** -7),
    "approx_decode_f32": lambda: _approx("f32"),
    "approx_decode_bf16": lambda: _approx("bf16"),
    "approx_decode_int8": lambda: _approx("int8"),
    "cyclic_recombine_bf16": lambda: _recombine("bf16"),
    "cyclic_recombine_int8": lambda: _recombine("int8"),
    "flash_fwd": lambda: _flash(grad=False),
    "flash_grad": lambda: _flash(grad=True),
    "flash_grad_with_lse_fully_visible": _flash_lse,
    "flash_grad_with_lse_causal": lambda: _flash_lse(causal=True),
    "flash_grad_qk192_v128": _flash_latent,
    "flash_grad_16_heads_on_2_d256": _flash_grouped_query,
    "flash_grad_16_heads_d128": _flash_equal_heads,
    "flash_grad_window_1024_32_heads_on_4": _flash_windowed,
    "flash_grad_t8192_32_heads_on_4": lambda: _flash_windowed(window=None),
    "flash_grad_32_heads_on_8_d64": _flash_grouped_query_d64,
    "grouped_dot_8_of_128": _grouped_dot,
    "delta_rule_fwd": lambda: _delta_rule(grad=False),
    "delta_rule_grad": lambda: _delta_rule(grad=True),
}


_TEXTS: dict = {}


def _compiled_text(case, one_chip) -> str:
    """The case's program compiled for the described chip, as text; compiled
    once a process (two tests read the rule's and the window's)."""
    if case not in _TEXTS:
        fn, specs = CASES[case]()
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        _TEXTS[case] = jax.jit(fn).lower(*args).compile().as_text()
    return _TEXTS[case]


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    # a kernel that quietly became plain XLA would pass a compile
    assert "tpu_custom_call" in _compiled_text(case, one_chip), case


def test_every_kernel_of_the_rule_carries_the_rules_scope(one_chip):
    """``deltarule_ms`` reads each instruction's innermost ``draco_*``
    segment: in the compiled gradient the solve, the pass and the backward
    kernel (its op is named ``transpose(jvp(draco_deltarule))``) all carry
    ``draco_deltarule``. A kernel that lost the label would leave
    ``deltarule_roofline`` nothing to divide by."""
    import re

    text = _compiled_text("delta_rule_grad", one_chip)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in calls]
    assert len(names) == 3, names
    assert all("draco_deltarule" in name for name in names), names
    assert sum("transpose(jvp(draco_deltarule))" in name
               for name in names) == 1, names
    for kernel in ("solve_kernel", "pass_kernel", "backward_kernel"):
        assert sum(kernel in name for name in names) == 1, names


def test_every_kernel_of_the_window_carries_the_windows_scope(one_chip):
    """``window_kernel_ms`` reads each instruction's innermost ``draco_*``
    segment: the forward kernel and the one backward kernel of a sliding
    layer's core carry ``draco_window``, and so do the copies of k and v to
    the query heads' count."""
    import re

    text = _compiled_text("flash_grad_window_1024_32_heads_on_4", one_chip)
    names = _kernel_names(text)
    assert len(names) == 2, names
    assert all("draco_window" in name for name in names), names
    assert sum("transpose(jvp(draco_window))" in name
               for name in names) == 1, names


def _resnet18_step_text(chip) -> str:
    """The headline cell's step program (ResNet-18, cyclic s=1, n=8, r=3,
    batch 32 a worker) compiled for the described chip: the mesh is built
    from the described device and ``device_put`` hands back shapes, as
    there is no device to hold an array (on-chip-measurement guide, 2)."""
    import numpy as np
    from unittest import mock

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from draco_tpu.config import TrainConfig
    from draco_tpu.training.step import build_train_setup

    def shapes_only(tree, sharding=None, **kw):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                           sharding=sharding), tree)

    cfg = TrainConfig(network="ResNet18", dataset="synthetic-cifar10",
                      approach="cyclic", num_workers=8, worker_fail=1,
                      err_mode="rev_grad", redundancy="simulate",
                      batch_size=32, lr=0.01, momentum=0.9, max_steps=8,
                      eval_freq=0, train_dir="", decode_impl="auto")
    mesh = Mesh(np.asarray([chip._device_assignment[0]]), ("w",))
    rows = NamedSharding(mesh, P("w"))
    x = jax.ShapeDtypeStruct((8, 32, 32, 32, 3), jnp.float32, sharding=rows)
    y = jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=rows)
    # the decode lowering is chosen where the step is traced (the one coded
    # tail, parallel/common.py): the patch stays on through the lowering
    with mock.patch.object(jax, "device_put", shapes_only), \
            mock.patch.object(dk, "use_pallas", lambda: True):
        setup = build_train_setup(cfg, mesh, dataset_name=cfg.dataset)
        return setup.train_step.lower(
            setup.state, x, y, np.zeros((8,), bool)).compile().as_text()


def test_the_dense_expert_layer_is_three_plain_products(one_chip):
    """mellum2.maj_vote_r3's expert layer at the published widths (8 of 64
    experts held, top-8, a row of 8 192 tokens), forward and backward, for
    the described chip: ``MoeSpec.dense`` — no sort, no gather or
    scatter-add of rows, no loop with the data's trip count and no grouped
    kernel, so its work is the same at every routing; the products carry
    ``draco_experts``, the weights' mask ``draco_route``."""
    import json
    import re
    from unittest import mock

    from draco_tpu.models import latent_moe, spec_lm
    from draco_tpu.models.windowed_moe import WindowedMoeLM

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "mellum2-12b-a2.5b-ep8.json")) as fh:
        spec = json.load(fh)["train_config"]["model_spec"]
    lm = WindowedMoeLM(dict(spec, layers=1))
    assert lm.moe.dense
    shapes = lm.param_shapes()["layer0"]
    layer = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    x = jax.ShapeDtypeStruct((8192, spec["hidden_size"]), jnp.float32,
                             sharding=one_chip)

    def fn(x, p):
        with mock.patch.object(latent_moe, "use_pallas", lambda: True), \
                mock.patch.object(spec_lm, "use_pallas", lambda: True):
            return jax.grad(lambda x, p: jnp.sum(
                jnp.sin(lm._experts(x, p)[0])), argnums=(0, 1))(x, p)

    text = jax.jit(fn).lower(x, layer).compile().as_text()
    ops = re.findall(r" = \S+ ([a-z\-]+)\(", text)
    assert "tpu_custom_call" not in text
    for op in ("sort", "scatter", "while"):
        assert op not in ops, op
    products = [line for line in text.splitlines()
                if " convolution(" in line and "draco_experts" in line]
    # gate, up, down; each one's two transposes
    assert len(products) == 9, len(products)
    assert any("draco_route" in line for line in text.splitlines())


def test_a_short_conv_layer_lowers_for_the_chip(one_chip):
    """lfm2.maj_vote_r3's convolution layer at the published widths (a row
    of 4 096 tokens; published layer 3: the operator, then 8 of 32 experts
    over every token), forward and backward under the per-layer
    rematerialisation, for the described chip (the attention layer's
    kernels at heads of 64 are the case ``flash_grad_32_heads_on_8_d64``).
    The operator's products carry ``draco_conv`` and run no kernel; the
    expert layer is plain products under ``draco_experts`` with no sort
    and no loop."""
    held_index, kind = 3, "conv"
    import json
    import re
    from unittest import mock

    from draco_tpu.models import latent_moe, spec_lm
    from draco_tpu.models.conv_moe import ShortConvMoeLM

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-8b-a1b-ep4.json")) as fh:
        spec = json.load(fh)["train_config"]["model_spec"]
    lm = ShortConvMoeLM(dict(spec, layers=1, layers_held=[held_index]),
                        attn_fn=functools.partial(flash_attention,
                                                  force=True), remat=True)
    assert lm.layer_types == [kind] and lm.moe.dense
    t, d = 4096, spec["hidden_size"]

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    params = jax.tree.map(struct, lm.param_shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    params.pop("embed")
    x = struct((1, t, d))

    def fn(x, params):
        def loss(x, params):
            y, stats = lm.hidden(
                dict(params, embed={"embedding": x[0]}),
                jnp.arange(t)[None])
            return jnp.sum(jnp.sin(y)) + stats["short_conv_absmax"]

        with mock.patch.object(latent_moe, "use_pallas", lambda: True), \
                mock.patch.object(spec_lm, "use_pallas", lambda: True):
            return jax.grad(loss, argnums=(0, 1))(x, params)

    text = jax.jit(fn).lower(x, params).compile().as_text()
    ops = re.findall(r" = \S+ ([a-z\-]+)\(", text)
    kernels = _kernel_names(text)
    products = [line for line in text.splitlines()
                if " convolution(" in line]
    assert kernels == []
    # W_in and W_out, each one's two transposes, and the recomputed forward
    # products the backward pass reads
    assert sum("draco_conv" in line for line in products) >= 6
    assert any("draco_experts" in line for line in products)
    assert any("draco_route" in line for line in text.splitlines())
    # (the one scatter is this test's: the gradient of its embedding gather)
    for op in ("sort", "while"):
        assert op not in ops, op


def _kimilinear_spec():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "kimi-linear-48b-a3b-ep32-tp2.json")) as fh:
        return json.load(fh)["train_config"]["model_spec"]


def _result_shapes(line):
    """The dimensions of an HLO instruction's result arrays (a tuple's
    members each), as strings: "1,8,4096,128"."""
    import re

    result = line.split(" = ", 1)[1]
    result = (result[:result.index(") ") + 1] if result.startswith("(")
              else result.split(" ", 1)[0])
    return re.findall(r"[a-z]+[0-9]+\[([0-9,]*)\]", result)


def _kernel_names(text):
    import re

    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_a_kda_layer_lowers_for_the_chip(one_chip):
    """kimilinear.maj_vote_r3's Kimi Delta Attention layer at the published
    widths (a row of 4 096 tokens, 16 of 32 heads held; published layer 2:
    the mixer, then 8 of 256 experts over the sorted pairs and the shared
    expert), forward and backward under the block's two checkpoints a
    layer, for the described chip. Mosaic builds the rule's three kernels
    (``ops/kda_rule.py``; ``kda_kernel_layers`` reads 1 a layer): the solve
    once, the pass twice — forward and rematerialised forward: the mixer's
    checkpoint keeps T —, the backward once, each under ``draco_kdarule``.
    Nothing under that scope is a loop, no array of a sub-block's pairwise
    decays (tokens x 16 x Dk a head: 537 MB a layer-lane) exists at all, no
    (heads, chunks, C, C) array but T (two heads side by side: (heads / 2,
    tokens, 2 C)) is an instruction's result under it, and the layer's
    backward pass fits 1.5 GB beside its own gradients. The expert layer is
    the shared one: grouped-product kernels under ``draco_experts``."""
    from unittest import mock

    from draco_tpu.models.kda_moe import KdaMoeLM
    from tests.test_step_scopes import _executed_lines

    spec = _kimilinear_spec()
    lm = KdaMoeLM(dict(spec, layers=1, layers_held=[2]),
                  attn_fn=functools.partial(flash_attention, force=True),
                  remat=True)
    assert lm.kept == [("kda", False)] and not lm.moe.dense
    t, d = 4096, spec["hidden_size"]
    heads, dk = lm.heads, spec["linear_attn_config"]["head_dim"]

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    params = jax.tree.map(struct, lm.param_shapes(),
                          is_leaf=lambda x: isinstance(x, tuple))
    params.pop("embed")
    x = struct((1, t, d))

    def fn(x, params):
        def loss(x, params):
            y, stats = lm.hidden(
                dict(params, embed={"embedding": x[0]}),
                jnp.arange(t)[None])
            return jnp.sum(jnp.sin(y)) + stats["kda_state_absmax"]

        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return jax.grad(loss, argnums=(0, 1))(x, params)

    compiled = jax.jit(fn).lower(x, params).compile()
    text = compiled.as_text()
    kernels = _kernel_names(text)
    rule = [name for name in kernels if "_kernel/pallas_call" in name]
    assert sorted(name.split("/")[-2] for name in rule) == [
        "backward_kernel", "pass_kernel", "pass_kernel", "solve_kernel"]
    assert all("draco_kdarule" in name for name in rule)
    assert any("draco_experts" in name for name in kernels)
    under = [line for line in _executed_lines(text)
             if "draco_kdarule" in line]
    assert not [line for line in under if " while(" in line]
    assert f"[{heads},{t // 64},4,16,16,{dk}]" not in text
    solves = {dims for line in under for dims in _result_shapes(line)
              if math.prod(map(int, dims.split(","))) == heads * t * 64}
    assert solves == {f"1,{heads // 2},{t},128"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_the_kda_rule_alone_builds_for_the_chip(one_chip):
    """The per-channel rule by itself at the cell's shapes, (1, 4 096, 16,
    128) float32, forward and backward: three kernels — ``solve_kernel``,
    ``pass_kernel``, ``backward_kernel`` — and beside the operands and the
    five gradients only T (16.8 MB), the chunk-start states (67 MB) and G
    in main memory."""
    from draco_tpu.ops import kda_rule

    shape = (1, 4096, 16, 128)
    assert kda_rule.kda_runs_in_kernels(shape, shape, force=True)
    q = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip)

    def loss(q, k, v, g, beta):
        o, state = kda_rule.chunked_kda_rule(q, k, v, g, beta, force=True)
        return jnp.sum(jnp.sin(o)) + jnp.sum(state)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, q, q, beta).compile()
    assert [name.split("/")[-2] for name in _kernel_names(
        compiled.as_text())] == ["solve_kernel", "pass_kernel",
                                 "backward_kernel"]
    # T + the chunk-start states + G and its cotangent's pass: 151 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


# the instrument's own scale: compiled for a chip that is described, not
# attached, by THIS file's recipe a token cell's whole step reads 3.4 GB above
# what the chip reserves for it (lfm2.maj_vote_r3: 14 211 114 496 B of
# ``preallocated-temp`` here, 10 748 805 120 on the chip; this cell
# 14 175 764 480 here, 10 620 272 640 on the chip: PERF.md section 4) — so
# the bound a new cell's step is held to is the standing cell's reading on
# the same instrument, temp + donated arguments, with the 2 % that cell has
# to spare on the chip (14.81 of 15.75 GB)
LFM2_STEP_BYTES_DESCRIBED = 14_211_114_496 + 4_062_683_136


@pytest.mark.slow  # one whole step program of five layers: three minutes
def test_the_kimilinear_step_holds_no_more_than_the_cell_that_fits(one_chip):
    """kimilinear.maj_vote_r3's whole step (n = r = 3 lanes in turn, 4 096
    tokens, d = 510 692 160) compiled for the described v5e: its
    ``preallocated-temp`` and donated bytes together stay within 2 % of
    what lfm2.maj_vote_r3's step reads on the same instrument. ISSUE 45
    asked for the sum under 15.75 GB; this recipe reads both cells over
    it, and the chip settled the fallbacks (neither taken: 10.62 GB
    reserved, 14.71 with the donated weights and momentum)."""
    import json

    import numpy as np
    from unittest import mock

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.sp_step import build_sp_train_setup

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep32-tp2.json")) as fh:
        fields = json.load(fh)["train_config"]
    with open(os.path.join(root, "benchmark", "traffic",
                           "lm_maj_vote_r3.json")) as fh:
        fields = dict(fields, **json.load(fh)["train_config"])
    cfg = TrainConfig(**dict(fields, max_steps=8, eval_freq=0,
                             train_dir="")).validate()
    mesh = Mesh(np.asarray([one_chip._device_assignment[0]]).reshape(1, 1),
                ("w", "sp"))

    def shapes_only(tree, sharding=None, **kw):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                           sharding=sharding), tree)

    whole = NamedSharding(mesh, P())
    with mock.patch.object(jax, "device_put", shapes_only), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        setup = build_sp_train_setup(cfg, mesh)
        compiled = setup.train_step.lower(
            setup.state,
            jax.ShapeDtypeStruct((3, 1, cfg.seq_len), jnp.int32,
                                 sharding=whole),
            jax.ShapeDtypeStruct((3,), bool, sharding=whole)).compile()
    memory = compiled.memory_analysis()
    assert int(setup.dim) == 510_692_160
    assert memory.alias_size_in_bytes >= 8 * int(setup.dim)  # donated
    assert (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            < 1.02 * LFM2_STEP_BYTES_DESCRIBED)


def test_four_exits_head_holds_one_blocks_logits_at_a_time(one_chip):
    """ouro.maj_vote_r3's head and loss (models/spec_lm.blocked_nll), forward
    and backward, at the cell's size — four exits' 16 384 rows against the
    whole 49 152-row vocabulary: logits exist a block of 2 048 rows at a
    time (0.4 GB), never all rows' (3.2 GB), and the program's temporaries
    stay under the state's and the weight gradient's 0.4 GB each plus a
    block's few arrays."""
    from draco_tpu.models import spec_lm

    rows, hidden, vocab = 4 * 4096, 2048, 49152
    block = spec_lm.head_block_rows(vocab)
    assert block == 2048

    def fn(h, kernel, targets):
        with jax.named_scope("draco_head"):
            return jax.grad(lambda h, kernel: jnp.sum(spec_lm.blocked_nll(
                h, kernel, targets)), argnums=(0, 1))(h, kernel)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((rows, hidden), jnp.float32),
                                 ((hidden, vocab), jnp.float32),
                                 ((rows,), jnp.int32))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert f"[{rows},{vocab}]" not in text
    assert f"[{rows // block},{block},{vocab}]" not in text
    assert f"f32[{block},{vocab}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


@pytest.mark.parametrize("fused,products", [(False, 4), (True, 3)])
def test_four_exits_head_runs_three_products_a_block_where_four_ran(
        one_chip, fused, products):
    """The same head at the same size, value and gradients: through
    ``spec_lm.weighted_nll`` (the training objective's surface) a block's
    forward pass takes the block's gradients, and the compiled program
    holds THREE products against the vocabulary — logits, the state's
    gradient, the weight gradient — in ONE loop; differentiating
    ``blocked_nll`` (the blocks rematerialised) it holds four in two."""
    import re

    from draco_tpu.models import spec_lm

    rows, hidden, vocab = 4 * 4096, 2048, 49152
    block = spec_lm.head_block_rows(vocab)

    def loss(h, kernel, targets, weights):
        if fused:
            return spec_lm.weighted_nll(h, kernel, targets, weights,
                                        rows)[0]
        return jnp.sum(spec_lm.blocked_nll(h, kernel, targets)
                       * weights) / rows

    def fn(h, kernel, targets, weights):
        with jax.named_scope("draco_head"):
            return jax.value_and_grad(loss, argnums=(0, 1, 3))(
                h, kernel, targets, weights)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((rows, hidden), jnp.float32),
                                 ((hidden, vocab), jnp.float32),
                                 ((rows,), jnp.int32),
                                 ((rows,), jnp.float32))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    shapes = dict(re.findall(r"%(\S+) = \w+\[([\d,]*)\]", text))
    against_vocab = [
        m.group(0) for m in re.finditer(
            r"%\S+ = \w+\[([\d,]*)\]\S* convolution\(%(\S+), %(\S+)\)",
            text)
        if any(str(vocab) in dims.split(",") for dims in (
            m.group(1), shapes[m.group(2)], shapes[m.group(3)]))]
    assert len(against_vocab) == products, against_vocab
    assert len(re.findall(r" while\(", text)) == (1 if fused else 2)
    # logits a block at a time, on either path
    assert f"[{rows},{vocab}]" not in text
    assert f"f32[{block},{vocab}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_resnet18_step_scopes_on_the_described_chip(one_chip, monkeypatch):
    """The TPU program's own labels (ISSUE 24): at ResNet-18 width every
    instruction the program wrote is under a ``draco_*`` scope, the Pallas
    locator is there, and switching the four new scopes off changes no
    instruction — only metadata (the kernel's serialized body holds the
    caller's line number, which a scope does not move either)."""
    from tests.test_step_scopes import (NEW_SCOPES, new_scopes_off,
                                        scope_report, strip_metadata)

    on = _resnet18_step_text(one_chip)
    assert "tpu_custom_call" in on
    report = scope_report(on)
    assert report["share"] >= 0.95, report["unscoped"][:10]
    # (no op of its own under draco_attack: the one reversed row fuses into
    # the encode's fusions, which carry their root's label)
    for scope in ("draco_comp", "draco_pack", "draco_input", "draco_health",
                  "draco_encode", "draco_decode", "draco_update"):
        assert report["bytes"].get(scope, 0) > 0, report["bytes"]
    new_scopes_off(monkeypatch)
    off = _resnet18_step_text(one_chip)
    assert not any(s in off for s in NEW_SCOPES)
    assert strip_metadata(on) == strip_metadata(off)


def test_vgg11_lanes_pool_as_fusions(one_chip):
    """ISSUE 25: VGG-11's forward + backward under the step builder's two
    ``vmap``s (n = 9 workers × r = 5 rows, batch cut to 8), once the chip's
    compiler is done with it: the three pools over planes of 8×8 and more
    are elementwise fusions; only the last two (4×4 and 2×2 planes, kept on
    ``nn.max_pool``: models/pooling.py) are still a 2×2 ``reduce-window``
    and a ``select-and-scatter``. With ``nn.max_pool`` throughout it held
    5 + 5, a third of ``vgg11.cyclic_s2``'s device time (PERF.md §6, PR 25)."""
    import re

    from draco_tpu.models import build_model

    model = build_model("VGG11")
    image = (32, 32, 3)
    params = jax.eval_shape(
        lambda k: model.init({"params": k, "dropout": k},
                             jnp.zeros((1, *image)), train=False)["params"],
        jax.random.key(0))

    def loss(p, x, y, key):
        logits = model.apply({"params": p}, x, train=True,
                             rngs={"dropout": jax.random.wrap_key_data(key)})
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def lanes(p, x, y, keys):
        lane = jax.vmap(jax.grad(loss), in_axes=(None, 0, 0, 0))
        return jax.vmap(lane, in_axes=(None, 0, 0, 0))(p, x, y, keys)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lanes).lower(
        jax.tree.map(lambda v: arg(v.shape, v.dtype), params),
        arg((9, 5, 8, *image), jnp.float32), arg((9, 5, 8), jnp.int32),
        arg((9, 5, 2), jnp.uint32)).compile().as_text()
    # (n, r, batch, rows, columns, channels) of each window operation left
    pooled = re.findall(
        r" = \w+\[9,5,8,(\d+),(\d+),\d+\]\S* reduce-window\(.*"
        r"window=\{size=1x1x1x2x2x1", text)
    scattered = re.findall(
        r" = \w+\[9,5,8,(\d+),(\d+),\d+\]\S* select-and-scatter\(", text)
    assert sorted(pooled) == [("1", "1"), ("2", "2")], pooled
    assert sorted(scattered) == [("2", "2"), ("4", "4")], scattered


def test_the_vote_reads_its_stack_once_and_copies_the_winner_once(one_chip):
    """ISSUE 29: the vote's tail — stack in; one sweep (finite check,
    simulated attack, fingerprints), the winner's row, an unravel of three
    leaves (the middle one (128,), so the third starts off a tile boundary)
    and an SGD-momentum update out — at a tiled stack of 11 009 tiles a
    lane, once the chip's compiler is done with it: ONE loop carries the
    stack and ONE fusion in it reads it (three ``u32[3]`` sums), nothing
    runs under ``draco_attack``, one more fusion reads the stack (the
    winner's copy),
    and at most two results of a row's size stand between stack and update.
    At the parent: three loops and an ``is-finite`` pass over the stack,
    four row-sized results (PERF.md section 6, PR 29)."""
    import math
    import re

    from draco_tpu import optim
    from draco_tpu.obs import device_attr as da
    from tests.test_step_scopes import _executed_lines

    from draco_tpu.config import TrainConfig
    from draco_tpu.parallel.common import (aggregate_flat_grads,
                                           finish_flat_step)
    from draco_tpu.training.step import TrainState, _make_unravel

    cfg = TrainConfig(network="TransformerLM", dataset="synthetic-text",
                      approach="maj_vote", num_workers=3, group_size=3,
                      worker_fail=1, err_mode="rev_grad", lr=0.01,
                      momentum=0.9, batch_size=1, seq_len=32, vocab=64,
                      eval_freq=0, train_dir="").validate()

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"a": arg((4096, 2048)), "b": arg((128,)),
              "c": arg((2048, 1408))}
    opt = optim.build_optimizer_from_cfg(cfg)
    unravel, dim, offsets = _make_unravel(params)
    assert offsets[2] % 1024 == 128  # "c" starts inside a tile
    lines = -(-dim // 1024) * 8
    state = TrainState(
        params=params, batch_stats=None, step=arg((), jnp.int32),
        opt_state=jax.tree.map(lambda a: arg(a.shape, a.dtype),
                               jax.eval_shape(opt.init, params)))

    def tail(state, stack, adv_mask):
        agg, health = aggregate_flat_grads(stack, adv_mask, cfg, None, None,
                                           step=state.step)
        new_state, _ = finish_flat_step(cfg, state, agg, health, opt,
                                        unravel)
        return new_state, health["flagged"], health["bad_rows"]

    text = jax.jit(tail, donate_argnums=(0,)).lower(
        state, arg((3, lines, 128)), arg((3,), jnp.bool_)).compile().as_text()
    assert "draco_attack" not in text
    row = lines * 128  # elements; the stack holds three, under any view

    def f32_sizes(result):
        return [math.prod(int(x) for x in dims.split(","))
                for dims in re.findall(r"f32\[([\d,]+)\]", result)]

    # (name, result type, op, operands) of what runs as an op of its own:
    # the entry computation and the loops' bodies, not the fusions' insides
    ins = []
    for line in _executed_lines(text):
        m = da._HLO_LINE_RE.match(line)
        if m:
            result, call = line.split("=", 1)[1].split(f" {m.group(2)}(", 1)
            ins.append((m.group(1), result, m.group(2), re.findall(
                r"%([\w.\-]+)", call.split("metadata=")[0])))
    moves = ("parameter", "tuple", "get-tuple-element", "bitcast")
    holds_stack = {name for name, result, _, _ in ins
                   if 3 * row in f32_sizes(result)}
    loops = [i for i in ins if i[2] == "while" and i[0] in holds_stack]
    assert len(loops) == 1, loops
    readers = [i for i in ins if i[2] not in moves + ("while",)
               and holds_stack & set(i[3])]
    # the sweep's three sums in the loop, the winner's copy after it
    assert [i[2] for i in readers] == ["fusion", "fusion"], readers
    assert sorted(f32_sizes(i[1]) for i in readers) == [[], [row]], readers
    assert readers[0][1].count("u32[3]") + readers[1][1].count("u32[3]") == 3
    row_sized = [i for i in ins if i[2] not in moves
                 and not i[1].lstrip().startswith("(")
                 and f32_sizes(i[1]) == [row]]
    assert 1 <= len(row_sized) <= 2, row_sized


def test_a_lane_writes_its_leaves_straight_into_the_stack(one_chip):
    """ISSUE 36: the lanes' loop as ``sp_step`` builds it where the stack is
    tiled — the stack is the loop's carry and ``_write_row`` writes each
    whole-line piece of a lane's gradient into its range of the lane's
    lines — once the chip's compiler is done with it: ONE loop carries the
    stack, every piece is an update-slice of the stack in place, and no
    result of a row's size (flat or in lines) stands beside it. Leaves as
    in the vote's test above (the third starts inside a tile) and two
    (3, 32) leaves last, which only close a line together with the zeros.
    At the parent the row was built flat — one ``concatenate`` of d
    elements where a leaf is no whole lines, 29.2 ms a step at d = 424 M
    (PERF.md section 6, PR 36) — and then copied into the stack."""
    import math
    import re

    from jax import lax

    from draco_tpu.obs import device_attr as da
    from draco_tpu.parallel.sp_step import (STACK_LANES, _write_row,
                                            row_layout)
    from draco_tpu.training.step import _make_unravel
    from tests.test_step_scopes import _executed_lines

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"a": arg((4096, 2048)), "b": arg((128,)),
              "c": arg((2048, 1408)),
              "heads": {"A_log": arg((3, 32)), "dt_bias": arg((3, 32))}}
    _, dim, offsets = _make_unravel(params)
    layout = row_layout(offsets[1:] - offsets[:-1])
    assert (layout.joined_leaves, layout.joined_size, layout.zeros,
            len(layout.pieces)) == (2, 192, 704, 4)

    def lanes(params, xs):
        def into_stack(stack, lane_x):
            # a gradient made inside the loop, a leaf at a time
            g = jax.tree.map(lambda p: jnp.sin(p * lane_x[1]), params)
            return _write_row(stack, lane_x[0], g, layout), None

        return lax.scan(
            into_stack, lax.empty((3, layout.lines, STACK_LANES),
                                  jnp.float32), (jnp.arange(3), xs))[0]

    text = jax.jit(lanes).lower(params, arg((3,))).compile().as_text()
    row = layout.lines * STACK_LANES

    def f32_sizes(result):
        return [math.prod(int(x) for x in dims.split(","))
                for dims in re.findall(r"f32\[([\d,]+)\]", result)]

    ins = []
    for line in _executed_lines(text):
        m = da._HLO_LINE_RE.match(line)
        if m:
            result = line.split("=", 1)[1].split(f" {m.group(2)}(", 1)[0]
            ins.append((m.group(1), result, m.group(2)))
    loops = [i for i in ins if i[2] == "while"
             and 3 * row in f32_sizes(i[1])]
    assert len(loops) == 1, loops
    assert not [i for i in ins if {row, dim} & set(f32_sizes(i[1]))]
    writes = [i for i in ins if f32_sizes(i[1]) == [3 * row]
              and i[2] in ("fusion", "dynamic-update-slice")]
    assert len(writes) == len(layout.pieces), writes
    assert "concatenate" not in {i[2] for i in ins if max(
        f32_sizes(i[1]), default=0) > layout.joined_size + layout.zeros}
