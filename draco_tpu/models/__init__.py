"""Model zoo — Flax ports of the reference's model_ops/ architectures.

The reference carries two copies of every model: a plain nn.Module and a
"*Split" variant whose hand-rolled per-layer backward streams each gradient
over MPI as soon as it exists (reference: src/model_ops/resnet_split.py:431-623).
Under XLA the overlap the Split models bought is the compiler's job (async
collectives + latency hiding), so there is exactly one copy of each model here.

Token models (``TOKEN_NETWORKS``, config.py) come from :func:`build_lm`, the
one factory the LM step builders call: ``TransformerLM`` (the repo's own
pre-LN / GELU / tied-head block, the only one the tp / ep / pp / sequence-
sharded routes build) and the six blocks that state a published config on
the single-shard route of parallel/sp_step.py (``config.SPEC_NETWORKS``;
seeded init, head and loss are one base's, models/spec_lm.py):
``LatentMoeLM`` (models/latent_moe.py: RMS norm, SwiGLU, latent key/value
attention, sigmoid top-k routing without drops over the experts this chip
holds, shared experts, untied head) and ``HybridMoeLM`` (models/
hybrid_moe.py: Gated DeltaNet linear-attention layers beside gated
grouped-query softmax attention, softmax routing, a gated shared expert —
over the same expert layer) and ``WindowedMoeLM`` (models/windowed_moe.py:
grouped-query attention under a sliding window three layers in four and
over the whole row every fourth, rotary parameters by the layer's kind,
softmax routing and no shared expert — over the same expert layer), and
``LoopedLM`` (models/looped.py: dense — one stack of four-norm layers run
``total_ut_steps`` times over the same weights, an exit gate and the whole
head after each pass, the loss an expectation over the exits), and
``ShortConvMoeLM`` (models/conv_moe.py: double-gated short convolutions 3:1
with GQA under per-head q/k norms, leading dense layers, bias-selected
sigmoid routing, no shared expert, the head tied to the embedding), and
``KdaMoeLM`` (models/kda_moe.py: a delta rule decaying per key channel 3:1
with position-free latent attention, both mixers told the heads they hold).
"""

from draco_tpu.config import SPEC_NETWORKS, TOKEN_NETWORKS
from draco_tpu.models.fc import FC_NN
from draco_tpu.models.lenet import LeNet
from draco_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from draco_tpu.models.transformer import TransformerLM
from draco_tpu.models.vgg import (
    VGG,
    VGG11,
    VGG11_bn,
    VGG13,
    VGG13_bn,
    VGG16,
    VGG16_bn,
    VGG19,
    VGG19_bn,
)

_REGISTRY = {
    "LeNet": LeNet,
    "FC": FC_NN,
    "ResNet18": ResNet18,
    "ResNet34": ResNet34,
    "ResNet50": ResNet50,
    "ResNet101": ResNet101,
    "ResNet152": ResNet152,
    "VGG11": VGG11,
    "VGG11_bn": VGG11_bn,
    "VGG13": VGG13,
    "VGG13_bn": VGG13_bn,
    "VGG16": VGG16,
    "VGG16_bn": VGG16_bn,
    "VGG19": VGG19,
    "VGG19_bn": VGG19_bn,
}


def build_model(name: str, num_classes: int = 10, dtype=None):
    """Name-based model construction (reference: build_model switches in
    baseline_master.py:30-47 / baseline_worker.py:37-50). ``dtype``: compute
    dtype for the conv/dense stacks ("bfloat16" rides the MXU at full rate;
    params, BN stats and logits stay float32)."""
    if name in TOKEN_NETWORKS:
        raise ValueError(
            f"{name} is a token model and does not run on the image "
            "pipeline; the CLI routes it automatically, or construct it via "
            "draco_tpu.parallel.sp_step.build_sp_train_setup (all knobs) / "
            "draco_tpu.models.build_lm directly"
        )
    if name not in _REGISTRY:
        raise ValueError(f"unknown network: {name} (have {sorted(_REGISTRY)})")
    kwargs = {"num_classes": num_classes}
    if dtype is not None:
        import jax.numpy as jnp

        kwargs["dtype"] = jnp.dtype(dtype)
    return _REGISTRY[name](**kwargs)


class _FlaxTokenLM:
    """TransformerLM behind the token-model surface of :func:`build_lm`."""

    stat_names = ()  # no per-step counters of its own

    def __init__(self, module, init_module, seq_len: int):
        self.module, self._init_module = module, init_module
        self._init_len = min(seq_len, 8)

    def init(self, key):
        import jax.numpy as jnp

        toks = jnp.zeros((1, self._init_len), jnp.int32)
        # single-shard (dense attention) init: the shapes are the same
        return self._init_module.init({"params": key}, toks,
                                      train=True)["params"]

    def token_nll(self, params, tokens, targets, pos_offset=0,
                  train: bool = True):
        import jax
        import jax.numpy as jnp

        logits = self.module.apply({"params": params}, tokens,
                                   pos_offset=pos_offset, train=train)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll, {}

    def weighted_nll(self, params, tokens, targets, weights, denom=1.0,
                     pos_offset=0, train: bool = True):
        import jax.numpy as jnp

        nll, stats = self.token_nll(params, tokens, targets, pos_offset,
                                    train)
        return jnp.sum(nll * weights) / denom, stats


def build_lm(cfg, attn_fn=None, kernel_fn=None):
    """The token model of ``cfg.network``: an object with ``init(key) ->
    params``, ``token_nll(params, tokens (B, T), targets (B, T),
    pos_offset, train) -> (per-position negative log-likelihood (B, T)
    float32, per-step counters {name: scalar})``, ``weighted_nll(params,
    tokens, targets, weights, denom, pos_offset, train) -> (Σ weights ·
    token_nll / denom, a scalar, the counters)`` — the training objective's
    surface: a head that knows the rows' weights can take its gradients in
    the forward pass (models/spec_lm.py) — and ``stat_names``, those
    counters' names in the metric row's order (empty where a model has
    none). The route offers two attentions ((q, k, v) -> o) and each model
    takes the one it can use: ``attn_fn``, the route's own (sequence-
    parallel wrappers included; equal head sizes), and ``kernel_fn``, the
    bare single-device kernel, which the published-config blocks take
    (``LatentMoeLM``'s q/k and v differ in head size, ``HybridMoeLM``'s
    key/value heads are fewer than its query heads, ``WindowedMoeLM`` hands
    each layer's call its own ``window=``, ``LoopedLM``, ``ShortConvMoeLM``
    and ``KdaMoeLM`` run on the same single-shard route). None is each
    model's plain lowering."""
    import importlib

    import jax.numpy as jnp

    cdtype = jnp.dtype(cfg.compute_dtype)
    if cfg.network in SPEC_NETWORKS:
        module = importlib.import_module(SPEC_NETWORKS[cfg.network])
        return getattr(module, cfg.network)(
            cfg.model_spec, attn_fn=kernel_fn, dtype=cdtype,
            remat=cfg.remat)
    if cfg.network != "TransformerLM":
        raise ValueError(f"{cfg.network!r} is not a token model")
    kw = dict(vocab=cfg.vocab, dim=cfg.model_dim, heads=cfg.model_heads,
              layers=cfg.model_layers, experts=cfg.moe_experts, dtype=cdtype,
              scan_layers=cfg.scan_layers)
    return _FlaxTokenLM(
        TransformerLM(attn_fn=attn_fn, remat=cfg.remat, **kw),
        TransformerLM(attn_fn=None, **kw), cfg.seq_len)


def input_shape(dataset: str):
    """Per-dataset sample shape, NHWC."""
    d = dataset.lower()
    if "mnist" in d:
        return (28, 28, 1)
    if "cifar" in d:
        return (32, 32, 3)
    raise ValueError(f"unknown dataset: {dataset}")
