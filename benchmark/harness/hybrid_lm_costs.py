"""Operations and bytes a decoder of Gated DeltaNet and gated-attention
layers with routed experts needs, from the configuration's mapping
(``model_spec``: the ``qwen3_next`` family's published config keys plus
``layers``, ``experts_held``, ``vocab_rows``). Two per multiply-add; norms,
activations, softmax, rotary and the gates' elementwise products are not
counted. Used for the derived utilization in PERF.md and the roofline of
the delta rule (harness/lm_costs.py has the latent-attention model's,
harness/costs.py the CNNs')."""

from __future__ import annotations

F32 = 4  # bytes: the configuration stores activations in float32


def _kinds(spec: dict) -> tuple:
    """(linear-attention layers, full-attention layers) among those kept."""
    every = spec["full_attention_interval"]
    full = sum(1 for i in range(spec["layers"]) if (i + 1) % every == 0)
    return spec["layers"] - full, full


def deltarule_forward_flops_per_token(spec: dict) -> float:
    """The recurrence as written, for one token of one layer: per value
    head the decay of S (Dk·Dv), the read Sᵀk, the rank-one write and the
    read Sᵀq (2·Dk·Dv each but the decay) — 6·Dk·Dv counting the write's
    scaling too. What a chunked form adds to solve for a chunk's writes at
    once is not the rule's work and is not counted."""
    return (6.0 * spec["linear_key_head_dim"] * spec["linear_value_head_dim"]
            * spec["linear_num_value_heads"])


def deltarule_bytes_per_token(spec: dict) -> float:
    """q, k, v, g, β read and o written once, float32, one layer."""
    hk, hv = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    dk, dv = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    return F32 * (2.0 * hk * dk + 2.0 * hv * dv + 2.0 * hv)


def linear_attention_forward_flops_per_token(spec: dict) -> float:
    """One Gated DeltaNet layer for one token: both input projections, the
    depthwise convolution, the rule, the output projection."""
    d = spec["hidden_size"]
    hk, hv = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    dk, dv = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    qkv = 2 * hk * dk + hv * dv
    proj = d * (qkv + hv * dv + 2 * hv) + hv * dv * d
    conv = spec["linear_conv_kernel_dim"] * qkv
    return 2.0 * proj + 2.0 * conv + deltarule_forward_flops_per_token(spec)


def attention_forward_flops_per_token(spec: dict, seq_len: int) -> float:
    """One gated-attention layer for one token of a causal sequence of
    ``seq_len``: q with its gate, k, v and o projections, and scores and
    mixing against the (seq_len + 1) / 2 keys a query sees on average."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    kv, dh = spec["num_key_value_heads"], spec["head_dim"]
    proj = d * (2 * h * dh + 2 * kv * dh) + h * dh * d
    return 2.0 * proj + 2.0 * h * 2 * dh * (seq_len + 1) / 2


def forward_flops_per_token(spec: dict, seq_len: int) -> dict:
    """{part: FLOPs} of one token's forward pass through the kept layers
    and the head. ``routed`` counts what THIS chip computes on average: each
    token's top-k lands on a held expert with probability held / experts."""
    d, layers = spec["hidden_size"], spec["layers"]
    linear, full = _kinds(spec)
    width = spec["moe_intermediate_size"]
    held = spec["experts_held"][1]
    return {
        "linear_attention": linear
        * linear_attention_forward_flops_per_token(spec),
        "attention": full * attention_forward_flops_per_token(spec, seq_len),
        "router": layers * 2.0 * d * spec["num_experts"],
        "shared": layers * (6.0 * d * spec["shared_expert_intermediate_size"]
                            + 2.0 * d),
        "routed": layers * 6.0 * d * width * spec["num_experts_per_tok"]
        * held / spec["num_experts"],
        "head": 2.0 * d * spec["vocab_rows"],
    }


def _tokens_computed(job: dict) -> int:
    """Token-gradients a step: every lane really computes its row."""
    return job["n"] * job["batch"] * job["seq_len"]


def train_flops_per_step(job: dict) -> float:
    """Forward plus backward (three times the forward pass) of every
    token-gradient a step computes; rematerialised work is not counted."""
    per_token = sum(forward_flops_per_token(job["model_spec"],
                                            job["seq_len"]).values())
    return 3.0 * per_token * _tokens_computed(job)


def deltarule_train_flops_per_step(job: dict) -> float:
    spec = job["model_spec"]
    return (3.0 * _kinds(spec)[0] * deltarule_forward_flops_per_token(spec)
            * _tokens_computed(job))


def deltarule_train_bytes_per_step(job: dict) -> float:
    """Forward plus backward at three times the forward pass's traffic (the
    backward reads the same six tensors and writes their five gradients)."""
    spec = job["model_spec"]
    return (3.0 * _kinds(spec)[0] * deltarule_bytes_per_token(spec)
            * _tokens_computed(job))
