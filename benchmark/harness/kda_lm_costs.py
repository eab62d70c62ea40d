"""Operations and bytes a decoder of Kimi Delta Attention layers (a delta
rule whose decay is per key channel) and position-free latent-attention
layers, with a leading dense SwiGLU layer and routed experts plus a shared
expert after it, needs — from the configuration's mapping (``model_spec``:
the ``kimi_linear`` family's published config keys plus ``layers``,
``layers_held``, ``heads_held``, ``experts_held``, ``vocab_rows``). Two per
multiply-add; norms, activations, softmax and the gates' elementwise
products are not counted. Heads and experts are the ones HELD: what this
chip computes. Used for the derived utilization in PERF.md and the roofline
of the rule (harness/hybrid_lm_costs.py, lm_costs.py, conv_lm_costs.py,
windowed_lm_costs.py and looped_lm_costs.py read the other families' keys,
harness/costs.py the CNNs')."""

from __future__ import annotations

F32 = 4  # bytes: the configuration stores activations in float32


def kept(spec: dict) -> dict:
    """How many of the kept layers are of each sort: ``kda`` / ``latent`` by
    the mixer, ``dense`` / ``sparse`` by the feed-forward (a kept layer is
    published layer ``layers_held[j]``, 1-based)."""
    held = spec["layers_held"]
    kda = sum(i in spec["linear_attn_config"]["kda_layers"] for i in held)
    dense = sum(i <= spec["first_k_dense_replace"] for i in held)
    return {"kda": kda, "latent": len(held) - kda, "dense": dense,
            "sparse": len(held) - dense}


def kda_rule_forward_flops_per_token(spec: dict) -> float:
    """The recurrence as written, for one token of one layer: per held head
    the decay of S (Dk·Dv), the read Sᵀk, the write's scaling and the
    rank-one write, and the read Sᵀq — 7·Dk·Dv. What a chunked form adds to
    solve for a chunk's writes at once is not the rule's work and is not
    counted."""
    dk = spec["linear_attn_config"]["head_dim"]
    return 7.0 * dk * dk * spec["heads_held"][1]


def kda_rule_bytes_per_token(spec: dict) -> float:
    """q, k, v, g (a decay a key channel) read and o written once, float32,
    one layer; β (one number a head) beside them."""
    dk = spec["linear_attn_config"]["head_dim"]
    heads = spec["heads_held"][1]
    return F32 * (5.0 * heads * dk + heads)


def kda_forward_flops_per_token(spec: dict) -> float:
    """One Kimi Delta Attention layer for one token: the q, k, v
    projections, both low-rank gates, β, the three depthwise convolutions,
    the rule, the output projection."""
    d = spec["hidden_size"]
    dk = spec["linear_attn_config"]["head_dim"]
    heads = spec["heads_held"][1]
    width = heads * dk
    proj = (3 * d * width + 2 * (d * dk + dk * width) + d * heads
            + width * d)
    conv = 3 * spec["linear_attn_config"]["short_conv_kernel_size"] * width
    return 2.0 * proj + 2.0 * conv + kda_rule_forward_flops_per_token(spec)


def latent_forward_flops_per_token(spec: dict, seq_len: int) -> float:
    """One latent-attention layer for one token of a causal row of
    ``seq_len``: q, the latent, its expansion and o, and scores and mixing
    against the (seq_len + 1) / 2 keys a query sees on average, over the
    held heads."""
    d, heads = spec["hidden_size"], spec["heads_held"][1]
    nope, rp, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                    spec["v_head_dim"])
    rank = spec["kv_lora_rank"]
    proj = (d * heads * (nope + rp) + d * (rank + rp)
            + rank * heads * (nope + vd) + heads * vd * d)
    return (2.0 * proj
            + 2.0 * heads * (nope + rp + vd) * (seq_len + 1) / 2)


def forward_flops_per_token(spec: dict, seq_len: int) -> dict:
    """{part: FLOPs} of one token's forward pass through the kept layers
    and the head. ``routed`` counts what THIS chip computes at uniform
    routing: each of a token's top-k lands on a held expert with
    probability held / experts."""
    d, n = spec["hidden_size"], kept(spec)
    width = spec["moe_intermediate_size"]
    return {
        "kda": n["kda"] * kda_forward_flops_per_token(spec),
        "latent_attention": n["latent"]
        * latent_forward_flops_per_token(spec, seq_len),
        "dense": n["dense"] * 6.0 * d * spec["intermediate_size"],
        "router": n["sparse"] * 2.0 * d * spec["num_experts"],
        "shared": n["sparse"] * 6.0 * d * width * spec["num_shared_experts"],
        "routed": n["sparse"] * 6.0 * d * width
        * spec["num_experts_per_token"] * spec["experts_held"][1]
        / spec["num_experts"],
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


def kda_rule_train_flops_per_step(job: dict) -> float:
    spec = job["model_spec"]
    return (3.0 * kept(spec)["kda"] * kda_rule_forward_flops_per_token(spec)
            * _tokens_computed(job))


def kda_rule_train_bytes_per_step(job: dict) -> float:
    """Forward plus backward at three times the forward pass's traffic (the
    backward reads the same tensors and writes their five gradients)."""
    spec = job["model_spec"]
    return (3.0 * kept(spec)["kda"] * kda_rule_bytes_per_token(spec)
            * _tokens_computed(job))
