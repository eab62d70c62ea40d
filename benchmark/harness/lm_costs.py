"""Operations a latent-attention / routed-expert decoder needs, from the
configuration's mapping (``model_spec``: the published config keys plus
``layers``, ``experts_held``, ``vocab_rows``). Two per multiply-add;
norms, activations, softmax and rotary products are not counted. Used for
the derived utilization in PERF.md and the roofline-type per-layer metrics
of the token cells (harness/costs.py has the CNNs')."""

from __future__ import annotations


def attention_forward_flops_per_token(spec: dict, seq_len: int) -> float:
    """One layer's attention for one token of a causal sequence of
    ``seq_len``: the four projections, and scores and mixing against the
    (seq_len + 1) / 2 keys a query sees on average."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    qk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    nope, v, rank = (spec["qk_nope_head_dim"], spec["v_head_dim"],
                     spec["kv_lora_rank"])
    proj = (d * h * qk + d * (rank + spec["qk_rope_head_dim"])
            + rank * h * (nope + v) + h * v * d)
    return 2.0 * proj + 2.0 * h * (qk + v) * (seq_len + 1) / 2


def forward_flops_per_token(spec: dict, seq_len: int) -> dict:
    """{part: FLOPs} of one token's forward pass through the kept layers
    and the head. ``routed`` counts what THIS chip computes on average: each
    token's top-k lands on a held expert with probability held / routed."""
    d, layers = spec["hidden_size"], spec["layers"]
    dense = spec["first_k_dense_replace"]
    moe = layers - dense
    width = spec["moe_intermediate_size"]
    held = spec["experts_held"][1]
    return {
        "attention": layers * attention_forward_flops_per_token(spec,
                                                                seq_len),
        "dense_mlp": dense * 6.0 * d * spec["intermediate_size"],
        "router": moe * 2.0 * d * spec["n_routed_experts"],
        "shared": moe * 6.0 * d * width * spec["n_shared_experts"],
        "routed": moe * 6.0 * d * width * spec["num_experts_per_tok"]
        * held / spec["n_routed_experts"],
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


def attention_train_flops_per_step(job: dict) -> float:
    spec = job["model_spec"]
    return (3.0 * spec["layers"]
            * attention_forward_flops_per_token(spec, job["seq_len"])
            * _tokens_computed(job))
