"""Operations and bytes a decoder of sliding-window and full grouped-query
attention layers with routed experts (no shared expert) needs, from the
configuration's mapping (``model_spec``: the ``mellum`` family's published
config keys plus ``layers``, ``experts_held``, ``vocab_rows``). Two per
multiply-add; norms, activations, softmax and rotary are not counted. Used
for the derived utilization in PERF.md and the roofline of the windowed
attention kernel (harness/lm_costs.py and harness/hybrid_lm_costs.py read
the other two families' keys, harness/costs.py the CNNs')."""

from __future__ import annotations

F32 = 4  # bytes: the configuration stores activations in float32


def _kinds(spec: dict) -> tuple:
    """(sliding-window layers, full-attention layers) among those kept."""
    kinds = spec["layer_types"][:spec["layers"]]
    sliding = kinds.count("sliding_attention")
    return sliding, len(kinds) - sliding


def window_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of one row inside causality AND the window: query
    t sees min(t + 1, window) keys."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def pair_flops(spec: dict) -> float:
    """One (query, key) pair over all query heads: the score's dot product
    and the value's multiply-add, 2·Dh each."""
    return 4.0 * spec["num_attention_heads"] * spec["head_dim"]


def projection_flops_per_token(spec: dict) -> float:
    """q, k, v and o of one attention layer for one token."""
    d, dh = spec["hidden_size"], spec["head_dim"]
    h, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    return 2.0 * (2 * d * h * dh + 2 * d * kv * dh)


def forward_flops_per_token(spec: dict, seq_len: int) -> dict:
    """{part: FLOPs} of one token's forward pass through the kept layers
    and the head, a token of a row of ``seq_len``. ``routed`` counts what
    THIS chip computes on average: each token's top-k lands on a held
    expert with probability held / experts. There is no shared expert."""
    d, layers = spec["hidden_size"], spec["layers"]
    sliding, full = _kinds(spec)
    held = spec["experts_held"][1]
    return {
        "projections": layers * projection_flops_per_token(spec),
        "full_attention": full * pair_flops(spec) * (seq_len + 1) / 2,
        "window_attention": sliding * pair_flops(spec) * window_pairs(
            seq_len, spec["sliding_window"]) / seq_len,
        "router": layers * 2.0 * d * spec["num_experts"],
        "routed": layers * 6.0 * d * spec["moe_intermediate_size"]
        * spec["num_experts_per_tok"] * held / spec["num_experts"],
        "head": 2.0 * d * spec["vocab_rows"],
    }


def _rows_computed(job: dict) -> int:
    """Row-gradients a step: every lane really computes its rows."""
    return job["n"] * job["batch"]


def train_flops_per_step(job: dict) -> float:
    """Forward plus backward (three times the forward pass) of every
    token-gradient a step computes; rematerialised work is not counted."""
    per_token = sum(forward_flops_per_token(job["model_spec"],
                                            job["seq_len"]).values())
    return 3.0 * per_token * _rows_computed(job) * job["seq_len"]


def window_train_flops_per_step(job: dict) -> float:
    """The windowed attention's own work: the pairs inside causality and
    window, forward plus backward (3 x), every sliding layer, every lane —
    whatever implements it; pairs a kernel computes and masks, rematerialised
    work and the copies of k and v to the query heads' count not counted."""
    spec = job["model_spec"]
    pairs = window_pairs(job["seq_len"], spec["sliding_window"])
    return (3.0 * _kinds(spec)[0] * pair_flops(spec) * pairs
            * _rows_computed(job))


def window_train_bytes_per_step(job: dict) -> float:
    """q and o (all query heads), k and v (the key/value heads) read or
    written once in float32, forward plus backward at three times the
    forward pass's traffic (the backward reads the same four and do, and
    writes three gradients)."""
    spec = job["model_spec"]
    dh = spec["head_dim"]
    per_token = F32 * dh * (2.0 * spec["num_attention_heads"]
                            + 2.0 * spec["num_key_value_heads"])
    return (3.0 * _kinds(spec)[0] * per_token * job["seq_len"]
            * _rows_computed(job))
