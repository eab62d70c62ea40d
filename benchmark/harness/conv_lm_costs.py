"""Operations and bytes a decoder of double-gated short convolutions and
grouped-query attention layers, with leading dense SwiGLU layers and routed
experts (no shared expert) after them and a head tied to the embedding,
needs — from the configuration's mapping (``model_spec``: the ``lfm2_moe``
family's published config keys plus ``layers``, ``layers_held``,
``experts_held``, ``vocab_rows``). Two per multiply-add; norms, softmax,
rotary and the activation are not counted, the convolution's gates and taps
are (they are the operator). Used for the derived utilization in PERF.md
and the roofline shares of the convolution operator and of the feed-forward
side (harness/lm_costs.py, hybrid_lm_costs.py, windowed_lm_costs.py and
looped_lm_costs.py read the other families' keys, harness/costs.py the
CNNs')."""

from __future__ import annotations

F32 = 4  # bytes: the configuration stores activations and weights in float32


def kept(spec: dict) -> dict:
    """How many of the kept layers are of each sort: ``conv`` /
    ``attention`` by the mixer, ``dense`` / ``sparse`` by the feed-forward
    (a kept layer is published layer ``layers_held[j]``)."""
    held = spec["layers_held"]
    conv = sum(spec["layer_types"][i] == "conv" for i in held)
    dense = sum(i < spec["num_dense_layers"] for i in held)
    return {"conv": conv, "attention": len(held) - conv, "dense": dense,
            "sparse": len(held) - dense}


def conv_flops_per_token(spec: dict) -> float:
    """One convolution operator for one token: the input projection
    (hidden -> 3 hidden) and the output projection (hidden -> hidden), and
    per channel the two gates (a multiply each) and the taps (a
    multiply-add each)."""
    d = spec["hidden_size"]
    return 2.0 * (3 * d * d + d * d) + d * (2.0 + 2.0 * spec["conv_L_cache"])


def attention_flops_per_token(spec: dict, seq_len: int) -> float:
    """One attention layer for one token of a causal row of ``seq_len``:
    q, k, v and o, and scores and mixing against the (seq_len + 1) / 2 keys
    a query sees on average, over all query heads."""
    d = spec["hidden_size"]
    kv_width = d // spec["num_attention_heads"] * spec["num_key_value_heads"]
    return (2.0 * (2 * d * d + 2 * d * kv_width)
            + 4.0 * d * (seq_len + 1) / 2)


def routed_flops_per_token(spec: dict) -> float:
    """One sparse layer's routed experts for one token AT UNIFORM ROUTING:
    each of its top-k choices lands on a held expert with probability
    held / experts — the same work whatever implements it, sorted pairs or
    every held expert over every token."""
    return (6.0 * spec["hidden_size"] * spec["moe_intermediate_size"]
            * spec["num_experts_per_tok"] * spec["experts_held"][1]
            / spec["num_experts"])


def forward_flops_per_token(spec: dict, seq_len: int) -> dict:
    """{part: FLOPs} of one token's forward pass through the kept layers
    and the tied head, a token of a row of ``seq_len``."""
    d, n = spec["hidden_size"], kept(spec)
    return {
        "conv": n["conv"] * conv_flops_per_token(spec),
        "attention": n["attention"] * attention_flops_per_token(spec,
                                                                seq_len),
        "dense_mlp": n["dense"] * 6.0 * d * spec["intermediate_size"],
        "router": n["sparse"] * 2.0 * d * spec["num_experts"],
        "routed": n["sparse"] * routed_flops_per_token(spec),
        "head": 2.0 * d * spec["vocab_rows"],
    }


def executed_flops_per_token(spec: dict, seq_len: int) -> dict:
    """The same with the routed experts as the program runs them where a
    held expert expects an eighth of the tokens or more: every held expert
    over every token (top_k / experts times as many pairs chosen)."""
    parts = forward_flops_per_token(spec, seq_len)
    parts["routed"] = (kept(spec)["sparse"] * 6.0 * spec["hidden_size"]
                       * spec["moe_intermediate_size"]
                       * spec["experts_held"][1])
    return parts


def _tokens_computed(job: dict) -> int:
    """Token-gradients a step: every lane really computes its rows."""
    return job["n"] * job["batch"] * job["seq_len"]


def train_flops_per_step(job: dict) -> float:
    """Forward plus backward (three times the forward pass) of every
    token-gradient a step computes; rematerialised work and the held
    experts' products for tokens that did not choose them are not
    counted."""
    per_token = sum(forward_flops_per_token(job["model_spec"],
                                            job["seq_len"]).values())
    return 3.0 * per_token * _tokens_computed(job)


def conv_train_flops_per_step(job: dict) -> float:
    """The convolution operators' own work, forward plus backward (3 x),
    every conv layer, every lane."""
    spec = job["model_spec"]
    return (3.0 * kept(spec)["conv"] * conv_flops_per_token(spec)
            * _tokens_computed(job))


def conv_train_bytes_per_step(job: dict) -> float:
    """A token's normed row read, its [B | C | X] and the operator's result
    written, once each in float32: 5 hidden floats a token and layer,
    forward plus backward at three times the forward pass's traffic. The
    matrices (once a lane) and the taps are not counted: a floor."""
    spec = job["model_spec"]
    return (3.0 * kept(spec)["conv"] * F32 * 5.0 * spec["hidden_size"]
            * _tokens_computed(job))


def ffn_train_flops_per_step(job: dict) -> float:
    """The feed-forward side's own work: the dense SwiGLU of the leading
    layers plus the routed pairs at uniform routing, forward plus backward
    (3 x), every lane."""
    parts = forward_flops_per_token(job["model_spec"], job["seq_len"])
    return 3.0 * (parts["dense_mlp"] + parts["routed"]) * _tokens_computed(
        job)


def ffn_train_bytes_per_step(job: dict) -> float:
    """The feed-forward matrices (a dense layer's three, a sparse layer's
    three a held expert) once a lane, and each token's input and output row
    once a layer, in float32; forward plus backward at three times the
    forward pass's traffic."""
    spec = job["model_spec"]
    d, n = spec["hidden_size"], kept(spec)
    matrices = 3.0 * d * (
        n["dense"] * spec["intermediate_size"]
        + n["sparse"] * spec["experts_held"][1]
        * spec["moe_intermediate_size"])
    rows = 2.0 * d * (n["dense"] + n["sparse"])
    return 3.0 * F32 * (matrices * job["n"] + rows * _tokens_computed(job))
