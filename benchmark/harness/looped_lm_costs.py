"""Operations and bytes a looped dense decoder needs — one stack of layers
run ``total_ut_steps`` times over the same weights, a whole head at every
exit — from the configuration's mapping (``model_spec``: the ``ouro``
family's published config keys plus ``layers`` and ``vocab_rows``). Two per
multiply-add; norms, activations, softmax, rotary, the exit gate's
distribution and the objective are not counted. Every layer APPLICATION
counts (layers x passes) and every exit's head (passes); what the backward
pass recomputes does not. Used for the derived utilization in PERF.md and
the rooflines of the looped attention and of the exits' head
(harness/lm_costs.py, hybrid_lm_costs.py and windowed_lm_costs.py read the
other three families' keys, harness/costs.py the CNNs')."""

from __future__ import annotations

F32 = 4  # bytes: the configuration stores weights and activations in float32


def applications(spec: dict) -> int:
    """Layer applications a token passes: the kept layers, every pass."""
    return spec["layers"] * spec["total_ut_steps"]


def _width(spec: dict) -> int:
    return spec["num_attention_heads"] * spec["head_dim"]


def attention_forward_flops_per_token(spec: dict, seq_len: int) -> float:
    """One attention application for one token of a causal row of
    ``seq_len``: q, k, v and o, and the score's dot product and the value's
    multiply-add (2·Dh each, all heads) against the (seq_len + 1) / 2 keys a
    query sees on average."""
    return (2.0 * 4 * spec["hidden_size"] * _width(spec)
            + 4.0 * _width(spec) * (seq_len + 1) / 2)


def forward_flops_per_token(spec: dict, seq_len: int) -> dict:
    """{part: FLOPs} of one token's forward pass: every layer application,
    every exit's head and gate."""
    d, passes = spec["hidden_size"], spec["total_ut_steps"]
    return {
        "attention": applications(spec) * attention_forward_flops_per_token(
            spec, seq_len),
        "mlp": applications(spec) * 6.0 * d * spec["intermediate_size"],
        "head": passes * 2.0 * d * spec["vocab_rows"],
        "gate": passes * 2.0 * d,
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
    return (3.0 * applications(spec)
            * attention_forward_flops_per_token(spec, job["seq_len"])
            * _tokens_computed(job))


def attention_train_bytes_per_step(job: dict) -> float:
    """An attention application reads its four matrices once and, a token,
    reads x and writes q, k, v, the mixed heads and the result once, in
    float32; forward plus backward at three times the forward pass's
    traffic (the backward reads the same and writes as many gradients)."""
    spec = job["model_spec"]
    d, width = spec["hidden_size"], _width(spec)
    lanes = job["n"] * job["batch"]
    weights = 4.0 * d * width * lanes
    activations = (2.0 * d + 4.0 * width) * _tokens_computed(job)
    return 3.0 * F32 * applications(spec) * (weights + activations)


def head_train_flops_per_step(job: dict) -> float:
    """Every exit's logits over the whole vocabulary held, forward plus
    backward (3 x), every lane."""
    spec = job["model_spec"]
    return (3.0 * spec["total_ut_steps"] * 2.0 * spec["hidden_size"]
            * spec["vocab_rows"] * _tokens_computed(job))


def head_train_bytes_per_step(job: dict) -> float:
    """An exit's head reads its (hidden, V) matrix once a lane and each
    token's state once, in float32 (the logits need never be stored);
    forward plus backward at three times that."""
    spec = job["model_spec"]
    d = spec["hidden_size"]
    lanes = job["n"] * job["batch"]
    return (3.0 * F32 * spec["total_ut_steps"]
            * (d * spec["vocab_rows"] * lanes + d * _tokens_computed(job)))
