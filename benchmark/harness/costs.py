"""Operations and bytes the algorithm needs, from shapes. Used for the
derived utilization in PERF.md and for roofline-type per-layer metrics."""

from __future__ import annotations

WIRE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def forward_flops(layers) -> int:
    """FLOPs of one example's forward pass through a layer table, two per
    multiply-add; normalisation, activations and pools are not counted.
    Rows: ["conv", out_h, out_w, k, c_in, c_out] | ["dense", d_in, d_out]."""
    total = 0
    for row in layers:
        if row[0] == "conv":
            _, oh, ow, k, cin, cout = row
            total += 2 * oh * ow * k * k * cin * cout
        elif row[0] == "dense":
            _, din, dout = row
            total += 2 * din * dout
        else:
            raise ValueError(f"unknown layer row {row!r}")
    return total


def train_flops_per_example(layers) -> int:
    """Forward plus backward (gradients to inputs and to weights): three
    times the forward pass. Redundant recomputation is not model work."""
    return 3 * forward_flops(layers)


def param_count(layers, conv_bias: bool, norm_after_conv: bool) -> int:
    d = 0
    for row in layers:
        if row[0] == "conv":
            _, _, _, k, cin, cout = row
            d += k * k * cin * cout + (cout if conv_bias else 0)
            d += 2 * cout if norm_after_conv else 0
        else:
            _, din, dout = row
            d += din * dout + dout
    return d


def cyclic_decode_min_bytes(n: int, d: int, wire: str = "f32") -> int:
    """Least HBM traffic of one cyclic decode: the encoded stack (real and
    imaginary rows, (n, d) each) read once in its wire type, the decoded
    d-vector written once in float32."""
    return 2 * n * d * WIRE_BYTES[wire] + 4 * d
