"""Chip probe: the routed experts' grouped product at the cell's shapes,
megablox gmm against lax.ragged_dot, forward + backward, ms a call."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import json
    import time
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    M, K, N, G, HELD = 24576, 2048, 768, 128, 8
    key = jax.random.key(0)
    xs = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (HELD, K, N), jnp.float32) * 0.02
    sizes = jnp.full((G,), M // G, jnp.int32)  # 192 rows a group, uniform

    def mega(xs, w):
        y = megablox.gmm(xs.astype(jnp.bfloat16), w.astype(jnp.bfloat16), sizes, jnp.float32,
                         (256, 1024, 768), jnp.zeros((), jnp.int32))
        return y

    def ragged(xs, w):
        return lax.ragged_dot(xs, w, sizes[:HELD])

    def ragged_bf16(xs, w):
        return lax.ragged_dot(xs.astype(jnp.bfloat16), w.astype(jnp.bfloat16), sizes[:HELD],
                              preferred_element_type=jnp.float32)

    def dense_held(xs, w):
        # only the held rows exist: what a capacity-free dense product would cost
        return jnp.einsum("gmk,gkn->gmn", xs[:HELD * (M // G)].reshape(HELD, M // G, K), w)

    out = {}
    for name, fn in (("megablox", mega), ("ragged_dot", ragged), ("ragged_dot_bf16", ragged_bf16), ("dense_held_rows", dense_held)):
        try:
            f = jax.jit(lambda xs, w, fn=fn: jax.value_and_grad(lambda a, b: jnp.sum(fn(a, b) ** 2), argnums=(0, 1))(xs, w))
            r = f(xs, w); jax.block_until_ready(r)
            ts = []
            for _ in range(10):
                t0 = time.perf_counter(); r = f(xs, w); jax.block_until_ready(r); ts.append(time.perf_counter() - t0)
            out[name] = {"ms_p50": 1e3 * float(np.median(ts)), "loss": float(r[0])}
        except Exception as e:
            out[name] = {"error": repr(e)[:300]}
        print(name, out[name], flush=True)
    if "megablox" in out and "ragged_dot_bf16" in out and "loss" in out["megablox"] and "loss" in out["ragged_dot_bf16"]:
        print("agree:", out["megablox"]["loss"], out["ragged_dot_bf16"]["loss"])
    json.dump(out, open("chiprun_out/grouped_probe.json", "w"), indent=1)


if __name__ == "__main__":
    main()
