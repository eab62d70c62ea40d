"""Chip probe: routing is discrete. How many (token, layer) top-k sets differ
between the float32 reference at ``highest`` and the same reference at the
configuration's stated product precision (its twin), on seeded weights and
rows; and how many under the fp8 control."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import json
    import jax, jax.numpy as jnp, numpy as np
    from benchmark.harness import manifest, runner, seeded
    from benchmark.reference.nets import latent_moe as net
    from benchmark.reference.nets.common import operands
    from draco_tpu.models.latent_moe import LatentMoeLM

    m = manifest.load_manifest()
    cell = manifest.cell_of(m, "kanana2.maj_vote_r3")
    config = manifest.config_of(m, cell)
    spec = config["train_config"]["model_spec"]


    def _bf16_products(t):
        """Round a product's operand through bfloat16, keep float32 storage:
        what the TPU's default precision does to a float32 product (used where
        the backend has no such precision: the CPU)."""
        return t + jax.lax.stop_gradient(t.astype(jnp.bfloat16).astype(t.dtype) - t)


    def chosen(params, seq, precision, dtype):
        cast, q = operands(dtype)
        if precision == "bfloat16" and jax.default_backend() != "tpu":
            q = _bf16_products
        with jax.default_matmul_precision(precision):
            x = cast(params["embed"]["embedding"][seq])
            out = []
            for i in range(spec["layers"]):
                p = params[f"layer{i}"]
                if i < spec["first_k_dense_replace"]:
                    x = net.layer(x, p, spec, q, True)
                    continue
                eps = spec["rms_norm_eps"]
                x = x + net.attention(net.rms(x, p["attn_norm"]["scale"], eps), p, spec, q)
                h = net.rms(x, p["mlp_norm"]["scale"], eps)
                out.append(jnp.sort(net.route(h, p, spec)[0], axis=-1))
                x = x + net.experts(h, p, spec, q)
            return jnp.stack(out)


    res = []
    for seed in [int(s) for s in sys.argv[1].split(",")]:
        data = runner.make_data(config, seed)
        shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, "float32"), LatentMoeLM(spec).param_shapes(),
                              is_leaf=lambda x: isinstance(x, tuple))
        weights = seeded.make_weights(shapes, config["weights"], seed)
        seq = jnp.asarray(data[0])
        ref = jax.jit(lambda p, s: chosen(p, s, "highest", "float32"))(weights, seq)
        twin = jax.jit(lambda p, s: chosen(p, s, "bfloat16", "float32"))(weights, seq)
        fp8 = jax.jit(lambda p, s: chosen(p, s, "highest", "float8_e4m3fn"))(weights, seq)
        first, held = spec["experts_held"]
        def held_sets(c):  # a set's part that lands on the experts held here
            return jnp.where((c >= first) & (c < first + held), c, -1)
        row = {"seed": seed, "pairs": int(ref.shape[0] * ref.shape[1]),
               "twin_sets_differ": int(jnp.sum(jnp.any(ref != twin, axis=-1))),
               "twin_held_part_differs": int(jnp.sum(jnp.any(held_sets(ref) != held_sets(twin), axis=-1))),
               "fp8_sets_differ": int(jnp.sum(jnp.any(ref != fp8, axis=-1))),
               "per_layer_twin": [int(x) for x in jnp.sum(jnp.any(ref != twin, axis=-1), axis=-1)]}
        print(json.dumps(row), flush=True)
        res.append(row)
        del weights
    json.dump(res, open("chiprun_out/route_flips.json", "w"), indent=1)


if __name__ == "__main__":
    main()
