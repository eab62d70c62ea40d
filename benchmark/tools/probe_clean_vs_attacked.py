"""Chip probe: the attacked job against the clean job of the same seed —
exact recovery is the guarantee. Six steps each through the token route."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import json
    import jax, numpy as np
    from benchmark.harness import manifest, runner, seeded
    from draco_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    m = manifest.load_manifest()
    cell = manifest.cell_of(m, "kanana2.maj_vote_r3")
    config, traffic = manifest.config_of(m, cell), manifest.traffic_of(cell)
    seed = int(sys.argv[1])
    data = runner.make_data(config, seed)
    from benchmark.routes.token import Route
    out = {}
    for name, extra in (("attacked", {}), ("clean", {"adversary_count": 0})):
        fields = dict(config["train_config"], **traffic["train_config"], **extra)
        route = Route(fields, data, jax.devices()[:1])
        weights = seeded.make_weights(route.param_shapes(), config["weights"], seed, route.replicated())
        route.install_weights(weights)
        rows, _, _ = route.run_to(6)
        leaves = [np.asarray(x) for x in jax.tree.leaves(route.params())]
        out[name] = {"losses": [r["loss"] for r in rows], "vote_agree": [r["vote_agree"] for r in rows],
                     "located": [r["located_errors"] for r in rows],
                     "moe": [[r["moe_assignments_held"], r["moe_load_max_over_mean"], r["moe_dropped"]] for r in rows]}
        out[name + "_params"] = leaves
        route.close(); del route, weights
    same = all(np.array_equal(a, b) for a, b in zip(out.pop("attacked_params"), out.pop("clean_params")))
    out["params_bit_equal_after_6_steps"] = bool(same)
    out["loss_gaps"] = [abs(a - c) for a, c in zip(out["attacked"]["losses"], out["clean"]["losses"])]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
