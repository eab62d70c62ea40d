"""Single-process sanity/benchmark path (reference: src/single_machine.py +
src/nn_ops/__init__.py NN_Trainer). Equivalent to the distributed trainer
with num_workers=1, approach=baseline, no adversaries — one device, plain SGD.

  python -m draco_tpu.single_machine --network LeNet --dataset MNIST --max-steps 500
"""

from __future__ import annotations

import argparse

from draco_tpu.cli import add_fit_args, config_from_args, maybe_force_cpu_mesh
from draco_tpu.config import TOKEN_NETWORKS


def main(argv=None):
    parser = add_fit_args(argparse.ArgumentParser(description="draco_tpu single machine"))
    args = parser.parse_args(argv)
    args.approach = "baseline"
    args.mode = "normal"
    args.num_workers = 1
    args.worker_fail = 0

    maybe_force_cpu_mesh(args)

    cfg = config_from_args(args)
    if cfg.network in TOKEN_NETWORKS:
        # LM single-machine path: the (w=1, sp=1) token loop — same
        # dispatch the distributed CLI uses, minus the coded axes. The
        # model-parallel knobs span devices this entry point doesn't have:
        # reject them loudly rather than silently running unsharded.
        if (cfg.seq_shards > 1 or cfg.tensor_shards > 1
                or cfg.expert_shards > 1 or cfg.pipeline_shards > 1
                or cfg.pp_microbatches > 0):
            raise SystemExit(
                "single_machine is the one-device path; use "
                "python -m draco_tpu.cli for seq/tensor/expert/pipeline "
                "shards"
            )
        from draco_tpu.parallel import make_mesh_2d
        from draco_tpu.parallel.sp_step import train_sp

        _, last = train_sp(cfg, make_mesh_2d(1, 1))
        return last

    from draco_tpu.training.trainer import Trainer

    trainer = Trainer(cfg)
    return trainer.run()


if __name__ == "__main__":
    main()
