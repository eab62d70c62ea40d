"""The plain token job: each step, ``groups`` rows of ``batch`` sequences,
one gradient of the mean next-token loss a row, their mean, torch-style SGD
with momentum. No mesh, no lanes, no coding, no vote, no kernels — what the
coded step has to reproduce exactly, adversary or not.

``make_job`` / ``follow`` as reference/train.py has them. The seeded weights
and the first gradient may arrive in host memory (a model that fills the
chip leaves no room for the comparison's copies beside the job): ``follow``
puts the weights on the accelerator itself and hands its first gradient
back in host memory."""

from __future__ import annotations

import importlib
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.trees import leaf_norms
from benchmark.reference.train import Followed


class Job(NamedTuple):
    net: str  # module under benchmark/reference/nets
    spec: dict  # the configuration's model mapping
    groups: int  # distinct rows a step
    batch: int  # sequences a row
    lr: float
    momentum: float


def make_job(config: dict, job: dict) -> Job:
    return Job(net=config["reference"]["net"],
               spec=config["train_config"]["model_spec"],
               groups=job["groups"], batch=job["batch"], lr=job["lr"],
               momentum=job["momentum"])


def step_rows(n_sequences: int, step: int, groups: int,
              batch: int) -> np.ndarray:
    """(groups, batch) sequence indices of 1-based ``step``: the stream is
    read in order, ``groups * batch`` sequences a step, wrapping."""
    first = (step - 1) * groups * batch
    return ((first + np.arange(groups * batch)) % n_sequences).reshape(
        groups, batch)


def follow(job: Job, params0, data, steps: int = 3, dtype="float32",
           precision="highest") -> Followed:
    """Run ``steps`` plain steps from ``params0`` over ``data`` ((N, T)
    int32, as benchmark/data/token_stream makes it). ``dtype`` below
    float32, or a ``precision`` below ``highest``, is a lower-precision
    control, never the reference."""
    net = importlib.import_module(f"benchmark.reference.nets.{job.net}")
    device = jax.devices()[0]
    host = jax.devices("cpu")[0]
    params0 = jax.device_put(params0, device)

    @jax.jit
    def row(params, tokens):
        with jax.default_matmul_precision(precision):
            return jax.value_and_grad(net.loss)(params, tokens, job.spec,
                                                dtype)

    @jax.jit
    def update(params, buf, grad):
        buf = jax.tree.map(lambda b, g: job.momentum * b + g, buf, grad)
        return jax.tree.map(lambda p, b: p - job.lr * b, params, buf), buf

    params, buf = params0, None
    losses, first_grad = [], None
    t0 = time.perf_counter()
    for step in range(1, steps + 1):
        idx = step_rows(len(data), step, job.groups, job.batch)
        loss, grad = 0.0, None
        for g in range(job.groups):
            l, gr = row(params, jnp.asarray(data[idx[g]]))
            loss += float(l) / job.groups
            grad = gr if grad is None else jax.tree.map(jnp.add, grad, gr)
        if job.groups > 1:
            grad = jax.tree.map(lambda x: x / job.groups, grad)
        losses.append(loss)
        if step == 1:
            # torch-style momentum: the first buffer is the gradient itself
            first_grad = jax.device_put(jax.tree.leaves(grad), host)
            params, buf = jax.tree.map(lambda p, g: p - job.lr * g, params,
                                       grad), grad
        else:
            params, buf = update(params, buf, grad)
        del grad
        print(f"reference: dtype={dtype} precision={precision} step={step} "
              f"loss={loss:.6f} at {time.perf_counter() - t0:.1f}s",
              flush=True)
    return Followed(losses, leaf_norms(first_grad),
                    leaf_norms(params, params0), first_grad)
