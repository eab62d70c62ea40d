"""The plain image job: n batch rows, one gradient each, their mean,
torch-style SGD with momentum. No mesh, no coding, no kernels — what the
coded step has to reproduce exactly, adversary or not.

A configuration's ``reference.module`` names a file of this directory; each
such module gives ``make_job(config, job)`` and ``follow(job, weights, data,
steps, precision=...)`` returning a :class:`Followed`."""

from __future__ import annotations

import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.harness.trees import leaf_norms
from benchmark.reference import feed


class Job(NamedTuple):
    net: str  # module under benchmark/reference/nets
    policy: str  # feed.step_indices policy
    n: int
    batch: int
    seed: int  # the job's schedule seed (not the run's --seed)
    lr: float
    momentum: float
    augment: bool


class Followed(NamedTuple):
    losses: list  # per step
    grad_norms: list  # per leaf, first step's mean gradient
    delta_norms: list  # per leaf, parameters' change after all steps
    grad: list  # the first step's mean gradient itself, leaves on device


def make_job(config: dict, job: dict) -> Job:
    """The plain job of a run: the net from the configuration's
    ``reference`` block, the rest from what the route says it ran."""
    return Job(net=config["reference"]["net"], policy=job["policy"],
               n=job["n"], batch=job["batch"], seed=job["seed"],
               lr=job["lr"], momentum=job["momentum"],
               augment=job["augment"])


def follow(job: Job, params0, data, steps: int = 3, dtype="float32",
           precision="highest") -> Followed:
    """Run ``steps`` plain steps from ``params0`` over ``data`` (train_x,
    train_y, as benchmark/data/image_blobs makes them). ``dtype`` below
    float32, or a ``precision`` below ``highest``, is a lower-precision
    control, never the reference."""
    train_x, train_y = data
    net = importlib.import_module(f"benchmark.reference.nets.{job.net}")

    @jax.jit
    def row(params, acc, x, y, aug_key, drop_key):
        """One batch row's loss and gradient, added to ``acc``."""
        with jax.default_matmul_precision(precision):
            if job.augment:
                x = feed.augment(x, aug_key)
            loss, g = jax.value_and_grad(net.loss)(params, x, y, drop_key,
                                                   dtype)
        return acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)

    @jax.jit
    def update(params, buf, grad_sum, first):
        g = jax.tree.map(lambda s: s / job.n, grad_sum)
        buf = jax.tree.map(
            lambda b, gi: jnp.where(first, gi, job.momentum * b + gi), buf, g)
        return jax.tree.map(lambda p, b: p - job.lr * b, params, buf), buf, g

    params = params0
    buf = jax.tree.map(jnp.zeros_like, params0)
    losses, first_grad = [], None
    for step in range(1, steps + 1):
        idx = feed.step_indices(job.policy, len(train_x), step, job.n,
                                job.batch, job.seed)
        acc = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, buf))
        for k in range(job.n):
            ka, kd = feed.row_keys(job.seed, step, k)
            acc = row(params, acc, jnp.asarray(train_x[idx[k]]),
                      jnp.asarray(train_y[idx[k]]), ka, kd)
        losses.append(float(acc[0]) / job.n)
        params, buf, g = update(params, buf, acc[1], step == 1)
        if step == 1:
            first_grad = jax.tree.leaves(g)
    return Followed(losses, leaf_norms(first_grad),
                    leaf_norms(params, params0), first_grad)
