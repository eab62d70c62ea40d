"""Negative controls: one deliberately-defective program per lint rule.

A linter that silently stops seeing defects is worse than no linter — the
round-5 d-sized-constant bug shipped precisely because nothing was looking.
Each control here seeds exactly ONE defect of the kind its rule exists to
catch, into an otherwise-clean miniature of the training-step shape
(donated state carry, sharded batch, scalar metrics). The test suite
(tests/test_program_lint.py) and the artifact
(``baselines_out/program_lint.json`` ``negative_controls`` section) assert
that each control trips exactly its rule and every other rule stays green —
the same proving-the-harness-is-live discipline as the mis-tiled
pallas_call in tools/tpu_attn_lowering_check.py.

The controls are self-contained (no model/route imports) so a route
refactor cannot accidentally blunt them.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from draco_tpu.analysis.registry import (
    BuiltProgram,
    LintProgram,
    Manifest,
)


@dataclasses.dataclass(frozen=True)
class Control:
    program: LintProgram
    expected_fail: str  # the one rule this defect must trip


def _mini_mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices())
    return Mesh(devs.reshape(len(devs)), ("w",))


def _mini_state(mesh, d=64):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())
    return (
        jax.device_put(jnp.zeros((d,), jnp.float32), repl),
        jax.device_put(jnp.asarray(1, jnp.int32), repl),
    )


def _mini_batch(mesh, d=64):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    return jax.device_put(jnp.ones((n, d), jnp.float32),
                          NamedSharding(mesh, P("w")))


def _psum_grads(mesh):
    """The honest miniature's gradient fold: an explicit per-device psum
    (ONE all_reduce), the smallest stand-in for a route's collective
    structure."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    return shard_map(lambda x: lax.psum(x, "w"), mesh=mesh,
                     in_specs=P("w", None), out_specs=P(),
                     check_vma=False)


_MINI_COLLECTIVES = {"all_reduce": 1}


def _build_baked_constant() -> BuiltProgram:
    """Defect: a ~2 MB array closed over as a program constant (the round-5
    bug shape, at CI scale)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mesh = _mini_mesh()
    big = jnp.asarray(np.ones(512 * 1024 + 1, np.float32))  # > 1 MB limit

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        w = w - 0.01 * (g + big[: w.shape[0]])
        return (w, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_baked_constant", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES))


def _build_undonated_carry() -> BuiltProgram:
    """Defect: the state carry is NOT donated (donate_argnums dropped)."""
    import jax
    import jax.numpy as jnp

    mesh = _mini_mesh()

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f)  # <- no donate_argnums
    return BuiltProgram("control_undonated_carry", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES))


def _build_f64_upcast() -> BuiltProgram:
    """Defect: an f64 accumulation inside the step (traced under
    jax.enable_x64, the only way f64 can sneak in)."""
    import jax
    import jax.numpy as jnp

    mesh = _mini_mesh()

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        g = g.astype(jnp.float64).cumsum().astype(jnp.float32)  # the upcast
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_f64_upcast", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES),
                        trace_ctx=lambda: jax.enable_x64(True))


def _build_extra_all_gather() -> BuiltProgram:
    """Defect: a gratuitous all_gather next to the budgeted psum (the
    accidental-reshard shape the collective budget exists for)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mini_mesh()

    def fold(x):
        g = lax.psum(x, "w")
        extra = lax.all_gather(jnp.sum(x, axis=-1), "w")  # <- unbudgeted
        return g + jnp.sum(extra)

    folded = shard_map(fold, mesh=mesh, in_specs=P("w", None), out_specs=P(),
                       check_vma=False)

    def f(state, x):
        w, step = state
        g = folded(x).sum(0)
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_extra_all_gather", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES))


def _build_host_outfeed_in_scan() -> BuiltProgram:
    """Defect: a host hop inside the scanned body — the round-trip that
    re-serializes every chunk on the dispatch link. (An ordered
    ``io_callback``: jax 0.9 has no ``lax.outfeed`` left to write one
    with; the control keeps the name the committed artifacts know.)"""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import io_callback

    mesh = _mini_mesh()

    def f(state, xs):
        def body(st, x):
            w, step = st
            g = _psum_grads(mesh)(x).sum(0)
            # the host hop per scanned step
            io_callback(lambda v: None, None, jnp.sum(g), ordered=True)
            return (w - 0.01 * g, step + 1), jnp.sum(w)

        return lax.scan(body, state, xs)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    xs = jnp.stack([_mini_batch(mesh)] * 2)
    return BuiltProgram("control_host_outfeed_in_scan", fn,
                        (_mini_state(mesh), xs), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES))


def _build_wide_narrow_wire() -> BuiltProgram:
    """Defect (ISSUE 15): the manifest DECLARES a bf16 narrow wire
    (``required_dtypes={"bf16"}``) but the program never materializes a
    bf16 tensor — the silently-f32 "narrow" program shape: a dropped or
    dead-code-eliminated quantize ships the wide wire under a narrow
    name, which only the required-dtypes half of the dtype rule can
    see (all element types are individually allowed)."""
    import jax
    import jax.numpy as jnp

    mesh = _mini_mesh()

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)  # all-f32: the quantize is "gone"
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    from draco_tpu.analysis.registry import BF16_DTYPES

    return BuiltProgram("control_wide_narrow_wire", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 allowed_dtypes=BF16_DTYPES,
                                 required_dtypes=frozenset({"bf16"})))


def _build_memory_hog() -> BuiltProgram:
    """Defect: a working set far beyond the manifest's declared peak-memory
    budget — a runtime (1024, 1024) matrix product whose operands and
    result must materialize (~8 MB of temps against a 4 MB budget). The
    matrix derives from the batch, so neither constant folding nor the
    serialized module absorbs it: the bytes exist only as run-time buffers,
    exactly the class of regression (dropped donation, lost remat, stray
    materialized temp) the memory_budget rule exists to see."""
    import jax
    import jax.numpy as jnp

    mesh = _mini_mesh()

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        t = jnp.sin(x.sum()
                    + jnp.arange(1024 * 1024, dtype=jnp.float32)
                    ).reshape(1024, 1024)
        waste = (t @ t.T).sum()  # forces the big temps to materialize
        w = w - 0.01 * (g + waste * 1e-20)
        return (w, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_memory_hog", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 max_peak_bytes=4 << 20))


# arg naming + per-axis budget shared by the sharding-auditor controls
# (rules 7-9); the partition table is built lazily (module stays jax-free)
_MINI_ARGS = ("state", "batch")
_MINI_AXES = {"w": {"all_reduce": 1}}


def _mini_rules():
    """The honest miniature's partition table: replicated carry, batch
    rows over w."""
    from jax.sharding import PartitionSpec as P

    return (("^state/", P()), ("^batch$", P("w")))


def _build_resharded_carry() -> BuiltProgram:
    """Defect (the PR 6 bug shape, statically): the donated carry enters
    replicated but the step's output pins it to ``P('w')`` — compiled
    input sharding != output sharding, so the SECOND dispatch of the real
    training loop reshards (and retraces) the carry every step. Only the
    carry half of sharding_contract can see it: counts, dtypes, donation
    and memory are all unchanged."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mini_mesh()
    shard_w = NamedSharding(mesh, P("w"))

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        w = jax.lax.with_sharding_constraint(w - 0.01 * g, shard_w)
        return (w, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_resharded_carry", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 collective_axes=_MINI_AXES),
                        partition_rules=_mini_rules(), arg_names=_MINI_ARGS)


def _build_unnormalized_spec() -> BuiltProgram:
    """Defect (PR 6's other half): the partition table declares the batch
    as ``P('w', None)`` — NOT a ``norm_spec`` fixed-point. XLA reports
    shardings normalized, so any spec comparison or jit-boundary pin made
    with the trailing-None form compares unequal and silently reshards/
    retraces. The program itself is clean; only the table is wrong."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _mini_mesh()

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_unnormalized_spec", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 collective_axes=_MINI_AXES),
                        partition_rules=(("^state/", P()),
                                         ("^batch$", P("w", None))),
                        arg_names=_MINI_ARGS)


def _build_unmatched_param() -> BuiltProgram:
    """Defect: the partition table has no rule for the batch operand — an
    array leaf whose sharding nobody declared. Coverage holes are how new
    buffers (a fresh optimizer slot, a new wire tensor) silently pick up
    compiler-chosen layouts; the table subcheck makes the hole itself the
    failure."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _mini_mesh()

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_unmatched_param", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 collective_axes=_MINI_AXES),
                        partition_rules=(("^state/", P()),),
                        arg_names=_MINI_ARGS)


def _build_wrong_axis_psum() -> BuiltProgram:
    """Defect: on a 2-D (w, tp) mesh the gradient psum reduces over ``tp``
    instead of ``w`` — the COUNT budget (rule 4) still sees exactly one
    all_reduce, but the reduction spans the wrong device groups (summing a
    worker's tensor-parallel replicas instead of folding workers). Only
    the per-axis classification (rule 8) can see it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    devs = np.asarray(jax.devices())
    mesh = Mesh(devs.reshape(len(devs) // 2, 2), ("w", "tp"))

    fold = shard_map(lambda x: lax.psum(x, "tp"),  # <- should be "w"
                     mesh=mesh, in_specs=P("w", None),
                     out_specs=P("w", None), check_vma=False)

    def f(state, x):
        w, step = state
        g = fold(x).sum(0)
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_wrong_axis_psum", fn,
                        (_mini_state(mesh), _mini_batch(mesh)), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 collective_axes=_MINI_AXES),
                        partition_rules=_mini_rules(), arg_names=_MINI_ARGS)


def _build_replicated_wire() -> BuiltProgram:
    """Defect (the PR 7 neighborhood): the table declares the batch wire
    sharded over ``w`` but the program commits it fully replicated — every
    device holds all n workers' rows, the silent O(n*d) memory/bandwidth
    regression. The shard_map boundary reshards internally so the psum
    (and every count/dtype/donation invariant) is unchanged; only
    replication_leaks compares the compiled input against the table."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mini_mesh()
    n = mesh.devices.size
    batch = jax.device_put(jnp.ones((n, 64), jnp.float32),
                           NamedSharding(mesh, P()))  # <- replicated wire

    def f(state, x):
        w, step = state
        g = _psum_grads(mesh)(x).sum(0)
        return (w - 0.01 * g, step + 1), jnp.sum(w)

    with mesh:
        fn = jax.jit(f, donate_argnums=(0,))
    return BuiltProgram("control_replicated_wire", fn,
                        (_mini_state(mesh), batch), mesh,
                        Manifest(collectives=_MINI_COLLECTIVES,
                                 collective_axes=_MINI_AXES),
                        partition_rules=_mini_rules(), arg_names=_MINI_ARGS)


def control_programs() -> Tuple[Control, ...]:
    mk = lambda name, build: LintProgram(  # noqa: E731
        name=name, build=build, route="controls")
    return (
        Control(mk("control_baked_constant", _build_baked_constant),
                "constant_bloat"),
        Control(mk("control_undonated_carry", _build_undonated_carry),
                "donation"),
        Control(mk("control_f64_upcast", _build_f64_upcast), "dtype"),
        Control(mk("control_wide_narrow_wire", _build_wide_narrow_wire),
                "dtype"),
        Control(mk("control_extra_all_gather", _build_extra_all_gather),
                "collectives"),
        Control(mk("control_host_outfeed_in_scan",
                   _build_host_outfeed_in_scan), "host_traffic"),
        Control(mk("control_memory_hog", _build_memory_hog),
                "memory_budget"),
        # the static sharding auditor's live defects (rules 7-9)
        Control(mk("control_resharded_carry", _build_resharded_carry),
                "sharding_contract"),
        Control(mk("control_unnormalized_spec", _build_unnormalized_spec),
                "sharding_contract"),
        Control(mk("control_unmatched_param", _build_unmatched_param),
                "sharding_contract"),
        Control(mk("control_wrong_axis_psum", _build_wrong_axis_psum),
                "collective_axes"),
        Control(mk("control_replicated_wire", _build_replicated_wire),
                "replication_leaks"),
    )
