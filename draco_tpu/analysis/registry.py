"""The catalog of chip-bound programs and their manifests.

Every jitted hot-loop program that can ever reach a chip window — the
coded-DP ``train_step``/``train_many`` (training/step.py) and the five LM
token-route drivers including the K-fused ``make_token_train_many`` scans
(parallel/{sp,tp,pp,ep}_step.py) — registers here with CI-sized example
arguments and a :class:`Manifest` of the compiled-program invariants no
output-level unit test can see: constant bytes, donation, dtype discipline,
explicit collective counts, host traffic. ``analysis/rules.py`` checks the
manifests; ``tools/program_lint.py`` drives the whole catalog and writes
``baselines_out/program_lint.json``.

Why a registry instead of per-route bespoke tests: round 5 shipped a
d-sized closed-over constant that wedged a 27-minute chip window, and PR
1/2 re-found donation and placement defects by hand. Each of those
invariants was guarded for exactly ONE program (tests/test_program_size.py
and the three copy-adjacent lowering tools); every other program trusted
review. The registry makes the guard a property of *registration*: a new
route ships with a manifest or it does not lint, and the manifest IS the
reviewable statement of the program's communication structure — which the
CodedReduce / CC-efficient gradient-coding lines (PAPERS.md) treat as the
algorithm itself.

Registration is lazy: each route module exposes ``lint_programs()``
returning :class:`LintProgram` entries whose ``build`` callables construct
the mesh/setup/args only when the linter runs them (imports stay cheap,
and the CPU-host device count is whatever the caller's process set up —
tools/_lowering_common.setup_cpu_host or tests/conftest.py, 8 virtual
devices either way). Chip-scale audit tools register their own
chip-tier entries through the same dataclasses (tools/tpu_lm_lowering_check,
tools/tpu_parallel_lowering_check).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional, Tuple

# Element types an honest draco_tpu program may contain (MLIR spelling).
# f64/complex<f64> are NEVER allowed — rules.rule_dtype hard-fails on them
# regardless of the manifest. i64 shows up as index arithmetic on the
# shard_map/GSPMD routes (iota/gather bookkeeping), not as compute; ui64
# is jax.random's own block counter (since jax 0.9 threefry's random_bits
# counts in a ui64 iota and splits it into its two ui32 words).
DEFAULT_DTYPES = frozenset(
    {"f32", "i1", "i8", "i16", "i32", "i64", "ui8", "ui16", "ui32", "ui64"}
)
BF16_DTYPES = DEFAULT_DTYPES | {"bf16"}

# The explicit collective kinds the budget rule counts (StableHLO op
# names; reduce_scatter is what lax.psum_scatter lowers to). GSPMD-inserted
# collectives (from shardings/with_sharding_constraint) materialize only
# inside the XLA SPMD partitioner, AFTER export — a manifest pins the
# *explicit* ICI structure (shard_map psum/ppermute/a2a rings); routes that
# rely purely on sharding propagation legitimately pin all-zero counts.
COLLECTIVE_KINDS = ("all_reduce", "all_gather", "all_to_all",
                    "collective_permute", "reduce_scatter")


@dataclasses.dataclass(frozen=True)
class Manifest:
    """Per-program invariants the five lint rules enforce.

    ``require_donated``: exact number of input leaves that must carry a
    donation attr in the exported module (``jax.buffer_donor`` /
    ``tf.aliasing_output``). The sentinel ``"state"`` resolves to
    ``len(jax.tree.leaves(args[0]))`` at lint time — the whole state carry.
    ``None`` skips the rule (timing-harness loops that deliberately re-call
    with the same state cannot donate).

    ``collectives``: expected explicit-collective op counts by kind
    (missing kinds default to 0). ``None`` skips the rule.

    ``collective_axes``: the per-axis extension of that budget (rule 8,
    analysis/sharding.py): ``{axis: {kind: count}}`` — every explicit
    collective must classify onto a declared mesh axis with exactly the
    declared count (a tree combine pins one psum per level ON that
    level's axis; wrong-axis psums fail even at an unchanged op count).
    ``{}`` asserts zero explicit collectives on every axis (the
    GSPMD-deferred routes); ``None`` skips the rule.

    ``host_transfer_budget`` is 0 for every registered program: a single
    infeed/outfeed/host-callback inside a scanned body serializes the chunk
    on the host link and defeats the whole scan-chunk design (PERF_HISTORY.md §0).

    ``max_peak_bytes``: cap on the program's peak-memory estimate from
    XLA's ``compiled.memory_analysis()`` (argument + output + temp +
    generated-code bytes, minus donated-alias bytes) — the
    ``memory_budget`` rule. The CI-sized registrations sit far under the
    default 2 GiB cap; the cap exists so the manifest is a reviewable
    memory budget a program cannot silently outgrow (a dropped donation or
    a remat regression shows up here as bytes, not as an OOM three rungs up
    the chip ladder). ``None`` skips the rule. The measured columns
    (memory/cost) are recorded on every row regardless of the cap.
    """

    max_constant_bytes: int = 1 << 20  # per closed-over constant
    max_module_bytes: int = 1 << 20  # whole serialized StableHLO module
    require_donated: Any = "state"  # int | "state" | None
    allowed_dtypes: frozenset = DEFAULT_DTYPES
    bf16_promotion_whitelist: Tuple[str, ...] = ("convert_element_type",)
    # Element types that MUST appear in the exported module (ISSUE 15):
    # a narrow-wire production program declares its wire dtype here
    # ({"bf16"} / {"i8"}), so a "narrow" registration whose module is
    # silently all-f32 (the quantize got dropped, dead-code-eliminated,
    # or the config stopped reaching the step body) trips the dtype rule
    # instead of shipping a wide wire under a narrow name. Empty = no
    # requirement (every pre-ISSUE-15 manifest).
    required_dtypes: frozenset = frozenset()
    collectives: Optional[dict] = None
    collective_axes: Optional[dict] = None  # {axis: {kind: count}}
    host_transfer_budget: int = 0
    max_peak_bytes: Optional[int] = 2 << 30  # memory_budget rule cap


@dataclasses.dataclass
class BuiltProgram:
    """A traceable chip-bound program: the jitted callable, CI-sized example
    args, the mesh to trace under, and the manifest to lint against.

    ``trace_ctx`` wraps trace+export (negative controls use
    ``jax.enable_x64``); ``donate_argnums`` names which args
    the ``"state"`` donation sentinel resolves over (arg 0 by convention).

    ``capture_memory``: compile for the host backend to record the
    memory/cost ledger (rules.rule_memory_budget). Chip-tier audit rows
    opt out where a host compile is pointless or prohibitive — the
    d≈159M lm_big rungs (a CPU backend-compile of the flagship costs
    real minutes; the lowering audit needs only trace+export) and the
    Pallas kernel rows (tpu_custom_call cannot compile for CPU at all);
    the rule then reports ``skipped`` with the reason.

    ``partition_rules``: the program's declared partition table — a tuple
    of ``(path_regex, PartitionSpec)`` rows (parallel/partition.py is the
    single source; routes pass their table). The sharding auditor (rules
    7/9) holds every array arg leaf to it. ``arg_names`` names the
    positional args for the leaf-path vocabulary the regexes match
    (``state/params/...``, ``tokens``); unnamed args fall back to
    ``arg<i>``. ``None`` partition_rules = the table halves of rules 7/9
    report skipped (kernel rows with no mesh).
    """

    name: str
    fn: Any  # jitted callable
    args: tuple
    mesh: Any = None
    manifest: Manifest = dataclasses.field(default_factory=Manifest)
    trace_ctx: Callable = contextlib.nullcontext
    extra: dict = dataclasses.field(default_factory=dict)  # report fields
    capture_memory: bool = True
    partition_rules: Optional[Tuple] = None  # ((regex, PartitionSpec), ...)
    arg_names: Optional[Tuple[str, ...]] = None


@dataclasses.dataclass(frozen=True)
class LintProgram:
    """A registered program: ``build()`` constructs the BuiltProgram lazily.

    ``fast``: part of the ``--fast`` / CI-core subset (small models, a few
    seconds each). The big-d constant-bloat guard program is the deliberate
    exception — meaningful only when d is CI-large, so it builds ~3.3M
    params and stays out of ``--fast``.

    ``export_platforms``: lowering target for jax.export. ``("tpu",)``
    exercises the TPU lowering stack on the CPU host (the lowering-check
    methodology, tools/tpu_attn_lowering_check.py); the big-d program uses
    ``("cpu",)`` — its rule is about serialized bytes, and a cpu lowering
    of a 3.3M-param scan is substantially cheaper.
    """

    name: str
    build: Callable[[], BuiltProgram]
    route: str  # which module registered it (report/filtering)
    fast: bool = True
    export_platforms: Tuple[str, ...] = ("tpu",)


def collect() -> "list[LintProgram]":
    """All registered programs, by importing each route module and asking it
    for ``lint_programs()``. Import order is the route order; names must be
    unique across routes."""
    from draco_tpu.coding import topology
    from draco_tpu.ops import decode_kernels
    from draco_tpu.parallel import ep_step, pp_step, sp_step, tp_step
    from draco_tpu.training import step as cnn_step

    programs: list[LintProgram] = []
    for mod in (cnn_step, sp_step, tp_step, pp_step, ep_step,
                decode_kernels, topology):
        programs.extend(mod.lint_programs())
    names = [p.name for p in programs]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise ValueError(f"duplicate lint program names: {sorted(dupes)}")
    return programs


def get(name: str) -> LintProgram:
    for p in collect():
        if p.name == name:
            return p
    raise KeyError(
        f"no lint program named {name!r}; registered: "
        f"{[p.name for p in collect()]}"
    )


def ci_lm_config(**overrides):
    """The CI-sized TransformerLM config the LM route registrations share
    (one source so the routes cannot drift apart on the baseline shape).
    n=8 logical coded workers (folds onto a 4-wide mesh w axis in equal
    lane blocks on the 8-device CI host), cyclic s=1 shared redundancy."""
    from draco_tpu.config import TrainConfig

    kw = dict(
        network="TransformerLM", dataset="synthetic-text", batch_size=2,
        num_workers=8, approach="cyclic", redundancy="shared", mode="normal",
        worker_fail=1, err_mode="rev_grad", seq_len=64, vocab=64,
        model_dim=64, model_heads=2, model_layers=1, max_steps=2,
        eval_freq=0, train_dir="", log_every=10 ** 9,
    )
    kw.update(overrides)
    return TrainConfig(**kw)


def lm_example_tokens(cfg, k: Optional[int] = None):
    """Example (tokens, adv_mask[s]) for an LM route program — the same
    synthetic stream the production loop feeds (sp_step.synthetic_text),
    stacked to (K, n, B, T) when ``k`` is given."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu import rng as drng
    from draco_tpu.parallel.sp_step import synthetic_text

    adv = drng.adversary_schedule(cfg.seed, (k or 1) + 1, cfg.num_workers,
                                  cfg.num_adversaries)
    if k is None:
        toks = jnp.asarray(synthetic_text(cfg.seed, 1, cfg.num_workers,
                                          cfg.batch_size, cfg.seq_len,
                                          cfg.vocab))
        return toks, jnp.asarray(np.asarray(adv[1]))
    toks = jnp.asarray(np.stack([
        synthetic_text(cfg.seed, s, cfg.num_workers, cfg.batch_size,
                       cfg.seq_len, cfg.vocab)
        for s in range(1, k + 1)
    ]))
    return toks, jnp.asarray(np.asarray(adv[1:k + 1]))


def built_token_program(name, cfg, mesh, setup, manifest, many=False,
                        k=2, partition_rules=None) -> BuiltProgram:
    """Wrap an LM route setup's chip-bound callable as a BuiltProgram:
    either the single ``train_step`` or the K-fused ``train_token_many``
    scan (K = leading dim of the example operands; ``cfg.token_gen ==
    'device'`` feeds the (K,) step-index vector the production chunked loop
    uploads, parallel/token_loop.py). ``partition_rules`` is the route's
    declared partition table (parallel/partition.py); the arg-path
    vocabulary is fixed here: ``state``, ``tokens``, ``adv_mask``,
    ``present``."""
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu import rng as drng

    arg_names = ("state", "tokens", "adv_mask", "present")
    extra = {"dim": setup.dim, "devices_in_mesh": int(mesh.devices.size)}
    if many:
        if cfg.token_gen == "device":
            # the program regenerates tokens in-graph; its whole token
            # input is the (K,) step vector — don't build host batches
            adv = drng.adversary_schedule(cfg.seed, k + 1, cfg.num_workers,
                                          cfg.num_adversaries)
            toks = jnp.arange(1, k + 1, dtype=jnp.int32)
            masks = jnp.asarray(np.asarray(adv[1:k + 1]))
        else:
            toks, masks = lm_example_tokens(cfg, k)
        return BuiltProgram(name, setup.train_token_many,
                            (setup.state, toks, masks, None), mesh,
                            manifest, extra=extra,
                            partition_rules=partition_rules,
                            arg_names=arg_names)
    toks, mask = lm_example_tokens(cfg)
    return BuiltProgram(name, setup.train_step, (setup.state, toks, mask),
                        mesh, manifest, extra=extra,
                        partition_rules=partition_rules,
                        arg_names=arg_names)
