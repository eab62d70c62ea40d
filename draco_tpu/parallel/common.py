"""The one tail of every training step — the CNN path (training/step.py) and
the LM routes (sp / tp / ep / pp): fault and attack injection → encode →
wire → coded decode, vote or robust aggregation → health → optimizer
update → guard.

One implementation so a fix to injection, decode, the wire, or the update
convention cannot diverge between the paths: a step builder computes its
rows (which lanes run is the only per-approach, per-route part), hands
the (n, [r,] d) stack to ``aggregate_flat_grads`` and the result to
``finish_flat_step``. What a caller needs that another does not is an
argument (``constrain``, ``carry``) or a ``health`` key, never a branch on
who is calling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from draco_tpu import aggregation, attacks, rng as drng
from draco_tpu.coding import approx as approx_mod
from draco_tpu.coding import cyclic as cyclic_mod
from draco_tpu.coding import repetition as rep_mod
from draco_tpu.coding import topology as topology_mod
from draco_tpu.obs import forensics as forensics_mod
from draco_tpu.obs import numerics as numerics_mod
from draco_tpu.ops.decode_kernels import resolve_decode_impl
from draco_tpu.resilience import faults as faults_mod
from draco_tpu.resilience.guards import GUARD_METRIC_NAMES, guard_update


def build_code_from_cfg(cfg):
    """The route-shared code constructor: CyclicCode for approach="cyclic",
    ApproxCode for "approx", None otherwise — one place so the CNN path and
    every LM route build the identical code from a config. Under
    ``topology == "tree"`` (ISSUE 17) the constructor returns a TreeCode
    wrapping ONE small group code at the (fanout, s_g) shape — the
    aggregation tails below dispatch on the code type, so every route gets
    the hierarchical path through the same seam."""
    if cfg.approach in ("cyclic", "approx") and cfg.topology == "tree":
        return topology_mod.build_tree_code(cfg)
    if cfg.approach == "cyclic":
        return cyclic_mod.build_cyclic_code(cfg.num_workers, cfg.worker_fail)
    if cfg.approach == "approx":
        return approx_mod.build_approx_code(
            cfg.num_workers, cfg.code_redundancy, cfg.assignment_scheme)
    return None


def _is_tree(code) -> bool:
    """Code-type dispatch for the aggregation tails."""
    return isinstance(code, topology_mod.TreeCode)


def segment_decode_bounds(cfg, dim: int, leaf_offsets=None):
    """The decode partition the streaming segmented wire induces (ISSUE
    16): the quantum-aligned segment cuts (obs/numerics.cfg_segment_bounds
    — THE bounds source the ledger and tools share), refined by the static
    leaf boundaries when the decode runs at layer granularity so every
    parameter tensor keeps its own locator."""
    bounds = list(numerics_mod.cfg_segment_bounds(cfg, dim))
    if leaf_offsets is not None:
        cuts = sorted({int(o) for o in leaf_offsets}
                      | {int(b) for b in bounds})
        bounds = [c for c in cuts if 0 <= c <= dim]
    return bounds


def _stash_watch(cfg, health, grads, wire_parts, agg, shadow):
    """The numerics observatory (obs/numerics.py, ISSUE 10) of one step:
    dynamic-range columns of the three stages and — ``shadow()`` — the
    shadow-quantized decode's, stashed under ``health["watch"]`` for
    ``decode_health_metrics`` to merge into the metric row. The f32 decode
    alone feeds the update; no added ops when the watch is off."""
    if not numerics_mod.watch_enabled(cfg):
        return
    with jax.named_scope("draco_health"):
        watch = {}
        if cfg.numerics_watch == "on":
            watch.update(numerics_mod.numerics_columns(
                cfg, [grads], wire_parts, agg))
        if cfg.shadow_wire != "off":
            watch.update(shadow())
        health["watch"] = watch


def approx_aggregate(code, grads: jnp.ndarray, cfg, adv_mask, present=None,
                     constrain=None, step=None, mesh=None):
    """The approx family's branch of ``aggregate_flat_grads`` — ingest
    forensics → weighted-partial-sum encode → present mask → wire →
    optimal-decoding partial recovery → residual-vs-bound health.

    No adversary injection: config.validate rejects live adversaries under
    this family (no Byzantine certificate); stragglers are the fault model
    and the only per-worker accusation signal is the non-finite ingest
    check."""
    decode_impl = resolve_decode_impl(cfg.decode_impl, mesh)
    tree = _is_tree(code)
    with jax.named_scope("draco_health"):
        bad_rows = forensics_mod.nonfinite_rows(grads)
    with jax.named_scope("draco_encode"):
        if tree:
            rows = topology_mod.encode_tree(code, grads)
        else:
            rows = approx_mod.encode_shared(code, grads)
        if present is not None:
            rows = jnp.where(jnp.asarray(present).astype(bool)[:, None],
                             rows, jnp.zeros_like(rows))
        # the REAL narrow wire (ISSUE 15): quantize the partial-sum rows
        # into narrow buffers — THE arrays that cross the sharding
        # boundary — and widen to f32 only for the decode; identity (no
        # ops) on the f32 wire
        rows, wire = numerics_mod.narrow_wire_single(
            cfg, rows, step=step, constrain=constrain)
        if wire is None and constrain is not None:
            rows = constrain(rows)
    dim = int(rows.shape[-1])
    with jax.named_scope("draco_decode"):
        if tree:
            # hierarchical tree aggregation (ISSUE 17): per-group optimal
            # decoding at the (g, d) block, level-structured combine, root
            # residual + Cauchy-Schwarz-folded bound (decode_tree_approx)
            bounds = (numerics_mod.cfg_segment_bounds(cfg, dim)
                      if cfg.wire_segments > 1 else None)
            agg, _v, health = topology_mod.decode_tree_approx(
                code, rows, present=present, batch_grads=grads,
                impl=decode_impl, wire=wire, bounds=bounds)
        elif cfg.wire_segments > 1:
            # streaming segmented wire (ISSUE 16): the presence-only
            # weight solve runs once; each segment combines on arrival and
            # the residual accumulators fold to one per-step verdict
            agg, _v, health = approx_mod.decode_segments(
                code, rows, numerics_mod.cfg_segment_bounds(cfg, dim),
                present=present, with_health=True, batch_grads=grads,
                impl=decode_impl, wire=wire)
        else:
            agg, _v, health = approx_mod.decode(
                code, rows, present=present, with_health=True,
                batch_grads=grads, impl=decode_impl, wire=wire)
    health["bad_rows"] = bad_rows
    _stash_watch(cfg, health, grads, [rows], agg,
                 lambda: numerics_mod.approx_shadow(
                     cfg, code, rows, grads, agg, present, adv_mask, step))
    return agg, health


def _inject_rows(grads, adv_mask, cfg, step):
    """``attacks.inject_plain`` on the raw rows of the (n, ...) stack,
    stored: what the robust rules aggregate, and what the vote reads where
    it cannot apply the attack as it reads (:func:`_vote_row_map`). A large
    stack (its rows laid out in tiles, sp_step.STACK_LANES) is attacked one
    row at a time through a dynamic-update-slice, which XLA performs in
    place: a second stack does not fit beside the first. Row-local attacks
    only (rev_grad / constant / random). Same values either way: the layout
    decides, and only the vote's large stack has it
    (tests/test_lm_maj_vote.py holds both sides to the same bits)."""
    kw = dict(n_mal=cfg.num_adversaries, step=step, seed=cfg.seed)
    if grads.ndim == 2:
        return attacks.inject_plain(grads, adv_mask, cfg.err_mode,
                                    cfg.adversarial, **kw)
    if cfg.err_mode in ("alie", "ipm"):
        raise ValueError(
            f"err_mode={cfg.err_mode} reads every row at once and is not "
            "implemented for a stack attacked a row at a time")

    def body(i, g):
        # one row and its one mask bit: the (1, 1) mask broadcasts over
        # whatever axes the row is laid out in
        row = jax.lax.dynamic_slice_in_dim(g, i, 1, axis=0)
        bad = attacks.inject_plain(
            row, jax.lax.dynamic_slice_in_dim(adv_mask, i, 1),
            cfg.err_mode, cfg.adversarial, **kw)
        return jax.lax.dynamic_update_slice_in_dim(g, bad, i, axis=0)

    return jax.lax.fori_loop(0, grads.shape[0], body, grads)


def _vote_row_map(cfg, adv_mask):
    """The simulated adversary as ``majority_vote``'s ``row_map`` — applied
    to each block of the stack as the vote's one sweep reads it and to the
    winner's row, never stored — or None where the attacked stack has to
    exist: an attack that is not an elementwise map of its own row (a keyed
    ``random`` row; ``alie`` / ``ipm`` read every row), or a further reader
    of the attacked rows (the narrow wire quantises them, the numerics
    watch and the shadow vote measure them, ``vote_check="exact"`` compares
    them whole). Same bits either way (tests/test_lm_maj_vote.py)."""
    if (cfg.err_mode not in ("rev_grad", "constant")
            or cfg.vote_check != "fingerprint" or cfg.wire_dtype != "f32"
            or numerics_mod.watch_enabled(cfg)):
        return None
    return (lambda rows: attacks.attack_plain(rows, cfg.err_mode,
                                              cfg.adversarial), adv_mask)


def aggregate_flat_grads(grads: jnp.ndarray, adv_mask, cfg, code, rand_factor,
                         present=None, leaf_offsets=None, step=None,
                         mesh=None, constrain=None):
    """The (n, d) stack of per-worker flat gradients → ``(aggregated (d,),
    health)``: the one coded tail of every step builder. Two further
    layouts: (n, hat_s, d), each worker's own redundant lanes
    (``redundancy="simulate"``), and under maj_vote a large stack with its
    rows laid out in tiles, (n, d / 128, 128): sp_step.STACK_LANES — the
    winner then comes back as such a row, (d / 128, 128), which ``unravel``
    (training/step._make_unravel) cuts into leaves where it lies.

    ``step`` (optional traced scalar): the training step, threaded so the
    deterministic fault plan (``cfg.fault_spec``,
    resilience/faults.corrupt_grads) can inject its in-graph NaN/Inf
    worker-gradient faults — identity (no added ops) when no plan is
    configured.

    cyclic: encode, adversarial injection on the encoded rows, the wire,
    exact decode — ``health`` is the in-graph decode-health dict
    (coding/cyclic.decode ``with_health``: scalar ``residual`` ≈ 0 iff the
    decode is self-consistent, (n,) bool ``flagged`` of located-error
    rows), plus ``honest`` (the decode's honest mask, (n,)) and
    ``bad_rows`` (non-finite ingest rows). maj_vote: injection on the raw
    rows, the wire, the vote — in ONE sweep of the stack where nothing else
    reads the attacked rows (:func:`_vote_row_map`) — ``health`` is the
    vote's (``vote_agree``, ``flagged_groups``, ``flagged``, and
    ``bad_rows`` of the rows as computed). approx:
    :func:`approx_aggregate`. Otherwise: injection on the raw rows, then
    the configured robust aggregation (mean / geo-median / krum) —
    approximate rules carry no exactness certificate, so ``health`` is
    None and the telemetry layer emits no decode-health columns for them.

    ``present`` ((n,) bool, optional): straggler rows marked False never
    arrive — cyclic decodes around them as erasures (known-missing, one
    redundancy unit each), the robust rules aggregate over present rows
    only.

    ``leaf_offsets``: static per-tensor segment boundaries from
    _make_unravel — required when ``cfg.decode_granularity == "layer"`` so
    the cyclic decode runs one locator per parameter tensor like the
    reference (cyclic_master.py:125-129).

    ``mesh``: the mesh the calling route's step is built for — it decides
    the decode lowering (ops/decode_kernels.resolve_decode_impl: the
    kernels are a one-device lowering).

    ``constrain`` (optional): pins the arrays that cross the wire — the
    encoded rows, or the narrow wire's buffers — to the caller's worker
    sharding; it is what places the gather on a mesh of several devices.

    The phases run under ``jax.named_scope`` so XProf device traces group
    ops by Draco's reference phase names (the device-side counterpart of
    the host SpanTracer, draco_tpu/obs).
    """
    with jax.named_scope("draco_attack"):
        grads = faults_mod.corrupt_grads(grads, cfg, step)
    if cfg.approach == "approx":
        return approx_aggregate(code, grads, cfg, adv_mask, present=present,
                                constrain=constrain, step=step, mesh=mesh)
    if cfg.approach == "cyclic":
        # ingest-row health, BEFORE encode: a non-finite per-worker gradient
        # row attributes to its worker here, where row k still means worker
        # k — the shared-redundancy encode below smears any NaN across every
        # codeword (0·NaN = NaN in the masked matmul), so the wire rows
        # cannot (obs/forensics.nonfinite_rows docstring)
        with jax.named_scope("draco_health"):
            bad_rows = forensics_mod.nonfinite_rows(grads)
        tree = _is_tree(code)
        with jax.named_scope("draco_encode"):
            if tree:
                # hierarchical tree encode (ISSUE 17): each leaf group
                # encodes with the ONE shared small code — rows stay
                # worker-indexed (n, d), so injection/presence/wire below
                # are byte-identical to flat
                enc_re, enc_im = topology_mod.encode_tree(code, grads)
            elif grads.ndim == 3:
                # (n, hat_s, d): true per-worker redundant lanes
                # (cfg.redundancy == "simulate" — the reference's r× compute,
                # cyclic_worker.py:122-146); each worker encodes its own rows
                enc_re, enc_im = cyclic_mod.encode(code, grads)
            else:
                # (n, d): one-copy batch gradients, rows formed algebraically
                # (cfg.redundancy == "shared", the TPU-native fast path)
                enc_re, enc_im = cyclic_mod.encode_shared(code, grads)
        with jax.named_scope("draco_attack"):
            # simulation: what a deployment does not pay
            enc_re, enc_im = attacks.inject_cyclic(
                enc_re, enc_im, adv_mask, cfg.err_mode, cfg.adversarial,
                step=step, seed=cfg.seed
            )
        decode_impl = resolve_decode_impl(cfg.decode_impl, mesh)
        with jax.named_scope("draco_encode"):
            if present is not None:
                # straggler rows never arrive: zero-fill (erasures at known
                # positions; decode recovers exactly within the budget —
                # config.validate)
                pw = present[:, None].astype(enc_re.dtype)
                enc_re, enc_im = enc_re * pw, enc_im * pw
            # the REAL narrow wire (ISSUE 15): the codeword pair is
            # rounded into narrow buffers — THE arrays that cross the
            # sharding boundary (the constraint pins them, not a widened
            # copy); the decode widens to f32 and runs the quantization-
            # aware flag threshold + Tikhonov-regularized locator.
            # Identity on the f32 wire, where the pair itself is pinned.
            enc_re, enc_im, wire = numerics_mod.narrow_wire_pair(
                cfg, enc_re, enc_im, step=step, constrain=constrain)
            if wire is None and constrain is not None:
                enc_re, enc_im = constrain(enc_re), constrain(enc_im)
        if tree:
            # the tree decodes each leaf group at the GROUP shape — its
            # narrow-wire thresholds come from the (fanout, s_g) table row
            wire_tol, wire_lam = numerics_mod.wire_decode_params(
                cfg, n=code.plan.fanout, s=code.group_code.s)
        else:
            wire_tol, wire_lam = numerics_mod.wire_decode_params(cfg)
        kw = dict(present=present, impl=decode_impl, lam=wire_lam,
                  rel_tol=(cyclic_mod.HEALTH_REL_TOL if wire_tol is None
                           else wire_tol))
        dim = int(grads.shape[-1])
        with jax.named_scope("draco_decode"):
            if tree:
                # hierarchical decode (ISSUE 17): per-group small-n decode
                # (segmented when the streaming wire is on), level-
                # structured combine, PR 16-style health fold — same
                # health keys as flat, and honest already folded to (n,)
                bounds = (numerics_mod.cfg_segment_bounds(cfg, dim)
                          if cfg.wire_segments > 1 else None)
                agg, honest, health = topology_mod.decode_tree_cyclic(
                    code, enc_re, enc_im, rand_factor, wire=wire,
                    bounds=bounds, **kw)
            elif cfg.decode_granularity == "layer" and leaf_offsets is None:
                raise ValueError("decode_granularity='layer' needs "
                                 "leaf_offsets from _make_unravel")
            elif cfg.wire_segments > 1:
                # streaming segmented wire (ISSUE 16): per-segment
                # syndromes/locators, one folded verdict per step. At layer
                # granularity the partition is the REFINEMENT of the leaf
                # boundaries by the quantum-aligned segment cuts — every
                # layer still gets (at least) its own locator, and the
                # health fold is unchanged (max / union over a finer
                # partition)
                bounds = segment_decode_bounds(
                    cfg, dim, leaf_offsets
                    if cfg.decode_granularity == "layer" else None)
                agg, honest, health = cyclic_mod.decode_segments(
                    code, enc_re, enc_im, rand_factor, bounds,
                    with_health=True, wire=wire, **kw)
                honest = jnp.all(honest, axis=0)
            elif cfg.decode_granularity == "layer":
                # per-parameter-tensor locator + projection, like the
                # reference's per-layer decode loop
                # (cyclic_master.py:125-129)
                agg, honest, health = cyclic_mod.decode_layers(
                    code, enc_re, enc_im, rand_factor, leaf_offsets,
                    with_health=True, **kw)
                honest = jnp.all(honest, axis=0)
            else:
                agg, honest, health = cyclic_mod.decode(
                    code, enc_re, enc_im, rand_factor, with_health=True,
                    wire=wire, **kw)
        health["honest"] = honest
        health["bad_rows"] = bad_rows
        _stash_watch(cfg, health, grads, [enc_re, enc_im], agg,
                     lambda: numerics_mod.cyclic_shadow(
                         cfg, code, enc_re, enc_im, agg, health, rand_factor,
                         leaf_offsets, present, adv_mask, step))
        return agg, health
    if cfg.approach == "maj_vote":
        # repetition code: the members of a group were fed the same rows
        # (the batching layer; token_loop.step_tokens) and ran the same
        # program on them, so honest rows agree bitwise and the vote over
        # the raw rows is exact (coding/repetition.py). The per-step
        # fingerprint salt is folded from the replicated step: identical on
        # every device and, being seed-derived, NOT secret from a
        # participant that knows the experiment seed —
        # cfg.vote_check="exact" is the collision-free option for that
        # threat model (repetition.py module docstring, tier 3).
        rep_code = rep_mod.build_repetition_code(cfg.num_workers,
                                                 cfg.group_size)
        with jax.named_scope("draco_input"):
            vkey = drng.fold(jax.random.key(cfg.seed + 4), step)
        # ONE sweep of the stack (under draco_decode): the vote's
        # fingerprint scan also reads the ingest-row health off the rows
        # as computed and applies the simulated attack to each block it
        # hashes, so the attacked stack is never stored. Where it has to
        # be (_vote_row_map), it is, and the rows as computed are checked
        # before the attack rewrites them.
        row_map = _vote_row_map(cfg, adv_mask)
        if row_map is None:
            with jax.named_scope("draco_health"):
                bad_rows = ~jnp.all(jnp.isfinite(grads),
                                    axis=tuple(range(1, grads.ndim)))
            with jax.named_scope("draco_attack"):
                grads = _inject_rows(grads, adv_mask, cfg, step)
        # the REAL narrow wire (ISSUE 15): this family's wire IS the raw
        # gradient rows — quantized into narrow buffers (the shared noise
        # draw keeps within-group rows bitwise identical, the vote's
        # soundness condition; pinned in tests/test_wire.py), the vote runs
        # over the widened rows. Identity on the f32 wire.
        with jax.named_scope("draco_encode"):
            vote_rows, _wire = numerics_mod.narrow_wire_single(
                cfg, grads, step=step, constrain=constrain)
        # the winner's row comes back as the stack's rows are laid out:
        # the leaves are cut from it where it lies (training/step.py
        # _make_unravel)
        with jax.named_scope("draco_decode"):
            voted, health = rep_mod.majority_vote(
                rep_code, vote_rows, present=present, key=vkey,
                method=cfg.vote_check, with_health=True, row_map=row_map)
        if row_map is None:
            health["bad_rows"] = bad_rows
        # (the shadow re-votes over the quantized rows: deterministic
        # rounding preserves within-group bitwise equality)
        _stash_watch(cfg, health, grads, [vote_rows], voted,
                     lambda: numerics_mod.majvote_shadow(
                         cfg, rep_code, grads, voted, health, vkey, present,
                         adv_mask, step))
        return voted, health
    with jax.named_scope("draco_attack"):
        grads = _inject_rows(grads, adv_mask, cfg, step)
    with jax.named_scope("draco_decode"):
        agg = aggregation.aggregate(
            grads, cfg.mode, s=cfg.worker_fail,
            geomedian_iters=cfg.geomedian_iters, present=present,
        )
    return agg, None


def masked_loss_metric(losses, present):
    """Mean loss over received rows only — a straggler's loss was never
    observed (mirrors the CNN path's _metrics, training/step.py)."""
    if present is None:
        return jnp.mean(losses)
    w = present.astype(losses.dtype)
    return jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1.0)


def apply_flat_update(state, agg: jnp.ndarray, opt, unravel):
    """Aggregated flat gradient → (new_params, new_opt_state) via the
    grads-as-argument optimizer convention (reference sgd_modified.py:53)."""
    with jax.named_scope("draco_pack"):
        grads_tree = unravel(agg)
    with jax.named_scope("draco_update"):
        updates, new_opt = opt.update(grads_tree, state.opt_state,
                                      state.params)
        new_params = jax.tree.map(lambda p, u: p + u, state.params, updates)
    return new_params, new_opt


def finish_flat_step(cfg, state, agg, health, opt, unravel, present=None,
                     constrain=None, constrain_opt=None, carry=None):
    """The shared flat-gradient step tail: optimizer update → optional
    param/opt-state sharding constraints → advance the carry, with the
    in-graph step guard folded in when ``cfg.step_guard == "on"``
    (resilience/guards.guard_update: untrusted steps keep the previous
    state via branch-free carry passthrough, the step counter still
    advances). One implementation for the CNN path and every LM route (sp /
    tp / ep / pp) so the guard semantics cannot diverge between them.
    Returns ``(new_state, guard_metric_columns)`` — the columns dict is
    empty when the guard is off, so the metric schema only grows for
    guarded configs (metric_family_names).

    ``carry``: further fields of the state the route advances itself (the
    CNN's per-worker BatchNorm statistics), replaced before the guard so
    that its passthrough covers them.

    ``constrain_opt``: routes whose carry must hold a GSPMD-stable layout
    (the real tp/ep meshes) pin the new opt state to the input layout here
    — otherwise the partitioner is free to reshard momentum buffers on the
    first execution and the SECOND dispatch of the K-fused program
    retraces against the drifted shardings (a silent steady-state
    recompile the PR 5 sentinel flags)."""
    new_params, new_opt = apply_flat_update(state, agg, opt, unravel)
    if constrain is not None:
        new_params = constrain(new_params)
    if constrain_opt is not None:
        new_opt = constrain_opt(new_opt)
    with jax.named_scope("draco_update"):
        new_state = state._replace(params=new_params, opt_state=new_opt,
                                   step=state.step + 1, **(carry or {}))
    with jax.named_scope("draco_health"):
        return guard_update(cfg, state, new_state, agg, health, present)


# column order of the (K, m) metric block train_token_many returns on the
# non-coded routes; cyclic routes append DECODE_HEALTH_NAMES and guarded
# configs (cfg.step_guard == "on") append GUARD_METRIC_NAMES — use
# token_metric_names(cfg), never these tuples directly, so the step bodies
# and the host flush can't disagree on the column order
TOKEN_METRIC_NAMES = ("loss",)

# (GUARD_METRIC_NAMES, resilience/guards.py: guard_trips = health signals
# fired, skipped_steps = 1 iff the update was passthrough-skipped)

# per-step decode-health columns (in-graph scalars; coding/cyclic.py):
#   decode_residual  self-consistency residual, ≈ 0 iff decode exact
#   located_errors   present rows flagged as corrupt by the decode
#   det_tp           flagged ∧ adversarial ∧ present (true positives)
#   det_adv          adversarial ∧ present (the detectable ground truth)
# flush boundaries derive detection precision = Σdet_tp/Σlocated_errors and
# recall = Σdet_tp/Σdet_adv from these (obs/heartbeat.py) — the seeded
# schedules are step inputs, so the comparison runs in-graph with no host
# traffic.
DECODE_HEALTH_NAMES = ("decode_residual", "located_errors", "det_tp",
                       "det_adv")

# per-step health columns of the approx family (coding/approx.py; ISSUE 8):
#   decode_residual        measured relative decode error vs the TRUE batch-
#                          gradient sum (available in-graph — the fleet is
#                          simulated in one SPMD program), dimensionless
#   decode_residual_bound  the arrived support's analytic optimal-decoding
#                          bound ‖u − 1‖₂ (arXiv:2006.09638); residual ≤
#                          bound is algebra, so any violation is a fault
#   recovered_fraction     fraction of batches with ≥ 1 present worker —
#                          1.0 is full coverage, the redundancy payoff
APPROX_HEALTH_NAMES = ("decode_residual", "decode_residual_bound",
                       "recovered_fraction")


def metric_family_names(cfg) -> tuple:
    """The OPTIONAL column families a route's metric schema appends after
    its base columns, declared once for every consumer (ISSUE 10 satellite):
    the CNN path's ``metric_names`` (training/step.py) and every LM route's
    ``token_metric_names`` below both call this, so a new column family —
    decode health, packed forensics masks, the numerics observatory, guard
    columns, whatever comes next — is declared HERE once and both loops'
    step bodies and host flushes agree on the order by construction.

    Family order: per-approach health columns → packed forensics masks →
    numerics/shadow observatory columns (cfg.numerics_watch /
    cfg.shadow_wire, obs/numerics.py) → guard columns. The baseline
    approach contributes nothing before the guard block — no exactness
    certificate, no accusation set, no coded wire (the PR 4 invariant)."""
    masks = forensics_mod.mask_metric_names(cfg.num_workers)
    names = ()
    if cfg.approach == "cyclic":
        names += DECODE_HEALTH_NAMES + masks
    elif cfg.approach == "approx":
        names += APPROX_HEALTH_NAMES + masks
    elif cfg.approach == "maj_vote":
        # the vote's flag count ships under the name the cyclic decode's has
        names += ("vote_agree", "flagged_groups", "located_errors", "det_tp",
                  "det_adv") + masks
    names += numerics_mod.watch_metric_names(cfg)
    if cfg.step_guard == "on":
        names += GUARD_METRIC_NAMES
    return names


def token_metric_names(cfg, stat_names=()) -> tuple:
    """Column order of the (K, m) metric block for an LM route at ``cfg``
    — every route builder stores this on its setup so the shared token
    loop flushes the right schema. The optional families (health masks /
    forensics / numerics / guard) come from the one shared assembly
    (:func:`metric_family_names`); baseline routes emit only the base
    columns. ``stat_names``: the token model's own per-step counters
    (``models.build_lm``'s surface), which close the row."""
    return TOKEN_METRIC_NAMES + metric_family_names(cfg) + tuple(stat_names)


def accusation_mask(health, present=None):
    """The step's per-worker accusation set from a coded health dict: the
    code's own flag set ∪ the forensic-only signals — magnitude-outlier
    ``loud`` rows (cyclic LOUD_REL_TOL: the attribution that survives the
    beyond-budget regime) and non-finite ingest ``bad_rows``. The approx
    family carries no ``flagged`` set at all (no Byzantine certificate —
    its only signal is the non-finite ingest check), so the union starts
    empty there; a *scheduled* straggler is in particular never accused.
    Present-gated at pack time too (forensics.pack_mask_columns): an absent
    worker is never an accused worker."""
    accused = None
    for key in ("flagged", "loud", "bad_rows"):
        if key in health:
            m = jnp.asarray(health[key], bool)
            accused = m if accused is None else accused | m
    if accused is None:
        raise ValueError("health dict carries no per-worker accusation "
                         "signal (flagged/loud/bad_rows)")
    if present is not None:
        accused = accused & present
    return accused


def decode_health_metrics(health, adv_mask, present) -> dict:
    """The DECODE_HEALTH_NAMES columns + the packed per-worker forensics
    masks from a decode-health dict + the step's seeded schedules ({} when
    the route has no exactness certificate, i.e. health is None).

    Detection counts vs the seeded schedules (both of which are step
    INPUTS, so the comparison runs in-graph — no host traffic):
    located_errors = flagged ∧ present, det_tp = flagged ∧ adversarial ∧
    present, det_adv = adversarial ∧ present. A straggling adversary's row
    never arrives — neither detectable nor ground truth, hence the
    ``present`` gate on both sides. Flush boundaries fold these into
    precision/recall (obs/heartbeat.py). The scalar counts keep their
    historical meaning (the code's own flag set, feeding the guard and the
    P/R fold); the packed ``accused`` mask is the wider forensic union
    (accusation_mask)."""
    if health is None:
        return {}
    # numerics-observatory columns (obs/numerics.py, ISSUE 10) stashed by
    # the aggregation tails — already final column-name -> scalar pairs
    watch = health.pop("watch", {})
    if "bound" in health:
        # approx family (APPROX_HEALTH_NAMES docstring): the certificate is
        # residual ≤ bound, there is no located-error set — the packed
        # accused mask is the non-finite ingest rows only, and the present/
        # adv masks ride along so the AccusationLedger folds this family
        # with the same absent≠accused semantics as the exact codes
        out = {
            "decode_residual": health["residual"],
            "decode_residual_bound": health["bound"],
            "recovered_fraction": health["recovered_fraction"],
        }
        out.update(forensics_mod.pack_mask_columns(
            accusation_mask(health, present), present, adv_mask))
        out.update(watch)
        return out
    pres = (jnp.ones_like(adv_mask, dtype=bool) if present is None
            else present)
    adv_live = adv_mask & pres
    flagged = health["flagged"] & pres
    if "vote_agree" in health:
        # repetition code: the vote's agreement record where the cyclic
        # decode has its residual; the flag count keeps the one name
        out = {"vote_agree": health["vote_agree"],
               "flagged_groups": health["flagged_groups"]}
    else:
        out = {"decode_residual": health["residual"]}
    out.update({
        "located_errors": jnp.sum(flagged.astype(jnp.int32)),
        "det_tp": jnp.sum((flagged & adv_live).astype(jnp.int32)),
        "det_adv": jnp.sum(adv_live.astype(jnp.int32)),
    })
    out.update(forensics_mod.pack_mask_columns(
        accusation_mask(health, present), present, adv_mask))
    out.update(watch)
    return out


def make_token_train_many(step_body, token_fn=None,
                          metric_names=TOKEN_METRIC_NAMES):
    """K fused LM coded steps in ONE ``lax.scan`` — the token-route analogue
    of the CNN path's ``train_many`` (training/step.py).

    ``step_body(state, tokens, adv_mask, present) -> (state, metrics)`` is
    any route's single-step body (sp/tp/ep share the flat-gradient tail in
    this module; pp brings its pipeline schedule). The returned
    ``many_body(state, tokens, masks, presents)`` scans it over the leading
    K axis of every operand and stacks the per-step metrics into a (K, m)
    float32 block the host fetches once per flush window. ``presents=None``
    threads through as an empty pytree, exactly like ``train_many``.

    ``token_fn`` (optional): in-graph token generator ``step -> (n, B, T)``
    (cfg.token_gen == "device"). When set, the first scanned operand is the
    (K,) int32 step-index vector instead of the (K, n, B, T) token block —
    the host uploads K scalars per chunk and the device synthesizes the
    tokens itself, the same closed-over-constant-free discipline as
    rng.random_projection_factors_in_graph.

    Callers jit with ``donate_argnums=(0,)`` inside the route's mesh context
    so the K-step state carry reuses the input buffers.
    """

    def many_body(state, tokens, masks, presents):
        def body(st, operand):
            toks, adv_mask, present = operand
            if token_fn is not None:
                with jax.named_scope("draco_input"):
                    toks = token_fn(toks)
            st, metrics = step_body(st, toks, adv_mask, present)
            with jax.named_scope("draco_health"):
                row = jnp.stack(
                    [jnp.asarray(metrics[k], jnp.float32)
                     for k in metric_names]
                )
            return st, row

        return jax.lax.scan(body, state, (tokens, masks, presents))

    return many_body
