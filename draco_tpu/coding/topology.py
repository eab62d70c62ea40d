"""Hierarchical CodedReduce aggregation — the tree topology (ISSUE 17).

Every coded route used to decode flat PS-style: all n codewords land at one
logical aggregation point, so decode time and ingest bandwidth at that point
grow with n (the PR 9 device ledger prices decode at 17-25% of LM device
time, and the PR 15 threshold table shows the locator degrading as n grows).
CodedReduce (PAPERS.md, arXiv:1902.01981) replaces the star with a tree whose
per-node fan-in is CONSTANT: the (n,) worker axis is partitioned into
``G = n / g`` leaf groups of fan-in ``g`` (the same consecutive-window
algebra as ``coding/assignment.clustered_assignment`` — worker ``i`` sits in
group ``i // g``), each group runs its OWN small-n code over its g batches,
decodes locally, and parents combine the decoded (d,) partials level by
level until one aggregate remains. Per-node decode cost and ingest bytes are
then O(g·d) at the leaves and O(f·d) at each combine node — independent of
n — while the flat aggregation point pays O(n·d).

Group algebra (mirrors the flat Σ/n convention bitwise at the seams):

  * leaf group j covers workers [j·g, (j+1)·g) and THEIR batch rows — under
    ``redundancy="shared"`` batch k's gradient sits at row k, so group j's
    code mixes exactly its own g rows (a block-diagonal encode; the [lo, hi)
    slice of the tree encode equals the small code's flat encode of those
    rows bit-for-bit);
  * each group decode returns Σ_{k∈group} grads_k / g (the family's own
    Σ/n convention at n=g);
  * the combine is the level-structured mean of group partials —
    mean_j(Σ_group/g) = Σ_all/n — exactly the flat decode's output
    convention.

Per-group code strength: the per-(n, s, dtype) threshold table (PR 15) and
the cyclic existence bound pick the per-group ``s_g``:
``s_g = min(worker_fail, (g-1)//4)`` (the small code needs g > 4·s_g), and
under a narrow wire additionally ``wire_rel_tol(g, s_g, dtype) < 1`` —
config.validate walks s_g down and rejects configs whose declared adversary
load exceeds the worst-case per-group budget (all adversaries in one group).

Health fold (the PR 16 segment fold, applied across worker GROUPS instead
of wire segments): residual = max over groups (a single inconsistent group
is a fault), flagged/loud/dev_rel = the disjoint-group union (per-group
(g,) masks concatenate back to (n,)), honest = concatenation — so the
detection/forensics columns are (n,)-shaped and IDENTICAL to the flat
decode's under the same faults (pinned by tests/test_tree.py and the
committed tree_study cells, live adversaries and straggler drops included).

The mesh-sub-axis form (``lint_programs``): the combine levels map onto
named mesh axes ("tl1" innermost) and parents combine via ``lax.psum`` over
the level's axis name — one all_reduce per level, pinned EXACTLY by the
collectives manifest (the communication structure IS the algorithm). The
production jit routes keep the structured sum (GSPMD schedules it;
collectives={} stays pinned there like every data-parallel route).

Jax-free header: the plan/byte math (``tree_plan``, ``tree_ledger_block``)
imports no jax, so obs/numerics.wire_ledger and config.validate can price
and validate tree configs host-side; everything below build_tree_code
imports jax lazily.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

TOPOLOGIES = ("flat", "tree")

# partial-combine wire width: parents ingest decoded f32 (d,) partials
PARTIAL_BYTES = 4


# --------------------------------------------------------------------------
# jax-free plan algebra (config.validate + obs/numerics consume this)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TreePlan:
    """The static tree shape: who groups with whom, and how groups fold."""

    n: int
    fanout: int
    levels: int  # total levels including the leaf level (>= 2)
    num_groups: int
    # combine fan-ins, innermost (level 1, adjacent groups) first; their
    # product is num_groups and each is <= fanout
    level_fanouts: Tuple[int, ...]
    # leaf group j = workers [group_slices[j][0], group_slices[j][1])
    group_slices: Tuple[Tuple[int, int], ...]

    @property
    def level_widths(self) -> Tuple[int, ...]:
        """Node count per level, leaves first: (G, G/f1, ..., 1)."""
        widths = [self.num_groups]
        for f in self.level_fanouts:
            widths.append(widths[-1] // f)
        return tuple(widths)


def auto_levels(n: int, fanout: int) -> int:
    """Leaf level + enough combine levels of fan-in <= ``fanout`` to fold
    G = n/fanout groups to one root: ``1 + ceil(log_g(G))`` (min 2)."""
    groups = n // fanout
    return 1 + max(1, math.ceil(math.log(groups, fanout))) if groups > 1 \
        else 2


def level_fanouts(num_groups: int, fanout: int,
                  levels: int) -> Tuple[int, ...]:
    """Split the group-folding into ``levels - 1`` per-level fan-ins, each
    <= ``fanout``, innermost first, product exactly ``num_groups``."""
    fans = []
    remaining = num_groups
    for _ in range(levels - 1):
        f = min(fanout, remaining)
        fans.append(max(f, 1))
        remaining = -(-remaining // max(f, 1))
    if math.prod(fans) != num_groups:
        raise ValueError(
            f"tree_levels={levels} cannot fold {num_groups} groups with "
            f"fan-in <= {fanout} (per-level fan-ins {fans} multiply to "
            f"{math.prod(fans)})")
    return tuple(fans)


def tree_plan(n: int, fanout: int, levels: int = 0) -> TreePlan:
    """Validated tree shape for ``n`` workers at fan-in ``fanout``.
    ``levels=0`` auto-derives ``auto_levels``."""
    n, fanout = int(n), int(fanout)
    if fanout < 2:
        raise ValueError(f"tree_fanout must be >= 2, got {fanout}")
    if n % fanout != 0:
        raise ValueError(
            f"topology='tree' needs num_workers % tree_fanout == 0, got "
            f"n={n}, g={fanout}")
    groups = n // fanout
    if groups < 2:
        raise ValueError(
            f"topology='tree' needs at least 2 leaf groups (n > fanout), "
            f"got n={n}, g={fanout} — use topology='flat'")
    lv = int(levels) or auto_levels(n, fanout)
    if lv < 2:
        raise ValueError(f"tree_levels must be >= 2 (or 0 = auto), got {lv}")
    fans = level_fanouts(groups, fanout, lv)
    slices = tuple((j * fanout, (j + 1) * fanout) for j in range(groups))
    return TreePlan(n=n, fanout=fanout, levels=lv, num_groups=groups,
                    level_fanouts=fans, group_slices=slices)


def group_worker_fail(fanout: int, worker_fail: int) -> int:
    """The per-group cyclic error budget: the flat ``s`` capped by the small
    code's existence bound g > 4·s_g. The threshold-table narrowing cap is
    applied on top by config.validate (wire_rel_tol at the GROUP shape)."""
    return min(int(worker_fail), max((int(fanout) - 1) // 4, 0))


def tree_ledger_block(n: int, fanout: int, levels: int, dim: int,
                      physical_bytes_per_worker: int) -> dict:
    """The wire ledger's ``tree`` sub-block (jax-free): per-level ingest
    bytes per step. Level 0 is the leaf ingest — each leaf node receives its
    g workers' codewords, and the per-group bytes SUM EXACTLY to the flat
    ``physical_bytes_per_step`` (the same n codeword rows, partitioned, no
    padding at the seams — perf_watch pins the sum both directions). Combine
    level l >= 1 ingests its children's decoded f32 (d,) partials:
    ``level_widths[l-1] · 4 · dim`` bytes per step — the tree's internal
    traffic, CONSTANT per node (fan-in · 4 · dim) as n grows."""
    plan = tree_plan(n, fanout, levels)
    leaf_group = fanout * int(physical_bytes_per_worker)
    widths = plan.level_widths
    level_bytes = [leaf_group * plan.num_groups]
    level_bytes += [widths[l - 1] * PARTIAL_BYTES * int(dim)
                    for l in range(1, plan.levels)]
    return {
        "fanout": plan.fanout,
        "levels": plan.levels,
        "num_groups": plan.num_groups,
        "level_fanouts": list(plan.level_fanouts),
        "level_widths": list(widths),
        "ingest_bytes_per_group": leaf_group,
        # per-node ingest at each level: what ONE aggregation point pays
        "node_ingest_bytes": [leaf_group] + [
            f * PARTIAL_BYTES * int(dim) for f in plan.level_fanouts],
        "level_bytes_per_step": level_bytes,
    }


# --------------------------------------------------------------------------
# tree codes (jax from here down, imported lazily)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TreeCode:
    """One small per-group code + the plan that tiles it over the fleet.
    Groups are homogeneous (equal size, same scheme), so ONE small code is
    shared by every group — the same constants, the same compiled decode."""

    plan: TreePlan
    group_code: object  # CyclicCode(g, s_g) or ApproxCode(g, r, scheme)
    family: str  # "cyclic" | "approx"

    @property
    def n(self) -> int:
        return self.plan.n

    @property
    def s(self) -> int:
        """Per-group error budget (cyclic); 0 for approx."""
        return getattr(self.group_code, "s", 0)


def build_tree_code(cfg) -> TreeCode:
    """The tree code a config names: cyclic groups at
    ``s_g = group_worker_fail`` or approx groups at the configured
    fractional redundancy. config.validate has already checked the shape."""
    from draco_tpu.coding import approx as approx_mod
    from draco_tpu.coding import cyclic as cyclic_mod

    plan = tree_plan(cfg.num_workers, cfg.tree_fanout, cfg.tree_levels)
    if cfg.approach == "cyclic":
        s_g = group_worker_fail(cfg.tree_fanout, cfg.worker_fail)
        return TreeCode(plan, cyclic_mod.build_cyclic_code(plan.fanout, s_g),
                        "cyclic")
    if cfg.approach == "approx":
        return TreeCode(
            plan,
            approx_mod.build_approx_code(plan.fanout, cfg.code_redundancy,
                                         cfg.assignment_scheme),
            "approx")
    raise ValueError(
        f"topology='tree' supports cyclic/approx, got {cfg.approach!r} "
        "(maj_vote's repetition groups are already a one-level tree)")


def _slice_wire(wire, lo: int, hi: int):
    """The [lo, hi) worker-row slice of a narrow wire tuple — the per-group
    (g, d) block the narrow-ingest kernels take instead of (n, d). Buffers
    are row-major over workers and int8 scales are per-row, so slicing rows
    never splits a scale block."""
    if wire is None:
        return None
    if len(wire) == 4:  # cyclic pair: (mode, buf_re, buf_im, block)
        mode, buf_re, buf_im, block = wire
        return (mode, {k: v[lo:hi] for k, v in buf_re.items()},
                {k: v[lo:hi] for k, v in buf_im.items()}, block)
    mode, buf, block = wire  # approx/maj_vote single: (mode, buf, block)
    return (mode, {k: v[lo:hi] for k, v in buf.items()}, block)


def combine_partials(plan: TreePlan, parts):
    """Level-structured combine of the (G, d) group partials: each combine
    level sums its fan-in children (C-order reshape — level 1 folds adjacent
    groups), the root divides by G. Structurally the tree (the shard_map
    form runs the same sums as per-level psum), numerically the flat
    mean-of-groups = Σ_all/n."""
    import jax.numpy as jnp

    x = jnp.asarray(parts)
    for f in plan.level_fanouts:
        x = x.reshape(-1, f, x.shape[-1]).sum(axis=1)
    return x[0] / plan.num_groups


def encode_tree(tcode: TreeCode, batch_grads):
    """Block-diagonal tree encode from one-copy batch gradients (n, d):
    group j's [lo, hi) rows are the small code's flat encode of that group's
    batch rows — bit-for-bit (same kernel, same operands). Returns the
    cyclic (enc_re, enc_im) pair or the approx (n, d) partial-sum rows."""
    import jax.numpy as jnp

    from draco_tpu.coding import approx as approx_mod
    from draco_tpu.coding import cyclic as cyclic_mod

    code = tcode.group_code
    if tcode.family == "cyclic":
        pairs = [cyclic_mod.encode_shared(code, batch_grads[lo:hi])
                 for lo, hi in tcode.plan.group_slices]
        return (jnp.concatenate([p[0] for p in pairs]),
                jnp.concatenate([p[1] for p in pairs]))
    rows = [approx_mod.encode_shared(code, batch_grads[lo:hi])
            for lo, hi in tcode.plan.group_slices]
    return jnp.concatenate(rows)


def decode_tree_cyclic(tcode: TreeCode, r_re, r_im, rand_factor,
                       present=None, rel_tol: Optional[float] = None,
                       impl: str = "xla", lam: float = 0.0, wire=None,
                       bounds=None):
    """Tree cyclic decode: each leaf group runs the small code's own decode
    (segmented when ``bounds`` has interior cuts — the wire_segments
    composition; the narrow-ingest kernels take the group's (g, d) wire
    block via :func:`_slice_wire`), parents combine the (d,) partials
    level-structured, and the per-group health verdicts fold like the PR 16
    segment fold: residual = max, flagged/loud/dev_rel = disjoint-group
    union back to (n,), honest = concatenation.

    Returns ``(decoded (d,), honest (n,), health)`` — the flat decode's
    contract with honest already folded over segments."""
    import jax.numpy as jnp

    from draco_tpu.coding import cyclic as cyclic_mod

    code = tcode.group_code
    if rel_tol is None:
        rel_tol = cyclic_mod.HEALTH_REL_TOL
    segmented = bounds is not None and len(bounds) > 2
    parts, honests, healths = [], [], []
    for lo, hi in tcode.plan.group_slices:
        pres_g = None if present is None else present[lo:hi]
        wire_g = _slice_wire(wire, lo, hi)
        if segmented:
            dec, hon, hl = cyclic_mod.decode_segments(
                code, r_re[lo:hi], r_im[lo:hi], rand_factor, bounds,
                present=pres_g, with_health=True, rel_tol=rel_tol,
                impl=impl, lam=lam, wire=wire_g)
            hon = jnp.all(hon, axis=0)  # (S', g) -> (g,): the segment fold
        else:
            dec, hon, hl = cyclic_mod.decode(
                code, r_re[lo:hi], r_im[lo:hi], rand_factor,
                present=pres_g, with_health=True, rel_tol=rel_tol,
                impl=impl, lam=lam, wire=wire_g)
        parts.append(dec)
        honests.append(hon)
        healths.append(hl)
    decoded = combine_partials(tcode.plan, jnp.stack(parts))
    honest = jnp.concatenate(honests)
    health = {"residual": jnp.max(jnp.stack([h["residual"]
                                             for h in healths])),
              "flagged": jnp.concatenate([h["flagged"] for h in healths]),
              "loud": jnp.concatenate([h["loud"] for h in healths])}
    if all("dev_rel" in h for h in healths):
        health["dev_rel"] = jnp.concatenate([h["dev_rel"] for h in healths])
    return decoded, honest, health


def decode_tree_approx(tcode: TreeCode, rows, present=None,
                       batch_grads=None, impl: str = "xla", wire=None,
                       bounds=None):
    """Tree approx decode: per-group optimal-decoding (segmented under the
    wire_segments composition), level-structured combine, and the health
    fold that keeps the family's certificate comparable to flat:

      * ``residual`` is measured at the ROOT against the full true mean —
        the flat formula on the tree aggregate, so guard/incident
        thresholds keep their meaning;
      * ``bound`` = sqrt(Σ_j bound_j²) — the exact ‖u − 1‖₂ of the
        block-diagonal system, and err ≤ bound·‖G‖_F/n still holds
        (Cauchy-Schwarz across groups);
      * ``recovered_fraction`` = mean over equal-size groups (the same
        batch-coverage fraction as flat).

    Returns ``(decoded (d,), v (n,), health)``."""
    import jax.numpy as jnp

    from draco_tpu.coding import approx as approx_mod

    code = tcode.group_code
    segmented = bounds is not None and len(bounds) > 2
    parts, vs, bounds_sq, rec = [], [], [], []
    for lo, hi in tcode.plan.group_slices:
        pres_g = None if present is None else present[lo:hi]
        wire_g = _slice_wire(wire, lo, hi)
        bg = None if batch_grads is None else batch_grads[lo:hi]
        if segmented:
            dec, v, hl = approx_mod.decode_segments(
                code, rows[lo:hi], bounds, present=pres_g,
                with_health=True, batch_grads=bg, impl=impl, wire=wire_g)
        else:
            dec, v, hl = approx_mod.decode(
                code, rows[lo:hi], present=pres_g, with_health=True,
                batch_grads=bg, impl=impl, wire=wire_g)
        parts.append(dec)
        vs.append(v)
        bounds_sq.append(hl["bound"] ** 2)
        rec.append(hl["recovered_fraction"])
    decoded = combine_partials(tcode.plan, jnp.stack(parts))
    v_all = jnp.concatenate(vs)
    n = tcode.plan.n
    true_mean = jnp.sum(batch_grads, axis=0) / n
    gfro = jnp.sqrt(jnp.sum(jnp.asarray(batch_grads,
                                        jnp.float32) ** 2))
    scale = jnp.maximum(gfro / n, 1e-30)
    health = {
        "residual": jnp.sqrt(jnp.sum((decoded - true_mean) ** 2)) / scale,
        "bound": jnp.sqrt(jnp.sum(jnp.stack(bounds_sq))),
        "recovered_fraction": jnp.mean(jnp.stack(rec)),
    }
    return decoded, v_all, health


# --------------------------------------------------------------------------
# mesh-sub-axis form: per-level psum combine (the registered programs)
# --------------------------------------------------------------------------


def tree_axis_names(plan: TreePlan) -> Tuple[str, ...]:
    """Combine-level mesh axis names, innermost (level 1) first."""
    return tuple(f"tl{l + 1}" for l in range(len(plan.level_fanouts)))


def tree_mesh(plan: TreePlan, devices=None):
    """Mesh whose axes ARE the combine levels: the device grid is shaped
    (f_top, ..., f_1[, wi]) so C-order places group j at grid multi-index
    unravel(j) — adjacent groups share the innermost ("tl1") axis, exactly
    the groups level 1 folds. A trailing replication axis "wi" soaks up
    devices beyond one per group (each group's block is replicated across
    it). Needs num_groups | device count or device count | num_groups·wi;
    raises when the grid cannot be filled exactly."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    g_axes = tree_axis_names(plan)
    grid_shape = tuple(reversed(plan.level_fanouts))
    need = plan.num_groups
    if len(devices) % need != 0:
        raise ValueError(
            f"tree_mesh: {len(devices)} devices cannot tile {need} groups "
            "evenly")
    wi = len(devices) // need
    names = tuple(reversed(g_axes))
    if wi > 1:
        grid_shape = grid_shape + (wi,)
        names = names + ("wi",)
    grid = np.asarray(devices[: need * wi]).reshape(grid_shape)
    return Mesh(grid, names)


def make_tree_decode_shmap(tcode: TreeCode, mesh, impl: str = "xla",
                           rel_tol: Optional[float] = None,
                           lam: float = 0.0):
    """The mesh-sub-axis tree decode: each device holds its leaf group's
    whole (g, d) codeword block (replicated across "wi" when present),
    decodes it LOCALLY with the small code, then parents combine the (d,)
    partials with one ``lax.psum`` PER LEVEL over that level's axis name —
    the collectives manifest pins exactly ``levels - 1`` all_reduce ops
    (the communication structure is the algorithm; sp_step's ppermute ring
    budget is the precedent for nonzero pins). Returns a jitted
    ``fn(r_re, r_im, rand_factor, present) -> (d,)`` aggregate, replicated.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from draco_tpu.coding import cyclic as cyclic_mod
    from jax import shard_map

    from draco_tpu.parallel.partition import tree_rows

    code = tcode.group_code
    plan = tcode.plan
    tol = cyclic_mod.HEALTH_REL_TOL if rel_tol is None else rel_tol
    level_axes = tree_axis_names(plan)
    # rows partition over the level axes only: each device (and every "wi"
    # replica) holds its group's full (g, d) block
    row_spec = tree_rows(level_axes)

    def device_decode(r_re, r_im, rand_factor, present):
        dec, _ = cyclic_mod.decode(code, r_re, r_im, rand_factor,
                                   present=present, with_health=False,
                                   rel_tol=tol, impl=impl, lam=lam)
        out = dec
        for ax in level_axes:  # one all_reduce per combine level
            out = jax.lax.psum(out, ax)
        return out / plan.num_groups

    fn = shard_map(
        device_decode,
        mesh=mesh,
        in_specs=(row_spec, row_spec, P(), row_spec),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def lint_programs():
    """Registered mesh-sub-axis tree programs (analysis/registry.collect):
    the per-level psum counts are pinned EXACTLY by the collectives
    manifest. Shapes are small (the leaf decode is the point — fan-in g,
    not n) and CPU-exportable like every other lint row."""
    import jax
    import numpy as np

    from draco_tpu.analysis.registry import (BuiltProgram, LintProgram,
                                             Manifest)

    def _build(n, g, name):
        import dataclasses as _dc

        from draco_tpu.config import TrainConfig

        cfg = TrainConfig(approach="cyclic", num_workers=n, worker_fail=1,
                          adversary_count=0, redundancy="shared",
                          topology="tree", tree_fanout=g,
                          dataset="synthetic-mnist", network="LeNet",
                          batch_size=2)
        from draco_tpu.parallel.partition import tree_combine_rules

        tcode = build_tree_code(cfg)
        mesh = tree_mesh(tcode.plan)
        fn = make_tree_decode_shmap(tcode, mesh)
        level_axes = tree_axis_names(tcode.plan)
        d = 8192
        args = (np.zeros((n, d), np.float32), np.zeros((n, d), np.float32),
                np.ones((d,), np.float32), np.ones((n,), np.float32))
        manifest = Manifest(
            max_constant_bytes=1 << 20,
            max_module_bytes=1 << 20,
            require_donated=None,
            collectives={"all_reduce": tcode.plan.levels - 1},
            # the combine IS the communication structure: exactly one psum
            # per level, each on that level's own mesh sub-axis
            collective_axes={ax: {"all_reduce": 1} for ax in level_axes},
            host_transfer_budget=0,
            max_peak_bytes=1 << 30,
        )
        return BuiltProgram(name=name, fn=fn, args=args, mesh=mesh,
                            manifest=manifest,
                            partition_rules=tree_combine_rules(level_axes),
                            arg_names=("r_re", "r_im", "rand_factor",
                                       "present"))

    return [
        LintProgram(name="tree_combine_g2_l3",
                    build=lambda: _build(8, 2, "tree_combine_g2_l3"),
                    route="cnn", fast=True),
        LintProgram(name="tree_combine_g4_l2",
                    build=lambda: _build(8, 4, "tree_combine_g4_l2"),
                    route="cnn", fast=True),
    ]
