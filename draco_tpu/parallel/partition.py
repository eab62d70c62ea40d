"""Declarative partition-rule tables: the single source of sharding truth.

Before this module, every route carried its own ad-hoc ``P(...)`` literals
(``repl`` / ``shard_w`` in training/step.py and sp_step.py, the Megatron
``param_partition_spec`` in tp_step.py, the stage-stack ``_leaf_spec`` in
pp_step.py, the tree ``row_spec`` in coding/topology.py) and its own copy
of the trailing-``None`` spec normalizer that PR 6's retrace-on-reshard
bug forced into tp_step. Both GSPMD defects the chaos harness has caught
(PR 6: an unnormalized ``P('tp', None)`` carry spec retraced every second
dispatch; PR 7: a sharded bitmask pack shifting every bit) were *runtime*
catches of *statically decidable* properties — so the sharding layer
becomes declared-and-audited here instead of scattered-and-hoped:

- :func:`norm_spec` — THE canonical normalizer (PR 6 fix, deduped out of
  tp/ep); every spec a table declares must be its own ``norm_spec``.
- :func:`match_partition_rules` — the fmengine/EasyLM regex-table pattern
  (SNIPPETS.md [3]): first matching rule wins, scalars map to ``P()``,
  unmatched array leaves raise.
- Per-route rule tables (``CNN_STEP_RULES`` … ``tree_combine_rules``):
  params, opt-state slots, token/batch operands, codeword/wire buffers and
  tree partials — written DISJOINT (each path matches exactly one rule) so
  the static auditor (analysis/sharding.py, lint rules 7–9) can hold every
  chip-bound program to them.

A table spec declares *axis membership* — which mesh axes a leaf is
distributed over. Multi-dim kernels under a scanned ``blocks/`` stack
shift the sharded dim right (tp_step.param_partition_spec stays the
placement authority for device_put); the auditor checks the declared axes
appear in the compiled sharding, not the exact dim index.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec as P

from draco_tpu.parallel.mesh import EP_AXIS, PP_AXIS, SEQ_AXIS, TP_AXIS
from draco_tpu.runtime import WORKER_AXIS

# ---- canonical specs (the migrated ad-hoc literals) -----------------------

REPLICATED = P()
# per-worker row blocks: flat grads (n, d), codeword/wire buffers, masks
WORKER_ROWS = P(WORKER_AXIS)
# simulate-lane batches (n, B, ...) with trailing dims explicit
WORKER_ROWS3 = P(WORKER_AXIS, None, None)
# ring-sequence tokens (n, B, T): workers over w, sequence over sp
SEQ_TOKENS = P(WORKER_AXIS, None, SEQ_AXIS)


def sharding(mesh, spec: P):
    """NamedSharding helper so routes write ``sharding(mesh, WORKER_ROWS)``
    instead of re-spelling ``NamedSharding(mesh, P(...))`` literals."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, spec)


# ---- the canonical normalizer (PR 6's _norm_spec, deduped) ----------------

def norm_spec(spec: Optional[P]) -> P:
    """Strip trailing ``None`` entries from a PartitionSpec.

    XLA reports shardings in normalized form (``P('tp')``, never
    ``P('tp', None)``). Pinning a jit boundary or comparing carry
    shardings with an UNnormalized spec is the PR 6 bug: the specs
    compare unequal, the second dispatch silently retraces and reshards,
    and the route pays a full compile + all-to-all every step. Idempotent:
    ``norm_spec(norm_spec(s)) == norm_spec(s)``.
    """
    if spec is None:
        return P()
    entries = tuple(spec)
    while entries and entries[-1] is None:
        entries = entries[:-1]
    return P(*entries)


def spec_axes(spec: Optional[P]) -> frozenset:
    """The set of mesh axis names a spec distributes over (flattening
    tuple entries like ``P(('tl2', 'tl1'))``)."""
    axes = set()
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.update(a for a in entry if a is not None)
        else:
            axes.add(entry)
    return frozenset(axes)


# ---- path utilities -------------------------------------------------------

def _key_str(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def leaf_paths(tree, prefix: str) -> "list[tuple[str, Any]]":
    """``[(path, leaf), ...]`` with '/'-joined path strings rooted at
    ``prefix`` — the naming vocabulary the rule tables match against
    (``state/params/block0/qkv/kernel``, ``state/opt_state/0/
    momentum_buf/...``, ``tokens``)."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = [prefix] + [_key_str(k) for k in path]
        out.append(("/".join(p for p in parts if p), leaf))
    return out


def arg_leaf_paths(args: Sequence, arg_names: Optional[Sequence[str]]
                   ) -> "list[tuple[str, Any]]":
    """Leaf paths across a program's positional args tuple."""
    out = []
    for i, arg in enumerate(args):
        name = (arg_names[i] if arg_names is not None and i < len(arg_names)
                else f"arg{i}")
        out.extend(leaf_paths(arg, name))
    return out


def _is_scalar_like(leaf) -> bool:
    import numpy as np

    try:
        return int(np.size(leaf)) <= 1
    except Exception:
        return False


# ---- the matcher (SNIPPETS.md [3] pattern) --------------------------------

def match_partition_rules(rules: Sequence[Tuple[str, P]], tree,
                          prefix: str = "") -> Any:
    """Map a pytree to a pytree of PartitionSpecs via a regex rule table.

    Precedence is first-match-wins (``re.search``) in table order; scalar
    and size-1 leaves map to ``P()`` without consulting the table (they
    are replicated by construction); an unmatched array leaf raises
    ``ValueError`` naming the path — a partition table that does not cover
    its tree is a lint failure, not a silent default.
    """
    import jax

    def assign(path, leaf):
        if _is_scalar_like(leaf):
            return P()
        name = "/".join(p for p in ([prefix] if prefix else [])
                        + [_key_str(k) for k in path])
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        raise ValueError(
            f"no partition rule matches leaf {name!r} "
            f"(shape {getattr(leaf, 'shape', ())}) — extend the table")

    return jax.tree_util.tree_map_with_path(assign, tree)


def match_report(rules: Sequence[Tuple[str, P]],
                 paths_and_leaves: Sequence[Tuple[str, Any]]
                 ) -> "list[dict]":
    """The lint-facing coverage report: for every array leaf, how many
    table rules match it, the claimed spec, and whether that spec is
    normalized. Scalar/size-1 leaves are implicitly ``P()`` and excluded
    (same convention as :func:`match_partition_rules`)."""
    report = []
    for path, leaf in paths_and_leaves:
        if _is_scalar_like(leaf):
            continue
        matches = [(pat, spec) for pat, spec in rules
                   if re.search(pat, path)]
        spec = matches[0][1] if matches else None
        report.append({
            "path": path,
            "shape": tuple(getattr(leaf, "shape", ())),
            "n_matches": len(matches),
            "spec": str(spec) if matches else None,
            "normalized": (spec == norm_spec(spec)) if matches else None,
        })
    return report


# ---- per-route rule tables ------------------------------------------------
# Paths: state/params/..., state/opt_state/<i>/momentum_buf/..., and the
# operand names built_token_program / the CNN _build register. Tables are
# DISJOINT by construction (negative lookaheads complement the sharded
# leaf patterns) so rule 7's exactly-one-match check holds.

# CNN coded-DP route (cyclic/approx, seg-wire and tree-combine variants):
# LeNet state fully replicated; the CI-shape compiler replicates the image
# batch too (every device redundantly computes all workers' grads — the
# honest n=8-on-8-devices fold); only the adversary mask rides the w axis.
CNN_STEP_RULES: Tuple[Tuple[str, P], ...] = (
    (r"^state/batch_stats/", WORKER_ROWS),  # per-worker BN stats (has_bn)
    (r"^state/(?!batch_stats/)", REPLICATED),
    (r"^(?:x|y)$", REPLICATED),
    (r"^adv_mask$", WORKER_ROWS),
)

# Sequence-ring route: replicated state, tokens sharded (w, _, sp).
SP_STEP_RULES: Tuple[Tuple[str, P], ...] = (
    (r"^state/", REPLICATED),
    (r"^tokens$", SEQ_TOKENS),
    (r"^adv_mask$", WORKER_ROWS),
)

# Megatron TP route (and the folded w×1 fold_* family): the five sharded
# leaf kinds of param_partition_spec; momentum slots inherit the layout
# (opt.init zeros_like), so the patterns are prefix-insensitive.
_TP_SHARDED = (r"(?:(?:qkv|mlp_in)/kernel|(?:proj|mlp_out)/kernel"
               r"|mlp_in/bias)$")
TP_STEP_RULES: Tuple[Tuple[str, P], ...] = (
    (r"^state/.*(?:qkv|mlp_in)/kernel$", P(None, TP_AXIS)),
    (r"^state/.*(?:proj|mlp_out)/kernel$", P(TP_AXIS)),
    (r"^state/.*mlp_in/bias$", P(TP_AXIS)),
    (rf"^state/(?!.*{_TP_SHARDED})", REPLICATED),
    (r"^tokens$", WORKER_ROWS),
    (r"^adv_mask$", WORKER_ROWS),
)

# Expert-parallel route: expert stacks over ep, router/backbone replicated.
_EP_SHARDED = r"moe/(?:w1|w2|b1|b2)$"
EP_STEP_RULES: Tuple[Tuple[str, P], ...] = (
    (rf"^state/.*{_EP_SHARDED}", P(EP_AXIS)),
    (rf"^state/(?!.*{_EP_SHARDED})", REPLICATED),
    (r"^tokens$", WORKER_ROWS),
    (r"^adv_mask$", WORKER_ROWS),
)

# GPipe route: every blocks/ stage stack (params AND momentum) over pp.
PP_STEP_RULES: Tuple[Tuple[str, P], ...] = (
    (r"^state/.*/blocks/", P(PP_AXIS)),
    (r"^state/(?!.*/blocks/)", REPLICATED),
    (r"^tokens$", WORKER_ROWS),
    (r"^adv_mask$", WORKER_ROWS),
)


def override(rules: Sequence[Tuple[str, P]],
             *overrides: Tuple[str, P]) -> Tuple[Tuple[str, P], ...]:
    """A table with specific patterns re-declared (keeps disjointness:
    the overridden pattern's original row is dropped, not shadowed). The
    devgen rows use it — their ``tokens`` operand is the (K,) step-index
    vector, which rides replicated instead of the host token batch."""
    pats = {p for p, _ in overrides}
    return tuple(overrides) + tuple(r for r in rules if r[0] not in pats)


def approx_rules(rules: Sequence[Tuple[str, P]]
                 ) -> Tuple[Tuple[str, P], ...]:
    """A route's table under the approx family: config.validate admits no
    live adversary there, so the mask reaches nothing but the packed
    forensics columns and the compiler keeps its n bits replicated."""
    return override(rules, (r"^adv_mask$", REPLICATED))


def tree_rows(level_axes: Sequence[str]) -> P:
    """Worker-row spec on a tree-combine mesh: dim 0 folded over the
    REVERSED level axes, so C-order places leaf group j at grid
    multi-index unravel(j) (coding/topology.tree_mesh docstring)."""
    return P(tuple(reversed(tuple(level_axes))))


def tree_combine_rules(level_axes: Sequence[str]
                       ) -> Tuple[Tuple[str, P], ...]:
    """Partition table for a CodedReduce tree-combine program
    (coding/topology.make_tree_decode_shmap): codeword partials and the
    presence mask ride the worker rows while the projection factors stay
    replicated."""
    rows = tree_rows(level_axes)
    return (
        (r"^r_(?:re|im)$", rows),
        (r"^present$", rows),
        (r"^rand_factor$", REPLICATED),
    )
