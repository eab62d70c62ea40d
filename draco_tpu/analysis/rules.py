"""The nine static rules run against every registered chip-bound program.

Each rule inspects the static artifacts of a :class:`~draco_tpu.analysis.
registry.BuiltProgram` — the closed jaxpr (``jit_fn.trace``), the
``jax.export``-ed StableHLO module, and the compiled executable's
memory/cost analysis — against the program's
:class:`~draco_tpu.analysis.registry.Manifest`:

  constant_bloat   no closed-over constant ≥ manifest.max_constant_bytes and
                   the serialized module ≤ max_module_bytes (generalizes the
                   round-5 d-sized-constant guard, tests/test_program_size.py
                   lineage: a (d,) f32 closure serialized 638 MB at the
                   d≈159M flagship and compiled for 27 min, PERF_HISTORY.md
                   §4 / rng.random_projection_factors_in_graph)
  donation         the state carry is actually marked for buffer reuse in
                   the exported module (``jax.buffer_donor`` /
                   ``tf.aliasing_output`` attrs on exactly the expected
                   number of inputs), and each donated input has a distinct
                   same-shape/dtype output to alias into — requesting
                   donation in jit is not the same as XLA being able to
                   honour it (a carry-structure change silently doubles
                   peak HBM)
  dtype            no f64/complex<f64> anywhere; module element types ⊆ the
                   manifest's allowed set; on bf16 routes every bf16→f32
                   promotion site is a whitelisted primitive (accumulation
                   converts), so accidental f32 upcasts of whole activations
                   fail statically
  collectives      explicit collective-op counts by kind equal the manifest
                   (the communication structure IS the algorithm — an
                   accidental extra all-gather is a correctness/perf bug
                   even when outputs match)
  host_traffic     zero infeed/outfeed/send/recv ops and zero host-callback
                   custom calls or callback primitives — one host hop inside
                   a scanned body re-serializes the chunk on the ~70 ms
                   dispatch link the scan exists to hide (PERF_HISTORY.md §0)
  memory_budget    the compiled executable's peak-memory estimate
                   (``compiled.memory_analysis()``: argument + output +
                   temp + generated-code bytes, minus donated-alias bytes)
                   stays under manifest.max_peak_bytes; the rule row is
                   also the per-program memory/cost LEDGER — every row
                   carries the raw byte columns and the program's analytic
                   flops (``cost_analysis``), so the committed artifact is
                   the round-over-round record tools/perf_watch.py diffs
                   (PERF_HISTORY.md §8). Measured on the CPU-host compile of the
                   same program the CI mesh executes — an estimate of
                   shape, not a chip HBM number.

Rules 7-9 are the static sharding auditor (analysis/sharding.py):
``sharding_contract`` (partition-table coverage + donated-carry sharding
equality, the static form of PR 6's retrace-on-reshard),
``collective_axes`` (each collective classified by the mesh axis it
reduces over, checked against Manifest.collective_axes, with a per-axis
byte ledger), and ``replication_leaks`` (table-declared-sharded arrays
must not compile fully-replicated — the PR 7 neighborhood).

Rules degrade gracefully: host callbacks make a program un-exportable on
this jax (NotImplementedError), so the jaxpr-level half of host_traffic
still trips while module-level rules report ``skipped`` with the export
error; any OTHER export failure is itself a violation (synthetic rule
``export``). Likewise a program the host backend cannot compile reports
``memory_budget`` as ``skipped`` with the compile error rather than
blocking the jaxpr/module-level rules. A rule whose manifest field is
``None`` reports ``skipped``.
"""

from __future__ import annotations

import re
from typing import Any, Optional

from draco_tpu.analysis.registry import (
    COLLECTIVE_KINDS,
    BuiltProgram,
    LintProgram,
)

RULE_NAMES = ("constant_bloat", "donation", "dtype", "collectives",
              "host_traffic", "memory_budget", "sharding_contract",
              "collective_axes", "replication_leaks")

# jaxpr primitives that move data to/from the host at run time
_HOST_PRIMS = frozenset({
    "debug_callback", "pure_callback", "io_callback", "infeed", "outfeed",
})

# custom_call targets that are device-side compiler intrinsics, not host
# traffic: sharding markers, Mosaic kernels, and the XLA linalg lowerings
# (spelled Qr/Eigh/... when exported for tpu, lapack_*/blas_* for cpu)
_SAFE_CUSTOM_CALLS = re.compile(
    r"^(Sharding|SPMDFullToShardShape|SPMDShardToFullShape|mhlo\.\w+|"
    r"Qr|Eigh|LuDecomposition|ProductOfElementaryHouseholderReflectors|"
    r"Cholesky|tpu_custom_call|annotate_device_placement|"
    r"lapack_\w+|blas_\w+)$"
)

_TENSOR_ELEM_RE = re.compile(
    r"tensor<(?:\d+x)*"
    r"(f64|f32|f16|bf16|i64|i32|i16|i8|i1|ui64|ui32|ui16|ui8|"
    r"complex<f32>|complex<f64>)"
)


class Artifacts:
    """What one trace+export+compile pass yields; rules only read this."""

    def __init__(self, built: BuiltProgram, closed_jaxpr, mlir_text,
                 serialized_bytes, export_error, memory=None,
                 cost_flops=None, compile_error=None,
                 input_shardings=None, output_shardings=None):
        self.built = built
        self.manifest = built.manifest
        self.jaxpr = closed_jaxpr  # ClosedJaxpr | None
        self.mlir_text: Optional[str] = mlir_text
        self.serialized_bytes: Optional[int] = serialized_bytes
        self.export_error: Optional[str] = export_error
        self.memory: Optional[dict] = memory  # _memory_columns() | None
        self.cost_flops: Optional[float] = cost_flops
        self.compile_error: Optional[str] = compile_error
        # flattened compiled I/O shardings (the sharding auditor's
        # ground truth, rules 7/9) — None when the host compile is
        # skipped or failed
        self.input_shardings: Optional[list] = input_shardings
        self.output_shardings: Optional[list] = output_shardings


def _memory_columns(compiled) -> Optional[dict]:
    """The per-program memory ledger: XLA's static memory analysis of the
    compiled executable, as integer byte columns + the peak estimate the
    memory_budget rule caps. ``peak_bytes`` = argument + output + temp +
    generated-code − aliased (donated buffers alias into outputs, so they
    are counted once) — XLA's own working-set accounting of the program."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    cols = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    cols["peak_bytes"] = (cols["argument_bytes"] + cols["output_bytes"]
                          + cols["temp_bytes"]
                          + cols["generated_code_bytes"]
                          - cols["alias_bytes"])
    return cols


def _cost_flops(compiled) -> Optional[float]:
    """Analytic FLOPs of the optimized program (same source the MFU column
    uses; a scan body is counted once regardless of trip count)."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    return flops if flops > 0 else None


def trace_and_export(built: BuiltProgram,
                     platforms=("tpu",)) -> Artifacts:
    """Trace the closed jaxpr, cross-platform-export the module on the CPU
    host (the lowering-check methodology: the whole StableHLO (+Pallas)
    lowering stack runs without a chip, tools/tpu_attn_lowering_check.py),
    and compile for the host backend to capture the executable's
    memory/cost analysis (the memory_budget ledger)."""
    import contextlib

    import jax.export

    mesh_ctx = (built.mesh if built.mesh is not None
                else contextlib.nullcontext())
    with mesh_ctx, built.trace_ctx():
        closed = built.fn.trace(*built.args).jaxpr
        mlir_text = serialized = export_error = None
        try:
            exp = jax.export.export(built.fn, platforms=list(platforms))(
                *built.args)
            mlir_text = exp.mlir_module()
            serialized = len(exp.mlir_module_serialized)
        except Exception as e:
            export_error = f"{type(e).__name__}: {str(e)[:300]}"
        memory = cost_flops = compile_error = None
        in_sh = out_sh = None
        if not built.capture_memory:
            compile_error = ("capture_memory disabled for this program "
                             "(chip-tier row: host compile prohibitive or "
                             "impossible)")
        else:
            try:
                import jax

                compiled = built.fn.lower(*built.args).compile()
                memory = _memory_columns(compiled)
                cost_flops = _cost_flops(compiled)
                # the sharding auditor's ground truth (rules 7/9): the
                # executable's resolved I/O shardings, flattened in arg /
                # output pytree order
                in_sh = jax.tree.leaves(compiled.input_shardings[0])
                out_sh = jax.tree.leaves(compiled.output_shardings)
            except Exception as e:  # un-compilable on the host backend:
                # memory_budget skips with the reason, other rules still run
                compile_error = f"{type(e).__name__}: {str(e)[:300]}"
    return Artifacts(built, closed, mlir_text, serialized, export_error,
                     memory=memory, cost_flops=cost_flops,
                     compile_error=compile_error,
                     input_shardings=in_sh, output_shardings=out_sh)


def _walk_eqns(jaxpr):
    """Yield every eqn in a (Closed)Jaxpr including sub-jaxprs (scan/pjit/
    cond/remat bodies)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            vals = p if isinstance(p, (list, tuple)) else (p,)
            for v in vals:
                if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                    yield from _walk_eqns(v)


def _skip(reason):
    return {"ok": True, "skipped": True, "reason": reason}


def _need_mlir(art: Artifacts):
    if art.mlir_text is None:
        return _skip(f"export unavailable: {art.export_error}")
    return None


def rule_constant_bloat(art: Artifacts) -> dict:
    import numpy as np

    m = art.manifest
    consts = getattr(art.jaxpr, "consts", [])
    sizes = sorted(
        int(np.prod(np.shape(c))) * np.dtype(getattr(c, "dtype", np.float32)
                                             ).itemsize
        for c in consts
    )
    biggest = sizes[-1] if sizes else 0
    res = {"max_constant_bytes": biggest, "num_constants": len(sizes),
           "module_bytes": art.serialized_bytes}
    if biggest > m.max_constant_bytes:
        return {"ok": False, **res,
                "error": f"closed-over constant of {biggest} bytes embedded "
                         f"in the program (limit {m.max_constant_bytes}) — "
                         f"generate it in-graph instead "
                         f"(rng.random_projection_factors_in_graph)"}
    if art.serialized_bytes is None:
        return {**_skip(f"export unavailable: {art.export_error}"), **res}
    if art.serialized_bytes > m.max_module_bytes:
        return {"ok": False, **res,
                "error": f"serialized module is {art.serialized_bytes} bytes "
                         f"(limit {m.max_module_bytes}) — a large array is "
                         f"being baked into the program (PERF_HISTORY.md §4)"}
    return {"ok": True, **res}


def _expected_donated(built: BuiltProgram):
    import jax

    m = built.manifest
    if m.require_donated is None:
        return None
    if m.require_donated == "state":
        return len(jax.tree.leaves(built.args[0]))
    return int(m.require_donated)


def rule_donation(art: Artifacts) -> dict:
    import collections

    import jax

    expected = _expected_donated(art.built)
    if expected is None:
        return _skip("manifest.require_donated is None (timing-harness "
                     "loops re-call with the same state and cannot donate)")
    missing = _need_mlir(art)
    if missing:
        return missing
    txt = art.mlir_text
    observed = (len(re.findall(r"jax\.buffer_donor\s*=\s*true", txt))
                + len(re.findall(r"tf\.aliasing_output", txt)))
    res = {"expected_donated": expected, "observed_donated": observed}
    if observed != expected:
        return {"ok": False, **res,
                "error": f"{observed} inputs carry a donation attr in the "
                         f"exported module but the state carry has "
                         f"{expected} leaves — donation is requested in jit "
                         f"but not reaching the module (dropped "
                         f"donate_argnums?); the carry will be copied, "
                         f"doubling its HBM footprint"}
    # feasibility: XLA aliases a donated input only into an output of
    # identical shape/dtype; every carry leaf must find a distinct one
    # or the donation silently degrades to a copy at compile time
    outs = collections.Counter(
        (tuple(a.shape), str(a.dtype)) for a in art.jaxpr.out_avals
    )
    unmatched = []
    for leaf in jax.tree.leaves(art.built.args[0]):
        key = (tuple(leaf.shape), str(leaf.dtype))
        if outs[key] > 0:
            outs[key] -= 1
        else:
            unmatched.append(key)
    if unmatched:
        return {"ok": False, **res,
                "error": f"{len(unmatched)} donated inputs have no "
                         f"same-shape/dtype output to alias into (first: "
                         f"{unmatched[0]}) — XLA will keep the input buffer "
                         f"live and the donation is a no-op"}
    return {"ok": True, **res}


def rule_dtype(art: Artifacts) -> dict:
    m = art.manifest
    # jaxpr side runs even when export is blocked: f64 avals anywhere?
    wide = set()
    for eqn in _walk_eqns(art.jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and str(dt) in ("float64", "complex128"):
                wide.add(str(dt))
    if wide:
        return {"ok": False, "found": sorted(wide),
                "error": f"{sorted(wide)} values in the jaxpr — double "
                         f"precision never belongs in a chip-bound program "
                         f"(silent 2x HBM + emulated math on TPU)"}
    promos = set()
    if "bf16" in m.allowed_dtypes:
        for eqn in _walk_eqns(art.jaxpr):
            if any(hasattr(v, "jaxpr") or hasattr(v, "eqns")
                   for p in eqn.params.values()
                   for v in (p if isinstance(p, (list, tuple)) else (p,))):
                continue  # container (scan/pjit/remat/...): its body is
                # walked separately; mixed carry dtypes are not a site
            ins = {str(getattr(getattr(v, "aval", None), "dtype", ""))
                   for v in eqn.invars}
            outs = {str(getattr(getattr(v, "aval", None), "dtype", ""))
                    for v in eqn.outvars}
            if "bfloat16" in ins and "float32" in outs:
                promos.add(str(eqn.primitive))
        rogue = promos - set(m.bf16_promotion_whitelist)
        if rogue:
            return {"ok": False, "promotion_sites": sorted(promos),
                    "error": f"bf16->f32 promotion at non-whitelisted "
                             f"primitives {sorted(rogue)} — only explicit "
                             f"accumulation converts "
                             f"({m.bf16_promotion_whitelist}) may promote"}
    missing = _need_mlir(art)
    res = {"promotion_sites": sorted(promos)} if promos else {}
    if missing:
        return {**missing, **res}
    types = set(_TENSOR_ELEM_RE.findall(art.mlir_text))
    res["element_types"] = sorted(types)
    hard_bad = types & {"f64", "complex<f64>"}
    if hard_bad:
        return {"ok": False, **res,
                "error": f"{sorted(hard_bad)} tensors in the exported module"}
    extra = types - m.allowed_dtypes
    if extra:
        return {"ok": False, **res,
                "error": f"element types {sorted(extra)} not in the "
                         f"manifest's allowed set {sorted(m.allowed_dtypes)}"}
    missing_req = m.required_dtypes - types
    if missing_req:
        # the narrow-wire contract (ISSUE 15): a manifest that declares a
        # narrow wire dtype REQUIRES it in the module — a silently-f32
        # "narrow" program means the quantize was dropped or DCE'd and
        # the wire is wide again under a narrow name
        return {"ok": False, **res,
                "error": f"manifest requires element types "
                         f"{sorted(m.required_dtypes)} in the module but "
                         f"{sorted(missing_req)} never appear — a "
                         f"narrow-wire program whose wire is silently f32 "
                         f"(dropped/dead-code-eliminated quantize?)"}
    return {"ok": True, **res}


def count_collectives(mlir_text: str) -> dict:
    return {k: len(re.findall(rf"stablehlo\.{k}\b", mlir_text))
            for k in COLLECTIVE_KINDS}


def rule_collectives(art: Artifacts) -> dict:
    m = art.manifest
    if m.collectives is None:
        return _skip("manifest.collectives is None (GSPMD-deferred or "
                     "kernel-only program)")
    missing = _need_mlir(art)
    if missing:
        return missing
    observed = count_collectives(art.mlir_text)
    expected = {k: int(m.collectives.get(k, 0)) for k in COLLECTIVE_KINDS}
    unknown = set(m.collectives) - set(COLLECTIVE_KINDS)
    if unknown:
        return {"ok": False, "observed": observed,
                "error": f"manifest names unknown collective kinds "
                         f"{sorted(unknown)}"}
    if observed != expected:
        diff = {k: (expected[k], observed[k]) for k in COLLECTIVE_KINDS
                if expected[k] != observed[k]}
        return {"ok": False, "observed": observed, "expected": expected,
                "error": f"explicit collective counts drifted from the "
                         f"manifest (kind: expected, observed) {diff} — if "
                         f"the change is a deliberate algorithm change, "
                         f"update the manifest (PERF_HISTORY.md §6)"}
    return {"ok": True, "observed": observed}


def rule_host_traffic(art: Artifacts) -> dict:
    m = art.manifest
    hits = []
    for eqn in _walk_eqns(art.jaxpr):
        if str(eqn.primitive) in _HOST_PRIMS:
            hits.append(f"jaxpr:{eqn.primitive}")
    if art.mlir_text is not None:
        txt = art.mlir_text
        for op in re.findall(r"stablehlo\.(infeed|outfeed|send|recv)\b", txt):
            hits.append(f"mlir:{op}")
        for target in re.findall(r'custom_call\s*@([\w.$]+)', txt):
            if not _SAFE_CUSTOM_CALLS.match(target):
                hits.append(f"custom_call:{target}")
    res = {"transfers": len(hits), "sites": hits[:8]}
    if len(hits) > m.host_transfer_budget:
        return {"ok": False, **res,
                "error": f"{len(hits)} host-transfer sites (budget "
                         f"{m.host_transfer_budget}) — a host hop inside "
                         f"the program serializes every scanned chunk on "
                         f"the dispatch link (PERF_HISTORY.md §0): {hits[:4]}"}
    return {"ok": True, **res}


def rule_memory_budget(art: Artifacts) -> dict:
    m = art.manifest
    if m.max_peak_bytes is None:
        return _skip("manifest.max_peak_bytes is None")
    if art.memory is None:
        return _skip(f"memory analysis unavailable: "
                     f"{art.compile_error or 'backend reported none'}")
    res = {"memory": art.memory, "flops": art.cost_flops}
    peak = art.memory["peak_bytes"]
    if peak > m.max_peak_bytes:
        return {"ok": False, **res,
                "error": f"peak-memory estimate {peak} bytes exceeds the "
                         f"manifest budget {m.max_peak_bytes} — the "
                         f"program's working set outgrew its declared "
                         f"budget (dropped donation? lost remat? an "
                         f"accidental materialized temp?); raise the "
                         f"manifest only for a deliberate change "
                         f"(PERF_HISTORY.md §8)"}
    return {"ok": True, **res}


from draco_tpu.analysis.sharding import (  # noqa: E402 (rule wiring)
    rule_collective_axes,
    rule_replication_leaks,
    rule_sharding_contract,
)

_RULES = {
    "constant_bloat": rule_constant_bloat,
    "donation": rule_donation,
    "dtype": rule_dtype,
    "collectives": rule_collectives,
    "host_traffic": rule_host_traffic,
    "memory_budget": rule_memory_budget,
    "sharding_contract": rule_sharding_contract,
    "collective_axes": rule_collective_axes,
    "replication_leaks": rule_replication_leaks,
}


def lint_built(built: BuiltProgram, platforms=("tpu",), only=None) -> dict:
    """Run the rules; returns the report row for this program.

    ``lint_ok`` is True iff no rule failed AND the export either succeeded
    or was blocked by host traffic that the host rule already flagged (any
    other export failure is reported as the synthetic rule ``export``).
    ``only`` restricts to a subset of rule names (the
    ``tools/program_lint.py --only`` fast-iteration path); the row then
    carries just those rules.
    """
    names = RULE_NAMES if only is None else tuple(
        n for n in RULE_NAMES if n in set(only))
    art = trace_and_export(built, platforms=platforms)
    rules = {name: _RULES[name](art) for name in names}
    failed = [n for n in names if not rules[n]["ok"]]
    if art.export_error is not None and "host_traffic" not in failed:
        rules["export"] = {"ok": False, "error": art.export_error}
        failed.append("export")
    return {
        "lint_ok": not failed,
        "failed_rules": failed,
        "rules": rules,
        "export_platforms": list(platforms),
        **built.extra,
    }


def lint_program(program: LintProgram, only=None) -> dict:
    """Build + lint one registered program (the tools' row thunk)."""
    row = lint_built(program.build(), platforms=program.export_platforms,
                     only=only)
    return {"ok": row["lint_ok"], "route": program.route, **row}
