#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the coded trainer still starts, and is
still right, on a TPU.

    python3 chip_smoke.py              # one chip: device, kernels, cnn, lm
    python3 chip_smoke.py --multichip  # four chips: the sharded paths only

One process, the entry points a user calls (``draco_tpu.cli.main``;
``Trainer(cfg, mesh=…)`` / ``train_sp(cfg, mesh)`` for the mesh comparison),
models at full width with random seeded weights and seeded synthetic data,
no network, no git. Every phase prints one JSON line (``phase``, ``ok``,
``seconds``, what it checked); the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase passed. If the first device is not
a TPU the script fails at once — there is no CPU continuation and no option
or variable that relaxes the check. The per-step human log of the CLI goes to
stderr. ms/step and peak bytes on the phase lines are information, not claims.

The phase functions take their sizes as arguments so that a rehearsal on the
CPU (tests/test_chip_smoke.py) can import this file and call them small, with
the kernels in interpret mode; ``main`` always calls them at the real sizes
below.

Phases
  device    what jax attached, versions, compile-cache directory, whether the
            native library built from source
  kernels   every Pallas kernel the main path can select, compiled for the
            chip (``tpu_custom_call`` must be in the compiled text — a kernel
            that silently became XLA fails) against its jnp reference at
            ResNet-18 widths: cyclic locator, approx decode f32/bf16/int8,
            narrow cyclic recombine bf16/int8, flash attention fwd + grad
  cnn       ``cyclic-resnet18`` preset unchanged (ResNet-18, n=9, s=1,
            rev_grad, batch 32/worker, f32): K=1 and K=3 attacked, K=1 with no
            adversary, and the uncoded mean
  lm        TransformerLM dim 768 × 8 layers, 12 heads, vocab 8192, T=512,
            batch 2/worker, n=8, cyclic s=1 rev_grad shared, bf16, flash: the
            same four runs

The guarantee under test is EXACT RECOVERY: the run with a live adversary
must reproduce the run without one. tests/test_train_step.py holds each
decoded update to ``rtol=2e-3, atol=2e-5`` of the clean one. To first order a
relative perturbation ε of every update so far moves the loss by at most
ε × (the loss movement those updates produced), which over these first steps
is below the loss itself; so the per-step losses of the two runs are held to
the same pair, ``|L_att − L_clean| ≤ 2e-3·|L_clean| + 2e-5``. The same band
holds K=1 against K=3 (one scanned program against three dispatches) and,
under ``--multichip``, four chips against one.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

# decoded-update tolerance of tests/test_train_step.py:142,158 — the band the
# attacked-equals-clean and K-equivalence loss checks derive from (docstring)
RTOL, ATOL = 2e-3, 2e-5

CNN_PRESET = "cyclic-resnet18"
CNN_STEPS, CNN_CHUNK = 6, 3
# the LM width this repo has measured before (PERF.md §5: d ≈ 63 M), as
# TrainConfig fields — lm_argv() spells them as CLI flags
LM_CONFIG = dict(network="TransformerLM", dataset="synthetic-text",
                 approach="cyclic", worker_fail=1, err_mode="rev_grad",
                 redundancy="shared", compute_dtype="bfloat16",
                 attn_impl="flash", lr=0.01, momentum=0.9, model_dim=768,
                 model_layers=8, model_heads=12, vocab=8192, seq_len=512,
                 batch_size=2, num_workers=8)
LM_STEPS, LM_CHUNK = 4, 2
FLASH_SHAPE = (2, 1024, 12, 64)
MULTICHIP_STEPS = 4


# ---------------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------------

class Phase:
    """One JSON line per phase: named checks (all must hold) + information."""

    def __init__(self, name: str):
        self.name = name
        self.t0 = time.perf_counter()
        self.info: dict = {}
        self.failed: list = []
        self.cache0 = dict(_CACHE_EVENTS)

    def check(self, name: str, cond, **info) -> bool:
        self.info.update(info)
        if not bool(cond):
            self.failed.append(name)
        return bool(cond)

    def done(self) -> bool:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        line = {"phase": self.name, "ok": not self.failed,
                "seconds": round(time.perf_counter() - self.t0, 2)}
        if self.failed:
            line["failed_checks"] = self.failed
        line.update(self.info)
        # peaks are the PROCESS's so far, not this phase's. `in_use` counts
        # the arrays the program holds; a step program's scratch space
        # shows up under `reserved`
        for key in ("peak_bytes_in_use", "peak_bytes_reserved",
                    "bytes_in_use"):
            line[key] = stats.get(key)
        line["cache_hits"] = _CACHE_EVENTS["hits"] - self.cache0["hits"]
        line["cache_misses"] = _CACHE_EVENTS["misses"] - self.cache0["misses"]
        print(json.dumps(line), flush=True)
        return not self.failed


# persistent-compile-cache traffic of this process, from jax's own events
_CACHE_EVENTS = {"hits": 0, "misses": 0}


def _count_cache_events() -> None:
    import jax.monitoring

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _CACHE_EVENTS["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _CACHE_EVENTS["misses"] += 1

    jax.monitoring.register_event_listener(on_event)


def _rel_err(got, want) -> float:
    """max |got − want| over max |want| — NaN if anything is not finite."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("nan")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _compile(fn, *args):
    """AOT-compile a jitted function; return (executable, has_custom_call)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device(cache_dir: str, want_count: int) -> bool:
    import importlib.metadata as md

    import jax
    import jaxlib

    from draco_tpu import native

    ph = Phase("device")
    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    ph.check("device_count", len(jax.devices()) >= want_count,
             platform=dev.platform, device_kind=dev.device_kind,
             count=len(jax.devices()),
             memory_stats_keys=sorted((dev.memory_stats() or {}).keys()),
             jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
             compile_cache_dir=cache_dir,
             compile_cache_placed_by_env=bool(
                 os.environ.get("JAX_COMPILATION_CACHE_DIR")),
             # rebuilt from native/*.cpp by this run (main deletes any
             # binary the copy brought along), or unavailable and why
             native_available=native.AVAILABLE,
             native_build_error=native.BUILD_ERROR)
    return ph.done()


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def check_cyclic_locator(ph: Phase, n: int, s: int, layers: int,
                         interpret: bool, lam: float = 0.0) -> None:
    """Kernel vs the SAME locator_core lowered through XLA on this device:
    identical honest/flagged/loud sets, v pair and residual at solve noise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from draco_tpu.coding import cyclic
    from draco_tpu.ops import decode_kernels as dk

    tag = f"locator_n{n}_s{s}" + ("_lam" if lam else "")
    code = cyclic.build_cyclic_code(n, s)
    m = n - 2 * s
    rs = np.random.RandomState(n * 100 + s)
    c1 = code.c1_re.astype(np.float64) + 1j * code.c1_im
    cols = c1 @ (rs.randn(m, layers) + 1j * rs.randn(m, layers))
    for col in range(layers):  # s corrupt rows per column, 100× honest scale
        cols[rs.choice(n, size=s, replace=False), col] *= -100.0
    e_re = jnp.asarray(cols.real.astype(np.float32))
    e_im = jnp.asarray(cols.imag.astype(np.float32))
    pres = jnp.ones((n, 1), jnp.float32)
    consts = tuple(jnp.asarray(getattr(code, k)) for k in (
        "c2h_re", "c2h_im", "c1_re", "c1_im", "est_re", "est_im"))

    def kernel(e_re, e_im, pres):
        return dk.cyclic_locator(code, e_re, e_im, pres,
                                 cyclic.HEALTH_REL_TOL, interpret=interpret,
                                 lam=lam)

    def reference(e_re, e_im, pres):
        return cyclic.locator_core(e_re, e_im, *consts, pres, code.s,
                                   cyclic.HEALTH_REL_TOL, lam=lam)

    compiled, custom = _compile(kernel, e_re, e_im, pres)
    got = jax.device_get(compiled(e_re, e_im, pres))
    want = jax.device_get(jax.jit(reference)(e_re, e_im, pres))
    ph.check(f"{tag}_is_tpu_custom_call", custom or interpret)
    ph.check(f"{tag}_sets_identical",
             all(np.array_equal(got[i], want[i]) for i in (2, 3, 4)))
    ph.check(f"{tag}_locates_every_corrupt_row",
             (np.asarray(got[2]).sum(axis=0) == m).all()
             and (np.asarray(got[3]).sum(axis=0) == s).all())
    v_err = max(_rel_err(got[0], want[0]), _rel_err(got[1], want[1]))
    ph.check(f"{tag}_v_matches_reference", v_err < 1e-3,
             **{f"{tag}_v_rel_err": v_err,
                f"{tag}_residual_max": float(np.max(got[5]))})
    ph.check(f"{tag}_residual_at_noise",
             float(np.max(got[5])) < cyclic.HEALTH_REL_TOL
             and float(np.max(want[5])) < cyclic.HEALTH_REL_TOL)


# the wire kernels' gate: 1e-2 of the result's scale. An f32 matmul on the MXU
# may round its operands to bf16 (2^-8 per product, n ≤ 9 products per output)
WIRE_GATE = 1e-2
WIRE_BLOCK = 256  # cfg.shadow_block default: the int8 wire's scale granularity


def _wire_codec(mode: str):
    """(narrow, widen) for one wire dtype, each ONE jitted program: run
    eagerly, the quantizer's dozen elementwise steps each hold an (n, d)
    temporary — 15 GB of a 16 GB chip at d = 11 M."""
    import jax

    from draco_tpu.obs import numerics as nx

    return (jax.jit(lambda x: nx.narrow_wire_rows(x, mode, WIRE_BLOCK)),
            jax.jit(lambda buf: nx.widen_wire_rows(buf, mode, WIRE_BLOCK)))


def check_approx_kernels(ph: Phase, n: int, d: int, interpret: bool) -> None:
    """approx_decode — f32 and the bf16 / int8 narrow-ingest variants —
    against plain jnp at full f32 precision."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.coding import approx

    hi = jax.lax.Precision.HIGHEST
    impl = "pallas_interpret" if interpret else "pallas"
    bg = jax.random.normal(jax.random.key(0), (n, d), jnp.float32)
    present = jnp.ones((n,), bool).at[n // 2].set(False)
    code = approx.build_approx_code(n, 1.5)
    rows = approx.encode_shared(code, bg) * present[:, None]
    v = approx.decode_weights(code, present)[0]
    true_mean = jnp.sum(bg, axis=0) / n
    g_scale = jnp.sqrt(jnp.sum(bg ** 2)) / n
    for mode in ("f32", "bf16", "int8"):
        # the narrow buffers enter as ARGUMENTS (a closed-over array would
        # be baked into the program as a 100 MB constant)
        buf, wide = None, rows
        if mode != "f32":
            narrow, widen = _wire_codec(mode)
            buf = narrow(rows)
            wide = widen(buf)

        def kernel(wide, bg, present, buf, mode=mode):
            dec, _, health = approx.decode(
                code, wide, present=present, with_health=True,
                batch_grads=bg, impl=impl,
                wire=None if buf is None else (mode, buf, WIRE_BLOCK))
            return dec, health["residual"]

        compiled, custom = _compile(kernel, wide, bg, present, buf)
        dec, resid = compiled(wide, bg, present, buf)
        want = jnp.matmul(v / n, jnp.where(present[:, None], wide, 0.0),
                          precision=hi)
        want_resid = float(jnp.sqrt(jnp.sum((want - true_mean) ** 2))
                           / g_scale)
        tag = f"approx_decode_{mode}"
        ph.check(f"{tag}_is_tpu_custom_call", custom or interpret)
        err = _rel_err(dec, want)
        ph.check(f"{tag}_matches_reference", err < WIRE_GATE,
                 **{f"{tag}_rel_err": err})
        ph.check(f"{tag}_residual_matches_reference",
                 abs(float(resid) - want_resid)
                 < WIRE_GATE * max(want_resid, 1.0))
        del buf, wide, dec, want, compiled  # (n, d) each: free before next


def check_recombine_kernels(ph: Phase, n: int, d: int,
                            interpret: bool) -> None:
    """The narrow cyclic recombine (bf16 / int8 wire) against plain jnp."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.coding import cyclic
    from draco_tpu.ops import decode_kernels as dk

    hi = jax.lax.Precision.HIGHEST
    code = cyclic.build_cyclic_code(n, 1)
    enc_re, enc_im = cyclic.encode_shared(
        code, jax.random.normal(jax.random.key(0), (n, d), jnp.float32))
    v_re = jax.random.normal(jax.random.key(1), (n,), jnp.float32)
    v_im = jax.random.normal(jax.random.key(2), (n,), jnp.float32)
    for mode in ("bf16", "int8"):
        narrow, widen = _wire_codec(mode)
        buf_re, buf_im = narrow(enc_re), narrow(enc_im)

        def kernel(v_re, v_im, buf_re, buf_im, mode=mode):
            return dk.cyclic_narrow_recombine(
                v_re, v_im, (mode, buf_re, buf_im, WIRE_BLOCK),
                interpret=interpret)

        compiled, custom = _compile(kernel, v_re, v_im, buf_re, buf_im)
        got = compiled(v_re, v_im, buf_re, buf_im)
        want = (jnp.matmul(v_re, widen(buf_re), precision=hi)
                - jnp.matmul(v_im, widen(buf_im), precision=hi))
        tag = f"cyclic_recombine_{mode}"
        ph.check(f"{tag}_is_tpu_custom_call", custom or interpret)
        err = _rel_err(got, want)
        ph.check(f"{tag}_matches_reference", err < WIRE_GATE,
                 **{f"{tag}_rel_err": err})
        del buf_re, buf_im, got, want, compiled


def check_flash_attention(ph: Phase, shape, interpret: bool) -> None:
    """Flash forward and gradient (of Σ sin(o)) against dense attention. Gates from the last hardware study of the
    same comparison (2026-08-02: 4.7e-4 forward, 1.7e-2 gradient, absolute,
    on O(1) values), with headroom."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.ops.flash_attention import flash_attention
    from draco_tpu.parallel.ring_attention import dense_attention

    q, k, v = (jax.random.normal(jax.random.key(i), shape, jnp.float32)
               for i in range(3))

    def flash(q, k, v):
        return flash_attention(q, k, v, force=True, interpret=interpret)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=True)

    def grads(attn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v))),
                        argnums=(0, 1, 2))

    fwd, custom_f = _compile(flash, q, k, v)
    bwd, custom_b = _compile(grads(flash), q, k, v)
    ph.check("flash_fwd_is_tpu_custom_call", custom_f or interpret)
    ph.check("flash_grad_is_tpu_custom_call", custom_b or interpret)
    fwd_err = _rel_err(fwd(q, k, v), jax.jit(dense)(q, k, v))
    want_g = jax.jit(grads(dense))(q, k, v)
    grad_err = max(_rel_err(a, b) for a, b in zip(bwd(q, k, v), want_g))
    ph.check("flash_fwd_matches_dense", fwd_err < 5e-3,
             flash_fwd_rel_err=fwd_err)
    ph.check("flash_grad_matches_dense", grad_err < 5e-2,
             flash_grad_rel_err=grad_err)


def phase_kernels(n: int, d: int, layers: int, flash_shape,
                  interpret: bool = False) -> bool:
    from draco_tpu.ops.decode_kernels import resolve_decode_impl

    ph = Phase("kernels")
    ph.info["auto_resolves_to"] = resolve_decode_impl("auto")
    ph.info["shapes"] = {"n": n, "d": d, "layers": layers,
                         "flash": list(flash_shape)}
    check_cyclic_locator(ph, n, 1, layers, interpret)
    check_cyclic_locator(ph, n, 2, layers, interpret)  # the cyclic-vgg11 code
    check_cyclic_locator(ph, n, 1, layers, interpret, lam=2.0 ** -7)
    # one function each: their (n, d) operands die with the frame, and the
    # phase stays well inside the chip's memory
    check_approx_kernels(ph, n, d, interpret)
    gc.collect()
    check_recombine_kernels(ph, n, d, interpret)
    gc.collect()
    check_flash_attention(ph, flash_shape, interpret)
    return ph.done()


def resnet18_shape():
    """(d, parameter-leaf count) of the preset's model, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.models import build_model, input_shape

    model = build_model("ResNet18")
    x = jnp.zeros((2, *input_shape("synthetic-cifar10")), jnp.float32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), x, train=False))
    leaves = jax.tree.leaves(shapes["params"])
    return sum(int(l.size) for l in leaves), len(leaves)


# ---------------------------------------------------------------------------
# phases: cnn / lm — four runs each through draco_tpu.cli.main
# ---------------------------------------------------------------------------

def _flags(**kw) -> list:
    return [x for k, v in kw.items()
            for x in (f"--{k.replace('_', '-')}", str(v))]


def cnn_argv() -> list:
    """The cyclic-resnet18 preset, field for field, as explicit flags (the
    CLI's --preset path keeps its own steps-per-call and adversary count);
    only the data is pinned to the seeded synthetic set."""
    from draco_tpu.presets import PRESETS

    p = PRESETS[CNN_PRESET]
    return _flags(approach=p.approach, network=p.network,
                  dataset="synthetic-cifar10", num_workers=p.num_workers,
                  worker_fail=p.worker_fail, err_mode=p.err_mode,
                  batch_size=p.batch_size, lr=p.lr, momentum=p.momentum,
                  redundancy=p.redundancy, compute_dtype=p.compute_dtype)


def lm_argv() -> list:
    return _flags(**LM_CONFIG)


def _cli_run(argv: list, steps: int, chunk: int, root: str, name: str):
    """One training run through the CLI entry point. Returns (metrics rows,
    final status.json)."""
    from draco_tpu import cli

    train_dir = os.path.join(root, name)
    full = argv + _flags(max_steps=steps, steps_per_call=chunk, eval_freq=0,
                         log_every=1, train_dir=train_dir,
                         compile_guard="raise")
    with contextlib.redirect_stdout(sys.stderr):  # the human per-step log
        cli.main(full)
    gc.collect()  # the run's device buffers, before the next run allocates
    with open(os.path.join(train_dir, "status.json")) as fh:
        return _metric_rows(train_dir), json.load(fh)


def _metric_rows(train_dir: str) -> list:
    with open(os.path.join(train_dir, "metrics.jsonl")) as fh:
        return [json.loads(line) for line in fh]


def _located_every_step(rows: list) -> bool:
    """A live adversary on every step, and every one of them located."""
    return all(r["det_adv"] >= 1 and r["det_tp"] == r["det_adv"]
               and r["located_errors"] == r["det_adv"] for r in rows)


def _ms_per_step(rows: list):
    """Host-clock ms/step of an eager (K=1) run after its first step (which
    compiles): each row is stamped when its step's metrics were fetched. A
    chunked run stamps a whole chunk at its flush, so it has no such clock."""
    if len(rows) < 2:
        return None
    dt = rows[-1]["time"] - rows[0]["time"]
    return round(1000.0 * dt / (len(rows) - 1), 2)


def _within_band(a_rows: list, b_rows: list):
    """(every |a − b| within RTOL·|b| + ATOL, worst |a − b| / band)."""
    ratios = [abs(a["loss"] - b["loss"]) / (RTOL * abs(b["loss"]) + ATOL)
              for a, b in zip(a_rows, b_rows)]
    return len(a_rows) == len(b_rows) and max(ratios) <= 1.0, max(ratios)


def phase_training(name: str, argv: list, steps: int, chunk: int) -> bool:
    """attacked K=1, attacked K=chunk, clean K=1 (same code, no live
    adversary), uncoded mean — and what must hold between them."""
    import math

    ph = Phase(name)
    uncoded = [x for flag, value in zip(argv[::2], argv[1::2])
               if flag not in ("--approach", "--worker-fail", "--redundancy")
               for x in (flag, value)]
    with tempfile.TemporaryDirectory(prefix=f"chip_smoke_{name}_") as root:
        runs = {
            "attacked_k1": _cli_run(argv, steps, 1, root, "attacked_k1"),
            "attacked_kn": _cli_run(argv, steps, chunk, root, "attacked_kn"),
            "clean_k1": _cli_run(argv + _flags(adversary_count=0), steps, 1,
                                 root, "clean_k1"),
            "mean_k1": _cli_run(
                uncoded + _flags(approach="baseline", mode="normal",
                                 worker_fail=0), steps, 1, root, "mean_k1"),
        }
    for run, (rows, status) in runs.items():
        losses = [r["loss"] for r in rows]
        ph.check(f"{run}_ran_every_step", len(rows) == steps)
        ph.check(f"{run}_losses_finite_and_decreasing",
                 all(math.isfinite(x) for x in losses)
                 and losses[-1] < losses[0], **{f"{run}_losses": losses})
        ph.check(f"{run}_status_done", status.get("state") == "done")
        # the PR 5 compile sentinel ran under guard=raise: a build after the
        # first dispatch of any program would have raised RetraceError
        ph.check(f"{run}_zero_compiles_after_first_step",
                 status.get("steady_recompiles") == 0
                 and status.get("compiles", 0) >= 1,
                 **{f"{run}_compiles": status.get("compiles")})
    for run in ("attacked_k1", "attacked_kn"):
        ph.check(f"{run}_adversary_located_every_step",
                 _located_every_step(runs[run][0]))
    ph.check("clean_run_has_no_adversary",
             all(r["det_adv"] == 0 and r["located_errors"] == 0
                 for r in runs["clean_k1"][0]))
    ok, worst = _within_band(runs["attacked_k1"][0], runs["clean_k1"][0])
    ph.check("attacked_equals_clean", ok,
             attacked_vs_clean_worst_fraction_of_band=worst,
             band=f"{RTOL}*|loss|+{ATOL}")
    ok, worst = _within_band(runs["attacked_kn"][0], runs["attacked_k1"][0])
    ph.check("k1_equals_kn", ok, kn_vs_k1_worst_fraction_of_band=worst,
             chunk=chunk)
    ph.info["ms_per_step"] = {run: _ms_per_step(rows)
                              for run, (rows, _) in runs.items()
                              if run != "attacked_kn"}
    return ph.done()


# ---------------------------------------------------------------------------
# --multichip: the same configurations on a 4-device mesh vs a 1-device mesh
# ---------------------------------------------------------------------------

def _worker_axis_collectives(text: str, mesh) -> list:
    """Lines of compiled HLO holding a collective whose replica groups are
    exactly the mesh's worker-axis groups (``w`` varies, every other axis
    fixed) — in both spellings XLA prints, explicit ``{{0,2},{1,3}}`` and
    iota ``[2,2]<=[2,2]T(1,0)``."""
    import re

    import numpy as np

    ids = np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    w_axis = mesh.axis_names.index("w")
    want = sorted(map(tuple, np.moveaxis(ids, w_axis, -1)
                      .reshape(-1, mesh.devices.shape[w_axis]).tolist()))
    out = []
    for line in text.splitlines():
        if not re.search(r" (all-reduce|all-gather|reduce-scatter|all-to-all)"
                         r"(-start)?\(", line):
            continue
        groups = None
        explicit = re.search(r"replica_groups=\{(\{[0-9,{} ]*\})\}", line)
        iota = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
                         r"(?:T\(([0-9,]+)\))?", line)
        if explicit:
            groups = [tuple(int(x) for x in g.split(","))
                      for g in re.findall(r"\{([0-9, ]+)\}",
                                          explicit.group(1))]
        elif iota:
            dims = [int(x) for x in iota.group(3).split(",")]
            perm = ([int(x) for x in iota.group(4).split(",")]
                    if iota.group(4) else list(range(len(dims))))
            groups = list(map(tuple, np.arange(int(np.prod(dims)))
                              .reshape(dims).transpose(perm)
                              .reshape(int(iota.group(1)),
                                       int(iota.group(2))).tolist()))
        if groups is not None and sorted(
                tuple(sorted(g)) for g in groups) == want:
            out.append(line.strip()[:200])
    return out


def multichip_cnn(ph: Phase, cfg, devices, steps: int, root: str) -> None:
    import numpy as np

    from draco_tpu.runtime import make_mesh, put_global
    from draco_tpu.training.trainer import Trainer

    losses = {}
    for tag, devs in (("w4", devices), ("w1", devices[:1])):
        run_cfg = dataclasses.replace(
            cfg, train_dir=os.path.join(root, f"cnn_{tag}"))
        tr = Trainer(run_cfg, mesh=make_mesh(cfg.num_workers, devices=devs),
                     quiet=True)
        try:
            if tag == "w4":
                # the batch as the trainer shards it: one block of workers
                # per device
                x, y = tr._host_batch(1)
                xb = put_global(np.asarray(x), tr._shard_w)
                ph.check("cnn_batch_shards_on_every_device",
                         {s.device for s in xb.addressable_shards}
                         == set(devices),
                         cnn_batch_shard_shape=list(
                             xb.addressable_shards[0].data.shape))
                adv = np.asarray(tr._adv_schedule[1])
                text = tr.setup.train_step.lower(
                    tr.state, xb, put_global(np.asarray(y), tr._shard_w),
                    adv).compile().as_text()
                rows_per_dev = cfg.num_workers // len(devices)
                # the partitioned program works on (n/4, d) blocks of the
                # gradient / encoded rows — never the whole (n, d) stack
                # before the worker-axis collective
                ph.check("cnn_encoded_rows_sharded_by_worker",
                         f"f32[{rows_per_dev},{tr.setup.dim}]" in text,
                         cnn_rows_per_device=rows_per_dev)
                coll = _worker_axis_collectives(text, tr.mesh)
                ph.check("cnn_has_worker_axis_collective", bool(coll),
                         cnn_collectives=len(coll), cnn_collective=coll[:1])
            with contextlib.redirect_stdout(sys.stderr):
                tr.run()
        finally:
            tr.close()
        losses[tag] = _metric_rows(run_cfg.train_dir)
        del tr
        gc.collect()
    _compare_meshes(ph, "cnn", losses, steps)


def multichip_lm(ph: Phase, cfg, devices, steps: int, root: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from draco_tpu.parallel import make_mesh_2d
    from draco_tpu.parallel.sp_step import (
        build_sp_train_setup, synthetic_text, train_sp)

    losses = {}
    for tag, devs, sp in (("w2sp2", devices, 2), ("w1", devices[:1], 1)):
        run_cfg = dataclasses.replace(
            cfg, seq_shards=sp, train_dir=os.path.join(root, f"lm_{tag}"))
        mesh = make_mesh_2d(cfg.num_workers, sp, devices=devs)
        if sp > 1:
            # the step program train_sp is about to run, built once more to
            # look inside it: tokens sharded (w, ·, sp) as its shard_map
            # declares, gradient rows (n/w, d) per device, a collective
            # over the worker axis
            setup = build_sp_train_setup(run_cfg, mesh)
            toks = jax.device_put(
                synthetic_text(cfg.seed, 1, cfg.num_workers, cfg.batch_size,
                               cfg.seq_len, cfg.vocab),
                NamedSharding(mesh, P("w", None, "sp")))
            ph.check("lm_batch_shards_on_every_device",
                     {s.device for s in toks.addressable_shards}
                     == set(devices),
                     lm_batch_shard_shape=list(
                         toks.addressable_shards[0].data.shape))
            text = setup.train_step.lower(
                setup.state, toks,
                np.zeros((cfg.num_workers,), bool)).compile().as_text()
            lanes = cfg.num_workers // mesh.shape["w"]
            ph.check("lm_gradient_rows_sharded_by_worker",
                     f"f32[{lanes},{setup.dim}]" in text,
                     lm_rows_per_device=lanes)
            coll = _worker_axis_collectives(text, mesh)
            ph.check("lm_has_worker_axis_collective", bool(coll),
                     lm_collectives=len(coll), lm_collective=coll[:1])
            del setup, toks
            gc.collect()
        with contextlib.redirect_stdout(sys.stderr):
            train_sp(run_cfg, mesh, quiet=True)
        losses[tag] = _metric_rows(run_cfg.train_dir)
        gc.collect()
    _compare_meshes(ph, "lm", losses, steps)


def _compare_meshes(ph: Phase, name: str, losses: dict, steps: int) -> None:
    """Four chips against one, held to the band of the module docstring —
    tests/test_train_step.py's ``rtol=2e-3, atol=2e-5``, the tolerance the
    virtual-device tests hold two layouts of one program to. (Their tighter
    1e-4 on a loss is an XLA:CPU figure: on the chip an f32 conv or matmul
    rounds its operands to bf16 at default precision, and two partitionings
    of the same step differ by ~1e-4 of the loss after four steps — measured
    9.8e-5 on ResNet-18, 2026-09-26.)"""
    (tag_a, a), (tag_b, b) = losses.items()
    ph.check(f"{name}_ran_every_step", len(a) == steps and len(b) == steps)
    ok, worst = _within_band(a, b)
    ph.check(f"{name}_losses_equal_one_chip", ok,
             **{f"{name}_losses_{tag_a}": [r["loss"] for r in a],
                f"{name}_losses_{tag_b}": [r["loss"] for r in b],
                f"{name}_worst_fraction_of_band": worst})
    ph.check(f"{name}_adversary_located_on_both_meshes",
             _located_every_step(a + b))


def phase_multichip(cnn_cfg, lm_cfg, steps: int, n_devices: int = 4) -> bool:
    import jax

    ph = Phase("multichip")
    devices = jax.devices()[:n_devices]
    if not ph.check("has_four_devices", len(devices) == n_devices,
                    devices=len(devices)):
        return ph.done()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_multichip_") as root:
        multichip_cnn(ph, cnn_cfg, devices, steps, root)
        multichip_lm(ph, lm_cfg, devices, steps, root)
    stats = [d.memory_stats() or {} for d in devices]
    peak = [st.get("peak_bytes_in_use") for st in stats]
    # a backend without memory_stats (the CPU rehearsal) reports None
    ph.check("every_device_held_bytes",
             all(p is None or p > 0 for p in peak),
             peak_bytes_in_use_per_device=peak,
             peak_bytes_reserved_per_device=[
                 st.get("peak_bytes_reserved") for st in stats])
    return ph.done()


def multichip_configs(steps: int):
    """The cnn and lm configurations of the one-chip phases at n=8 (a
    4-device w axis cannot hold the preset's n=9)."""
    from draco_tpu.config import TrainConfig
    from draco_tpu.presets import PRESETS

    common = dict(max_steps=steps, eval_freq=0, log_every=1,
                  compile_guard="raise")
    cnn = dataclasses.replace(PRESETS[CNN_PRESET], num_workers=8,
                              dataset="synthetic-cifar10", **common)
    return cnn, TrainConfig(**LM_CONFIG, **common)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(multichip: bool, device: dict) -> bool:
    # the native library is rebuilt from native/*.cpp, never loaded from a
    # binary that came along with a copy of the tree (it is gitignored and
    # rebuilt by mtime, so a stale one would win silently)
    stale = os.path.join(REPO, "draco_tpu", "native", "libdraco_native.so")
    if os.path.exists(stale):
        os.remove(stale)

    from draco_tpu.runtime import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    _count_cache_events()
    dev = jax.devices()[0]  # the one backend initialisation of this process
    device.update(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    if dev.platform != "tpu":
        print(json.dumps({
            "phase": "device", "ok": False, "seconds": 0.0,
            "failed_checks": ["platform_is_tpu"],
            "platform": dev.platform, "device_kind": dev.device_kind,
            "detail": "chip_smoke.py runs on a TPU only; nothing was run"}),
            flush=True)
        return False

    if multichip:
        cnn_cfg, lm_cfg = multichip_configs(MULTICHIP_STEPS)
        return (phase_device(cache_dir, want_count=4)
                and phase_multichip(cnn_cfg, lm_cfg, MULTICHIP_STEPS))
    d, layers = resnet18_shape()
    ok = phase_device(cache_dir, want_count=1)
    # every phase runs, whatever the earlier ones said: one call, all faults
    ok = phase_kernels(9, d, layers, FLASH_SHAPE) and ok
    ok = phase_training("cnn", cnn_argv(), CNN_STEPS, CNN_CHUNK) and ok
    ok = phase_training("lm", lm_argv(), LM_STEPS, LM_CHUNK) and ok
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip mesh comparison (cnn on a "
                         "4-device w axis, lm on w=2 × sp=2, each against "
                         "one chip); needs four chips")
    args = ap.parse_args(argv)
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    try:
        ok = run(args.multichip, device)
    except Exception:  # the boundary: report the failure, exit non-zero
        traceback.print_exc()
        print(json.dumps({"phase": "aborted", "ok": False,
                          "error": traceback.format_exc(limit=1)[-400:]}),
              flush=True)
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
