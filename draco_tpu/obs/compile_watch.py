"""Compile/retrace sentinel: the compiler-facing half of the telemetry spine.

The scan-chunk wins of PR 1–2 assume each registered program compiles ONCE
and then replays: a mid-run recompilation (a shape-polymorphic batch, a
schedule array that flips between committed and uncommitted, a carry whose
dtype drifts) silently re-pays the multi-second XLA compile on every
affected dispatch — the exact cost class the chunked loops exist to hide —
and no output-level test can see it (losses stay bitwise identical). This
module makes every compilation an observable event:

* **Ledger** — every XLA executable build becomes one JSON line in
  ``<dir>/compiles.jsonl`` (program label when the build happened inside a
  registered dispatch scope, trace + lowering + backend seconds, whether
  the persistent cache hit, a steady-state flag) and a
  ``compile``-category lane event in the existing ``trace.json``
  (obs/tracer.py) named ``compile`` or ``cache_load`` by what it paid, so
  Perfetto shows builds nested inside the dispatch span that paid for
  them.
* **Steady-state guard** — each labelled program is allowed ``warmup``
  *compiling dispatch windows* (default 1: the first dispatch of each
  (program, chunk shape) traces and compiles, possibly paying several
  sub-builds for operand fills); any build after that is a steady-state
  recompile. ``guard="warn"`` (production default) emits a
  ``RetraceWarning``; ``guard="raise"`` (the test/CI mode) raises
  :class:`RetraceError` at the dispatch site, which makes "0 steady-state
  recompiles" an assertable property of the K ∈ {1, 4} equivalence suites
  at zero extra training runs.

Event sourcing: ``jax.monitoring``, as jax 0.9.0 fires it (read from
``_src/pjit.py``, ``interpreters/pxla.py``, ``compiler.py``,
``compilation_cache.py``; checked on the chip cold and warm, PERF.md §3).
One executable build is, on the dispatching thread and in this order:

  ================================================  =====  ====  ====
  event                                             kind   miss  hit
  ================================================  =====  ====  ====
  /jax/core/compile/jaxpr_trace_duration            secs   yes   yes
  /jax/core/compile/jaxpr_to_mlir_module_duration   secs   yes   yes
  /jax/compilation_cache/cache_hits                 event  no    yes
  /jax/compilation_cache/cache_retrieval_time_sec   secs   no    yes
  /jax/compilation_cache/cache_misses               event  yes*  no
  /jax/core/compile/backend_compile_duration        secs   yes   yes
  ================================================  =====  ====  ====

The trace event fires once per ``jit`` traced — the nested ones first, the
one being built after them, and then more while its module is lowered
(lowering rules trace ``jnp`` helpers: ``add``, ``less``, ``_squeeze``) —
so a build takes, of the traces since the last lowering, the one whose
``fun_name`` its module's name ends with (``step_body`` for
``jit(step_body)``), and the others are dropped with it (a trace nobody
lowers — ``eval_shape`` — among them). The lowering event opens a build.
The backend event wraps ``compile_or_get_cached`` whole — so in this jax
it fires on a persistent-cache HIT too (0.4.x skipped it there), its
seconds then the key's hash plus the retrieval — and closes the build;
what the build paid is told by whether ``cache_hits`` fired in between.
``cache_misses`` (*) fires only where the
new executable is written to the cache: a build under the cache's
compile-time and size floors, or with no cache directory, fires neither and
is a ``compile``. So a span is named by what it paid — ``compile`` (trace +
lower + backend seconds, the backend compile ran) or ``cache_load`` (the
same three, the backend seconds a hash and a read) — and ``retrieval_s`` is
jax's own figure for the read inside it.

jax's listener registry has no per-listener removal, so ONE module-level
dispatcher is installed forever. It assembles the builds, keeps
process-wide totals (:func:`global_stats`, which jax-free consumers like
the study tools diff around a run to split compile from
steady-state wall-clock), adds every build's seconds to the set-up
ledger's totals (obs/tracer.py) and fans out to the currently-active
watches (a watch's lifetime is ``start()``/``stop()``, tied to its loop).
A build NO watch is there to see — the step builders' eager ``model.init``
pays dozens before a loop exists, and they call :func:`install` first — is
laid in the set-up ledger as a span, under the set-up span that paid for it,
and reaches ``trace.json`` when a tracer adopts the ledger.

Attribution: jax events carry no program name, so the loops label their
dispatch windows (the ISSUE's wrap-the-entry-points fallback) —
``with watch.expect("train_many", key=k): ...`` pushes a thread-local label;
a build that fires inside the scope belongs to that program. Compilation is
synchronous on the dispatching thread, so the scope is exact. Builds outside
any scope (eval steps, checkpoint codecs, jnp utility fills) are recorded
with ``program: null`` and never guarded.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import warnings
from typing import Optional

from draco_tpu.obs.tracer import NULL_TRACER, record_setup

# the jax.monitoring events this sentinel understands (jax 0.9.0; module
# docstring). Durations, then plain events.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
_COUNTED = {HIT_EVENT: "cache_hits", MISS_EVENT: "cache_misses"}

GUARD_MODES = ("off", "warn", "raise")


class RetraceError(RuntimeError):
    """A registered program recompiled in steady state under guard="raise"."""


class RetraceWarning(UserWarning):
    """A registered program recompiled in steady state under guard="warn"."""


# ---------------------------------------------------------------------------
# module-level dispatcher (installed once; jax has no listener removal)
# ---------------------------------------------------------------------------

_LOCK = threading.RLock()
_ACTIVE: list = []  # watches currently receiving events
_GLOBAL = {"builds": 0, "backend_compiles": 0, "lower_s": 0.0,
           "compile_s": 0.0, "trace_s": 0.0, "retrieval_s": 0.0,
           "cache_hits": 0, "cache_misses": 0}
_INSTALLED = False
# the calling thread's trace events since its last lowering (.traces:
# function name -> seconds) and the build that lowering opened (.build):
# compilation is synchronous on one thread
_TLS = threading.local()


def _letters(name) -> str:
    """A function's name as its module's name keeps it (``step_body`` in
    ``jit(step_body)``, ``<lambda>`` in ``jit(<lambda>)``)."""
    return "".join(c for c in str(name or "") if c.isalnum())


def _new_build(module, trace_s: float, lower_s) -> dict:
    return {"module": module, "trace_s": trace_s, "lower_s": lower_s,
            "retrieval_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def _dispatch(event: str, duration: float, **kw) -> None:
    if event == TRACE_EVENT:
        traces = getattr(_TLS, "traces", None)
        if traces is None:
            traces = _TLS.traces = {}
        traces[_letters(kw.get("fun_name"))] = duration
    elif event == LOWER_EVENT:
        traces = getattr(_TLS, "traces", None) or {}
        module = kw.get("fun_name")
        traced = max((k for k in traces if k
                      and _letters(module).endswith(k)), key=len, default="")
        trace_s = traces.get(traced, 0.0)
        traces.clear()
        build = _TLS.build = _new_build(module, trace_s, duration)
        with _LOCK:
            _GLOBAL["builds"] += 1
            _GLOBAL["lower_s"] += duration
            _GLOBAL["trace_s"] += trace_s
            active = list(_ACTIVE)
        try:
            for w in active:
                w._on_build(build)
        except Exception:
            _TLS.build = None  # the guard aborted this compilation
            raise
    elif event == RETRIEVAL_EVENT:
        build = getattr(_TLS, "build", None)
        if build is not None:
            build["retrieval_s"] = duration
        with _LOCK:
            _GLOBAL["retrieval_s"] += duration
    elif event == BACKEND_EVENT:
        # (or a backend compile with no lowering seen on this thread)
        build = (getattr(_TLS, "build", None)
                 or _new_build(kw.get("fun_name"), 0.0, None))
        _TLS.build = None
        build["compile_s"] = duration
        with _LOCK:
            _GLOBAL["backend_compiles"] += not build["cache_hits"]
            _GLOBAL["compile_s"] += duration
            active = list(_ACTIVE)
        # the build as a span that ends now; a live watch lays it on its
        # own tracer, so the ledger only counts its seconds then
        now = time.perf_counter()
        record_setup(span_name(build), now - build_seconds(build), now,
                     listed=not active)
        for w in active:
            w._on_backend(build)


def _count(event: str, **_kw) -> None:
    key = _COUNTED.get(event)
    if key is None:
        return
    build = getattr(_TLS, "build", None)
    if build is not None:
        build[key] = 1
    with _LOCK:
        _GLOBAL[key] += 1


def span_name(build: dict) -> str:
    """A build is named by what it paid (module docstring)."""
    return "cache_load" if build.get("cache_hits") else "compile"


def build_seconds(build: dict) -> float:
    return ((build.get("trace_s") or 0.0) + (build.get("lower_s") or 0.0)
            + (build.get("compile_s") or 0.0))


def install() -> None:
    """Idempotently register the module dispatcher with jax.monitoring.
    Called by CompileWatch.start() and by the step builders; call directly
    (before the compiles you want counted) when only :func:`global_stats`
    is needed."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_dispatch)
        jax.monitoring.register_event_listener(_count)
        _INSTALLED = True


def global_stats() -> dict:
    """Process-wide executable-build totals since :func:`install`:
    ``builds`` (lowerings = executable-cache misses), ``backend_compiles``
    (builds that paid a real XLA compile: no persistent-cache hit),
    ``trace_s``, ``lower_s``, ``compile_s`` (seconds inside
    ``compile_or_get_cached``, hit or miss), ``retrieval_s`` (of those, the
    persistent cache's reads), ``cache_hits``, ``cache_misses`` (entries
    written). Diff two snapshots around a run to split its compile cost
    from steady-state wall-clock."""
    with _LOCK:
        return dict(_GLOBAL)


# ---------------------------------------------------------------------------
# the per-run watch
# ---------------------------------------------------------------------------

class CompileWatch:
    """One run's compile ledger + steady-state retrace guard.

    Lifecycle: ``start()`` activates event delivery, ``stop()`` detaches and
    closes the ledger (loops call them from __init__/close). An unstarted
    watch is inert — safe as a default telemetry object.

    ``expect(name, key=...)`` labels the calling thread's dispatch window;
    ``key`` distinguishes legitimate shape variants of one program (the
    chunked loops pass the chunk length k, so a remainder chunk's first
    build is warmup for *its* shape, not a retrace of the main one).

    Warmup is counted in dispatch *windows*, not raw builds: a single cold
    dispatch may pay several executable builds (the program itself plus
    utility fills for its operands), and that is one warmup unit. A build
    firing after ``warmup`` windows of the same label have already paid
    builds is a steady-state recompile.
    """

    def __init__(self, ledger_dir: Optional[str] = None, tracer=NULL_TRACER,
                 warmup: int = 1, guard: str = "warn"):
        if guard not in GUARD_MODES:
            raise ValueError(f"guard must be one of {GUARD_MODES}, "
                             f"got {guard!r}")
        self.path = (os.path.join(ledger_dir, "compiles.jsonl")
                     if ledger_dir else None)
        self._tracer = tracer
        self.warmup = max(int(warmup), 0)
        self.guard = guard
        self.builds = 0  # executable builds seen while active
        self.backend_compiles = 0  # of them, those that really compiled
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.compile_s = 0.0  # inside compile_or_get_cached, hit or miss
        self.retrieval_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.steady_recompiles = 0
        self.builds_by_program: dict = {}  # raw builds per label
        self._compiled_windows: dict = {}  # label -> windows that built
        self._tls = threading.local()
        self._fh = None
        self._lock = threading.Lock()

    # ---- lifecycle -------------------------------------------------------
    def start(self) -> "CompileWatch":
        install()
        with _LOCK:
            if self not in _ACTIVE:
                _ACTIVE.append(self)
        return self

    def stop(self) -> None:
        with _LOCK:
            if self in _ACTIVE:
                _ACTIVE.remove(self)
        self._flush_pending()
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self) -> "CompileWatch":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # ---- labelling -------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    @contextlib.contextmanager
    def expect(self, name: str, key=None):
        """Label this thread's dispatch window: builds firing inside belong
        to ``name`` (``key`` appended for shape variants, e.g. chunk k)."""
        label = f"{name}[{key}]" if key is not None else name
        stack = self._stack()
        entry = [label, False]  # fired flag set by _on_build
        stack.append(entry)
        try:
            yield self
        finally:
            stack.pop()
            # the window is over: a build still pending (persistent-cache
            # hit, so no backend event arrived) belongs to this label —
            # finalize before the label goes away
            self._flush_pending()
            if entry[1]:
                with self._lock:
                    self._compiled_windows[label] = (
                        self._compiled_windows.get(label, 0) + 1)

    # ---- event sinks (called by the module dispatcher) -------------------
    def _on_build(self, build: dict) -> None:
        self._flush_pending()  # previous build on this thread, if any
        trace_s, lower_s = build["trace_s"], build["lower_s"]
        stack = self._stack()
        entry = stack[-1] if stack else None
        label = entry[0] if entry is not None else None
        with self._lock:
            self.builds += 1
            self.trace_s += trace_s
            self.lower_s += lower_s
            n = self.builds_by_program.get(label, 0) + 1
            if label is not None:
                self.builds_by_program[label] = n
            # steady iff `warmup` prior dispatch windows of this label have
            # already paid builds — this window's own earlier builds (a cold
            # dispatch compiles the program plus operand fills) don't count
            steady = (label is not None
                      and self._compiled_windows.get(label, 0) >= self.warmup)
        if entry is not None:
            entry[1] = True
        row = {
            "time": time.time(),
            "program": label,
            "n_for_program": n if label is not None else None,
            "module": build["module"],
            "trace_s": round(trace_s, 6),
            "lower_s": round(lower_s, 6),
            "steady_recompile": steady,
        }
        if not steady:
            self._tls.pending = row  # backend event may still attach cost
            return
        with self._lock:
            self.steady_recompiles += 1
        if self.guard == "raise":
            # raising here aborts the compilation, so no backend event will
            # ever attach — emit the ledger row now, then fail the dispatch
            self._emit(row)
            raise RetraceError(self._retrace_msg(label, n))
        # warn/off: compilation proceeds; keep the row pending so the
        # backend event attaches its compile seconds to THIS row instead of
        # orphaning them on a program-less duplicate
        self._tls.pending = row
        if self.guard == "warn":
            warnings.warn(self._retrace_msg(label, n), RetraceWarning,
                          stacklevel=2)

    def _retrace_msg(self, label, n) -> str:
        return (f"steady-state recompilation of registered program "
                f"{label!r} (build #{n}, after "
                f"{self._compiled_windows.get(label, 0)} compiled dispatch "
                f"windows, warmup={self.warmup}): the program "
                f"re-paid trace+lower+compile mid-run — a shape/dtype/"
                f"structure change in its arguments is defeating the "
                f"compile-once contract (obs/compile_watch.py, PERF_HISTORY.md §8)")

    def _on_backend(self, build: dict) -> None:
        cost = {"compile_s": round(build["compile_s"], 6),
                "retrieval_s": round(build["retrieval_s"], 6),
                "cache_hits": build["cache_hits"],
                "cache_misses": build["cache_misses"]}
        with self._lock:
            self.backend_compiles += not build["cache_hits"]
            self.compile_s += build["compile_s"]
            self.retrieval_s += build["retrieval_s"]
            self.cache_hits += build["cache_hits"]
            self.cache_misses += build["cache_misses"]
        row = getattr(self._tls, "pending", None)
        self._tls.pending = None
        if row is None:  # no lowering was seen on this thread
            row = {"time": time.time(), "program": None,
                   "module": build["module"]}
        row.update(cost)
        self._emit(row)

    def _flush_pending(self) -> None:
        row = getattr(self._tls, "pending", None)
        if row is not None:
            self._tls.pending = None
            self._emit(row)

    # ---- emission --------------------------------------------------------
    def _emit(self, row: dict) -> None:
        self._tracer.complete(span_name(row), build_seconds(row),
                              cat="compile", program=row.get("program"),
                              steady_recompile=row.get("steady_recompile",
                                                       False))
        if self.path is None:
            return
        with self._lock:
            if self._fh is None:
                os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
                self._fh = open(self.path, "a")
            self._fh.write(json.dumps(row) + "\n")
            self._fh.flush()  # compiles are rare; keep the ledger live

    # ---- surface ---------------------------------------------------------
    def snapshot(self) -> dict:
        """The heartbeat extra both production loops merge into status.json:
        how many executable builds this run has paid, the wall-clock they
        cost, and whether any happened in steady state (must stay 0)."""
        with self._lock:
            return {
                "compiles": self.builds,
                "compile_s": round(self.lower_s + self.compile_s, 3),
                "steady_recompiles": self.steady_recompiles,
            }


def make_compile_watch(cfg, tracer=NULL_TRACER, is_main: bool = True
                       ) -> CompileWatch:
    """The one construction rule both production loops share: ledger next to
    the trace (cfg.trace_dir) when tracing, else next to metrics.jsonl
    (cfg.train_dir); guard/warmup from config; only the metrics-emitting
    process writes a ledger (counters stay live everywhere)."""
    ledger_dir = (cfg.trace_dir or cfg.train_dir or None) if is_main else None
    watch = CompileWatch(ledger_dir=ledger_dir, tracer=tracer,
                         warmup=cfg.compile_warmup, guard=cfg.compile_guard)
    return watch.start()
