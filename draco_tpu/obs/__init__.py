"""Telemetry spine: host span tracing + run heartbeats.

The scan-chunked loops (PR 1–2) are fast precisely because the host goes
dark between flushes — which also means nothing shows where a chunk's
wall-clock went, and no artifact of a run shows whether the decode caught
the seeded adversaries. This package is the observability layer ROADMAP's
production north star needs, built under the PR 1–2 invariant: **no new
device fetches in steady state** and zero overhead when disabled.

  tracer.py     SpanTracer — Chrome-trace-event host spans
                (gather/upload/dispatch/sync/flush/eval/ckpt, sync's two
                parts device_wait and drain, book between two steps, +
                prefetcher worker-thread lanes + queue-depth counters)
                written to ``trace_dir/trace.json``, loadable in Perfetto /
                chrome://tracing; an open span is also a
                ``jax.profiler.TraceAnnotation``, so a profiler capture
                holds the spans on its own clock; ``NULL_TRACER`` is the
                allocation-free disabled path every loop runs by default.
                Also the process's SET-UP LEDGER (``setup_span``): what the
                step builders and the loops do before the first step —
                ``setup.model_init`` / ``setup.state`` / ``setup.step_build``
                / ``setup.schedules``, and the builds no watch saw — kept
                always, adopted by a tracer made later, summed into the
                heartbeat's ``setup`` block; and each loop call's two edges,
                ``loop.prologue`` / ``loop.epilogue``.
                The eager loop's per-step record carries the same ledger
                as seconds (utils/metrics.Segments): ``t_fetch``,
                ``t_comp`` = ``t_dispatch + t_wait + t_drain``, ``t_book``
                — and ``ahead``, 1.0 where the Trainer's loop dispatched
                the step before it waited for the one before.
  heartbeat.py  RunHeartbeat — ``train_dir/status.json`` rewritten
                atomically at every flush boundary (step, steps/s, ETA,
                last loss, decode health, prefetch queue depth, compile
                counters) so external monitors can watch a long chip job
                without touching the process.
  compile_watch.py  CompileWatch — the compiler-facing half (ISSUE 5):
                every XLA executable build becomes a ``compiles.jsonl``
                ledger row + a ``compile`` lane event in trace.json via
                jax.monitoring — named ``compile`` or ``cache_load`` by
                what the build paid —, and a steady-state guard (warn by
                default, raise in tests) trips on any recompilation of a
                labelled registered program after its warmup build.
  profiling.py  profiler_window — the ONE jax.profiler start/stop window
                both production loops run (drain-before-stop + the
                wall-clock anchor the merged host+device timeline needs,
                stamped inside a ``draco_anchor`` annotation); on stop it
                writes ``device_scope_map.json`` — instruction -> scope —
                from the programs the loop dispatched in the window
                (train_step, train_many[k], the token loop's), lowered
                from the call's own arguments.
  device_attr.py  The device-side half of the spine (ISSUE 9; jax-free but
                for reading an ``.xplane.pb``): parses a jax.profiler
                capture — the chip's xplane, whose events are named by
                their instruction's HLO text, or XLA:CPU's — into the
                per-phase chip ledger (draco_comp / pack / input / attack
                / health / encode / decode / update + explicit
                residual, rows summing to the profiled window), the
                collective comms ledger cross-checked against the PR 3
                Manifest counts (mismatch = hard error), and the merged
                host+device Perfetto timeline. Driven by
                tools/device_profile.py; folded by tools/trace_report.py.
  incidents.py  IncidentEngine (ISSUE 13): typed, attributed, stateful
                run-health incidents folded from the per-step column
                families at the heartbeat observer hook — declaratively
                registered detectors (throughput / residual drift / trust
                collapse / guard burn / numerics / compile storm /
                prefetch starvation) with onset/offset hysteresis,
                streamed to ``train_dir/incidents.jsonl`` and the
                ``incidents`` status block; replayed jax-free by
                tools/incident_report.py.
  replay.py     The shared torn-tail-tolerant JSONL reader every jax-free
                replay tool folds metrics.jsonl / incidents.jsonl through.
  forensics.py  Per-worker Byzantine forensics (ISSUE 7): the coded steps'
                (n,) accusation/present/seeded-adversary masks packed into
                f32-carried uint32 bitmask columns riding the (K, m) metric
                block, and the host ``AccusationLedger`` folding them (via
                the heartbeat's observer hook) into per-worker counters,
                trust scores, and attack episodes — the ``forensics`` block
                of status.json and the input to tools/forensics_report.py.

The in-graph half of the telemetry (decode-health metric columns) lives
where the math lives: coding/cyclic.py + coding/repetition.py produce the
per-step health values inside the jitted programs, and they ride the
existing (K, m) metric block through DeferredMetricWriter — named scopes
and metric columns, never host callbacks, so every registered program
stays green under the PR 3 linter's host_traffic rule.
"""

from draco_tpu.obs.compile_watch import (
    CompileWatch,
    RetraceError,
    RetraceWarning,
    make_compile_watch,
)
from draco_tpu.obs.forensics import AccusationLedger
from draco_tpu.obs.heartbeat import (
    STATUS_SCHEMA,
    RunHeartbeat,
    check_status_schema,
)
from draco_tpu.obs.incidents import IncidentEngine, make_engine
from draco_tpu.obs.profiling import NULL_PROFILER_WINDOW, profiler_window
from draco_tpu.obs.tracer import NULL_TRACER, SpanTracer, make_tracer

__all__ = ["NULL_PROFILER_WINDOW", "NULL_TRACER", "STATUS_SCHEMA",
           "AccusationLedger", "CompileWatch", "IncidentEngine",
           "RetraceError", "RetraceWarning", "RunHeartbeat", "SpanTracer",
           "check_status_schema", "make_compile_watch", "make_engine",
           "make_tracer", "profiler_window"]
