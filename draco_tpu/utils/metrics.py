"""Structured metric emission (replaces the reference's print-to-stdout
observability, SURVEY.md §5.5) while keeping the reference's segment names —
fetch/comp/encode/comm/decode/update wall-clock splits
(cyclic_worker.py:154-156, baseline_master.py:145) — so per-step timing is
comparable against BASELINE.md."""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np

from draco_tpu.obs.forensics import record_value


class MetricWriter:
    """JSONL metrics to ``train_dir/metrics.jsonl`` + human lines to stdout.

    Records are BUFFERED: ``write`` appends to an in-memory list and the
    file is touched only at :meth:`flush` (called by the loops at their
    flush/eval/checkpoint boundaries and by the DeferredMetricWriter), when
    ``buffer_records`` lines have accumulated, or on :meth:`close` — one
    write+fsync-sized syscall burst per boundary instead of one per record,
    matching the chunked loops' host-dark steady state. ``close()`` always
    drains the buffer, so the tail of an interrupted-but-closed run is
    never lost; ``buffer_records=1`` restores per-record flushing for
    callers that tail the file live.
    """

    def __init__(self, train_dir: Optional[str], quiet: bool = False,
                 buffer_records: int = 64):
        self._fh = None
        self._quiet = quiet
        self._buf: list = []
        self._buffer_records = max(int(buffer_records), 1)
        if train_dir:
            os.makedirs(train_dir, exist_ok=True)
            self._fh = open(os.path.join(train_dir, "metrics.jsonl"), "a")

    def write(self, record: dict):
        record = dict(record, time=time.time())
        if self._fh:
            self._buf.append(json.dumps(record))
            if len(self._buf) >= self._buffer_records:
                self.flush()
        if not self._quiet:
            step = record.get("step", "?")
            body = ", ".join(
                f"{k}: {v:.4f}" if isinstance(v, float) else f"{k}: {v}"
                for k, v in record.items()
                if k not in ("step", "time")
            )
            print(f"Step: {step}, {body}", file=sys.stdout, flush=True)

    def flush(self):
        """Drain the buffer to disk (loops call this at flush boundaries)."""
        if self._fh and self._buf:
            self._fh.write("\n".join(self._buf) + "\n")
            self._fh.flush()
            self._buf = []

    def close(self):
        if self._fh:
            self.flush()
            self._fh.close()
            self._fh = None


class DeferredMetricWriter:
    """Chunk-boundary materialization for the scan-fused trainer loop.

    The chunked loop (trainer._run_chunked) hands each chunk's (K, m) device
    metrics block over right after dispatch via :meth:`defer` — no device
    fetch, no host sync. Only :meth:`flush` (called at log/eval/checkpoint
    boundaries) converts the pending blocks to host floats and writes the
    per-step records through the wrapped :class:`MetricWriter`. The JSONL
    schema and the reference segment names are unchanged; only WHEN the
    device→host fetch happens moves, which is the whole point: in steady
    state the host never blocks on the device between chunks.

    ``observer`` (optional callable) sees EVERY materialized record at
    flush time, logged or not — the run heartbeat (obs/heartbeat.py) hooks
    here to accumulate decode-health precision/recall without adding any
    device fetch beyond the flush's own block materialization.
    """

    def __init__(self, writer: MetricWriter, observer=None):
        self._writer = writer
        self._observer = observer
        # (steps, names, device block, per-chunk extras)
        self._pending: list = []
        self.last: dict = {}  # most recent materialized record (any step)

    @property
    def depth(self) -> int:
        return len(self._pending)

    def defer(self, steps, names, block, extras: Optional[dict] = None):
        """Queue a chunk: ``block[i, j]`` is metric ``names[j]`` at
        ``steps[i]``. ``extras`` maps key -> scalar (broadcast over the
        chunk) or per-step sequence; values must already be host data."""
        self._pending.append((list(steps), tuple(names), block, extras or {}))

    def wait(self) -> None:
        """Block until the NEWEST pending block is ready (chunks run in
        program order). On an attached device that is the whole wait; on a
        remote-dispatch backend only :meth:`sync` proves execution."""
        if self._pending:
            ready = getattr(self._pending[-1][2], "block_until_ready", None)
            if ready is not None:
                ready()

    def sync(self) -> None:
        """Execution barrier: device→host fetch of one element of the
        NEWEST pending block. ``jax.block_until_ready`` only awaits dispatch
        on remote-dispatch backends (tools/_timing.py, PERF_HISTORY.md §0); an actual
        transfer is the one portable way to await execution, and chunks run
        in program order, so the newest block landing means every pending
        chunk has executed. No-op when nothing is pending."""
        if self._pending:
            np.asarray(self._pending[-1][2][-1, 0])

    def flush(self, should_log=None, common: Optional[dict] = None) -> dict:
        """Materialize every pending chunk (THE device fetch) and write the
        records for steps where ``should_log(step)`` (default: all).
        ``common`` merges into every flushed record (e.g. the amortized
        t_comp known only at the sync point). Returns the last record."""
        for steps, names, block, extras in self._pending:
            vals = np.asarray(block)  # blocks until the chunk has executed
            for i, step in enumerate(steps):
                rec = {"step": step}
                # record_value: packed forensics bitmask columns become
                # exact integer words (a float()/JSON round trip would
                # destroy NaN-pattern payloads — obs/forensics docstring)
                rec.update(
                    {k: record_value(k, vals[i, j])
                     for j, k in enumerate(names)}
                )
                for k, v in extras.items():
                    rec[k] = float(v[i]) if np.ndim(v) else float(v)
                if common:
                    rec.update(common)
                self.last = rec
                if self._observer is not None:
                    self._observer(rec)
                if should_log is None or should_log(step):
                    self._writer.write(rec)
        self._pending = []
        # a flush boundary is THE durability point of the chunked regime:
        # drain the wrapped writer's record buffer with it
        self._writer.flush()
        return self.last


class Segments:
    """Wall-clock segment timer with the reference's phase names.

    Durations come from ``time.perf_counter`` — monotonic, so an NTP slew
    or DST step mid-segment cannot produce negative or wildly wrong
    t_fetch/t_comp values the way the old ``time.time()`` deltas could.
    The record-level ``time`` field (MetricWriter.write) deliberately stays
    wall-clock: it timestamps the record for humans; only durations need
    monotonicity.

    A segment has PARTS: ``lap(name)`` closes one inside the open segment
    (from the previous lap, or the segment's begin, to now) and ``end(lap)``
    closes the last with the segment's own final clock read, so the parts
    tile the segment exactly (``t_dispatch + t_wait + t_drain == t_comp``).
    ``begin(name, since=..., gap="book")`` books the time since ``since`` —
    the previous step's ``end()`` — under ``gap``, so consecutive steps'
    records tile the loop's wall time. A segment still open when the next
    begins ends on that same clock read (with its last part, ``lap``), and
    a name begun twice adds up."""

    def __init__(self):
        self.t = {}
        self._start = None
        self._lap = None
        self._name = None

    def begin(self, name: str, since: Optional[float] = None,
              gap: Optional[str] = None, lap: Optional[str] = None):
        now = time.perf_counter()
        if self._name is not None:
            self._close(now, lap)
        if gap is not None:
            # since=None: the run's first step, which follows nothing
            self.t[gap] = 0.0 if since is None else max(now - since, 0.0)
        self._name, self._start, self._lap = name, now, now

    def _close(self, now: float, lap: Optional[str]):
        if lap is not None:
            self.lap(lap, now)
        self.t[self._name] = self.t.get(self._name, 0.0) + now - self._start
        self._name = None

    def lap(self, name: str, now=None):
        now = time.perf_counter() if now is None else now
        self.t[name] = self.t.get(name, 0.0) + now - self._lap
        self._lap = now

    def end(self, lap: Optional[str] = None) -> Optional[float]:
        """Close the open segment (and its last part ``lap``); returns the
        clock read it closed on, for the next step's ``begin(since=)``."""
        if self._name is None:
            return None
        now = time.perf_counter()
        self._close(now, lap)
        return now

    def as_dict(self, prefix: str = "t_"):
        return {prefix + k: round(v, 6) for k, v in self.t.items()}
