#!/usr/bin/env python
"""Fold a run's telemetry artifacts into a per-phase wall-clock table.

Reads the host span trace (``trace.json``, Chrome trace events written by
draco_tpu/obs/tracer.py) and, when present, ``metrics.jsonl`` from the same
train_dir, and prints where the run's host wall-clock went:

  python tools/trace_report.py train_out/            # a train/trace dir
  python tools/trace_report.py path/to/trace.json --json report.json

Per phase (gather/upload/dispatch/sync/flush/eval/ckpt + the prefetcher
lanes): call count, total/mean/max milliseconds, and share of the traced
wall. The metrics side contributes the device-facing per-step averages the
records already carry (t_fetch / t_comp) and the step count, so one table
answers the question the chunked regime's dark host otherwise hides: how
much of a chunk's wall-clock was host work vs device execution.

No jax import — this is a pure-host artifact folder usable on a laptop
against artifacts scp'd from a chip job. It tolerates the partial-artifact
states a killed run leaves behind (missing/empty metrics.jsonl, a torn
JSONL tail) and surfaces the tracer's top-level ``droppedEvents`` count in
the header — a long run's trace is a sliding window of its newest spans,
and a report that hid the drop count would present the window as the run.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_trace(path: str) -> "tuple[list, int]":
    """(events, droppedEvents). The tracer's bounded buffer drops the
    oldest spans on very long runs and records the count top-level
    (obs/tracer.py); a report that hid it would present a sliding window
    as the whole run."""
    with open(path) as fh:
        payload = json.load(fh)
    if isinstance(payload, list):  # bare event-array form of the format
        return payload, 0
    events = payload.get("traceEvents", [])
    if not isinstance(events, list):
        raise SystemExit(f"{path}: no traceEvents array")
    return events, int(payload.get("droppedEvents", 0) or 0)


def fold_spans(events: list) -> "tuple[dict, float]":
    """name -> {count, total_ms, mean_ms, max_ms, share}; traced wall is the
    envelope of all complete events (ts..ts+dur, microseconds)."""
    by_name = collections.defaultdict(lambda: {"count": 0, "total_ms": 0.0,
                                               "max_ms": 0.0})
    t_lo, t_hi = float("inf"), float("-inf")
    for ev in events:
        if ev.get("ph") != "X":
            continue
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        row = by_name[ev["name"]]
        row["count"] += 1
        row["total_ms"] += dur_ms
        row["max_ms"] = max(row["max_ms"], dur_ms)
        t_lo = min(t_lo, float(ev["ts"]))
        t_hi = max(t_hi, float(ev["ts"]) + float(ev.get("dur", 0.0)))
    wall_ms = (t_hi - t_lo) / 1e3 if t_hi > t_lo else 0.0
    for row in by_name.values():
        row["mean_ms"] = row["total_ms"] / row["count"]
        row["share"] = row["total_ms"] / wall_ms if wall_ms else 0.0
    return dict(by_name), wall_ms


def fold_counters(events: list) -> dict:
    """counter name -> {samples, last, max}."""
    out = {}
    for ev in events:
        if ev.get("ph") != "C":
            continue
        val = list(ev.get("args", {}).values())
        if not val:
            continue
        row = out.setdefault(ev["name"], {"samples": 0, "last": 0, "max": 0})
        row["samples"] += 1
        row["last"] = val[0]
        row["max"] = max(row["max"], val[0])
    return out


# the loop's per-step host clocks (utils/metrics.Segments): t_fetch and
# t_comp, t_comp's parts on the eager loop (the call until it returns, the
# wait for the device, the metric columns' fetches), and the bookkeeping
# between two steps — t_book + t_fetch + t_comp tile the loop's wall time
SEGMENT_KEYS = ("t_fetch", "t_comp", "t_dispatch", "t_wait", "t_drain",
                "t_book")


def fold_metrics(path: str) -> dict:
    """Step count + summed per-step segment seconds from metrics.jsonl
    (t_fetch/t_comp are per-step amortized values, so their sums are the
    regime's host-gather and device-execution wall respectively), plus the
    cumulative guard totals and the run's final decode-health detection
    precision/recall folded from the per-step columns (the PR 6 guard
    columns and PR 4 health counts used to be invisible to this jax-free
    path). Torn/empty/missing states are the shared replay scaffold's job
    (draco_tpu/obs/replay.py — one tolerance rule for every report tool)."""
    steps = 0
    sums = collections.defaultdict(float)
    first = last = None
    guard_seen = health_seen = False
    for rec in _train_records(path):
        steps += 1
        last = rec
        if first is None:
            first = rec
        for key in SEGMENT_KEYS:
            if key in rec:
                sums[key] += float(rec[key])
        if "guard_trips" in rec:
            guard_seen = True
            sums["guard_trips"] += float(rec["guard_trips"])
            sums["skipped_steps"] += float(rec.get("skipped_steps", 0.0))
        if "det_tp" in rec:
            health_seen = True
            sums["det_tp"] += float(rec["det_tp"])
            sums["det_adv"] += float(rec.get("det_adv", 0.0))
            for k in ("located_errors", "det_flagged"):
                if k in rec:
                    sums["det_flagged"] += float(rec[k])
                    break
    out = {"train_records": steps}
    out.update({f"{k}_total_s": round(v, 4) for k, v in sums.items()
                if k in SEGMENT_KEYS})
    if guard_seen:
        out["guard_trips"] = sums["guard_trips"]
        out["skipped_steps"] = sums["skipped_steps"]
    if health_seen:
        # same empty-denominator convention as obs/heartbeat.decode_health:
        # nothing flagged / no live adversary is a healthy 1.0
        tp, fl, adv = sums["det_tp"], sums["det_flagged"], sums["det_adv"]
        out["det_precision"] = round(tp / fl, 4) if fl else 1.0
        out["det_recall"] = round(tp / adv, 4) if adv else 1.0
    if first is not None:
        out["first_loss"] = first.get("loss")
        out["last_loss"] = last.get("loss")
    return out


# The status.json schema contract lives in ONE table now —
# obs/heartbeat.STATUS_BLOCKS / check_status_schema (ISSUE 13 satellite:
# previously this tool carried its own accepted-set literal, and a schema
# bump could strand it). draco_tpu/obs imports without jax; only a BARE
# tools/ checkout (no package at all) degrades to unvalidated folding with
# a visible note, the same discipline as fold_device's capture probe.
try:
    from draco_tpu.obs.heartbeat import check_status_schema
    from draco_tpu.obs.replay import train_records as _train_records
except ImportError:  # bare tools/ checkout
    check_status_schema = None

    def _train_records(path):
        out = []
        try:
            fh = open(path)
        except OSError:
            return out
        with fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line of an interrupted run
                if not isinstance(rec, dict) or "loss" not in rec \
                        or rec.get("split") == "eval":
                    continue
                out.append(rec)
        return out


def fold_status(path: str) -> dict:
    """The run's heartbeat terminal state (obs/heartbeat.py): state
    done/preempted/crashed/running (+ cause / resumable_step) — how an
    operator tells a crash from a preemption from a finished run without a
    traceback. {} when no status.json exists. A ``schema`` field, when
    present, must satisfy the central contract table
    (obs/heartbeat.check_status_schema) — silently folding an unknown
    payload shape would misreport the run."""
    try:
        with open(path) as fh:
            status = json.load(fh)
    except (OSError, ValueError):
        return {}
    if not isinstance(status, dict):
        return {}
    if check_status_schema is not None:
        check_status_schema(status, path, "tools/trace_report.py")
    out = {}
    for key in ("schema", "state", "cause", "resumable_step", "step",
                "updated_at", "wire", "numerics", "incidents"):
        if key in status:
            out[key] = status[key]
    if check_status_schema is None and "schema" in status:
        out["schema_unvalidated"] = True  # bare checkout: note, don't guess
    return out


def fold_device(profile_dir: str):
    """The device half (ISSUE 9): when the run dir holds a jax.profiler
    capture, fold it into the per-phase device table + collective comms
    ledger via obs/device_attr (jax-free, but part of draco_tpu — this
    tool stays usable from a bare tools/ checkout by degrading to a note
    when the package is absent). Missing or torn captures are tolerated
    exactly like metrics.jsonl."""
    try:
        from draco_tpu.obs import device_attr
    except ImportError:
        # bare tools/ checkout: probe the capture layout inline (the one
        # place the package's find_capture glob can't be reused)
        import glob

        if glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                  "*.trace.json*")):
            return {"note": "profiler capture present but draco_tpu not "
                            "importable — device attribution skipped"}
        return None  # no capture at all — the common case, no note
    try:
        fold = device_attr.fold_capture(profile_dir)
    except Exception:
        return None
    if not fold:
        return None  # no capture (the common case) or a torn one
    out = {"trace": fold.get("trace"), "programs": []}
    anchor = fold.get("anchor") or {}
    if anchor.get("steps_profiled") is not None:
        out["steps_profiled"] = anchor["steps_profiled"]
    for prog in fold["programs"]:
        row = {
            "module": prog["module"],
            "total_device_us": round(prog["total_device_us"], 1),
            "wall_us": round(prog["wall_us"], 1),
            "phases": {k: {"time_us": round(v["time_us"], 1),
                           "frac": round(v["frac"], 4),
                           "events": v["events"]}
                       for k, v in prog["phases"].items()},
            "collectives": prog["collectives"],
        }
        out["programs"].append(row)
    return out


def make_report(trace_path: str, metrics_path=None, profile_dir=None) -> dict:
    events, dropped = load_trace(trace_path)
    phases, wall_ms = fold_spans(events)
    report = {
        "trace": trace_path,
        "traced_wall_ms": round(wall_ms, 3),
        "dropped_events": dropped,
        "phases": {
            name: {k: (round(v, 3) if isinstance(v, float) else v)
                   for k, v in row.items()}
            for name, row in sorted(phases.items())
        },
        "counters": fold_counters(events),
    }
    # status.json lives in train_dir, which may differ from trace_dir (the
    # CLI flags are independent) — probe both the trace's and the metrics
    # file's directory
    candidates = [os.path.join(os.path.dirname(trace_path), "status.json")]
    if metrics_path:
        candidates.append(os.path.join(os.path.dirname(metrics_path),
                                       "status.json"))
    for cand in candidates:
        status = fold_status(cand)
        if status:
            report["run_status"] = status
            break
    # a missing or empty metrics.jsonl is a normal state (no train_dir, or
    # a run killed before its first flush) — the trace half still folds
    if metrics_path and os.path.exists(metrics_path):
        try:
            report["metrics"] = fold_metrics(metrics_path)
            report["metrics"]["path"] = metrics_path
        except OSError:
            pass
    # device half (ISSUE 9): default probe is the trace's own directory —
    # runs that pointed --profile-dir at the train/trace dir get the device
    # table for free; a missing capture folds nothing
    probe = profile_dir or os.path.dirname(trace_path) or "."
    device = fold_device(probe)
    if device:
        report["device"] = device
    return report


def print_table(report: dict, out=None) -> None:
    # resolve stdout at call time: a default bound at import time pins
    # whatever stream was installed then (pytest capture, a redirect) and
    # outlives it
    out = out if out is not None else sys.stdout
    dropped = report.get("dropped_events", 0)
    print(f"trace: {report['trace']}   traced wall: "
          f"{report['traced_wall_ms']:.1f} ms"
          + (f"   DROPPED EVENTS: {dropped} (sliding window — totals "
             f"undercount the run)" if dropped else ""), file=out)
    status = report.get("run_status")
    if status:
        line = f"run state: {status.get('state', '?')}"
        if status.get("cause"):
            line += f"   cause: {status['cause']}"
        if status.get("resumable_step") is not None:
            line += f"   resumable from step {status['resumable_step']}"
        print(line, file=out)
    # wire ledger + numerics observatory (ISSUE 10): the status blocks a
    # watch-enabled run stamps — logical bytes per worker per step with
    # the narrow-dtype candidates, and the folded range/shadow extremes
    wire = (status or {}).get("wire")
    if wire:
        b = wire.get("bytes_per_worker", {})
        f32 = b.get("f32")
        parts = [f"wire[{wire.get('family')}]: d={wire.get('dim')}"]
        if f32:
            parts.append(f"f32 {f32 / 1024:.1f} KiB/worker/step")
            for dt in ("bf16", "int8"):
                if b.get(dt):
                    parts.append(f"{dt} {b[dt] / 1024:.1f} KiB "
                                 f"({b[dt] / f32:.2f}x)")
        if wire.get("shadow_wire", "off") != "off":
            parts.append(f"shadow={wire['shadow_wire']}")
        # the MATERIALIZED wire (ISSUE 15): what the run physically ships
        if wire.get("wire_dtype", "f32") != "f32":
            phys = wire.get("physical_bytes_per_worker")
            tag = f"materialized={wire['wire_dtype']}"
            if phys:
                tag += f" ({phys / 1024:.1f} KiB/worker/step physical)"
            parts.append(tag)
        print("   ".join(parts), file=out)
    nx = (status or {}).get("numerics")
    if nx:
        bits = []
        for k in ("nx_wire_absmax", "nx_wire_rms", "shadow_err_max",
                  "shadow_residual_max", "shadow_flag_agree_min",
                  "nx_wire_uf_int8_max", "nx_grad_nonfinite_max"):
            if k in nx:
                bits.append(f"{k.replace('nx_', '')}={nx[k]:.4g}")
        if bits:
            print("numerics: " + "  ".join(bits), file=out)
    # incident engine roll-up (obs/incidents.py, ISSUE 13): the status
    # block a watch-enabled run stamps — open episodes are the headline
    inc = (status or {}).get("incidents")
    if inc:
        line = f"incidents: {inc.get('total', 0)} total"
        by_type = inc.get("by_type") or {}
        if by_type:
            line += " (" + ", ".join(f"{k}:{v}" for k, v
                                     in sorted(by_type.items())) + ")"
        for ep in inc.get("open") or []:
            workers = ",".join(map(str, ep.get("workers") or ())) or "-"
            line += (f"   OPEN {ep.get('type')}@{ep.get('onset_step')} "
                     f"workers={workers}")
        print(line, file=out)
    # guard + decode-health header (folded from the per-step columns —
    # previously invisible to this jax-free path)
    m = report.get("metrics") or {}
    if "guard_trips" in m:
        print(f"guard: trips={m['guard_trips']:g} "
              f"skipped_steps={m['skipped_steps']:g}", file=out)
    if "det_precision" in m:
        print(f"decode health: precision={m['det_precision']:.4f} "
              f"recall={m['det_recall']:.4f}", file=out)
    hdr = f"{'phase':<22}{'count':>7}{'total ms':>12}{'mean ms':>10}" \
          f"{'max ms':>10}{'share':>8}"
    print(hdr, file=out)
    print("-" * len(hdr), file=out)
    rows = sorted(report["phases"].items(),
                  key=lambda kv: -kv[1]["total_ms"])
    for name, r in rows:
        print(f"{name:<22}{r['count']:>7}{r['total_ms']:>12.2f}"
              f"{r['mean_ms']:>10.3f}{r['max_ms']:>10.2f}"
              f"{r['share']:>8.1%}", file=out)
    for name, c in sorted(report.get("counters", {}).items()):
        print(f"counter {name}: samples={c['samples']} last={c['last']} "
              f"max={c['max']}", file=out)
    m = report.get("metrics")
    if m:
        bits = [f"train_records={m['train_records']}"]
        bits += [f"{k}={m[k]}" for k in sorted(m)
                 if k.endswith("_total_s")]
        if "last_loss" in m:
            bits.append(f"loss {m.get('first_loss'):.4f} -> "
                        f"{m.get('last_loss'):.4f}")
        print("metrics: " + "  ".join(bits), file=out)
    # per-phase device table + comms ledger (ISSUE 9) — only when the run
    # dir holds a profiler capture
    dev = report.get("device")
    if dev and dev.get("note"):
        print(f"device: {dev['note']}", file=out)
    elif dev:
        steps = dev.get("steps_profiled")
        for prog in dev.get("programs", []):
            print(f"device program {prog['module']}: "
                  f"{prog['total_device_us'] / 1e3:.1f} ms device self-time"
                  + (f" over {steps} profiled steps" if steps else ""),
                  file=out)
            hdr = f"  {'device phase':<20}{'events':>8}{'total ms':>12}" \
                  f"{'share':>8}"
            print(hdr, file=out)
            print("  " + "-" * (len(hdr) - 2), file=out)
            rows = sorted(prog["phases"].items(),
                          key=lambda kv: -kv[1]["time_us"])
            for name, r in rows:
                print(f"  {name:<20}{r['events']:>8}"
                      f"{r['time_us'] / 1e3:>12.2f}{r['frac']:>8.1%}",
                      file=out)
            for side in ("explicit", "gspmd"):
                for kind, row in sorted(
                        (prog.get("collectives", {}).get(side) or {})
                        .items()):
                    if not row.get("instructions"):
                        continue
                    print(f"  collective {side}/{kind}: "
                          f"instructions={row['instructions']} "
                          f"events={row['events']} bytes={row['bytes']} "
                          f"time_ms={row['time_us'] / 1e3:.2f}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="trace.json, or a directory holding "
                                 "trace.json (+ metrics.jsonl)")
    ap.add_argument("--metrics", default="",
                    help="metrics.jsonl path (default: next to the trace)")
    ap.add_argument("--json", default="",
                    help="also write the folded report as JSON here")
    ap.add_argument("--profile-dir", default="",
                    help="jax.profiler capture dir for the device table "
                         "(default: probe the trace's own directory)")
    args = ap.parse_args(argv)

    trace_path = args.path
    if os.path.isdir(trace_path):
        trace_path = os.path.join(trace_path, "trace.json")
    metrics_path = args.metrics or os.path.join(
        os.path.dirname(trace_path), "metrics.jsonl")
    report = make_report(trace_path, metrics_path,
                         profile_dir=args.profile_dir or None)
    print_table(report)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
