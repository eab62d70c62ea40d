"""The eager loop accounts for its own wall time (ISSUE 24): every step's
record carries ``t_comp``'s parts (``t_dispatch + t_wait + t_drain``) and
the bookkeeping before it (``t_book``), which with ``t_fetch`` and ``t_comp``
tile the loop; ``trace.json`` nests ``device_wait`` and ``drain`` in ``sync``
and holds ``book``; and a profiled run writes the scope map of the program
it dispatched, from which the heartbeat's ``device`` block attributes the
capture's op time. LeNet size, on the CPU mesh."""

import json
import os
import time

import jax
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.data.datasets import load_dataset
from draco_tpu.obs import device_attr as da
from draco_tpu.runtime import make_mesh
from draco_tpu.training.trainer import Trainer

PARTS = ("t_dispatch", "t_wait", "t_drain")


class _Records:
    """Stands where the metric writer stands; keeps every record."""

    def __init__(self):
        self.rows = []

    def write(self, record):
        self.rows.append(dict(record))

    def flush(self):
        pass

    close = flush


@pytest.fixture(scope="module")
def ds():
    return load_dataset("synthetic-mnist", synthetic_train=1024,
                        synthetic_test=64)


def _trainer(ds, **kw):
    cfg = TrainConfig(network="LeNet", dataset="synthetic-mnist",
                      approach="cyclic", num_workers=8, worker_fail=1,
                      err_mode="rev_grad", redundancy="simulate",
                      batch_size=8, lr=0.01, momentum=0.9, max_steps=64,
                      eval_freq=0, log_every=1, **kw)
    tr = Trainer(cfg, mesh=make_mesh(cfg.num_workers), dataset=ds,
                 quiet=True)
    tr.writer = records = _Records()
    return tr, records


@pytest.fixture(scope="module")
def eager_run(ds, tmp_path_factory):
    """3 warm steps (the compile), then 12 steps with the wall clock around
    ``run()``; host spans on."""
    d = str(tmp_path_factory.mktemp("ledger"))
    tr, records = _trainer(ds, train_dir="", trace_dir=d)
    tr.run(max_steps=3)
    t0 = time.perf_counter()
    tr.run(max_steps=15)
    wall = time.perf_counter() - t0
    hlo = tr.dispatched_hlo()
    tr.close()
    with open(os.path.join(d, "trace.json")) as fh:
        events = json.load(fh)["traceEvents"]
    return records.rows[3:], wall, events, hlo


def test_every_record_has_the_parts_and_they_tile_t_comp(eager_run):
    rows, _, _, _ = eager_run
    assert [r["step"] for r in rows] == list(range(4, 16))
    for r in rows:
        assert all(r[k] >= 0.0 for k in PARTS + ("t_book", "t_fetch"))
        assert abs(sum(r[k] for k in PARTS) - r["t_comp"]) <= 50e-6, r
        assert r["t_wait"] > 0.0 and r["t_dispatch"] > 0.0


def test_records_tile_the_wall_time_of_run(eager_run):
    rows, wall, _, _ = eager_run
    assert rows[0]["t_book"] == 0.0  # a run's first step follows nothing
    assert all(r["t_book"] > 0.0 for r in rows[1:])
    total = sum(r["t_book"] + r["t_fetch"] + r["t_comp"] for r in rows)
    print(f"ledger: records {total:.6f}s of {wall:.6f}s around run()")
    assert total <= wall
    assert total == pytest.approx(wall, rel=0.01), (total, wall)


def test_trace_nests_wait_and_drain_in_sync_and_holds_book(eager_run):
    rows, _, events, _ = eager_run
    spans = {}
    for e in events:
        if e.get("ph") == "X" and (e.get("args") or {}).get("step") == 9:
            spans[e["name"]] = (e["ts"], e["ts"] + e["dur"], e.get("args"))
    assert {"gather+upload", "dispatch", "sync", "device_wait", "drain",
            "book"} <= set(spans)
    s0, s1, _ = spans["sync"]
    for child in ("device_wait", "drain"):
        c0, c1, _ = spans[child]
        assert s0 <= c0 <= c1 <= s1, (child, spans)
    assert spans["device_wait"][1] <= spans["drain"][0]
    assert spans["dispatch"][1] <= s0 and s1 <= spans["book"][0]
    # the drain says how many columns it fetched, one transfer each
    row = next(r for r in rows if r["step"] == 9)
    columns = [k for k in row
               if k not in ("step", "present") and not k.startswith("t_")]
    assert spans["drain"][2]["columns"] == len(columns)
    # and the spans agree with the records they were timed beside
    assert (spans["device_wait"][1] - spans["device_wait"][0]) * 1e-6 == \
        pytest.approx(row["t_wait"], abs=200e-6)


def test_dispatched_hlo_is_the_step_program(eager_run):
    hlo = eager_run[3]
    sm = da.scope_map_from_hlo(hlo)
    assert sm["module"] == "jit_step_body"
    assert {"draco_comp", "draco_pack", "draco_health", "draco_encode",
            "draco_decode", "draco_update"} <= set(sm["ops"].values())


def test_profiled_eager_run_attributes_its_own_capture(ds, tmp_path):
    """The window writes ``device_scope_map.json`` from the call it saw; the
    map covers the capture's op time; the tracer's spans are on the
    capture's host plane, and the anchor annotation ties the clocks."""
    d = str(tmp_path)
    tr, records = _trainer(ds, train_dir=d, trace_dir=d)
    tr.run(max_steps=8, profile_dir=d, profile_steps=(4, 8))
    tr.close()
    sm = da.load_scope_map(d)
    assert sm["steps_profiled"] == 4 and "errors" not in sm
    (prog,) = sm["programs"]
    assert prog["module"] == "jit_step_body" and prog["label"] == "train_step"
    cap = da.find_capture(d)
    assert cap.endswith(".xplane.pb")
    events, _ = da.load_trace(cap)
    pairs = da.self_times([e for e in events if e.get("args")
                           and e["ph"] == "X"])
    total = sum(us for _, us in pairs)
    known = sum(us for ev, us in pairs
                if ev["args"]["hlo_op"] in prog["ops"]
                and ev["args"]["hlo_module"] == prog["module"])
    assert total > 0 and known / total >= 0.9
    with open(os.path.join(d, "status.json")) as fh:
        dev = json.load(fh)["device"]
    assert "error" not in dev, dev
    assert dev["profiled_steps"] == 4 and dev["attributed_frac"] > 0.9
    assert dev["phase_fracs"]["draco_comp"] > 0
    assert sum(dev["phase_fracs"].values()) == pytest.approx(1.0, abs=2e-3)
    # one clock: the program's spans are annotations on the host plane
    host = {e["name"] for e in events if e.get("cat") == "host"}
    assert {"draco_anchor", "dispatch", "sync", "device_wait", "drain",
            "book", "gather+upload"} <= host
    with open(os.path.join(d, "trace.json")) as fh:
        spans = json.load(fh)["traceEvents"]
    merged = da.merge_timeline(spans, events, prog, da.load_anchor(d),
                               max_device_events=2000)
    mt = merged["mergedTimeline"]
    assert mt["anchor_kind"] == "annotation"
    # the offset the annotation gives puts a step's device ops between that
    # step's dispatch start and its sync end on the host tracer's clock
    step6 = {e["name"]: e for e in spans if e.get("ph") == "X"
             and (e.get("args") or {}).get("step") == 6}
    lo = step6["dispatch"]["ts"]
    hi = step6["sync"]["ts"] + step6["sync"]["dur"]
    inside = [e for e in merged["traceEvents"] if e.get("cat") == "device"
              and lo <= e["ts"] <= hi]
    assert inside


def test_null_tracer_takes_no_annotation(tmp_path):
    from draco_tpu.obs import tracer as tracer_mod

    null = tracer_mod.NULL_TRACER
    assert null.span("sync") is null.span("book") is tracer_mod._NULL_SPAN
    assert not hasattr(null, "_annotate")
    live = tracer_mod.SpanTracer(str(tmp_path / "trace.json"))
    assert live._annotate is jax.profiler.TraceAnnotation
    with live.span("sync") as span:
        assert isinstance(span._note, jax.profiler.TraceAnnotation)
