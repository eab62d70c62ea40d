"""The eager loop accounts for its own wall time (ISSUE 24): every step's
record carries ``t_comp``'s parts (``t_dispatch + t_wait + t_drain``) and
the bookkeeping before it (``t_book``), which with ``t_fetch`` and ``t_comp``
tile the loop; ``trace.json`` nests ``device_wait`` and ``drain`` in ``sync``
and holds ``book``; and a profiled run writes the scope map of the program
it dispatched, from which the heartbeat's ``device`` block attributes the
capture's op time. The loop keeps one step in flight (ISSUE 42): step k+1 is
dispatched before step k is waited for, except where something reads the
state at step k; the records still tile, and a stop lands on the newest
dispatched step. LeNet size, on the CPU mesh."""

import json
import os
import signal
import time

import jax
import numpy as np
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.data.datasets import load_dataset
from draco_tpu.obs import device_attr as da
from draco_tpu.obs import profiling
from draco_tpu.resilience.faults import FaultPlan, HostFaultInjector
from draco_tpu.resilience.supervisor import ImmediateStopError
from draco_tpu.runtime import make_mesh
from draco_tpu.training.trainer import Trainer
from draco_tpu.utils import checkpoint as ckpt
from draco_tpu.utils.metrics import Segments

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
    kw.setdefault("eval_freq", 0)
    cfg = TrainConfig(network="LeNet", dataset="synthetic-mnist",
                      approach="cyclic", num_workers=8, worker_fail=1,
                      err_mode="rev_grad", redundancy="simulate",
                      batch_size=8, lr=0.01, momentum=0.9, max_steps=64,
                      log_every=1, **kw)
    tr = Trainer(cfg, mesh=make_mesh(cfg.num_workers), dataset=ds,
                 quiet=True)
    tr.writer = records = _Records()
    return tr, records


@pytest.fixture(scope="module", params=[0, 2],
                ids=["one_ahead", "synced_every_2nd"])
def eager_run(request, ds, tmp_path_factory):
    """3 warm steps (the compile), then 12 steps with the wall clock around
    ``run()``; host spans on. Once with a step in flight all the way, once
    with an evaluation every second step, where the loop may not run
    ahead."""
    d = str(tmp_path_factory.mktemp("ledger"))
    tr, records = _trainer(ds, train_dir="", trace_dir=d,
                           eval_freq=request.param)
    tr.run(max_steps=3)
    t0 = time.perf_counter()
    tr.run(max_steps=15)
    wall = time.perf_counter() - t0
    hlo = tr.dispatched_hlo()
    tr.close()
    with open(os.path.join(d, "trace.json")) as fh:
        events = json.load(fh)["traceEvents"]
    rows = [r for r in records.rows if "t_comp" in r]  # less the evals'
    return rows[3:], wall, events, hlo


def test_every_record_has_the_parts_and_they_tile_t_comp(eager_run):
    rows, _, events, _ = eager_run
    assert [r["step"] for r in rows] == list(range(4, 16))
    for r in rows:
        assert all(r[k] >= 0.0 for k in PARTS + ("t_book", "t_fetch"))
        # each part is rounded to the microsecond, and so is their segment
        assert abs(sum(r[k] for k in PARTS) - r["t_comp"]) <= 2e-6, r
        assert r["t_wait"] > 0.0
    # every step of the call was sent once, and none past its last: the
    # iteration that retires the last step sends nothing, and keeps the
    # parts' names
    sent = sorted(e["args"]["step"] for e in events if e.get("ph") == "X"
                  and e["name"] == "dispatch" and e["args"]["step"] >= 4)
    assert sent == list(range(4, 16))
    assert all(r["t_dispatch"] > 0.0 for r in rows if r["ahead"] == 0.0)


def test_ahead_is_one_but_where_the_loop_synced(eager_run, request):
    rows, _, events, _ = eager_run
    every_2nd = "synced_every_2nd" in request.node.callspec.id
    # the call's first step follows nothing; after an evaluation at an even
    # step the next one finds nothing in flight either
    want = [0.0 if r["step"] == 4 or (every_2nd and r["step"] % 2)
            else 1.0 for r in rows]
    assert [r["ahead"] for r in rows] == want
    said = {e["args"]["step"]: e["args"]["ahead"] for e in events
            if e.get("ph") == "X" and e["name"] == "dispatch"
            and e["args"]["step"] >= 4}
    assert said == {r["step"]: r["ahead"] for r in rows}


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
    columns = [k for k in row if k not in ("step", "present", "ahead")
               and not k.startswith("t_")]
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


# ---- one step in flight: the order of the calls ---------------------------

class _Value:
    """Stands for one of a step's outputs on the device: says when it is
    waited for and when it is fetched."""

    def __init__(self, log, what, step):
        self.log, self.what, self.step = log, what, step

    def block_until_ready(self):
        self.log.append(("wait" if self.what == "loss" else "wait_state",
                         self.step))
        return self

    def __float__(self):
        self.log.append(("drain", self.step))
        return float(self.step)


class _State:
    """Stands for the train state after step ``at``."""

    def __init__(self, log, at):
        self.log, self.at = log, at

    @property
    def params(self):
        return _Value(self.log, "params", self.at)


def _order_of(first, last, synced):
    """What the loop owes: step k+1 dispatched before the wait for step k
    unless k is in ``synced`` (or the call's last), every step waited for,
    drained and booked once, in order; the state waited for only where it
    is the retired step's."""
    out, sent = [], first - 1
    for k in range(first, last + 1):
        if sent < k:
            out.append(("dispatch", k))
        sent = k if (k in synced or k == last) else k + 1
        if sent > k:
            out.append(("dispatch", sent))
        out.append(("wait", k))
        if sent == k:
            out.append(("wait_state", k))
        out += [("drain", k), ("book", k)]
    return out


ORDER_CASES = {
    # name: (trainer options, run options, first step, the synced steps)
    "plain": ({}, {}, 1, set()),
    "resumed_call": ({}, {}, 4, set()),
    "eval_every_3rd": ({"eval_freq": 3}, {}, 1, {3, 6, 9}),
    # the capture starts before step 4 and stops after step 6
    "profiler_window": ({}, {"profile_steps": (4, 7)}, 1, {3, 6}),
    "window_from_the_first_step": ({}, {"profile_steps": (1, 3)}, 1, {2}),
    "scheduled_stop": ({"fault_spec": "sigterm@5"}, {}, 1, {5}),
    "scheduled_prefetch_fault":
        ({"fault_spec": "prefetch_hang@6:d0"}, {}, 1, {5}),
}


@pytest.fixture(params=sorted(ORDER_CASES))
def ordered_run(request, ds, tmp_path, monkeypatch):
    """The loop over a recording stand-in for ``train_step``: ten steps'
    calls in the order they were made."""
    options, run_options, first, synced = ORDER_CASES[request.param]
    log = []
    tr, records = _trainer(ds, train_dir=str(tmp_path), **options)

    def train_step(state, x, y, mask, present=None):
        log.append(("dispatch", state.at + 1))
        return (_State(log, state.at + 1),
                {"loss": _Value(log, "loss", state.at + 1)})

    tr.setup = tr.setup._replace(train_step=train_step)
    tr.state, tr._start_step = _State(log, first - 1), first
    records.write = lambda r: log.append(("book", r["step"]))
    tr.evaluate = lambda step: log.append(("eval", step, tr.state.at))
    monkeypatch.setattr(
        ckpt, "save",
        lambda d, step, state, **kw: log.append(("ckpt", step, state.at)))
    monkeypatch.setattr(profiling, "_quiet_start_trace",
                        lambda d: log.append(("capture_starts",)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: log.append(("capture_stops",)))
    if "profile_steps" in run_options:
        run_options = dict(run_options, profile_dir=str(tmp_path / "prof"))
    tr.run(max_steps=10, **run_options)
    tr.close()
    return request.param, log, first, synced, tr


def test_the_next_step_is_dispatched_before_this_one_is_waited_for(
        ordered_run):
    name, log, first, synced, tr = ordered_run
    last = 5 if name == "scheduled_stop" else 10
    calls = [e for e in log
             if e[0] in ("dispatch", "wait", "wait_state", "drain", "book")]
    if "window" in name:
        # the window's drain before it stops is a second wait on that state
        drains = [i for i, e in enumerate(calls)
                  if e == ("wait_state", max(synced))]
        del calls[drains[-1]]
    assert calls == _order_of(first, last, synced)
    assert tr.state.at == tr._eager_step == last


def test_what_reads_the_state_reads_it_at_its_step(ordered_run):
    name, log, first, synced, tr = ordered_run
    readers = [e for e in log if e[0] in ("eval", "ckpt")]
    assert all(step == at for _, step, at in readers), readers
    if name == "eval_every_3rd":
        assert [e[:2] for e in readers] == [
            (kind, k) for k in (3, 6, 9) for kind in ("eval", "ckpt")]
    if name == "scheduled_stop":  # lands on the step the plan names
        assert readers == [("ckpt", 5, 5)]
        assert tr._stopped_step == 5
    if "window" in name:
        # a capture holds whole steps: it starts with nothing in flight and
        # before its first step is sent; it stops on the drained state of
        # its last step, with the next one not yet sent
        lo, hi = ORDER_CASES[name][1]["profile_steps"]
        hi -= 1  # the window's own last step
        start, stop = log.index(("capture_starts",)), \
            log.index(("capture_stops",))
        assert log.index(("dispatch", lo)) > start
        if lo > first:
            assert log.index(("book", lo - 1)) < start
        assert log[stop - 1] == ("wait_state", hi)
        assert log.index(("dispatch", hi + 1)) > stop


# ---- a stop with a step in flight ------------------------------------------

def _params(tr):
    return np.concatenate([np.ravel(x) for x in jax.tree.leaves(
        jax.device_get(tr.state.params))])


@pytest.fixture(scope="module")
def uninterrupted(ds):
    tr, _ = _trainer(ds, train_dir="")
    tr.run(max_steps=10)
    tr.close()
    return _params(tr)


def _graceful(tr):
    tr._stop.deliver_signal(signal.SIGTERM)


def _second_signal(tr):
    raise ImmediateStopError("second SIGTERM")


@pytest.mark.parametrize("name,options,ask,at,lands", [
    # asked for while booking step 5, step 6 already on the device: the
    # newest dispatched step is the one the checkpoint names
    ("in_flight", {}, _graceful, 5, 6),
    ("in_flight_into_a_boundary", {"eval_freq": 4}, _graceful, 3, 4),
    # nothing in flight behind a boundary or the plan's own step: there the
    # stop lands on the step it was asked at
    ("on_a_boundary", {"eval_freq": 4}, _graceful, 4, 4),
    ("scheduled", {"fault_spec": "sigterm@5"}, None, 5, 5),
    ("second_signal_in_flight", {}, _second_signal, 5, 6),
])
def test_a_stop_names_the_step_the_state_is_at_and_resumes_bitwise(
        ds, tmp_path, uninterrupted, name, options, ask, at, lands):
    d = str(tmp_path)
    tr, records = _trainer(ds, train_dir=d, **options)
    tr.evaluate = lambda step: None
    keep = records.write

    def write(record):
        keep(record)
        if ask is not None and record["step"] == at:
            ask(tr)

    records.write = write
    tr.run(max_steps=10)
    tr.close()
    with open(os.path.join(d, "status.json")) as fh:
        st = json.load(fh)
    assert st["state"] == "preempted" and st["resumable_step"] == lands
    assert ckpt.exists(d, lands) and not ckpt.exists(d, lands + 1)
    if name != "second_signal_in_flight":  # which gives its records up
        assert [r["step"] for r in records.rows] == list(range(1, lands + 1))
    options.pop("fault_spec", None)
    resumed, _ = _trainer(ds, train_dir=d, checkpoint_step=lands, **options)
    resumed.evaluate = lambda step: None
    resumed.run(max_steps=10)
    resumed.close()
    np.testing.assert_array_equal(_params(resumed), uninterrupted)


def test_status_reports_the_share_of_steps_sent_ahead(ds, tmp_path):
    tr, records = _trainer(ds, train_dir=str(tmp_path))
    tr.run(max_steps=4)
    tr.run(max_steps=8)
    tr.close()
    assert [r["ahead"] for r in records.rows] == [0.0, 1.0, 1.0, 1.0] * 2
    with open(tmp_path / "status.json") as fh:
        assert json.load(fh)["ahead_share"] == 0.75


# ---- what the loop asks before it sends a step ahead -----------------------

@pytest.mark.parametrize("steps,holds", [
    ((4, 7), {3, 6}),  # before the capture's first step, at its last
    ((1, 3), {2}),
    ((3, 4), {2, 3}),
])
def test_a_window_holds_the_loop_at_its_two_edges(tmp_path, monkeypatch,
                                                  steps, holds):
    monkeypatch.setattr(profiling, "_quiet_start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    win = profiling.ProfilerWindow(str(tmp_path), steps)
    held = set()
    win.maybe_start(1)
    for step in range(1, 10):
        if win.holds(step):
            held.add(step)
        win.maybe_stop(step)
        win.maybe_start(step + 1)
    assert held == holds and win.profiled
    assert not profiling.NULL_PROFILER_WINDOW.holds(3)


def test_the_injector_says_beforehand_where_the_plan_has_a_host_event():
    plan = FaultPlan.parse("sigterm@5,prefetch_crash@8,nan_grad@3:w1", 428, 8)
    inj = HostFaultInjector(plan)
    # the stop is due from its step on, until it has fired; the prefetch
    # fault holds the step before the one whose data it names
    assert [k for k in range(1, 11) if inj.holds(k)] == [5, 6, 7, 8, 9, 10]
    assert inj.sigterm_due(5) and not inj.sigterm_due(5)
    assert [k for k in range(1, 11) if inj.holds(k)] == [7]
    assert not HostFaultInjector(None).holds(5)


def test_a_segment_begun_on_an_open_one_closes_it_on_the_same_read():
    seg = Segments()
    t0 = time.perf_counter()
    seg.begin("fetch", since=t0 - 1.0, gap="book")
    seg.begin("comp")
    seg.begin("fetch", lap="dispatch")
    seg.begin("comp")
    seg.lap("dispatch")
    seg.lap("wait")
    t1 = seg.end(lap="drain")
    t = seg.t
    assert t["dispatch"] + t["wait"] + t["drain"] == pytest.approx(
        t["comp"], abs=1e-9)
    assert t["fetch"] + t["comp"] == pytest.approx(t1 - t0, abs=50e-6)
    assert t["fetch"] + t["comp"] <= t1 - t0
    assert t["book"] == pytest.approx(1.0, abs=1e-3)
