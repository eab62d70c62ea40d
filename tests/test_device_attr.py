"""Device-time attribution (obs/device_attr.py + obs/profiling.py, ISSUE 9):
scope-map parsing from optimized HLO, per-thread self-time accounting, the
phase ledger's sums-to-window contract, the collective cross-check (proven
live on a seeded extra-all-gather mismatch), the merged host+device
timeline, the heartbeat ``device`` status block, and a core-marked live
capture smoke on the CPU mesh.

The chip's capture is an ``.xplane.pb`` whose device events are named by
their instruction's whole HLO text (ISSUE 24): the xplane cases feed
``events_from_planes`` planes rebuilt from the recorded TPU v5e trace the
benchmark keeps (benchmark/testdata/tpu_v5e_two_steps.json.gz), and must
read the same per-scope times the benchmark's own reducer reads from it.

The committed fixture (tests/data/device_profile_fixture/) is a synthetic
jax.profiler capture in the XLA:CPU fallback trace shape this container
produces (PERF_HISTORY.md §12): hlo_module/hlo_op args on each complete event, the
named-scope path only in the runner-dumped scope map, a nested ``call``
wrapper on one thread, a GSPMD collective, and an op absent from the scope
map entirely (the honest ``unattributed`` row).
"""

import json
import os

import pytest

from draco_tpu.obs import device_attr as da

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "device_profile_fixture")

# hand-computable ledger of the fixture (see the generator values):
#   draco_comp  = dot.1 400
#   draco_decode= sine.2 280 + all-reduce.3 100 = 380
#   draco_encode= fusion.7 250
#   other       = call self (300-280=20) + all-gather.9 150 = 170
#   unattributed= copy.5 50
FIX_EXPECT = {"draco_comp": 400.0, "draco_pack": 0.0, "draco_input": 0.0,
              "draco_attack": 0.0, "draco_health": 0.0,
              "draco_encode": 250.0, "draco_decode": 380.0,
              "draco_update": 0.0, "other": 170.0, "unattributed": 50.0}

RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "testdata", "tpu_v5e_two_steps.json.gz")


class _Ev:
    def __init__(self, name, start_ns, duration_ns, stats=()):
        self.name, self.start_ns = name, start_ns
        self.duration_ns = duration_ns
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _recorded_planes():
    """The recorded chip trace as the profiler's planes: the ``XLA Ops``
    line as recorded (names are HLO instruction texts), an ``XLA Modules``
    line with one event per step (the record holds two), and a host plane
    with the window's anchor annotation."""
    import gzip

    with gzip.open(RECORDED, "rt") as fh:
        rec = json.load(fh)
    (plane, evs), = rec["devices"].items()
    ops = [_Ev(n, s, d) for n, s, d in evs]
    lo = min(e.start_ns for e in ops)
    hi = max(e.start_ns + e.duration_ns for e in ops)
    # the widest idle stretch of the record separates its two steps
    edges = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in ops)
    reach, cut = edges[0][1], None
    for a, b in edges[1:]:
        if cut is None or a - reach > cut[1] - cut[0]:
            cut = (reach, a) if a > reach else cut
        reach = max(reach, b)
    mods = [_Ev("jit_step_body(7)", lo, cut[0] - lo),
            _Ev("jit_step_body(7)", cut[1], hi - cut[1])]
    host = _Line("python", [_Ev("draco_anchor", rec["anchor_ns"], 2000.0),
                            _Ev("dispatch", lo - 5000.0, 900.0)])
    planes = [_Plane("/host:metadata", []),
              _Plane(plane, [_Line("Steps", []), _Line("XLA Modules", mods),
                             _Line("XLA Ops", ops),
                             _Line("Async XLA Ops", [])]),
              _Plane("/host:CPU", [host])]
    return planes, {"module": "jit_step_body", "ops": rec["scope_map"],
                    "collectives": {}}


def _fixture_events():
    with open(os.path.join(FIXTURE, "plugins", "profile", "0001",
                           "fixture.trace.json")) as fh:
        return json.load(fh)["traceEvents"]


def _fixture_scope():
    with open(os.path.join(FIXTURE, "device_scope_map.json")) as fh:
        return json.load(fh)["programs"][0]


# --------------------------------------------------------------------------
# scope map parsing
# --------------------------------------------------------------------------

HLO_TEXT = """HloModule jit_step_body, entry_computation_layout={()->f32[]}

%region_0.5 (Arg_0.6: f32[], Arg_1.7: f32[]) -> f32[] {
  ROOT %add.8 = f32[] add(f32[] %a, f32[] %b), metadata={op_name="jit(f)/jit(main)/draco_decode/reduce_sum"}
}

ENTRY %main {
  %dot.3 = f32[256,256]{1,0} dot(f32[256,256]{1,0} %x, f32[256,256]{1,0} %x), metadata={op_name="jit(f)/jit(main)/draco_comp/dot_general"}
  %all-reduce.2 = f32[64]{0} all-reduce(f32[64]{0} %g), replica_groups={{0,1}}, metadata={op_name="jit(f)/draco_comp/psum"}
  %all-gather = f32[8,64]{1,0} all-gather(f32[64]{0} %g), dimensions={0}, metadata={op_name="jit(f)/draco_encode/dot_general"}
  %collective-permute.9 = f32[4]{0} collective-permute(f32[4]{0} %t), metadata={op_name="jit(f)/draco_comp/ppermute"}
  ROOT %copy.1 = f32[] copy(f32[] %r)
}
"""


@pytest.mark.core
def test_scope_map_from_hlo():
    sm = da.scope_map_from_hlo(HLO_TEXT)
    assert sm["module"] == "jit_step_body"
    assert sm["ops"]["dot.3"] == "draco_comp"
    assert sm["ops"]["add.8"] == "draco_decode"
    assert sm["ops"]["copy.1"] == ""  # no metadata: mapped, phaseless
    colls = sm["collectives"]
    # explicit iff the op_name path ends in the jax collective primitive
    assert colls["all-reduce.2"] == {
        "kind": "all_reduce", "bytes": 256, "explicit": True,
        "phase": "draco_comp"}
    assert colls["all-gather"]["explicit"] is False  # GSPMD-inserted
    assert colls["all-gather"]["kind"] == "all_gather"
    assert colls["all-gather"]["bytes"] == 8 * 64 * 4
    assert colls["collective-permute.9"]["explicit"] is True


@pytest.mark.core
def test_self_times_nesting_and_threads():
    """A wrapper event pays out its nested children's time on the SAME
    thread; partial overlaps on different threads stay independent."""
    events = [
        {"ph": "X", "tid": 1, "ts": 0.0, "dur": 100.0, "name": "outer"},
        {"ph": "X", "tid": 1, "ts": 10.0, "dur": 30.0, "name": "inner_a"},
        {"ph": "X", "tid": 1, "ts": 50.0, "dur": 40.0, "name": "inner_b"},
        {"ph": "X", "tid": 2, "ts": 20.0, "dur": 60.0, "name": "other_tid"},
    ]
    got = {ev["name"]: dur for ev, dur in da.self_times(events)}
    assert got == {"outer": 30.0, "inner_a": 30.0, "inner_b": 40.0,
                   "other_tid": 60.0}


# --------------------------------------------------------------------------
# fixture: phase ledger sums, collective ledger, cross-check
# --------------------------------------------------------------------------

@pytest.mark.core
def test_fixture_attribution_sums_to_window():
    row = da.attribute_phases(_fixture_events(), _fixture_scope())
    assert row["module"] == "jit_many_body"
    got = {k: v["time_us"] for k, v in row["phases"].items()}
    assert got == FIX_EXPECT
    # the provably-sums contract: phase rows + explicit residual rows ==
    # total device self-time, nothing absorbed, nothing double-counted
    assert sum(got.values()) == pytest.approx(row["total_device_us"])
    assert row["total_device_us"] == pytest.approx(1250.0)
    # wall is the envelope of the module's events (1000 .. 1950), and the
    # other module's event did not leak in
    assert row["wall_us"] == pytest.approx(950.0)
    assert row["matched_events"] == 7  # jit_other's event stayed out
    fr = {k: v["frac"] for k, v in row["phases"].items()}
    assert sum(fr.values()) == pytest.approx(1.0)
    assert fr["draco_decode"] == pytest.approx(380.0 / 1250.0)
    # the chip's shape (xplane): an event is named by its instruction's
    # whole HLO text and belongs to the module event that contains it; a
    # scope the ledger predates lands in the unattributed residual, and an
    # op outside every module event is left out
    text = "%fusion.7 = (f32[8]{0:T(8)}, f32[8]) fusion(f32[8] %p), kind=kLoop"
    planes = [_Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_many_body(3)", 0.0, 100e3)]),
        _Line("XLA Ops", [_Ev(text, 10e3, 10e3),
                          _Ev("%new.1 = f32[] add(%a, %b)", 30e3, 5e3),
                          _Ev("%late.2 = f32[] add(%a, %b)", 200e3, 5e3)])])]
    scope = dict(_fixture_scope())
    scope["ops"] = dict(scope["ops"], **{"new.1": "draco_future"})
    srow = da.attribute_phases(da.events_from_planes(planes), scope)
    assert srow["matched_events"] == 2
    assert srow["phases"]["draco_encode"]["time_us"] == pytest.approx(10.0)
    assert srow["phases"]["unattributed"]["time_us"] == pytest.approx(5.0)


@pytest.mark.core
def test_fixture_collective_ledger_and_cross_check():
    led = da.collective_ledger(_fixture_events(), _fixture_scope())
    assert led["explicit"]["all_reduce"] == {
        "instructions": 1, "events": 1, "bytes": 1024, "time_us": 100.0}
    assert led["gspmd"]["all_gather"]["instructions"] == 1
    assert led["gspmd"]["all_gather"]["bytes"] == 2048
    # reconciles against the linted manifest (missing kinds default 0)
    ok = da.cross_check(led, {"all_reduce": 1}, "fixture")
    assert ok["ok"] and ok["observed"]["all_reduce"] == 1
    # the chip's shape (xplane): the collective's event is its HLO text;
    # it uses the SAME selection as the phase ledger — counted, not
    # dropped into an empty ledger that would then hard-fail the manifest
    # cross-check
    tpu = da.events_from_planes([_Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_many_body(3)", 0.0, 100e3)]),
        _Line("XLA Ops", [_Ev("%all-reduce.3 = f32[256]{0} all-reduce("
                              "f32[256]{0} %g), replica_groups={{0,1}}",
                              50e3, 20e3)])])])
    tled = da.collective_ledger(tpu, _fixture_scope())
    assert tled["explicit"]["all_reduce"]["instructions"] == 1
    assert tled["explicit"]["all_reduce"]["time_us"] == pytest.approx(20.0)
    # manifest-skipped programs check nothing
    assert da.cross_check(led, None, "fixture")["skipped"]


@pytest.mark.core
def test_cross_check_trips_on_seeded_extra_all_gather():
    """The negative control (PR 3 controls.py pattern): an extra explicit
    all-gather appearing in the runtime trace that the static Manifest does
    not pin must raise, naming the drifted kind both ways."""
    scope = _fixture_scope()
    seeded = json.loads(json.dumps(scope))
    # the GSPMD all-gather drifts to explicit — i.e. the executed program
    # grew a shard_map all_gather the manifest never audited
    seeded["collectives"]["all-gather.9"]["explicit"] = True
    led = da.collective_ledger(_fixture_events(), seeded)
    with pytest.raises(da.CollectiveMismatchError) as ei:
        da.cross_check(led, {"all_reduce": 1}, "seeded_control")
    msg = str(ei.value)
    assert "all_gather" in msg and "seeded_control" in msg
    assert "'manifest': 0" in msg and "'trace': 1" in msg
    # the opposite direction (manifest expects more than the trace ran)
    # trips the same hard error
    led_ok = da.collective_ledger(_fixture_events(), scope)
    with pytest.raises(da.CollectiveMismatchError):
        da.cross_check(led_ok, {"all_reduce": 1, "collective_permute": 2},
                       "seeded_control")


# --------------------------------------------------------------------------
# fold_capture + merged timeline + heartbeat device block
# --------------------------------------------------------------------------

@pytest.mark.core
def test_fold_capture_fixture_end_to_end():
    fold = da.fold_capture(FIXTURE)
    assert fold is not None and fold["cell"] == "fixture"
    (prog,) = fold["programs"]
    assert prog["phases"]["draco_comp"]["time_us"] == 400.0
    assert prog["lint_row"] == "fixture_row"
    assert fold["anchor"]["steps_profiled"] == 5
    block = da.device_status_block(fold)
    assert block["decode_share"] == pytest.approx(380.0 / 1250.0, abs=1e-4)
    assert block["attributed_frac"] == pytest.approx(1 - 50.0 / 1250.0,
                                                     abs=1e-4)
    assert block["profiled_steps"] == 5
    # the fixture's scope map stamps flops_per_step, so the achieved rate
    # is computable; the CPU fallback has no honest peak so the fraction
    # stays None (PERF_HISTORY.md §12)
    assert block["achieved_flops_per_s"] == pytest.approx(
        1.0e6 * 5 / (1250.0 / 1e6))
    assert block["achieved_flops_frac"] is None


@pytest.mark.core
def test_fold_capture_missing_and_torn(tmp_path):
    assert da.fold_capture(str(tmp_path)) is None  # no capture: tolerated
    d = tmp_path / "plugins" / "profile" / "0001"
    d.mkdir(parents=True)
    (d / "torn.trace.json").write_text('{"traceEvents": [{"ph": "X"')
    assert da.fold_capture(str(tmp_path)) is None  # torn: tolerated
    with pytest.raises(ValueError):
        da.fold_capture(str(tmp_path), strict=True)  # tools demand it


@pytest.mark.core
def test_merge_timeline_anchored_shared_clock():
    events = _fixture_events()
    with open(os.path.join(FIXTURE, "trace.json")) as fh:
        host = json.load(fh)["traceEvents"]
    with open(os.path.join(FIXTURE, "host_anchor.json")) as fh:
        anchor = json.load(fh)
    merged = da.merge_timeline(host, events, _fixture_scope(), anchor)
    mt = merged["mergedTimeline"]
    assert mt["anchored"] is True
    assert mt["anchor_kind"] == "start_trace"
    # device origin = END of the python tracer's start_trace frame (900);
    # the anchor pins that instant at host-tracer ts 5000
    assert mt["device_offset_us"] == pytest.approx(5000.0 - 900.0)
    by_name = {}
    for ev in merged["traceEvents"]:
        by_name.setdefault(ev.get("name"), []).append(ev)
    # host lanes unchanged, device lanes shifted + namespaced + phased
    assert by_name["dispatch"][0]["ts"] == 4000.0
    dot = [e for e in by_name["dot.1"] if e.get("cat") == "device"][0]
    assert dot["ts"] == pytest.approx(1000.0 + 4100.0)
    assert dot["pid"] == 701 + da.DEVICE_PID_BASE
    assert dot["args"]["phase"] == "draco_comp"
    # device process metadata renamed so Perfetto shows both sides apart
    names = [e for e in merged["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert any(e["args"]["name"].startswith("device: ") for e in names)
    assert mt["droppedDeviceEvents"] == 0
    # quiet capture (python tracer off — the production default): no
    # start_trace event, so the DRAIN stamp anchors the capture's LAST
    # event END (all-reduce.3 at 1950) to the host instant the devices
    # went idle, instead of over-shifting early via the earliest event
    quiet = [e for e in events if "start_trace" not in e.get("name", "")]
    qm = da.merge_timeline([], quiet, _fixture_scope(), anchor)
    qmt = qm["mergedTimeline"]
    assert qmt["anchored"] is True and qmt["anchor_kind"] == "drain"
    assert qmt["device_offset_us"] == pytest.approx(1005000.0 - 1950.0)
    # unanchored merge (no host tracer ran): device lanes keep own origin
    un = da.merge_timeline([], events, _fixture_scope(), None)
    assert un["mergedTimeline"]["anchored"] is False
    assert un["mergedTimeline"]["anchor_kind"] is None


@pytest.mark.core
def test_merge_timeline_caps_device_events_loudly():
    events = [{"ph": "X", "pid": 1, "tid": 1, "ts": float(i),
               "dur": float(i % 7 + 1), "name": f"op.{i}"}
              for i in range(50)]
    merged = da.merge_timeline([], events, None, None, max_device_events=10)
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 10
    assert merged["mergedTimeline"]["droppedDeviceEvents"] == 40
    # longest events survive the cap (7 events of dur 7, then dur 6)
    assert min(e["dur"] for e in xs) == 6.0


@pytest.mark.core
def test_heartbeat_device_block(tmp_path):
    """RunHeartbeat.observe_device folds the capture into the ``device``
    status block on the next beat — consumers tolerate it missing, assert
    it when present (STATUS_SCHEMA stays 2; the block is additive)."""
    from draco_tpu.obs.heartbeat import STATUS_SCHEMA, RunHeartbeat

    hb = RunHeartbeat(str(tmp_path), num_workers=8)
    hb.observe({"step": 1, "loss": 1.0})
    payload = hb.beat(1, total_steps=4)
    assert "device" not in payload  # no capture observed yet
    hb.observe_device(FIXTURE)
    payload = hb.beat(2, total_steps=4)
    assert payload["schema"] == STATUS_SCHEMA
    dev = payload["device"]
    assert dev["decode_share"] == pytest.approx(0.304, abs=1e-3)
    assert dev["profile_dir"] == FIXTURE
    on_disk = json.loads((tmp_path / "status.json").read_text())
    assert on_disk["device"]["profiled_steps"] == 5
    # a fold that fails never raises, and is not dropped either: the
    # block holds the one-line cause
    hb.observe_device(str(tmp_path))
    assert "no capture" in hb.beat(3)["device"]["error"]
    d = tmp_path / "plugins" / "profile" / "0001"
    d.mkdir(parents=True)
    (d / "torn.trace.json").write_text('{"traceEvents": [{"ph": "X"')
    hb.observe_device(str(tmp_path))
    assert "JSONDecodeError" in hb.beat(4)["device"]["error"]


# --------------------------------------------------------------------------
# the chip's capture: an xplane whose events are HLO instruction texts
# --------------------------------------------------------------------------

@pytest.mark.core
def test_xplane_recorded_tpu_trace_reads_the_benchmarks_numbers():
    """The recorded v5e trace through the program's own reducer: the same
    per-scope seconds benchmark/harness/xplane.py reads from it
    (tests/benchmark/test_benchmark_trace.py pins those)."""
    planes, scope = _recorded_planes()
    events = da.events_from_planes(planes)
    ops = [e for e in events if e["ph"] == "X" and e.get("args")]
    assert ops[0]["name"] == ops[0]["args"]["hlo_op"]
    assert " = " not in ops[0]["name"] and not ops[0]["name"].startswith("%")
    assert {e["args"]["hlo_module"] for e in ops} == {"jit_step_body"}
    row = da.attribute_phases(events, scope)
    got = {k: v["time_us"] * 1e-6 for k, v in row["phases"].items()}
    assert got["draco_comp"] == pytest.approx(0.157849807, rel=1e-6)
    assert got["draco_encode"] == pytest.approx(0.008155637, rel=1e-6)
    assert got["draco_decode"] == pytest.approx(0.004225111, rel=1e-6)
    assert got["draco_update"] == pytest.approx(0.000191346, rel=1e-6)
    assert got["other"] == pytest.approx(0.028453528, rel=1e-6)
    assert got["unattributed"] == 0.0
    assert sum(got.values()) == pytest.approx(row["total_device_us"] * 1e-6)
    block = da.device_status_block({"programs": [row], "anchor": {
        "steps_profiled": 2}})
    assert block["attributed_frac"] == 1.0
    assert block["phase_fracs"]["draco_comp"] == pytest.approx(0.7937,
                                                               abs=1e-4)


@pytest.mark.core
def test_xplane_anchor_annotation_puts_both_sides_on_one_clock():
    """The window's ``draco_anchor`` annotation is the instant
    ``tracer_ts_us`` stamps: the merge shifts by exactly their difference,
    and the capture's host events stand in for a missing host trace."""
    planes, scope = _recorded_planes()
    events = da.events_from_planes(planes)
    mark = next(e for e in events if e["name"] == da.ANCHOR_EVENT)
    assert mark["cat"] == "host"
    anchor = {"tracer_ts_us": 123456.0, "drained_tracer_ts_us": 9e9}
    host = [{"name": "dispatch", "ph": "X", "ts": 1.0, "dur": 2.0,
             "pid": 1, "tid": 1}]
    merged = da.merge_timeline(host, events, scope, anchor,
                               max_device_events=100)
    mt = merged["mergedTimeline"]
    assert mt["anchor_kind"] == "annotation"
    assert mt["device_offset_us"] == pytest.approx(
        123456.0 - (mark["ts"] + mark["dur"]))
    # the host trace was given: the capture's own host events stay out
    assert not [e for e in merged["traceEvents"] if e.get("cat") == "host"]
    alone = da.merge_timeline([], events, scope, anchor,
                              max_device_events=100)
    names = {e["name"] for e in alone["traceEvents"]
             if e.get("cat") == "host"}
    assert names == {"draco_anchor", "dispatch"}


# --------------------------------------------------------------------------
# live capture smoke on the CPU mesh
# --------------------------------------------------------------------------

@pytest.mark.core
def test_live_capture_smoke_cpu_mesh(tmp_path):
    """The whole spine live on the 8-device CPU mesh: profiler_window
    captures a real jitted program with draco named scopes, the AOT scope
    map attributes its trace events, phases sum to the window, and the
    zero-collective cross-check agrees with an empty manifest."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.obs.profiling import ANCHOR_FILE, profiler_window

    def f(x):
        with jax.named_scope("draco_comp"):
            y = x @ x
        with jax.named_scope("draco_decode"):
            z = jnp.tanh(y).sum()
        return z

    jf = jax.jit(f)
    x = jnp.ones((128, 128), jnp.float32)
    jf(x).block_until_ready()  # warm: the window must not pay the compile
    sm = da.scope_map_from_hlo(jf.lower(x).compile().as_text())
    assert any(v == "draco_comp" for v in sm["ops"].values())

    prof = str(tmp_path / "prof")
    win = profiler_window(prof, (1, 4))
    assert win.active is False
    for step in range(1, 6):
        win.maybe_start(step)
        r = jf(x)
        win.maybe_stop(step, r)
    assert win.profiled and not win.active
    assert os.path.exists(os.path.join(prof, ANCHOR_FILE))
    trace = da.find_capture(prof)
    assert trace is not None, "no capture landed"
    events, _ = da.load_trace(trace)
    row = da.attribute_phases(events, sm)
    assert row["total_device_us"] > 0
    assert row["phases"]["draco_comp"]["time_us"] > 0
    assert sum(v["time_us"] for v in row["phases"].values()) == \
        pytest.approx(row["total_device_us"])
    led = da.collective_ledger(events, sm)
    assert da.cross_check(led, {}, "smoke")["ok"]  # zero-collective program

    anchor = da.load_anchor(prof)
    assert anchor["steps_profiled"] == 3  # steps 1..3 under window (1, 4)
    merged = da.merge_timeline([], events, sm, anchor)
    assert any(e.get("cat") == "device" for e in merged["traceEvents"])


@pytest.mark.core
def test_trace_report_appends_device_table(capsys):
    """tools/trace_report.py (jax-free): a run dir holding a profiler
    capture grows the per-phase device table + comms ledger; a dir without
    one folds the host half only, no note, no error."""
    from tools import trace_report

    report = trace_report.make_report(
        os.path.join(FIXTURE, "trace.json"),
        metrics_path=None, profile_dir=FIXTURE)
    dev = report["device"]
    assert dev["programs"][0]["module"] == "jit_many_body"
    assert dev["programs"][0]["phases"]["draco_decode"]["time_us"] == 380.0
    assert dev["steps_profiled"] == 5
    trace_report.print_table(report)
    out = capsys.readouterr().out
    assert "device program jit_many_body" in out
    assert "draco_decode" in out
    assert "collective explicit/all_reduce: instructions=1" in out
    # no capture → no device section (the common case, tolerated silently)
    report2 = trace_report.make_report(os.path.join(FIXTURE, "trace.json"),
                                       metrics_path=None,
                                       profile_dir=os.path.dirname(FIXTURE))
    assert "device" not in report2


def test_profiler_window_stop_survives_poisoned_drain(tmp_path):
    """stop() runs from the loops' finally blocks: a poisoned carry (fault
    injection, device error) raising on the drain await must not mask the
    original exception or leak the profiler session — the capture is
    truncated, the session still closes."""
    from draco_tpu.obs.profiling import profiler_window

    class Poisoned:
        def block_until_ready(self):
            raise RuntimeError("device error surfaced at drain")

    win = profiler_window(str(tmp_path / "prof"), (1, 4))
    win.maybe_start(1)
    assert win.active
    win.stop(Poisoned())  # must not raise
    assert win.profiled and not win.active


def test_null_window_is_inert():
    from draco_tpu.obs.profiling import NULL_PROFILER_WINDOW, profiler_window

    win = profiler_window(None)
    assert win is NULL_PROFILER_WINDOW
    assert profiler_window("", (1, 2)) is NULL_PROFILER_WINDOW
    assert profiler_window("/tmp/x", enabled=False) is NULL_PROFILER_WINDOW
    win.maybe_start(1)
    win.maybe_stop(1)
    win.stop()
    assert win.active is False and win.profiled is False
