"""The reduction from a profiler capture to per-layer numbers, on hand-made
events and on a small recorded TPU trace (benchmark/testdata/): two traced
steps of resnet18.cyclic_s1 on a TPU v5e, with the scope map of the compiled
step program."""

import gzip
import importlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import manifest, xplane  # noqa: E402
from benchmark.harness.peaks import peaks_of  # noqa: E402


def test_self_time_takes_children_out_of_the_parent():
    evs = [("while", 0.0, 100.0, None, ""), ("a", 10.0, 20.0, None, ""),
           ("b", 40.0, 30.0, None, ""), ("c", 100.0, 5.0, None, "")]
    got = {ev[0]: ns for ev, ns in xplane.self_times(evs)}
    assert got == {"while": 50.0, "a": 20.0, "b": 30.0, "c": 5.0}


def test_union_and_gaps():
    iv = [(0.0, 10.0), (5.0, 12.0), (20.0, 30.0), (22.0, 25.0)]
    assert xplane.union_ns(iv) == 22.0
    assert xplane.gaps(iv, 0.0, 40.0) == [(12.0, 20.0), (30.0, 40.0)]
    assert xplane.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_names_from_hlo_text():
    name = ("%fusion.16 = (bf16[8,32,32,32,3,64]{5,3,2,1,4,0:T(8,128)(2,1)}, "
            "bf16[8]) fusion(f32[8] %x), kind=kLoop")
    assert xplane.instruction_of(name) == "fusion.16"
    assert xplane.label_of(name) == "fusion.16 bf16[8,32,32,32,3,64]"
    assert xplane.instruction_of("%copy.3 = f32[2]{0} copy(%a)") == "copy.3"


def test_scope_map_from_hlo():
    hlo = "\n".join([
        "HloModule jit_step_body",
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step_body)/draco_comp/conv_general_dilated" source_file="x"}',
        '  ROOT %add.2 = f32[8]{0} add(%a, %b), metadata={op_name='
        '"jit(step_body)/draco_decode/add"}',
        "  %copy.3 = f32[8]{0} copy(%a)"])
    assert xplane.scope_map_from_hlo(hlo) == {
        "fusion.1": "draco_comp", "add.2": "draco_decode", "copy.3": ""}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(manifest.BENCH, "testdata",
                        "tpu_v5e_two_steps.json.gz")
    with gzip.open(path, "rt") as fh:
        rec = json.load(fh)
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    raw = {"devices": devices, "anchor_ns": rec["anchor_ns"]}
    return xplane.Trace(raw, rec["scope_map"], 0.0, (0.0, 0.32), 2)


def test_recorded_trace_busy_time(recorded):
    assert recorded.busy_s == pytest.approx(0.198875429, rel=1e-9)
    assert recorded.window_s == 0.32
    assert recorded.mapped_share() == pytest.approx(1.0)


@pytest.mark.parametrize("scope,seconds", [
    ("draco_comp", 0.157849807), ("draco_encode", 0.008155637),
    ("draco_decode", 0.004225111), ("draco_update", 0.000191346),
    ("", 0.028453528)])
def test_recorded_trace_scope_times(recorded, scope, seconds):
    assert recorded.scope_seconds({scope}) == pytest.approx(seconds,
                                                            rel=1e-6)


def test_recorded_trace_scopes_sum_to_busy(recorded):
    total = sum(recorded.scope_seconds({s}) for s in (
        "draco_comp", "draco_encode", "draco_decode", "draco_update", ""))
    assert total == pytest.approx(recorded.busy_s, rel=1e-5)


def test_a_foreign_scope_map_reads_nothing(recorded):
    other = xplane.Trace({"devices": {"d": [(f"%{e[0]} = f32[1]", e[1], e[2])
                                            for e in recorded.first()]},
                          "anchor_ns": None},
                         {"fusion.999999": "draco_comp"}, 0.0, (0.0, 0.32), 2)
    assert other.scope_seconds({"draco_comp"}) is None


def _read(metric, ctx):
    spec = manifest.load_json(os.path.join(
        manifest.BENCH, "layer_metrics", metric + ".json"))
    reader = importlib.import_module(
        f"benchmark.reductions.{spec['reduction']}")
    return reader.read(spec, ctx)


@pytest.fixture
def ctx(recorded):
    return {"trace": recorded, "records": [{"t_fetch": 0.004},
                                           {"t_fetch": 0.006},
                                           {"t_fetch": 0.005}],
            "spans": [], "window": (0.0, 1.0), "chips": 1,
            "job": {"n": 8, "dim": 11173962, "wire": "f32"},
            "peaks": peaks_of("TPU v5 lite"),
            "counters": {"compiles_in_window": 0}}


@pytest.mark.parametrize("metric,want", [
    ("fetch_ms", 5.0),
    ("compiles_in_window", 0.0),
    ("grad_compute_ms", 78.9249035),
    ("coding_ms", 6.190374),
    ("unscoped_ms", 14.226764),
    # 759 829 416 bytes / 819 GB/s = 0.92775 ms over 2.1125555 ms
    ("decode_roofline", 43.91604),
    # busy 99.44 ms a traced step; the untraced window ran 3 steps a second
    ("device_idle_share", 100.0 * (1 - (0.198875429 / 2) / (1.0 / 3))),
])
def test_layer_metric_readers_on_the_recorded_trace(ctx, metric, want):
    assert _read(metric, ctx) == pytest.approx(want, rel=1e-5)


def test_readers_return_nothing_without_a_trace(ctx):
    ctx["trace"] = None
    for metric in ("grad_compute_ms", "coding_ms", "decode_roofline",
                   "device_idle_share", "unscoped_ms"):
        assert _read(metric, ctx) is None


def test_breakdown_rows(recorded):
    out = recorded.breakdown([])
    assert len(out["device_ops"]) == 10
    name, seconds = out["device_ops"][0]
    assert name == "fusion.16 bf16[8,32,32,32,3,64] draco_comp"
    assert seconds == pytest.approx(0.007818111, rel=1e-6)
