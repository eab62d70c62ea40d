"""What PR 24 adds to the benchmark, as data only: seven per-layer metrics
that read what the program now says of itself — the eager loop's record
keys ``t_dispatch`` / ``t_wait`` / ``t_drain`` / ``t_book`` through the
``record_median_ms`` reduction, and the step program's ``draco_pack`` /
``draco_health`` / ``draco_input`` scopes through ``scope_ms_per_step``.
Their files load, name reductions that were there, and a run at the tiny
size (LeNet on the CPU, benchmark/testdata/) reports the four host ones,
whose parts add up."""

import importlib
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import manifest, runner  # noqa: E402

HOST = {"dispatch_ms": "t_dispatch", "device_wait_ms": "t_wait",
        "drain_ms": "t_drain", "bookkeeping_ms": "t_book"}
DEVICE = {"pack_ms": ["draco_pack"], "health_ms": ["draco_health"],
          "input_ms": ["draco_input"]}
CELLS = ["resnet18.cyclic_s1", "resnet18.mean_b96", "vgg11.cyclic_s2"]
TESTDATA = os.path.join(manifest.BENCH, "testdata")


def _spec(name):
    return manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))


@pytest.mark.parametrize("name", sorted(HOST) + sorted(DEVICE))
def test_new_layer_metric_file_and_manifest_entry(name):
    spec = _spec(name)
    if name in HOST:
        assert spec["reduction"] == "record_median_ms"
        assert spec["key"] == HOST[name]
    else:
        assert spec["reduction"] == "scope_ms_per_step"
        assert spec["scopes"] == DEVICE[name]
    # a reduction the benchmark already had: this PR brings no reader
    importlib.import_module(f"benchmark.reductions.{spec['reduction']}")
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == ("program_span" if name in HOST
                               else "device_trace")
    assert entry["layer"] == ("host loop" if name in HOST
                              else "step builder")
    assert entry["workloads"] == CELLS
    for cell in CELLS:
        assert name in {x["name"] for x in
                        manifest.metrics_for(m, cell, "per_layer")}


def test_new_entries_come_after_the_old_ones():
    names = [x["name"] for x in manifest.load_manifest()["per_layer"]]
    assert names[-7:] == ["dispatch_ms", "device_wait_ms", "drain_ms",
                          "bookkeeping_ms", "pack_ms", "health_ms",
                          "input_ms"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cell = {"name": "tiny.cyclic_s1", "config": "lenet-mnist-tiny",
            "traffic": "tiny_cyclic_s1", "chips": 1, "why": "test"}
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return runner.run_cell(
        cell, load("lenet-mnist-tiny.json"), load("tiny_cyclic_s1.json"),
        load("tiny_limits.json"), manifest.load_manifest()["per_layer"],
        2**31 + 24, 0.5, True, time.time(), require_tpu=False,
        scratch=str(tmp_path_factory.mktemp("ledger")))


@pytest.mark.parametrize("name", sorted(HOST))
def test_tiny_run_reports_the_host_metric(traced, name):
    got = traced["metrics"][name]
    assert got["unit"] == "ms" and got["value"] >= 0.0
    if name != "bookkeeping_ms":
        assert got["value"] > 0.0


def test_tiny_run_leaves_the_device_metrics_out(traced):
    # a CPU capture has no TPU plane: the scope readers find nothing
    assert traced["correct"] is True
    assert not set(DEVICE) & set(traced["metrics"])


def test_traced_line_reports_every_host_metric_and_no_other(traced):
    # what test_benchmark_run.py's traced case pinned as {"fetch_ms",
    # "compiles_in_window"} before this PR's four host metrics: that pin
    # is a benchmark PR's to move, this is the set as the manifest has it
    assert set(traced["metrics"]) == {
        x["name"] for x in manifest.load_manifest()["per_layer"]
        if x["source"] != "device_trace"}
    assert set(traced["metrics"]) == {"fetch_ms", "compiles_in_window",
                                      *HOST}
