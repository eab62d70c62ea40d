"""BENCHMARK.json against the contract's static rules, and every cell's data
files against the program's own config validation."""

import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import manifest  # noqa: E402

M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_manifest_as_committed_is_clean():
    assert manifest.check_manifest(M) == []


def _broken(edit):
    m = copy.deepcopy(M)
    edit(m)
    return manifest.check_manifest(m)


BREACHES = {
    "extra_top_key": lambda m: m.update(notes="x"),
    "name_with_space": lambda m: m["workloads"][0].update(name="a b"),
    "name_with_slash": lambda m: m["end_to_end"][0].update(name="a/b"),
    "name_too_long": lambda m: m["per_layer"][0].update(name="x" * 65),
    "unit_with_space": lambda m: m["end_to_end"][0].update(
        unit="examples per s"),
    "unit_greek": lambda m: m["end_to_end"][1].update(unit="µs"),
    "better_missing": lambda m: m["end_to_end"][0].pop("better"),
    "bound_over_tenth": lambda m: m["end_to_end"][0].update(bound=0.2),
    "setup_s_missing": lambda m: m["end_to_end"].pop(
        [x["name"] for x in m["end_to_end"]].index("setup_s")),
    "why_on_metric": lambda m: m["per_layer"][0].update(why="because"),
    "why_two_lines": lambda m: m["workloads"][0].update(why="a\nb"),
    "why_too_long": lambda m: m["workloads"][0].update(why="y" * 201),
    "chips_two": lambda m: m["workloads"][0].update(chips=2),
    "pair_twice": lambda m: m["workloads"].append(
        dict(m["workloads"][0], name="again")),
    "unknown_config": lambda m: m["workloads"][0].update(config="nope"),
    "moves_unknown": lambda m: m["per_layer"][0].update(moves="nope"),
    "source_unknown": lambda m: m["per_layer"][0].update(source="guess"),
    "e2e_program_source": lambda m: m["end_to_end"][0].update(
        source="program_counter"),
    "reduced_names_width": lambda m: m["configs"][0].update(
        reduced=["hidden_size"]),
    "file_outside_paths": lambda m: m["configs"][0].update(
        file="draco_tpu/presets.py"),
    "command_leaves_repo": lambda m: m["command"].append("../x.py"),
    "run_seconds_over": lambda m: m.update(run_seconds=52),
    "four_chip_quota": lambda m: [w.update(chips=4)
                                  for w in m["workloads"]],
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_manifest_rule_is_checked(breach):
    assert _broken(BREACHES[breach]), f"{breach} went unnoticed"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve_to_a_valid_train_config(cell):
    from draco_tpu.config import TrainConfig

    w = manifest.cell_of(M, cell)
    config = manifest.config_of(M, w)
    traffic = manifest.traffic_of(w)
    limits = manifest.limits_of(w)
    assert traffic["name"] == w["traffic"] and config["name"] == w["config"]
    fields = dict(config["train_config"], **traffic["train_config"])
    cfg = TrainConfig(**fields).validate()
    assert cfg.compute_dtype == "float32"
    assert {"loss_gap", "grad_norm_gap", "grad_diff",
            "delta_norm_gap"} <= set(limits)
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "routes", traffic["route"] + ".py"))
    # every module the configuration names is a file of its own
    for parts in (("reference", config["reference"]["module"]),
                  ("reference", "nets", config["reference"]["net"]),
                  ("data", config["data"]["kind"])):
        assert os.path.isfile(os.path.join(manifest.BENCH, *parts) + ".py")


def test_every_file_under_traffic_and_layer_metrics_is_used():
    """No data file that no cell runs."""
    used = {w["traffic"] + ".json" for w in M["workloads"]}
    assert set(os.listdir(os.path.join(manifest.BENCH, "traffic"))) == used
    named = {x["name"] + ".json" for x in M["per_layer"]}
    assert set(os.listdir(os.path.join(manifest.BENCH,
                                       "layer_metrics"))) == named


@pytest.mark.parametrize("metric", [x["name"] for x in M["per_layer"]])
def test_per_layer_metric_has_its_own_reader(metric):
    spec = manifest.load_json(os.path.join(
        manifest.BENCH, "layer_metrics", metric + ".json"))
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "reductions", spec["reduction"] + ".py"))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        manifest.cell_of(M, "no.such.cell")
