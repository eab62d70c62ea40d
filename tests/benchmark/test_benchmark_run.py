"""A run, end to end, at a size a CPU holds: LeNet on MNIST shapes, eight
logical workers, cyclic s=1 with a live adversary (benchmark/testdata/).
The harness's look for a chip is skipped here and nowhere else.

* the sound run is correct and its line has the contract's shape;
* with the timed path broken underneath (a step that returns its state
  unchanged) ``correct`` comes out false;
* the lower-precision control (the reference in bfloat16) fails the limits
  the sound run passes;
* the command itself exits 1 without a TPU and prints no result.
"""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import check, manifest, runner  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.cyclic_s1", "config": "lenet-mnist-tiny",
        "traffic": "tiny_cyclic_s1", "chips": 1, "why": "test"}


def _files():
    return (manifest.load_json(os.path.join(TESTDATA,
                                            "lenet-mnist-tiny.json")),
            manifest.load_json(os.path.join(TESTDATA,
                                            "tiny_cyclic_s1.json")),
            manifest.load_json(os.path.join(TESTDATA, "tiny_limits.json")))


def _run(tmp, trace=False, seed=2**31 + 11):
    config, traffic, limits = _files()
    m = manifest.load_manifest()
    metrics = m["per_layer"] if trace else m["end_to_end"]
    return runner.run_cell(CELL, config, traffic, limits, metrics, seed,
                           0.5, trace, time.time(), require_tpu=False,
                           scratch=str(tmp))


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("sound"))


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] >= 3 + 6 + 8


def test_line_has_the_contracts_keys(sound):
    assert set(sound) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert set(sound["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    json.dumps(sound)  # the last line is this object


@pytest.mark.parametrize("metric", [
    x["name"] for x in manifest.load_manifest()["end_to_end"]])
def test_every_end_to_end_metric_is_reported(sound, metric):
    got = sound["metrics"][metric]
    assert set(got) == {"value", "unit"}
    assert isinstance(got["value"], float)
    if metric != "peak_hbm_gb":  # the CPU backend reports no memory
        assert got["value"] > 0


def test_traced_line_carries_layers_and_breakdown(tmp_path):
    out = _run(tmp_path, trace=True)
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "breakdown"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # no TPU plane in a CPU capture: the device readers find nothing and
    # leave their metrics out; the host readers report
    assert set(out["metrics"]) == {"fetch_ms", "compiles_in_window"}
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0


def test_broken_step_comes_out_not_correct(tmp_path, monkeypatch):
    """The step program replaced, under the production loop, by one that
    hands its state back unchanged."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.training import trainer as trainer_mod

    real_build = trainer_mod.build_train_setup

    def build(cfg, mesh, dataset_name=None):
        setup = real_build(cfg, mesh, dataset_name=dataset_name)

        def idle_step(state, x, y, mask, *rest):
            kept = jax.tree.map(jnp.copy, state)
            new, metrics = setup.train_step(state, x, y, mask, *rest)
            return kept._replace(step=new.step), metrics

        return setup._replace(train_step=idle_step)

    monkeypatch.setattr(trainer_mod, "build_train_setup", build)
    out = _run(tmp_path)
    assert out["correct"] is False


def test_lower_precision_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees

    config, traffic, limits = _files()
    tc = dict(config["train_config"], **traffic["train_config"])
    seed = 77
    data = runner.make_data(config, seed)
    reference = runner.reference_of(config)
    job = reference.make_job(config, {
        "policy": "cyclic", "n": tc["num_workers"],
        "batch": tc["batch_size"], "seed": 428, "lr": tc["lr"],
        "momentum": tc["momentum"], "augment": False})
    shapes = jax.eval_shape(
        lambda: {"Conv_0": {"kernel": jax.numpy.zeros((5, 5, 1, 20)),
                            "bias": jax.numpy.zeros((20,))},
                 "Conv_1": {"kernel": jax.numpy.zeros((5, 5, 20, 50)),
                            "bias": jax.numpy.zeros((50,))},
                 "Dense_0": {"kernel": jax.numpy.zeros((800, 500)),
                             "bias": jax.numpy.zeros((500,))},
                 "Dense_1": {"kernel": jax.numpy.zeros((500, 10)),
                             "bias": jax.numpy.zeros((10,))}})
    weights = seeded.make_weights(shapes, config["weights"], seed)
    ref = reference.follow(job, weights, data, 3)
    low = reference.follow(job, weights, data, 3, dtype="bfloat16")

    def verdict(f):
        rows = check.compare(
            {"losses": f.losses, "grad_norms": f.grad_norms,
             "delta_norms": f.delta_norms,
             "grad_diff": check.noise_units(
                 trees.rel_diff(f.grad, ref.grad), 0.0),
             "unlocated_steps": 0,
             "nonfinite_steps": 0}, ref, limits)
        return all(ok for *_, ok in rows)

    assert verdict(ref) is True
    assert verdict(low) is False


def test_command_exits_nonzero_without_a_tpu(capsys):
    cell = manifest.load_manifest()["workloads"][0]["name"]
    with pytest.raises(SystemExit) as e:
        runner.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert e.value.code == 1
    out = capsys.readouterr()
    assert out.out == "" and "no accelerator" in out.err


def test_seed_makes_the_inputs():
    import numpy as np

    config = dict(_files()[0])
    config["data"] = dict(config["data"], train_examples=32)
    a = runner.make_data(config, 2**31 + 5)
    b = runner.make_data(config, 2**31 + 5)
    c = runner.make_data(config, 2**31 + 6)
    assert a[0].shape == (32, 28, 28, 1) and a[0].dtype == np.float32
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("rule,check_leaf", [
    ("normal_fan_in", lambda x: 0.05 < float(x.std()) * 8 < 2.0),
    ("normal:0.02", lambda x: 0.015 < float(x.std()) < 0.025),
    ("ones", lambda x: bool((x == 1).all())),
    ("zeros", lambda x: bool((x == 0).all())),
])
def test_weight_rules_come_from_the_configuration(rule, check_leaf):
    """A leaf name the CNNs do not have (an LM's ``embedding``) needs a
    line in the configuration's ``weights`` block and no code."""
    import jax

    from benchmark.harness import seeded

    shapes = {"tok": {"embedding": jax.ShapeDtypeStruct((64, 64),
                                                        "float32")}}
    tree = seeded.make_weights(shapes, {"embedding": rule}, 2**31 + 9)
    assert check_leaf(tree["tok"]["embedding"])
    with pytest.raises(ValueError, match="no rule for a leaf"):
        seeded.make_weights(shapes, {"kernel": "ones"}, 1)
