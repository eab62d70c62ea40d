"""What PR 26 adds to the benchmark: the token route, seeded token data, the
plain LM job, the nested-scope and collective reductions, the LM's FLOP
functions, one configuration and two cells — new files and new entries only.
A tiny token cell (the kanana-2 block at hidden 64, benchmark/testdata/)
runs end to end through ``runner.run_cell`` on the CPU: sound it is
correct, with the step broken underneath it is not, and the lower-precision
control fails the limits the sound run passes."""

import importlib
import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import check, lm_costs, manifest, runner  # noqa: E402
from benchmark.harness import xplane  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.lm_maj_vote_r3", "config": "latent-moe-tiny",
        "traffic": "tiny_lm_maj_vote_r3", "chips": 1, "why": "test"}
NEW_CELLS = ("kanana2.maj_vote_r3", "resnet18.cyclic_s1_b128")
NEW_METRICS = {
    "attention_ms": ("inner_scope_ms_per_step", "kanana2.maj_vote_r3"),
    "moe_route_ms": ("inner_scope_ms_per_step", "kanana2.maj_vote_r3"),
    "moe_experts_ms": ("inner_scope_ms_per_step", "kanana2.maj_vote_r3"),
    "lm_head_ms": ("inner_scope_ms_per_step", "kanana2.maj_vote_r3"),
    "vote_ms": ("scope_ms_per_step", "kanana2.maj_vote_r3"),
    "attention_roofline": ("inner_scope_roofline", "kanana2.maj_vote_r3"),
    "collective_ms": ("collective_ms_per_step", "resnet18.cyclic_s1_b128"),
}


def _files():
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return (load("latent-moe-tiny.json"), load("tiny_lm_maj_vote_r3.json"),
            load("tiny_lm_limits.json"))


def _run(tmp, trace=False, seed=2**31 + 26):
    config, traffic, limits = _files()
    m = manifest.load_manifest()
    metrics = m["per_layer"] if trace else m["end_to_end"]
    return runner.run_cell(CELL, config, traffic, limits, metrics, seed, 0.5,
                           trace, time.time(), require_tpu=False,
                           scratch=str(tmp))


# ---- the manifest's new entries ---------------------------------------

def test_manifest_gains_the_configuration_and_both_cells():
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    (entry,) = [c for c in m["configs"] if c["name"] == "kanana-2-30b-a3b-ep16"]
    assert entry["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells["kanana2.maj_vote_r3"]["chips"] == 1
    assert cells["kanana2.maj_vote_r3"]["traffic"] == "lm_maj_vote_r3"
    assert cells["resnet18.cyclic_s1_b128"]["chips"] == 4
    assert cells["resnet18.cyclic_s1_b128"]["config"] == "resnet18-cifar10"
    assert [w["name"] for w in m["workloads"]][-2:] == list(NEW_CELLS)


def test_every_width_of_the_configuration_is_the_catalog_rows():
    """The published config keys, verbatim but for the three reduced ones;
    the model's mapping keeps the router's published width."""
    config = manifest.load_json(os.path.join(
        manifest.BENCH, "configs", "kanana-2-30b-a3b-ep16.json"))
    published = {
        "hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 64, "kv_lora_rank": 512,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "num_experts_per_tok": 6, "n_shared_experts": 2,
        "num_hidden_layers": 48, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.448, "rope_theta": 1000000,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 32768}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 16032)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    spec = config["train_config"]["model_spec"]
    assert spec["n_routed_experts"] == 128 and spec["experts_held"] == [0, 8]
    assert spec["vocab_rows"] == config["data"]["vocab"] == 16032
    for key, value in published.items():
        assert spec[key] == value, key


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_layer_metric_has_its_file_its_reader_and_its_cell(name):
    reduction, cell = NEW_METRICS[name]
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    assert spec["reduction"] == reduction
    importlib.import_module(f"benchmark.reductions.{reduction}")
    m = manifest.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert entry["workloads"] == [cell]
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "step_ms_p50"
    assert name in {x["name"] for x in
                    manifest.metrics_for(m, cell, "per_layer")}


def test_the_new_metrics_come_at_the_end_of_the_list():
    """The driver reads an entry put before an accepted one as a change to
    that one (PR 26's first check was refused for it), so the accepted
    fourteen keep their places and the new seven follow them.  The pin of
    ``names[-7:]`` in test_benchmark_host_ledger.py fails for that since:
    it is a ``benchmark`` PR's to move (PERF.md section 7k)."""
    names = [x["name"] for x in manifest.load_manifest()["per_layer"]]
    assert names[:14] == [
        "fetch_ms", "compiles_in_window", "grad_compute_ms", "unscoped_ms",
        "coding_ms", "decode_roofline", "device_idle_share", "dispatch_ms",
        "device_wait_ms", "drain_ms", "bookkeeping_ms", "pack_ms",
        "health_ms", "input_ms"]
    assert names[14:] == list(NEW_METRICS)


def test_the_old_metrics_keep_to_the_old_cells():
    m = manifest.load_manifest()
    for cell in NEW_CELLS:
        assert {x["name"] for x in manifest.metrics_for(
            m, cell, "per_layer")} <= set(NEW_METRICS)
        assert {x["name"] for x in manifest.metrics_for(
            m, cell, "end_to_end")} == {x["name"] for x in m["end_to_end"]}


# ---- data, costs, reductions ------------------------------------------

def test_token_stream_is_seeded_zipf_over_the_slice():
    spec = {"kind": "token_stream", "vocab": 64, "seq_len": 128,
            "train_sequences": 64, "zipf_exponent": 1.0}
    config = {"data": spec}
    a = runner.make_data(config, 2**31 + 5)
    b = runner.make_data(config, 2**31 + 5)
    c = runner.make_data(config, 2**31 + 6)
    assert a.shape == (64, 128) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 64
    counts = np.sort(np.bincount(a.ravel(), minlength=64))[::-1]
    # heavy-tailed: the most frequent id near 1 / H_64 = 21 % of the draws
    assert 0.15 < counts[0] / a.size < 0.28
    assert counts[0] > 5 * counts[9]


def test_flop_shares_are_the_issues():
    spec = manifest.load_json(os.path.join(
        manifest.BENCH, "configs", "kanana-2-30b-a3b-ep16.json"))[
            "train_config"]["model_spec"]
    parts = lm_costs.forward_flops_per_token(spec, 4096)
    total = sum(parts.values())
    assert total == pytest.approx(0.706e9, rel=2e-3)
    assert parts["attention"] / total == pytest.approx(0.67, abs=0.005)
    assert parts["routed"] / total == pytest.approx(0.02, abs=0.002)
    job = {"n": 3, "batch": 1, "seq_len": 4096, "model_spec": spec}
    assert lm_costs.train_flops_per_step(job) == pytest.approx(26.03e12,
                                                               rel=1e-3)
    assert lm_costs.attention_train_flops_per_step(job) == pytest.approx(
        3 * 12288 * parts["attention"], rel=1e-9)


def _trace():
    text = lambda name: f"%{name} = f32[8]{{0}} fusion(%p)"  # noqa: E731
    events = [(text("while.1"), 0.0, 100.0), (text("fusion.1"), 10.0, 30.0),
              (text("fusion.2"), 50.0, 20.0), (text("all-reduce.3"), 100.0,
                                               8.0),
              (text("all-gather-start.4"), 110.0, 2.0)]
    return xplane.Trace({"devices": {"/device:TPU:0": events},
                         "anchor_ns": None},
                        {"while.1": "draco_comp", "fusion.1": "draco_comp",
                         "fusion.2": "draco_comp", "all-reduce.3": "",
                         "all-gather-start.4": ""}, 0.0, (0.0, 1.0), 2)


def _read(name, ctx):
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    return importlib.import_module(
        f"benchmark.reductions.{spec['reduction']}").read(spec, ctx)


def test_nested_scope_reductions_read_the_routes_map():
    spec = manifest.load_json(os.path.join(
        manifest.BENCH, "configs", "kanana-2-30b-a3b-ep16.json"))[
            "train_config"]["model_spec"]
    job = {"n": 3, "batch": 1, "seq_len": 4096, "model_spec": spec,
           "inner_scopes": {"fusion.1": "draco_attn", "fusion.2":
                            "draco_experts", "while.1": "draco_comp"}}
    ctx = {"trace": _trace(), "job": job, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 1, "counters": {},
           "peaks": {"bf16_flops_per_s": 197e12}}
    assert _read("attention_ms", ctx) == pytest.approx(30e-6 / 2)
    assert _read("moe_experts_ms", ctx) == pytest.approx(20e-6 / 2)
    assert _read("lm_head_ms", ctx) is None
    want = (100 * lm_costs.attention_train_flops_per_step(job) / 197e12
            / (30e-9 / 2))
    assert _read("attention_roofline", ctx) == pytest.approx(want)
    # a program without the nested scopes (the parent): nothing, no error
    ctx["job"] = {"n": 8, "dim": 11, "wire": "f32"}
    assert _read("attention_ms", ctx) is None
    assert _read("attention_roofline", ctx) is None


def test_collective_reduction_sums_the_collectives_of_chip_0():
    ctx = {"trace": _trace(), "job": {}, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 4, "counters": {}, "peaks": None}
    assert _read("collective_ms", ctx) == pytest.approx(10e-6 / 2)
    no_mesh = xplane.Trace({"devices": {"/device:TPU:0": [
        ("%fusion.1 = f32[8]{0} fusion(%p)", 0.0, 5.0)]}, "anchor_ns": None},
        {"fusion.1": "draco_comp"}, 0.0, (0.0, 1.0), 1)
    assert _read("collective_ms", dict(ctx, trace=no_mesh)) is None


def test_innermost_scope_of_an_instruction():
    from benchmark.routes.token import inner_scopes

    hlo = "\n".join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(step_body)/draco_comp/while/body/transpose(jvp(draco_attn))/'
        'dot_general" source_file="x"}',
        '  ROOT %add.2 = f32[8]{0} add(%a, %b), metadata={op_name='
        '"jit(step_body)/draco_decode/add"}',
        "  %copy.3 = f32[8]{0} copy(%a)"])
    assert inner_scopes(hlo) == {"fusion.1": "draco_attn",
                                 "add.2": "draco_decode"}
    assert xplane.scope_map_from_hlo(hlo)["fusion.1"] == "draco_comp"


# ---- the tiny token cell, end to end -----------------------------------

@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("sound"))


def test_sound_token_run_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] >= 3 + 6 + 8
    assert set(sound) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    json.dumps(sound)


@pytest.mark.parametrize("metric", [
    x["name"] for x in manifest.load_manifest()["end_to_end"]])
def test_token_run_reports_every_end_to_end_metric(sound, metric):
    got = sound["metrics"][metric]
    assert set(got) == {"value", "unit"} and isinstance(got["value"], float)
    if metric != "peak_hbm_gb":  # the CPU backend reports no memory
        assert got["value"] > 0


def test_traced_token_run_leaves_the_device_metrics_out(tmp_path):
    out = _run(tmp_path, trace=True)
    assert out["correct"] is True
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # no TPU plane in a CPU capture: the new readers find nothing to read
    assert not set(NEW_METRICS) & set(out["metrics"])
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0
    assert out["metrics"]["fetch_ms"]["value"] > 0.0


def test_broken_token_step_comes_out_not_correct(tmp_path, monkeypatch):
    """The step program replaced, under the production loop, by one that
    hands its state back unchanged."""
    import jax
    import jax.numpy as jnp

    from draco_tpu.parallel import sp_step

    real_build = sp_step.build_sp_train_setup

    def build(cfg, mesh):
        setup = real_build(cfg, mesh)

        def idle_step(state, toks, mask, *rest):
            kept = jax.tree.map(jnp.copy, state)
            new, metrics = setup.train_step(state, toks, mask, *rest)
            return kept._replace(step=new.step), metrics

        return setup._replace(train_step=idle_step)

    monkeypatch.setattr(sp_step, "build_sp_train_setup", build)
    out = _run(tmp_path)
    assert out["correct"] is False


def test_unlocated_adversary_counts_as_failed(tmp_path, monkeypatch):
    """A vote that flags nobody: every step is a failed operation."""
    monkeypatch.setattr(check, "unlocated_steps",
                        lambda records, adversaries: len(records))
    out = _run(tmp_path)
    assert out["correct"] is False and out["failed"] == out["attempted"]


def test_token_records_name_the_flag_count_located_errors():
    rows = [{"det_adv": 1.0, "det_tp": 1.0, "located_errors": 1.0}] * 3
    assert check.unlocated_steps(rows, 1) == 0
    assert check.unlocated_steps(
        [{"det_adv": 1.0, "det_tp": 1.0, "det_flagged": 1.0}], 1) == 1


def test_lower_precision_lm_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees
    from draco_tpu.models.latent_moe import LatentMoeLM

    config, traffic, limits = _files()
    tc = dict(config["train_config"], **traffic["train_config"])
    seed = 78
    data = runner.make_data(config, seed)
    reference = runner.reference_of(config)
    job = reference.make_job(config, {
        "groups": 1, "batch": tc["batch_size"], "lr": tc["lr"],
        "momentum": tc["momentum"]})
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "float32"),
        LatentMoeLM(tc["model_spec"]).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple))
    weights = seeded.make_weights(shapes, config["weights"], seed)
    ref = reference.follow(job, weights, data, 3)
    low = reference.follow(job, weights, data, 3,
                           dtype=config["control"]["reference_dtype"])

    def verdict(f):
        rows = check.compare(
            {"losses": f.losses, "grad_norms": f.grad_norms,
             "delta_norms": f.delta_norms,
             "grad_diff": check.noise_units(
                 trees.rel_diff(f.grad, ref.grad), 0.0),
             "unlocated_steps": 0, "nonfinite_steps": 0}, ref, limits)
        return all(ok for *_, ok in rows)

    assert verdict(ref) is True
    assert verdict(low) is False
