"""What PR 41 adds to the benchmark: a convolution-hybrid sparse language
model with a tied head through ``reference/lm_train.py`` with a ``nets/``
file and a costs file of its own — one configuration, one cell, six
per-layer metrics as JSON over the reductions the benchmark had, new files
and new entries only. Everything is found BY NAME: no position, no count of
cells and no "exactly these" is pinned, so a later PR's entries leave these
tests alone. A tiny cell of the same block (hidden 64, five of six layers
kept, benchmark/testdata/) runs end to end through ``runner.run_cell`` on
the CPU under the traffic file the other tiny LM cells use: sound it is
correct, and the lower-precision control fails the limits the sound run
passes (a program that leaves something out comes out not correct in the
other LM cells' files: the comparison is theirs)."""

import importlib
import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import check, manifest, runner  # noqa: E402
from benchmark.harness import conv_lm_costs, xplane  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.conv_moe_maj_vote_r3", "config": "conv-moe-tiny",
        "traffic": "tiny_lm_maj_vote_r3", "chips": 1, "why": "test"}
NEW_CELL = "lfm2.maj_vote_r3"
NEW_CONFIG = "lfm2-8b-a1b-ep4"
# name -> (reduction, scopes, unit, better)
NEW_METRICS = {
    "short_conv_ms": ("inner_scope_ms_per_step", ["draco_conv"], "ms",
                      "lower"),
    "short_conv_roofline": ("inner_scope_work_roofline", ["draco_conv"],
                            "%", "higher"),
    "expert_ffn_ms": ("inner_scope_ms_per_step", ["draco_experts"], "ms",
                      "lower"),
    "expert_ffn_roofline": ("inner_scope_work_roofline", ["draco_experts"],
                            "%", "higher"),
    "tied_head_ms": ("inner_scope_ms_per_step", ["draco_head"], "ms",
                     "lower"),
    # the attention layer at 64-wide heads in 128-lane kernels (REVIEW 41)
    "gqa64_attn_ms": ("inner_scope_ms_per_step", ["draco_attn"], "ms",
                      "lower"),
}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# LFM2-8B-A1B), every key
PATTERN = ["conv", "conv", "full_attention", "conv", "conv", "conv",
           "full_attention", "conv", "conv", "conv", "full_attention",
           "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
           "full_attention", "conv", "conv", "full_attention", "conv",
           "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": PATTERN,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_dense_layers": 1, "num_experts": 8, "vocab_size": 16384}
DIM = 507_820_288


def _files():
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return (load("conv-moe-tiny.json"), load("tiny_lm_maj_vote_r3.json"),
            load("tiny_lm_limits.json"))


def _run(tmp, trace=False, seed=2**31 + 41):
    config, traffic, limits = _files()
    m = manifest.load_manifest()
    metrics = m["per_layer"] if trace else m["end_to_end"]
    return runner.run_cell(CELL, config, traffic, limits, metrics, seed, 0.5,
                           trace, time.time(), require_tpu=False,
                           scratch=str(tmp))


def _config():
    return manifest.load_json(os.path.join(manifest.BENCH, "configs",
                                           NEW_CONFIG + ".json"))


def _job():
    return {"n": 3, "batch": 1, "seq_len": 4096,
            "model_spec": _config()["train_config"]["model_spec"]}


# ---- the manifest's new entries ---------------------------------------

def test_manifest_holds_the_configuration_and_the_cell_by_name():
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    (entry,) = [c for c in m["configs"] if c["name"] == NEW_CONFIG]
    assert entry["reduced"] == ["layers", "num_dense_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/LiquidAI/"
                               "LFM2-8B-A1B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{NEW_CONFIG}.json"
    cell = manifest.cell_of(m, NEW_CELL)
    assert cell["chips"] == 1 and cell["config"] == NEW_CONFIG
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the traffic file the benchmark already had
    assert cell["traffic"] == "lm_maj_vote_r3"
    # the one cell of this configuration
    assert [w["name"] for w in m["workloads"]
            if w["config"] == NEW_CONFIG] == [NEW_CELL]
    # no width is named among the reduced keys
    for key in entry["reduced"]:
        assert not any(w in key for w in manifest.WIDTH_WORDS), key


def test_every_number_of_the_configuration_is_the_catalog_rows():
    """The published config keys, verbatim but the three that count what
    this chip holds; the model's mapping keeps every published number and
    states the share beside them."""
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["layers"] == 5
    assert config["reduced"] == ["layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
        "vocab_size": 65536}
    assert set(config["held"]) == set(config["reduced"])
    spec = config["train_config"]["model_spec"]
    for key, value in PUBLISHED.items():
        assert spec[key] == value, key
    assert spec["layers"] == 5 and spec["layers_held"] == [0, 2, 3, 4, 5]
    # one whole period after the dense layers, 1 : 3 as published
    assert [PATTERN[i] for i in spec["layers_held"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert spec["experts_held"] == [0, 8]
    assert spec["vocab_rows"] == config["data"]["vocab"] == \
        config["train_config"]["vocab"] == 16384 == 65536 // 4
    assert "tie_word_embeddings" not in spec  # absent, read as true
    assert config["data"]["seq_len"] == config["train_config"]["seq_len"]
    assert config["data"]["seq_len"] in (4096, 2048)
    assert config["reference"] == {"module": "lm_train", "net": "lfm2"}
    for key in ("deployment", "assumed", "size", "precision", "products",
                "not_read", "source", "fallback"):
        assert config[key], key
    for key in ("tie_word_embeddings", "head_dim", "initializer_range",
                "qk_norm", "conv_taps", "expert_bias",
                "norm_topk_denominator", "rope_pairs", "optimizer", "data"):
        assert key in config["assumed"], key
    assert "507 820 288" in config["size"]
    assert "4 chips share each layer" in config["deployment"]


def test_the_configuration_validates_under_the_cells_traffic():
    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.models import build_lm
    from draco_tpu.models.conv_moe import ShortConvMoeLM
    from draco_tpu.training.step import _make_unravel

    config = _config()
    traffic = manifest.traffic_of({"traffic": "lm_maj_vote_r3"})
    fields = dict(config["train_config"], **traffic["train_config"])
    cfg = TrainConfig(**dict(fields, train_dir="", eval_freq=0)).validate()
    assert cfg.network == "ShortConvMoeLM" and cfg.approach == "maj_vote"
    lm = build_lm(cfg)
    assert isinstance(lm, ShortConvMoeLM) and lm.tied_head and lm.moe.dense
    # a seeded rule for every leaf name of the model
    paths = jax.tree_util.tree_flatten_with_path(
        lm.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))[0]
    assert {p[-1].key for p, _ in paths} == set(config["weights"])
    # the tied matrix at the matrices' std: the one departure
    assert config["weights"]["embedding"] == config["weights"]["kernel"] \
        == "normal:0.02"
    # the issue's hand count
    _, dim, _ = _make_unravel(jax.eval_shape(lm.init, jax.random.key(0)))
    assert dim == DIM == (60_827_648 + 98_635_936 + 3 * 104_933_408
                          + 33_554_432 + 2_048)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_layer_metric_has_its_file_its_reader_and_its_cell(name):
    reduction, scopes, unit, better = NEW_METRICS[name]
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    assert spec["reduction"] == reduction and spec["scopes"] == scopes
    importlib.import_module(f"benchmark.reductions.{reduction}")
    if reduction == "inner_scope_work_roofline":
        costs = importlib.import_module(
            f"benchmark.harness.{spec['costs']}")
        assert costs is conv_lm_costs
        assert callable(getattr(costs, spec["flops"]))
        assert callable(getattr(costs, spec["bytes"]))
    m = manifest.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "models",
                     "moves": "step_ms_p50", "workloads": [NEW_CELL]}


def test_the_new_cell_reports_the_end_to_end_metrics_and_its_own_layers():
    m = manifest.load_manifest()
    assert set(NEW_METRICS) <= {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "per_layer")}
    assert {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "end_to_end")} >= {
            "examples_per_s", "step_ms_p50", "step_ms_p95", "peak_hbm_gb",
            "setup_s"}
    # they are this cell's alone
    for w in m["workloads"]:
        if w["name"] != NEW_CELL:
            assert not set(NEW_METRICS) & {
                x["name"] for x in manifest.metrics_for(
                    m, w["name"], "per_layer")}, w["name"]


def test_the_cells_limits_lie_between_their_readings():
    """Every limit above the sound runs' largest reading and under the fp8
    control's: the control comes out not correct by each of the four. The
    loss, which the precision moves least here (its two readings lie 5 x
    apart), has the narrowest room; what the other three have is in the
    file's ``how``."""
    limits = manifest.limits_of({"name": NEW_CELL})
    readings = limits["readings"]
    for name in ("loss_gap", "grad_norm_gap", "grad_diff", "delta_norm_gap"):
        assert 2 * readings[name]["sound_max"] < limits[name] \
            < readings[name]["control"] / 2, name
    assert limits["delta_norm_gap"] < readings["delta_norm_gap"][
        "state_unchanged"] == 1.0


@pytest.mark.parametrize("key,value", [
    (("train_config", "lr"), 0.01), (("train_config", "momentum"), 0.9),
    (("train_config", "optimizer"), "sgd"),
    (("train_config", "attn_impl"), "flash"),
    (("train_config", "compute_dtype"), "float32"),
    (("weights", "kernel"), "normal:0.02"),
    (("weights", "scale"), "ones"),
    (("data", "zipf_exponent"), 1.0), (("data", "train_sequences"), 256),
    (("control", "reference_dtype"), "float8_e4m3fn"),
    (("products",), "bfloat16")])
def test_the_cell_shares_the_lm_cells_assumed_values(key, value):
    """What no published config states — optimizer, seeded scales, the
    ids' distribution, the control — is the one set the LM cells share
    (but the tied matrix's std, stated in ``assumed``)."""
    for name in ("kanana-2-30b-a3b-ep16", NEW_CONFIG):
        at = manifest.load_json(os.path.join(manifest.BENCH, "configs",
                                             name + ".json"))
        for part in key:
            at = at[part]
        assert at == value, (name, key)


# ---- costs and the rooflines' reduction --------------------------------

def test_costs_are_the_hand_counts():
    job = _job()
    spec = job["model_spec"]
    assert conv_lm_costs.kept(spec) == {"conv": 4, "attention": 1,
                                        "dense": 1, "sparse": 4}
    parts = conv_lm_costs.forward_flops_per_token(spec, 4096)
    # the two projections 2 x 16 777 216, and 8 a channel for gates and taps
    assert conv_lm_costs.conv_flops_per_token(spec) == \
        2 * 16_777_216 + 8 * 2048
    assert parts["conv"] == 4 * (2 * 16_777_216 + 8 * 2048)
    assert parts["dense_mlp"] == 6 * 2048 * 7168 == 88_080_384
    # T x 4 x 8 / 32 = T pairs a sparse layer, 22.0 MFLOP each
    assert conv_lm_costs.routed_flops_per_token(spec) == 6 * 2048 * 1792
    assert parts["routed"] == 4 * 22_020_096
    assert parts["router"] == 4 * 2 * 2048 * 32
    assert parts["head"] == 2 * 2048 * 16384
    assert parts["attention"] == pytest.approx(
        2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 4 * 32 * 64 * 2048.5)
    total = sum(parts.values())
    assert total == pytest.approx(416e6, rel=2e-3)
    # x 3 (forward + backward) x 12 288 token-gradients
    assert conv_lm_costs.train_flops_per_step(job) == pytest.approx(
        15.33e12, rel=2e-3)
    # as executed: every held expert over every token, 8 x the pairs chosen
    run = conv_lm_costs.executed_flops_per_token(spec, 4096)
    assert run["routed"] == 8 * parts["routed"]
    done = sum(run.values())
    assert done == pytest.approx(1033e6, rel=2e-3)
    assert (run["routed"] + run["dense_mlp"]) / done == pytest.approx(
        0.77, abs=0.01)
    assert run["conv"] / done == pytest.approx(0.13, abs=0.005)
    assert run["head"] / done == pytest.approx(0.065, abs=0.002)
    assert run["attention"] / done == pytest.approx(0.037, abs=0.002)
    assert conv_lm_costs.conv_train_flops_per_step(job) == \
        3 * 12288 * parts["conv"]
    assert conv_lm_costs.ffn_train_flops_per_step(job) == \
        3 * 12288 * (parts["dense_mlp"] + parts["routed"])
    assert conv_lm_costs.ffn_train_flops_per_step(job) == pytest.approx(
        6.49e12, rel=2e-3)
    # h read, [B | C | X] and y written: 5 x 2048 floats a token and layer
    assert conv_lm_costs.conv_train_bytes_per_step(job) == \
        3 * 4 * 4 * 5 * 2048 * 12288
    # the matrices once a lane, each token's row in and out a layer
    assert conv_lm_costs.ffn_train_bytes_per_step(job) == 3 * 4 * (
        3 * (44_040_192 + 4 * 88_080_384) + 2 * 2048 * 5 * 12288)


def _trace():
    text = lambda name: f"%{name} = f32[8]{{0}} fusion(%p)"  # noqa: E731
    events = [(text("while.1"), 0.0, 100.0), (text("fusion.1"), 10.0, 30.0),
              (text("fusion.2"), 50.0, 20.0), (text("fusion.3"), 80.0, 10.0),
              (text("fusion.4"), 92.0, 4.0)]
    return xplane.Trace({"devices": {"/device:TPU:0": events},
                         "anchor_ns": None},
                        dict.fromkeys(("while.1", "fusion.1", "fusion.2",
                                       "fusion.3", "fusion.4"), "draco_comp"),
                        0.0, (0.0, 1.0), 2)


def _read(name, ctx):
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    return importlib.import_module(
        f"benchmark.reductions.{spec['reduction']}").read(spec, ctx)


def test_the_rooflines_are_least_time_over_their_scopes_time():
    job = dict(_job(), inner_scopes={
        "fusion.1": "draco_conv", "fusion.2": "draco_experts",
        "fusion.3": "draco_head", "fusion.4": "draco_attn",
        "while.1": "draco_comp"})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": _trace(), "job": job, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 1, "counters": {}, "peaks": peaks}
    assert _read("short_conv_ms", ctx) == pytest.approx(30e-6 / 2)
    assert _read("expert_ffn_ms", ctx) == pytest.approx(20e-6 / 2)
    assert _read("tied_head_ms", ctx) == pytest.approx(10e-6 / 2)
    assert _read("gqa64_attn_ms", ctx) == pytest.approx(4e-6 / 2)
    for name, flops, moved, scope_ns in (
            ("short_conv_roofline",
             conv_lm_costs.conv_train_flops_per_step,
             conv_lm_costs.conv_train_bytes_per_step, 30.0),
            ("expert_ffn_roofline",
             conv_lm_costs.ffn_train_flops_per_step,
             conv_lm_costs.ffn_train_bytes_per_step, 20.0)):
        flops_s, bytes_s = flops(job) / 197e12, moved(job) / 819e9
        assert flops_s > bytes_s  # compute binds at the published widths
        assert _read(name, ctx) == pytest.approx(
            100 * flops_s / (scope_ns * 1e-9 / 2))
    # 4.95 TFLOP of convolution operators a step: 25 ms at the peak; 6.49
    # of feed-forward: 33 ms
    assert conv_lm_costs.conv_train_flops_per_step(job) / 197e12 == \
        pytest.approx(0.0251, rel=5e-3)
    assert conv_lm_costs.ffn_train_flops_per_step(job) / 197e12 == \
        pytest.approx(0.0330, rel=5e-3)
    # a program without the scopes (the parent, another cell): nothing, and
    # no error
    for other in ({"n": 8, "dim": 11, "wire": "f32"},
                  dict(job, inner_scopes={"fusion.1": "draco_linattn"})):
        for name in NEW_METRICS:
            assert _read(name, dict(ctx, job=other)) is None, name
    assert _read("short_conv_roofline", dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    """In a fresh interpreter: importing the reference's net and job leaves
    no module of draco_tpu loaded."""
    code = ("import sys; "
            "import benchmark.reference.nets.lfm2, "
            "benchmark.reference.lm_train; "
            "assert not [m for m in sys.modules if m.startswith('draco_tpu')]")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=manifest.ROOT, env=dict(os.environ,
                                               JAX_PLATFORMS="cpu"))


# ---- the tiny cell, end to end ------------------------------------------

@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("sound"))


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] >= 3 + 6 + 8
    assert {"examples_per_s", "step_ms_p50", "step_ms_p95", "peak_hbm_gb",
            "setup_s"} <= set(sound["metrics"])
    json.dumps(sound)


def test_the_compiled_step_names_the_new_scopes_and_the_record_the_counters():
    """The route's innermost-scope map of the step it dispatched holds
    ``draco_conv`` beside the scopes the other blocks have; every record
    carries the counters, ``short_conv_layers`` at 4 and ``tied_head`` at
    1."""
    import jax

    config, traffic, _ = _files()
    fields = dict(config["train_config"], **traffic["train_config"])
    data = runner.make_data(config, 5)
    route = importlib.import_module("benchmark.routes.token").Route(
        fields, data, jax.devices()[:1])
    try:
        route.step_hlo()
        scopes = set(route.job()["inner_scopes"].values())
        rows, _, _ = route.run_to(2)
        names = route.setup.model.stat_names
    finally:
        route.close()
    assert {"draco_conv", "draco_attn", "draco_route", "draco_experts",
            "draco_head"} <= scopes
    assert {"short_conv_layers", "short_conv_absmax", "tied_head",
            "moe_dropped"} <= set(names)
    for row in rows:
        assert row["short_conv_layers"] == 4.0 and row["tied_head"] == 1.0
        assert row["short_conv_absmax"] > 0.0
        assert row["moe_dropped"] == 0.0 and row["moe_full_dispatch"] == 0.0
        assert row["det_adv"] == row["det_tp"] == row["located_errors"] == 1.0


def test_lower_precision_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees
    from draco_tpu.models.conv_moe import ShortConvMoeLM

    config, traffic, limits = _files()
    tc = dict(config["train_config"], **traffic["train_config"])
    seed = 87
    data = runner.make_data(config, seed)
    reference = runner.reference_of(config)
    job = reference.make_job(config, {
        "groups": 1, "batch": tc["batch_size"], "lr": tc["lr"],
        "momentum": tc["momentum"]})
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "float32"),
        ShortConvMoeLM(tc["model_spec"]).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple))
    weights = seeded.make_weights(shapes, config["weights"], seed)
    ref = reference.follow(job, weights, data, 3)
    # the published configuration's control: operands through an 8-bit float
    fp8 = reference.follow(job, weights, data, 3, dtype="float8_e4m3fn")

    def verdict(f):
        rows = check.compare(
            {"losses": f.losses, "grad_norms": f.grad_norms,
             "delta_norms": f.delta_norms,
             "grad_diff": check.noise_units(
                 trees.rel_diff(f.grad, ref.grad), 0.0),
             "unlocated_steps": 0, "nonfinite_steps": 0}, ref, limits)
        return all(ok for *_, ok in rows)

    assert verdict(ref) is True
    assert verdict(fp8) is False
