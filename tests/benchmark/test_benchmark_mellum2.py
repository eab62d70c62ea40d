"""What PR 35 adds to the benchmark: a third language model through
``reference/lm_train.py`` with a ``nets/`` file and a costs file of its own
— one configuration, one cell, three per-layer metrics as JSON over the
reductions the benchmark had, new files and new entries only, each entry at
the END of its list. A tiny cell of the same block (hidden 64, a window of
24 tokens, benchmark/testdata/) runs end to end through ``runner.run_cell``
on the CPU under the traffic file the other tiny LM cells use: sound it is
correct, with the window dropped from the program's sliding layers it is
not, and the lower-precision control fails the limits the sound run
passes."""

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
from benchmark.harness import windowed_lm_costs, xplane  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.windowed_maj_vote_r3", "config": "windowed-moe-tiny",
        "traffic": "tiny_lm_maj_vote_r3", "chips": 1, "why": "test"}
NEW_CELL = "mellum2.maj_vote_r3"
NEW_CONFIG = "mellum2-12b-a2.5b-ep8"
NEW_METRICS = {
    "swa_attn_ms": ("inner_scope_ms_per_step", "models", "ms", "lower"),
    "window_kernel_ms": ("inner_scope_ms_per_step", "kernels", "ms",
                         "lower"),
    "window_kernel_roofline": ("inner_scope_work_roofline", "kernels", "%",
                               "higher"),
}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Mellum2-12B-A2.5B-Instruct), every key
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def _files():
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return (load("windowed-moe-tiny.json"), load("tiny_lm_maj_vote_r3.json"),
            load("tiny_lm_limits.json"))


def _run(tmp, trace=False, seed=2**31 + 35):
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
    return {"n": 3, "batch": 1, "seq_len": 8192,
            "model_spec": _config()["train_config"]["model_spec"]}


# ---- the manifest's new entries ---------------------------------------

# what the benchmark had before this PR, in its order: new entries stand
# after these (pinned as a prefix, so that the next PR's entries, after
# these again, leave the test alone)
HAD_CONFIGS = ["resnet18-cifar10", "vgg11-cifar10", "kanana-2-30b-a3b-ep16",
               "qwen3-next-80b-a3b-ep32"]
HAD_CELLS = ["resnet18.cyclic_s1", "resnet18.mean_b96", "vgg11.cyclic_s2",
             "kanana2.maj_vote_r3", "resnet18.cyclic_s1_b128",
             "qwen3next.maj_vote_r3"]
HAD_METRICS = 24  # per_layer entries, the last of them deltarule_roofline


def test_manifest_gains_the_configuration_and_the_cell_after_the_old_ones():
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    assert [c["name"] for c in m["configs"]][:5] == HAD_CONFIGS + [NEW_CONFIG]
    entry = m["configs"][4]
    assert entry["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert entry["source"].endswith(
        "JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert [w["name"] for w in m["workloads"]][:7] == HAD_CELLS + [NEW_CELL]
    cell = m["workloads"][6]
    assert cell["chips"] == 1 and cell["config"] == NEW_CONFIG
    assert len(cell["why"]) <= 200
    # the traffic file the benchmark already had, unchanged
    assert cell["traffic"] == "lm_maj_vote_r3"
    names = [x["name"] for x in m["per_layer"]]
    assert names[HAD_METRICS - 1] == "deltarule_roofline"
    assert names[HAD_METRICS:HAD_METRICS + 3] == list(NEW_METRICS)
    # one cell in seven may take four chips: this one does not
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4][:1] == [
        "resnet18.cyclic_s1_b128"]


def test_every_number_of_the_configuration_is_the_catalog_rows():
    """The published config keys, verbatim but for the two reduced counts
    (``layers`` is the third); the model's mapping keeps every published
    number, the router's width and the vocabulary's included."""
    config = _config()
    reduced = {"num_experts": 8, "vocab_size": 12288}
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
    assert config["layers"] == 4 and config["reduced"] == [
        "layers", "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 28,
                                   "num_experts": 64, "vocab_size": 98304}
    assert set(config["held"]) == set(config["reduced"])
    spec = config["train_config"]["model_spec"]
    for key, value in PUBLISHED.items():
        assert spec[key] == value, key
    assert spec["experts_held"] == [0, 8] and spec["layers"] == 4
    # one whole period, in the published order
    assert spec["layer_types"][:spec["layers"]] == PERIOD
    assert spec["vocab_rows"] == config["data"]["vocab"] == 12288
    assert spec["vocab_rows"] * 8 == PUBLISHED["vocab_size"]
    assert spec["experts_held"][1] * 8 == PUBLISHED["num_experts"]
    assert config["data"]["seq_len"] == 8192 == \
        config["train_config"]["seq_len"]
    assert config["reference"] == {"module": "lm_train", "net": "mellum"}
    for key in ("deployment", "assumed", "left_out", "precision"):
        assert config[key], key
    assert "qk_norm" in config["assumed"]
    # no width is named among the reduced keys, nor cut anywhere
    for key in config["reduced"]:
        assert not any(w in key for w in manifest.WIDTH_WORDS), key


def test_the_configuration_validates_under_the_cells_traffic():
    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.models import build_lm
    from draco_tpu.training.step import _make_unravel

    config = _config()
    traffic = manifest.traffic_of({"traffic": "lm_maj_vote_r3"})
    fields = dict(config["train_config"], **traffic["train_config"])
    cfg = TrainConfig(**dict(fields, train_dir="", eval_freq=0)).validate()
    assert cfg.network == "WindowedMoeLM" and cfg.approach == "maj_vote"
    lm = build_lm(cfg)
    # a seeded rule for every leaf name of the model; no shared expert
    paths = jax.tree_util.tree_flatten_with_path(
        lm.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))[0]
    assert {p[-1].key for p, _ in paths} == set(config["weights"])
    assert not any("shared" in jax.tree_util.keystr(p) for p, _ in paths)
    # the issue's hand count, and every leaf on the stack's 128-wide lines
    _, dim, offsets = _make_unravel(jax.eval_shape(lm.init,
                                                   jax.random.key(0)))
    assert dim == 340_349_184 == 4 * 70_930_944 + 56_623_104 + 2_304
    assert all(off % 128 == 0 for off in offsets)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_layer_metric_has_its_file_its_reader_and_its_cell(name):
    reduction, layer, unit, better = NEW_METRICS[name]
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    assert spec["reduction"] == reduction
    importlib.import_module(f"benchmark.reductions.{reduction}")
    m = manifest.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "step_ms_p50", "workloads": [NEW_CELL]}
    assert "draco_window" in spec["scopes"]


def test_the_new_cell_reports_every_end_to_end_metric_and_only_its_layers():
    m = manifest.load_manifest()
    assert {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "per_layer")} == set(NEW_METRICS)
    assert {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "end_to_end")} == {x["name"] for x in m["end_to_end"]}
    # nothing new for the cells the benchmark had
    for name in HAD_CELLS:
        assert not set(NEW_METRICS) & {x["name"] for x in manifest.metrics_for(
            m, name, "per_layer")}


def test_the_cells_limits_lie_between_their_readings():
    """Every limit, the loss's included, over the sound runs' largest
    reading and under the control's smallest: the fp8 control fails by
    each of the four."""
    limits = manifest.limits_of({"name": NEW_CELL})
    readings = limits["readings"]
    for name in ("loss_gap", "grad_norm_gap", "grad_diff",
                 "delta_norm_gap"):
        assert 3 * readings[name]["sound_max"] < limits[name] \
            < readings[name]["control"] / 3, name
    assert limits["delta_norm_gap"] < readings["delta_norm_gap"][
        "state_unchanged"] == 1.0


LM_CONFIGS = ["kanana-2-30b-a3b-ep16", "qwen3-next-80b-a3b-ep32", NEW_CONFIG]


@pytest.mark.parametrize("key,value", [
    (("train_config", "lr"), 0.01), (("train_config", "momentum"), 0.9),
    (("train_config", "optimizer"), "sgd"),
    (("weights", "embedding"), "normal:1.0"),
    (("weights", "kernel"), "normal:0.02"),
    (("data", "zipf_exponent"), 1.0), (("data", "train_sequences"), 256),
    (("control", "reference_dtype"), "float8_e4m3fn"),
    (("products",), "bfloat16")])
def test_the_lm_cells_share_one_set_of_assumed_values(key, value):
    """What no published config states — optimizer, seeded scales, the
    ids' distribution, the control — is one set for the three LM cells
    (ISSUE 35 fixed it before any run): a cell tuned apart from the others
    is no longer comparable with them."""
    for name in LM_CONFIGS:
        at = manifest.load_json(os.path.join(manifest.BENCH, "configs",
                                             name + ".json"))
        for part in key:
            at = at[part]
        assert at == value, (name, key)


# ---- costs and the roofline's reduction --------------------------------

def test_costs_are_the_hand_counts():
    job = _job()
    spec = job["model_spec"]
    # query t sees min(t + 1, 1024) keys: the first 1024 rows a triangle,
    # the other 7168 rows 1024 each
    assert windowed_lm_costs.window_pairs(8192, 1024) == 7_864_832 == \
        1024 * 1025 // 2 + 7168 * 1024
    assert windowed_lm_costs.window_pairs(80, 24) == sum(
        min(t + 1, 24) for t in range(80))
    assert windowed_lm_costs.window_pairs(16, 24) == 16 * 17 // 2
    parts = windowed_lm_costs.forward_flops_per_token(spec, 8192)
    total = sum(parts.values())
    assert total == pytest.approx(391.5e6, rel=1e-3)
    assert parts["projections"] / total == pytest.approx(0.434, abs=0.002)
    assert parts["full_attention"] / total == pytest.approx(0.171, abs=0.002)
    assert parts["window_attention"] / total == pytest.approx(0.121,
                                                              abs=0.002)
    assert parts["routed"] / total == pytest.approx(0.127, abs=0.002)
    assert parts["head"] / total == pytest.approx(0.145, abs=0.002)
    # a sliding layer's kernel work a token against a full layer's
    assert parts["window_attention"] / 3 == pytest.approx(15.7e6, rel=3e-3)
    assert parts["full_attention"] == pytest.approx(67.1e6, rel=1e-3)
    assert windowed_lm_costs.train_flops_per_step(job) == pytest.approx(
        28.9e12, rel=2e-3)
    # 4·32·128 FLOP a pair, 3 x, three layers, three lanes
    assert windowed_lm_costs.window_train_flops_per_step(job) == \
        3 * 3 * 3 * 7_864_832 * 4 * 32 * 128
    # q, o (32 heads) and k, v (4 heads) of 128 floats a token: 3 x
    assert windowed_lm_costs.window_train_bytes_per_step(job) == \
        3 * 3 * 3 * 8192 * 4 * 128 * (2 * 32 + 2 * 4)


def _trace():
    text = lambda name: f"%{name} = f32[8]{{0}} fusion(%p)"  # noqa: E731
    events = [(text("while.1"), 0.0, 100.0), (text("fusion.1"), 10.0, 30.0),
              (text("fusion.2"), 50.0, 20.0), (text("fusion.3"), 80.0, 10.0)]
    return xplane.Trace({"devices": {"/device:TPU:0": events},
                         "anchor_ns": None},
                        dict.fromkeys(("while.1", "fusion.1", "fusion.2",
                                       "fusion.3"), "draco_comp"),
                        0.0, (0.0, 1.0), 2)


def _read(name, ctx):
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    return importlib.import_module(
        f"benchmark.reductions.{spec['reduction']}").read(spec, ctx)


def test_the_roofline_is_the_windows_least_time_over_its_scopes_time():
    job = dict(_job(), inner_scopes={
        "fusion.1": "draco_window", "fusion.2": "draco_attn",
        "fusion.3": "draco_route", "while.1": "draco_comp"})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": _trace(), "job": job, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 1, "counters": {}, "peaks": peaks}
    assert _read("window_kernel_ms", ctx) == pytest.approx(30e-6 / 2)
    assert _read("swa_attn_ms", ctx) == pytest.approx(50e-6 / 2)
    flops_s = windowed_lm_costs.window_train_flops_per_step(job) / 197e12
    bytes_s = windowed_lm_costs.window_train_bytes_per_step(job) / 819e9
    assert flops_s == pytest.approx(17.66e-3, rel=1e-3)
    assert bytes_s == pytest.approx(9.96e-3, rel=1e-3)  # compute binds
    assert _read("window_kernel_roofline", ctx) == pytest.approx(
        100 * flops_s / (30e-9 / 2))
    # a program without the scope (the parent, another cell): nothing, and
    # no error
    for other in ({"n": 8, "dim": 11, "wire": "f32"},
                  dict(job, inner_scopes={"fusion.1": "draco_linattn"})):
        for name in NEW_METRICS:
            assert _read(name, dict(ctx, job=other)) is None, name
    assert _read("window_kernel_roofline", dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    """In a fresh interpreter: importing the reference's net and job leaves
    no module of draco_tpu loaded."""
    code = ("import sys; "
            "import benchmark.reference.nets.mellum, "
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
    assert set(sound["metrics"]) == {
        x["name"] for x in manifest.load_manifest()["end_to_end"]}
    json.dumps(sound)


def test_the_compiled_step_names_the_new_scope_and_the_record_the_counter():
    """The route's innermost-scope map of the step it dispatched:
    ``draco_window`` nested in ``draco_attn``, beside the expert layer's and
    the head's scopes; every record carries ``window_kernel_layers`` (0 off
    the chip) beside the four ``moe_*``."""
    import jax

    config, traffic, _ = _files()
    fields = dict(config["train_config"], **traffic["train_config"])
    data = runner.make_data(config, 5)
    route = importlib.import_module("benchmark.routes.token").Route(
        fields, data, jax.devices()[:1])
    try:
        route.step_hlo()
        scopes = set(route.job()["inner_scopes"].values())
        rows, _, _ = route.run_to(1)
    finally:
        route.close()
    assert {"draco_window", "draco_attn", "draco_route", "draco_experts",
            "draco_head"} <= scopes
    assert rows[0]["window_kernel_layers"] == 0.0
    for key in ("moe_assignments_held", "moe_load_max_over_mean",
                "moe_dropped", "moe_full_dispatch"):
        assert key in rows[0], key


def test_a_program_that_drops_the_window_comes_out_not_correct(
        tmp_path, monkeypatch):
    """The sliding layers made to see every earlier token (the window
    argument thrown away): ``correct`` is false."""
    from draco_tpu.ops import flash_attention as fa

    real = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, window=None, **kw: real(q, k, v, **kw))
    out = _run(tmp_path)
    assert out["correct"] is False


def test_lower_precision_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees
    from draco_tpu.models.windowed_moe import WindowedMoeLM

    config, traffic, limits = _files()
    tc = dict(config["train_config"], **traffic["train_config"])
    seed = 83
    data = runner.make_data(config, seed)
    reference = runner.reference_of(config)
    job = reference.make_job(config, {
        "groups": 1, "batch": tc["batch_size"], "lr": tc["lr"],
        "momentum": tc["momentum"]})
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "float32"),
        WindowedMoeLM(tc["model_spec"]).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple))
    weights = seeded.make_weights(shapes, config["weights"], seed)
    ref = reference.follow(job, weights, data, 3)
    low = reference.follow(job, weights, data, 3,
                           dtype=config["control"]["reference_dtype"])
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
    assert verdict(low) is False
    assert verdict(fp8) is False
