"""What PR 45 adds to the benchmark: a linear-attention sparse language model
whose delta rule decays per key channel, three layers in four, beside
position-free latent attention, through ``reference/lm_train.py`` with a
``nets/`` file and a costs file of its own — one configuration, one cell,
six per-layer metrics as JSON over the reductions the benchmark had, new
files and new entries only. Everything is found BY NAME: no position, no
count of cells and no "exactly these" is pinned, so a later PR's entries
leave these tests alone. A tiny cell of the same block (hidden 64, heads
2-3 of 4 held, five of six layers kept, benchmark/testdata/) runs end to end
through ``runner.run_cell`` on the CPU under the traffic file the other tiny
LM cells use: sound it is correct, with the step broken underneath it is
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

from benchmark.harness import check, kda_lm_costs, manifest, runner  # noqa: E402
from benchmark.harness import xplane  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.kda_moe_maj_vote_r3", "config": "kda-moe-tiny",
        "traffic": "tiny_lm_maj_vote_r3", "chips": 1, "why": "test"}
NEW_CELL = "kimilinear.maj_vote_r3"
NEW_CONFIG = "kimi-linear-48b-a3b-ep32-tp2"
# name -> (reduction, scopes, layer, unit, better)
NEW_METRICS = {
    "kda_attn_ms": ("inner_scope_ms_per_step",
                    ["draco_kda", "draco_kdarule"], "models", "ms", "lower"),
    "kda_rule_ms": ("inner_scope_ms_per_step", ["draco_kdarule"], "kernels",
                    "ms", "lower"),
    "kda_rule_roofline": ("inner_scope_work_roofline", ["draco_kdarule"],
                          "kernels", "%", "higher"),
    "nope_mla_ms": ("inner_scope_ms_per_step", ["draco_attn"], "models",
                    "ms", "lower"),
    "kda_expert_ffn_ms": ("inner_scope_ms_per_step", ["draco_experts"],
                          "models", "ms", "lower"),
    "kda_head_ms": ("inner_scope_ms_per_step", ["draco_head"], "models",
                    "ms", "lower"),
}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Kimi-Linear-48B-A3B-Instruct), every key
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26]
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": KDA_LAYERS, "num_heads": 32,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_experts": 8, "vocab_size": 20480}
REDUCED_KEYS = ["layers", "num_experts", "mixer_shards", "vocab_size"]
DIM = 510_692_160


def _files():
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return (load("kda-moe-tiny.json"), load("tiny_lm_maj_vote_r3.json"),
            load("tiny_lm_limits.json"))


def _run(tmp, trace=False, seed=2**31 + 45):
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
    config = _config()
    return {"n": 3, "batch": 1, "seq_len": config["data"]["seq_len"],
            "model_spec": config["train_config"]["model_spec"]}


# ---- the manifest's new entries ---------------------------------------

def test_manifest_holds_the_configuration_and_the_cell_by_name():
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    (entry,) = [c for c in m["configs"] if c["name"] == NEW_CONFIG]
    assert entry["reduced"] == REDUCED_KEYS
    assert entry["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
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
    """The published config keys, verbatim but the two that count what this
    chip holds (the nested group whole); the model's mapping keeps every
    published number and states the share beside them."""
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["layers"] == 5 and config["mixer_shards"] == 2
    assert config["reduced"] == REDUCED_KEYS
    assert config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256,
        "num_attention_heads": 32, "linear_attn_config.num_heads": 32,
        "vocab_size": 163840}
    assert set(config["held"]) == set(config["reduced"])
    spec = config["train_config"]["model_spec"]
    for key, value in PUBLISHED.items():
        assert spec[key] == value, key
    assert spec["layers"] == 5 and spec["layers_held"] == [1, 2, 3, 4, 5]
    # the dense layer and one whole period after it, 3 : 1 as published
    assert [i in KDA_LAYERS for i in spec["layers_held"]] == [
        True, True, True, False, True]
    assert spec["experts_held"] == [0, 8]
    assert spec["heads_held"] in ([0, 16], [0, 8])  # the cut, or fallback 2
    assert 32 // spec["heads_held"][1] == config["mixer_shards"]
    assert spec["vocab_rows"] == config["data"]["vocab"] == \
        config["train_config"]["vocab"] == 20480 == 163840 // 8
    assert config["data"]["seq_len"] == config["train_config"]["seq_len"]
    assert config["data"]["seq_len"] in (4096, 2048)  # the cut, fallback 1
    assert config["reference"] == {"module": "lm_train",
                                   "net": "kimi_linear"}
    for key in ("deployment", "assumed", "size", "precision", "products",
                "not_read", "left_out", "source", "fallback"):
        assert config[key], key
    for key in ("initializer_range", "conv_taps", "dt_bias", "A_log",
                "gate_rank", "l2_norm_eps", "e_score_correction_bias",
                "norm_topk_denominator", "chunk", "optimizer", "data"):
        assert key in config["assumed"], key
    assert "32 chips share each layer" in config["deployment"]
    assert "heads 2 ways" in config["deployment"]


def test_the_configuration_validates_under_the_cells_traffic():
    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.models import build_lm
    from draco_tpu.models.kda_moe import KdaMoeLM
    from draco_tpu.training.step import _make_unravel

    config = _config()
    traffic = manifest.traffic_of({"traffic": "lm_maj_vote_r3"})
    fields = dict(config["train_config"], **traffic["train_config"])
    cfg = TrainConfig(**dict(fields, train_dir="", eval_freq=0)).validate()
    assert cfg.network == "KdaMoeLM" and cfg.approach == "maj_vote"
    lm = build_lm(cfg)
    assert isinstance(lm, KdaMoeLM) and not lm.moe.dense
    assert lm.kept == [("kda", True), ("kda", False), ("kda", False),
                       ("mla", False), ("kda", False)]
    # a seeded rule for every leaf name of the model
    paths = jax.tree_util.tree_flatten_with_path(
        lm.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))[0]
    assert {p[-1].key for p, _ in paths} == set(config["weights"])
    _, dim, _ = _make_unravel(jax.eval_shape(lm.init, jax.random.key(0)))
    assert f"{dim:,}".replace(",", " ") in config["size"]
    if cfg.model_spec["heads_held"] == [0, 16]:
        # the issue's hand count
        assert dim == DIM == (4 * 20_052_112 + 15_221_248 + 5 * 4_608
                              + 63_700_992 + 4 * 64_291_072 + 94_371_840
                              + 2_304)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_layer_metric_has_its_file_its_reader_and_its_cell(name):
    reduction, scopes, layer, unit, better = NEW_METRICS[name]
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    assert spec["reduction"] == reduction and spec["scopes"] == scopes
    importlib.import_module(f"benchmark.reductions.{reduction}")
    if reduction == "inner_scope_work_roofline":
        costs = importlib.import_module(
            f"benchmark.harness.{spec['costs']}")
        assert costs is kda_lm_costs
        assert callable(getattr(costs, spec["flops"]))
        assert callable(getattr(costs, spec["bytes"]))
    m = manifest.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
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
    """The two limits the precision moves — the loss and the first gradient
    in twin units — lie above the sound runs' largest reading and under the
    fp8 control's smallest, with at least 2 x of room on both sides: the
    control comes out not correct by both. The two worst-leaf norms, which
    the precision hardly moves in this cell (the file's notes), lie between
    the first readings and 1, what a state left unchanged reads, with the
    more room above the reading."""
    limits = manifest.limits_of({"name": NEW_CELL})
    readings = limits["readings"]
    for name in ("loss_gap", "grad_diff"):
        assert 2 * readings[name]["sound_max"] < limits[name] \
            < readings[name]["control"] / 2, name
    for name in ("grad_norm_gap", "delta_norm_gap"):
        assert 3 * readings[name]["sound_max"] < limits[name] < 0.1, name
        assert readings[name]["control"] < 3 * readings[name]["sound_max"]
        assert limits[name] > readings[name]["control_max"], name
    assert readings["delta_norm_gap"]["state_unchanged"] == 1.0


@pytest.mark.parametrize("key,value", [
    (("train_config", "lr"), 0.01), (("train_config", "momentum"), 0.9),
    (("train_config", "optimizer"), "sgd"),
    (("train_config", "attn_impl"), "flash"),
    (("train_config", "compute_dtype"), "float32"),
    (("weights", "kernel"), "normal:0.02"),
    (("weights", "embedding"), "normal:1.0"),
    (("weights", "scale"), "ones"),
    (("weights", "e_score_correction_bias"), "normal:0.02"),
    (("data", "zipf_exponent"), 1.0), (("data", "train_sequences"), 256),
    (("control", "reference_dtype"), "float8_e4m3fn"),
    (("products",), "bfloat16")])
def test_the_cell_shares_the_lm_cells_assumed_values(key, value):
    """What no published config states — optimizer, seeded scales, the
    ids' distribution, the control — is the one set the LM cells share."""
    for name in ("kanana-2-30b-a3b-ep16", NEW_CONFIG):
        at = manifest.load_json(os.path.join(manifest.BENCH, "configs",
                                             name + ".json"))
        for part in key:
            at = at[part]
        assert at == value, (name, key)


def test_the_rules_seeded_leaves_are_the_other_delta_rule_cells():
    """The stated departures: log A ~ normal(0, 1), ``dt_bias`` ones and
    taps at variance 1 / 4 — qwen3next's values, under this tree's names."""
    ours = _config()["weights"]
    theirs = manifest.load_json(os.path.join(
        manifest.BENCH, "configs", "qwen3-next-80b-a3b-ep32.json"))["weights"]
    assert ours["A_log"] == theirs["A_log"] == "normal:1.0"
    assert ours["dt_bias"] == theirs["dt_bias"] == "ones"
    # normal_fan_in over (4, channels) is variance 1 / 4
    assert theirs["taps"] == "normal_fan_in" and ours["taps"] == "normal:0.5"


# ---- costs and the roofline's reduction ---------------------------------

def test_costs_are_the_hand_counts():
    job = dict(_job(), seq_len=4096)
    spec = dict(job["model_spec"], heads_held=[0, 16])
    job["model_spec"] = spec
    assert kda_lm_costs.kept(spec) == {"kda": 4, "latent": 1, "dense": 1,
                                       "sparse": 4}
    parts = kda_lm_costs.forward_flops_per_token(spec, 4096)
    # the recurrence: 7 x 128 x 128 a token a held head
    assert kda_lm_costs.kda_rule_forward_flops_per_token(spec) == \
        7 * 128 * 128 * 16 == 1_835_008
    # q, k, v 3 x 2304 x 2048; the two low-rank gates 2 x (2304 x 128 +
    # 128 x 2048); beta 2304 x 16; the output 2048 x 2304: 20 025 344
    # multiply-adds; three 4-tap convolutions over 2048 channels
    assert kda_lm_costs.kda_forward_flops_per_token(spec) == \
        2 * 20_025_344 + 2 * 3 * 4 * 2048 + 1_835_008 == 41_934_848
    assert parts["kda"] == 4 * 41_934_848
    assert parts["dense"] == 6 * 2304 * 9216 == 127_401_984
    assert parts["router"] == 4 * 2 * 2304 * 256
    assert parts["shared"] == 4 * 6 * 2304 * 1024
    # T x 8 x 8 / 256 = T / 4 pairs a sparse layer
    assert parts["routed"] == 4 * 6 * 2304 * 1024 / 4
    assert parts["head"] == 2 * 2304 * 20480
    # Wq 2304 x 3072, Wkva 2304 x 576, Wkvb 512 x 4096, Wo 2048 x 2304;
    # scores and mixing at 192 + 128 a head against 2048.5 keys
    assert parts["latent_attention"] == pytest.approx(
        2 * 15_220_736 + 2 * 16 * 320 * 2048.5)
    total = sum(parts.values())
    assert total == pytest.approx(516.4e6, rel=1e-3)
    assert parts["kda"] / total == pytest.approx(0.32, abs=0.01)
    assert parts["dense"] / total == pytest.approx(0.25, abs=0.01)
    assert parts["head"] / total == pytest.approx(0.18, abs=0.01)
    assert (parts["router"] + parts["shared"] + parts["routed"]) / total \
        == pytest.approx(0.15, abs=0.01)
    assert parts["latent_attention"] / total == pytest.approx(0.10, abs=0.01)
    # x 3 (forward + backward) x 12 288 token-gradients
    assert kda_lm_costs.train_flops_per_step(job) == pytest.approx(
        19.04e12, rel=1e-3)
    assert kda_lm_costs.kda_rule_train_flops_per_step(job) == \
        3 * 4 * 1_835_008 * 12288
    # q, k, v, g read and o written: 5 x 2048 floats a token and layer, and
    # beta's 16
    assert kda_lm_costs.kda_rule_train_bytes_per_step(job) == \
        3 * 4 * 4 * (5 * 2048 + 16) * 12288


def _trace():
    text = lambda name: f"%{name} = f32[8]{{0}} fusion(%p)"  # noqa: E731
    events = [(text("while.1"), 0.0, 100.0), (text("fusion.1"), 10.0, 30.0),
              (text("fusion.2"), 50.0, 20.0), (text("fusion.3"), 80.0, 10.0),
              (text("fusion.4"), 92.0, 4.0), (text("fusion.5"), 97.0, 2.0)]
    return xplane.Trace({"devices": {"/device:TPU:0": events},
                         "anchor_ns": None},
                        dict.fromkeys(("while.1", "fusion.1", "fusion.2",
                                       "fusion.3", "fusion.4", "fusion.5"),
                                      "draco_comp"),
                        0.0, (0.0, 1.0), 2)


def _read(name, ctx):
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    return importlib.import_module(
        f"benchmark.reductions.{spec['reduction']}").read(spec, ctx)


def test_the_roofline_is_the_rules_least_time_over_its_scopes_time():
    job = dict(_job(), seq_len=4096, inner_scopes={
        "fusion.1": "draco_kda", "fusion.2": "draco_kdarule",
        "fusion.3": "draco_experts", "fusion.4": "draco_attn",
        "fusion.5": "draco_head", "while.1": "draco_comp"})
    job["model_spec"] = dict(job["model_spec"], heads_held=[0, 16])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": _trace(), "job": job, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 1, "counters": {}, "peaks": peaks}
    assert _read("kda_attn_ms", ctx) == pytest.approx((30e-6 + 20e-6) / 2)
    assert _read("kda_rule_ms", ctx) == pytest.approx(20e-6 / 2)
    assert _read("kda_expert_ffn_ms", ctx) == pytest.approx(10e-6 / 2)
    assert _read("nope_mla_ms", ctx) == pytest.approx(4e-6 / 2)
    assert _read("kda_head_ms", ctx) == pytest.approx(2e-6 / 2)
    flops_s = kda_lm_costs.kda_rule_train_flops_per_step(job) / 197e12
    bytes_s = kda_lm_costs.kda_rule_train_bytes_per_step(job) / 819e9
    # 0.27 TFLOP: 1.4 ms at the peak; 6.0 GB: 7.4 ms — memory binds
    assert flops_s == pytest.approx(1.37e-3, rel=1e-2)
    assert bytes_s == pytest.approx(7.39e-3, rel=1e-2)
    assert _read("kda_rule_roofline", ctx) == pytest.approx(
        100 * bytes_s / (20e-9 / 2))
    # a program without the scopes (the parent, another cell): nothing, and
    # no error
    for other in ({"n": 8, "dim": 11, "wire": "f32"},
                  dict(job, inner_scopes={"fusion.1": "draco_linattn"})):
        for name in NEW_METRICS:
            assert _read(name, dict(ctx, job=other)) is None, name
    assert _read("kda_rule_roofline", dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    """In a fresh interpreter: importing the reference's net and job leaves
    no module of draco_tpu loaded."""
    code = ("import sys; "
            "import benchmark.reference.nets.kimi_linear, "
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
    ``draco_kda`` and the ``draco_kdarule`` nested in it beside the scopes
    the other blocks have; every record carries the counters as stated."""
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
    assert {"draco_kda", "draco_kdarule", "draco_attn", "draco_route",
            "draco_experts", "draco_head"} <= scopes
    assert {"kda_layers", "kda_kernel_layers", "kda_state_absmax",
            "kda_decay_min", "heads_held", "moe_dropped"} <= set(names)
    for row in rows:
        assert row["kda_layers"] == 4.0 and row["heads_held"] == 2.0
        assert row["kda_kernel_layers"] == 0.0
        assert row["kda_state_absmax"] > 0.0 > row["kda_decay_min"]
        assert row["moe_dropped"] == 0.0
        assert row["det_adv"] == row["det_tp"] == row["located_errors"] == 1.0


def test_broken_step_comes_out_not_correct(tmp_path, monkeypatch):
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


def test_lower_precision_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees
    from draco_tpu.models.kda_moe import KdaMoeLM

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
        KdaMoeLM(tc["model_spec"]).param_shapes(),
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
