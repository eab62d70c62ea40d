"""What PR 39 adds to the benchmark: a looped language model through
``reference/lm_train.py`` with a ``nets/`` file and a costs file of its own
— one configuration, one cell, five per-layer metrics as JSON over the
reductions the benchmark had, new files and new entries only, each entry
after the ones the benchmark had. Everything is pinned by name and by
prefix: a later PR's entries, after these again, leave these tests alone. A
tiny cell of the same block (hidden 64, 2 layers x 4 passes,
benchmark/testdata/) runs end to end through ``runner.run_cell`` on the CPU
under the traffic file the other tiny LM cells use: sound it is correct,
with one pass dropped from the program's loop it is not, and the
lower-precision control fails the limits the sound run passes."""

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
from benchmark.harness import looped_lm_costs, xplane  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.looped_maj_vote_r3", "config": "looped-tiny",
        "traffic": "tiny_lm_maj_vote_r3", "chips": 1, "why": "test"}
NEW_CELL = "ouro.maj_vote_r3"
NEW_CONFIG = "ouro-2.6b-l4"
# name -> (reduction, scopes, layer, unit, better)
NEW_METRICS = {
    "looped_attn_ms": ("inner_scope_ms_per_step", ["draco_attn"], "models",
                       "ms", "lower"),
    "looped_mlp_ms": ("inner_scope_ms_per_step", ["draco_mlp"], "models",
                      "ms", "lower"),
    "exit_head_ms": ("inner_scope_ms_per_step",
                     ["draco_head", "draco_exit"], "models", "ms", "lower"),
    "exit_head_roofline": ("inner_scope_work_roofline", ["draco_head"],
                           "models", "%", "higher"),
    "looped_attn_roofline": ("inner_scope_work_roofline", ["draco_attn"],
                             "kernels", "%", "higher"),
}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Ouro-2.6B), every key
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
# what the benchmark had before this PR, in its order (a prefix)
HAD_CONFIGS = ["resnet18-cifar10", "vgg11-cifar10", "kanana-2-30b-a3b-ep16",
               "qwen3-next-80b-a3b-ep32", "mellum2-12b-a2.5b-ep8"]
HAD_CELLS = ["resnet18.cyclic_s1", "resnet18.mean_b96", "vgg11.cyclic_s2",
             "kanana2.maj_vote_r3", "resnet18.cyclic_s1_b128",
             "qwen3next.maj_vote_r3", "mellum2.maj_vote_r3"]
HAD_LAST_METRIC = "loop_edges_ms"  # the last per_layer entry of PR 37


def _files():
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return (load("looped-tiny.json"), load("tiny_lm_maj_vote_r3.json"),
            load("tiny_lm_limits.json"))


def _run(tmp, trace=False, seed=2**31 + 39):
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

def test_manifest_gains_the_configuration_and_the_cell_after_the_old_ones():
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    names = [c["name"] for c in m["configs"]]
    assert names[:len(HAD_CONFIGS)] == HAD_CONFIGS
    assert names.index(NEW_CONFIG) == len(HAD_CONFIGS)
    (entry,) = [c for c in m["configs"] if c["name"] == NEW_CONFIG]
    assert entry["reduced"] == ["layers"]
    assert entry["source"].endswith(
        "ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{NEW_CONFIG}.json"
    cells = [w["name"] for w in m["workloads"]]
    assert cells[:len(HAD_CELLS)] == HAD_CELLS
    assert cells.index(NEW_CELL) == len(HAD_CELLS)
    cell = manifest.cell_of(m, NEW_CELL)
    assert cell["chips"] == 1 and cell["config"] == NEW_CONFIG
    assert len(cell["why"]) <= 200
    # the traffic file the benchmark already had, unchanged
    assert cell["traffic"] == "lm_maj_vote_r3"
    metrics = [x["name"] for x in m["per_layer"]]
    first = metrics.index(HAD_LAST_METRIC) + 1
    assert metrics[first:first + len(NEW_METRICS)] == list(NEW_METRICS)
    # the one four-chip cell is the one the benchmark had
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4][:1] == [
        "resnet18.cyclic_s1_b128"]


def test_every_number_of_the_configuration_is_the_catalog_rows():
    """The published config keys, verbatim: nothing but the depth is cut,
    and the depth is the ``layers`` key, so every published number —
    ``num_hidden_layers`` included — stands in the file and in the model's
    mapping."""
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["layers"] == 4 and config["reduced"] == ["layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert set(config["held"]) == set(config["reduced"])
    spec = config["train_config"]["model_spec"]
    for key, value in PUBLISHED.items():
        assert spec[key] == value, key
    assert spec["layers"] == 4
    # the whole vocabulary: ids, logits and loss over all of it
    assert spec["vocab_rows"] == config["data"]["vocab"] == \
        PUBLISHED["vocab_size"] == config["train_config"]["vocab"]
    assert config["data"]["seq_len"] == config["train_config"]["seq_len"]
    assert config["data"]["seq_len"] in (4096, 2048)
    assert config["reference"] == {"module": "lm_train", "net": "ouro"}
    for key in ("deployment", "assumed", "size", "precision", "not_read"):
        assert config[key], key
    for key in ("sandwich_norm", "final_norm_in_loop", "exit_gate",
                "objective", "optimizer", "initializer_range", "data"):
        assert key in config["assumed"], key
    assert "406 884 353" in config["size"]
    # no width is named among the reduced keys, nor cut anywhere
    for key in config["reduced"]:
        assert not any(w in key for w in manifest.WIDTH_WORDS), key


def test_the_configuration_validates_under_the_cells_traffic():
    import jax

    from draco_tpu.config import TrainConfig
    from draco_tpu.models import build_lm
    from draco_tpu.models.looped import LoopedLM
    from draco_tpu.training.step import _make_unravel

    config = _config()
    traffic = manifest.traffic_of({"traffic": "lm_maj_vote_r3"})
    fields = dict(config["train_config"], **traffic["train_config"])
    cfg = TrainConfig(**dict(fields, train_dir="", eval_freq=0)).validate()
    assert cfg.network == "LoopedLM" and cfg.approach == "maj_vote"
    lm = build_lm(cfg)
    assert isinstance(lm, LoopedLM)
    # a seeded rule for every leaf name of the model
    paths = jax.tree_util.tree_flatten_with_path(
        lm.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))[0]
    assert {p[-1].key for p, _ in paths} == set(config["weights"])
    # the issue's hand count
    _, dim, _ = _make_unravel(jax.eval_shape(lm.init, jax.random.key(0)))
    assert dim == 406_884_353 == (4 * 51_388_416 + 201_326_592 + 2_048
                                  + 2_049)


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
        assert costs is looped_lm_costs
        assert callable(getattr(costs, spec["flops"]))
        assert callable(getattr(costs, spec["bytes"]))
    m = manifest.load_manifest()
    (entry,) = [x for x in m["per_layer"] if x["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "step_ms_p50", "workloads": [NEW_CELL]}


def test_the_new_cell_reports_every_end_to_end_metric_and_its_five_layers():
    m = manifest.load_manifest()
    assert set(NEW_METRICS) <= {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "per_layer")}
    assert {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "end_to_end")} >= {
            "examples_per_s", "step_ms_p50", "step_ms_p95", "peak_hbm_gb",
            "setup_s"}
    # nothing new for the cells the benchmark had
    for name in HAD_CELLS:
        assert not set(NEW_METRICS) & {x["name"] for x in manifest.metrics_for(
            m, name, "per_layer")}


def test_the_cells_limits_lie_between_their_readings():
    """Every limit over the sound runs' largest reading and under the
    control's smallest, with room on both sides: the fp8 control fails."""
    limits = manifest.limits_of({"name": NEW_CELL})
    readings = limits["readings"]
    for name in ("loss_gap", "grad_norm_gap", "grad_diff",
                 "delta_norm_gap"):
        assert 2 * readings[name]["sound_max"] < limits[name] \
            < readings[name]["control"] / 2, name
    assert limits["delta_norm_gap"] < readings["delta_norm_gap"][
        "state_unchanged"] == 1.0


@pytest.mark.parametrize("key,value", [
    (("train_config", "lr"), 0.01), (("train_config", "momentum"), 0.9),
    (("train_config", "optimizer"), "sgd"),
    (("train_config", "attn_impl"), "flash"),
    (("weights", "embedding"), "normal:1.0"),
    (("weights", "kernel"), "normal:0.02"),
    (("weights", "scale"), "ones"),
    (("data", "zipf_exponent"), 1.0), (("data", "train_sequences"), 256),
    (("control", "reference_dtype"), "float8_e4m3fn"),
    (("products",), "bfloat16")])
def test_the_looped_cell_shares_the_lm_cells_assumed_values(key, value):
    """What no published config states — optimizer, seeded scales, the
    ids' distribution, the control — is the one set the LM cells share."""
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
    assert looped_lm_costs.applications(spec) == 16
    parts = looped_lm_costs.forward_flops_per_token(spec, 4096)
    # a layer's products 2 x (4 x 2048^2 + 3 x 2048 x 5632), 16 applications
    assert parts["mlp"] == 16 * 2 * 34_603_008
    assert parts["attention"] == pytest.approx(
        16 * (2 * 16_777_216 + 4 * 16 * 128 * 2048.5))
    assert parts["head"] == 4 * 2 * 2048 * 49152
    assert parts["gate"] == 4 * 2 * 2048
    total = sum(parts.values())
    assert total == pytest.approx(2718e6, rel=1e-3)
    assert parts["head"] / total == pytest.approx(0.296, abs=0.002)
    assert (parts["attention"] + parts["mlp"]) / total == pytest.approx(
        0.704, abs=0.002)
    # x 3 (forward + backward) x 12 288 token-gradients
    assert looped_lm_costs.train_flops_per_step(job) == pytest.approx(
        100.2e12, rel=1e-3)
    assert looped_lm_costs.attention_train_flops_per_step(job) == \
        3 * 12288 * parts["attention"]
    assert looped_lm_costs.head_train_flops_per_step(job) == \
        3 * 12288 * parts["head"]
    # the four matrices a lane and application; x (2048) in, q, k, v, the
    # mixed heads (2048 each) and the result out a token; float32; 3 x
    assert looped_lm_costs.attention_train_bytes_per_step(job) == \
        3 * 4 * 16 * (3 * 4 * 2048 * 2048 + 12288 * (2 * 2048 + 4 * 2048))
    # the (2048, 49152) matrix a lane and exit, each token's state once
    assert looped_lm_costs.head_train_bytes_per_step(job) == \
        3 * 4 * 4 * (3 * 2048 * 49152 + 12288 * 2048)
    # one pass is a plain dense decoder: a quarter of the looped work
    once = looped_lm_costs.forward_flops_per_token(
        dict(spec, total_ut_steps=1), 4096)
    assert sum(once.values()) == pytest.approx(total / 4)


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
        "fusion.1": "draco_attn", "fusion.2": "draco_head",
        "fusion.3": "draco_mlp", "fusion.4": "draco_exit",
        "while.1": "draco_comp"})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": _trace(), "job": job, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 1, "counters": {}, "peaks": peaks}
    assert _read("looped_attn_ms", ctx) == pytest.approx(30e-6 / 2)
    assert _read("looped_mlp_ms", ctx) == pytest.approx(10e-6 / 2)
    assert _read("exit_head_ms", ctx) == pytest.approx((20e-6 + 4e-6) / 2)
    for name, flops, moved, scope_ns in (
            ("exit_head_roofline",
             looped_lm_costs.head_train_flops_per_step,
             looped_lm_costs.head_train_bytes_per_step, 20.0),
            ("looped_attn_roofline",
             looped_lm_costs.attention_train_flops_per_step,
             looped_lm_costs.attention_train_bytes_per_step, 30.0)):
        flops_s, bytes_s = flops(job) / 197e12, moved(job) / 819e9
        assert flops_s > bytes_s  # compute binds at the published widths
        assert _read(name, ctx) == pytest.approx(
            100 * flops_s / (scope_ns * 1e-9 / 2))
    # 29.7 TFLOP of head and of attention a step: 0.151 s each at the peak
    assert looped_lm_costs.head_train_flops_per_step(job) / 197e12 == \
        pytest.approx(0.1507, rel=2e-3)
    # a program without the scopes (the parent, another cell): nothing, and
    # no error
    for other in ({"n": 8, "dim": 11, "wire": "f32"},
                  dict(job, inner_scopes={"fusion.1": "draco_linattn"})):
        for name in NEW_METRICS:
            assert _read(name, dict(ctx, job=other)) is None, name
    assert _read("exit_head_roofline", dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    """In a fresh interpreter: importing the reference's net and job leaves
    no module of draco_tpu loaded."""
    code = ("import sys; "
            "import benchmark.reference.nets.ouro, "
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
    """The route's innermost-scope map of the step it dispatched:
    ``draco_attn``, ``draco_mlp``, ``draco_head`` and ``draco_exit``; every
    record carries the five counters, ``loop_passes`` at 4."""
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
    assert {"draco_attn", "draco_mlp", "draco_head", "draco_exit"} <= scopes
    assert not {"draco_experts", "draco_route"} & scopes
    assert rows[0]["loop_passes"] == 4.0
    for key in ("exit_pass_mean", "exit_entropy", "exit_ce_first",
                "exit_ce_last"):
        assert key in rows[0], key


def test_a_program_that_drops_a_pass_comes_out_not_correct(tmp_path,
                                                           monkeypatch):
    """The program's loop made one pass shorter than the mapping says (the
    reference still runs all four): ``correct`` is false."""
    from draco_tpu.models import looped

    real = looped.LoopedLM.__init__

    def short(self, spec, *args, **kw):
        real(self, spec, *args, **kw)
        self.spec["total_ut_steps"] -= 1

    monkeypatch.setattr(looped.LoopedLM, "__init__", short)
    out = _run(tmp_path)
    assert out["correct"] is False


def test_lower_precision_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees
    from draco_tpu.models.looped import LoopedLM

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
        LoopedLM(tc["model_spec"]).param_shapes(),
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
