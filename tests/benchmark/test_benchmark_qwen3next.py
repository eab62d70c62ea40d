"""What PR 31 adds to the benchmark: a second language model through
``reference/lm_train.py`` with a ``nets/`` file, a costs file and a
roofline reduction of its own — one configuration, one cell, three per-layer
metrics, new files and new entries only, each entry at the END of its list.
A tiny cell of the same block (hidden 64, benchmark/testdata/) runs end to
end through ``runner.run_cell`` on the CPU under the traffic file the tiny
kanana cell uses: sound it is correct, with the step broken underneath it
is not, and the lower-precision control fails the limits the sound run
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

from benchmark.harness import check, hybrid_lm_costs, manifest, runner  # noqa: E402
from benchmark.harness import xplane  # noqa: E402

TESTDATA = os.path.join(manifest.BENCH, "testdata")
CELL = {"name": "tiny.hybrid_maj_vote_r3", "config": "hybrid-moe-tiny",
        "traffic": "tiny_lm_maj_vote_r3", "chips": 1, "why": "test"}
NEW_CELL = "qwen3next.maj_vote_r3"
NEW_CONFIG = "qwen3-next-80b-a3b-ep32"
NEW_METRICS = {
    "linear_attn_ms": ("inner_scope_ms_per_step", "models", "ms", "lower"),
    "deltarule_ms": ("inner_scope_ms_per_step", "kernels", "ms", "lower"),
    "deltarule_roofline": ("inner_scope_work_roofline", "kernels", "%",
                           "higher"),
}
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Qwen3-Next-80B-A3B-Instruct), every key
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def _files():
    def load(name):
        return manifest.load_json(os.path.join(TESTDATA, name))

    return (load("hybrid-moe-tiny.json"), load("tiny_lm_maj_vote_r3.json"),
            load("tiny_lm_limits.json"))


def _run(tmp, trace=False, seed=2**31 + 31):
    config, traffic, limits = _files()
    m = manifest.load_manifest()
    metrics = m["per_layer"] if trace else m["end_to_end"]
    return runner.run_cell(CELL, config, traffic, limits, metrics, seed, 0.5,
                           trace, time.time(), require_tpu=False,
                           scratch=str(tmp))


def _config():
    return manifest.load_json(os.path.join(manifest.BENCH, "configs",
                                           NEW_CONFIG + ".json"))


# ---- the manifest's new entries ---------------------------------------

def test_manifest_gains_the_configuration_and_the_cell_at_the_end():
    m = manifest.load_manifest()
    assert manifest.check_manifest(m) == []
    entry = m["configs"][-1]
    assert entry["name"] == NEW_CONFIG
    assert entry["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert entry["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    cell = m["workloads"][-1]
    assert cell["name"] == NEW_CELL and cell["chips"] == 1
    assert cell["config"] == NEW_CONFIG
    # the traffic file the benchmark already had, unchanged
    assert cell["traffic"] == "lm_maj_vote_r3"
    assert [x["name"] for x in m["per_layer"]][-3:] == list(NEW_METRICS)
    # one cell in six may take four chips: this one does not
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1


def test_every_number_of_the_configuration_is_the_catalog_rows():
    """The published config keys, verbatim but for the three reduced ones;
    the model's mapping keeps every published number, the router's width
    and the vocabulary's included."""
    config = _config()
    reduced = {"num_experts": 16, "vocab_size": 18992}
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
    assert config["layers"] == 4 and config["reduced"] == [
        "layers", "num_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert set(config["held"]) == set(config["reduced"])
    spec = config["train_config"]["model_spec"]
    for key, value in PUBLISHED.items():
        assert spec[key] == value, key
    assert spec["experts_held"] == [0, 16] and spec["layers"] == 4
    assert spec["vocab_rows"] == config["data"]["vocab"] == 18992
    assert spec["vocab_rows"] * 8 == PUBLISHED["vocab_size"]
    assert spec["experts_held"][1] * 32 == PUBLISHED["num_experts"]
    assert config["data"]["seq_len"] == 4096
    assert config["reference"] == {"module": "lm_train", "net": "qwen3_next"}
    # no width is named among the reduced keys, nor cut anywhere
    for key in config["reduced"]:
        assert not any(w in key for w in manifest.WIDTH_WORDS), key


def test_the_configuration_validates_under_the_cells_traffic():
    from draco_tpu.config import TrainConfig

    config = _config()
    traffic = manifest.traffic_of({"traffic": "lm_maj_vote_r3"})
    fields = dict(config["train_config"], **traffic["train_config"])
    cfg = TrainConfig(**dict(fields, train_dir="", eval_freq=0)).validate()
    assert cfg.network == "HybridMoeLM" and cfg.approach == "maj_vote"
    # a seeded rule for every leaf name of the model
    from draco_tpu.models import build_lm

    import jax
    names = {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(
        build_lm(cfg).param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple))[0]}
    assert names == set(config["weights"])


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
    assert "draco_deltarule" in spec["scopes"]


def test_the_new_cell_reports_every_end_to_end_metric_and_only_its_layers():
    m = manifest.load_manifest()
    assert {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "per_layer")} == set(NEW_METRICS)
    assert {x["name"] for x in manifest.metrics_for(
        m, NEW_CELL, "end_to_end")} == {x["name"] for x in m["end_to_end"]}
    # nothing new for the cells the benchmark had
    for w in m["workloads"][:-1]:
        assert not set(NEW_METRICS) & {x["name"] for x in manifest.metrics_for(
            m, w["name"], "per_layer")}


def test_the_cells_limits_lie_between_their_readings():
    limits = manifest.limits_of({"name": NEW_CELL})
    for name in ("loss_gap", "grad_norm_gap", "grad_diff", "delta_norm_gap"):
        assert 0 < limits[name] < 1e9, name
    readings = limits["readings"]
    for name in ("grad_norm_gap", "grad_diff", "delta_norm_gap"):
        assert readings[name]["sound_max"] < limits[name] \
            < readings[name]["control"], name
    assert readings["loss_gap"]["sound_max"] < limits["loss_gap"]
    assert limits["delta_norm_gap"] < readings["delta_norm_gap"][
        "state_unchanged"] == 1.0


# ---- costs and the roofline's reduction --------------------------------

def _job():
    return {"n": 3, "batch": 1, "seq_len": 4096,
            "model_spec": _config()["train_config"]["model_spec"]}


def test_flop_shares_are_the_issues():
    job = _job()
    parts = hybrid_lm_costs.forward_flops_per_token(job["model_spec"], 4096)
    total = sum(parts.values())
    assert total == pytest.approx(0.419e9, rel=2e-3)
    assert parts["linear_attention"] / total == pytest.approx(0.51, abs=0.01)
    assert parts["attention"] / total == pytest.approx(0.21, abs=0.005)
    assert parts["head"] / total == pytest.approx(0.185, abs=0.005)
    assert (parts["router"] + parts["shared"] + parts["routed"]) / total \
        == pytest.approx(0.10, abs=0.005)
    assert hybrid_lm_costs.train_flops_per_step(job) == pytest.approx(
        15.45e12, rel=1e-3)
    # the rule: 6·128·128 FLOP a token a value head, three layers, 3 x
    assert hybrid_lm_costs.deltarule_train_flops_per_step(job) == \
        3 * 3 * 12288 * 32 * 6 * 128 * 128
    # q, k (16 heads), v, o (32 heads) of 128 floats, g and β: 3 x
    assert hybrid_lm_costs.deltarule_train_bytes_per_step(job) == \
        3 * 3 * 12288 * 4 * (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32)


def _trace():
    text = lambda name: f"%{name} = f32[8]{{0}} fusion(%p)"  # noqa: E731
    events = [(text("while.1"), 0.0, 100.0), (text("fusion.1"), 10.0, 30.0),
              (text("fusion.2"), 50.0, 20.0), (text("fusion.3"), 80.0, 10.0)]
    return xplane.Trace({"devices": {"/device:TPU:0": events},
                         "anchor_ns": None},
                        {"while.1": "draco_comp", "fusion.1": "draco_comp",
                         "fusion.2": "draco_comp", "fusion.3": "draco_comp"},
                        0.0, (0.0, 1.0), 2)


def _read(name, ctx):
    spec = manifest.load_json(os.path.join(manifest.BENCH, "layer_metrics",
                                           name + ".json"))
    return importlib.import_module(
        f"benchmark.reductions.{spec['reduction']}").read(spec, ctx)


def test_the_roofline_is_the_rules_least_time_over_its_scopes_time():
    job = dict(_job(), inner_scopes={
        "fusion.1": "draco_deltarule", "fusion.2": "draco_linattn",
        "fusion.3": "draco_attn", "while.1": "draco_comp"})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": _trace(), "job": job, "records": [], "spans": [],
           "window": (0.0, 1.0), "chips": 1, "counters": {}, "peaks": peaks}
    assert _read("deltarule_ms", ctx) == pytest.approx(30e-6 / 2)
    assert _read("linear_attn_ms", ctx) == pytest.approx(50e-6 / 2)
    flops_s = hybrid_lm_costs.deltarule_train_flops_per_step(job) / 197e12
    bytes_s = hybrid_lm_costs.deltarule_train_bytes_per_step(job) / 819e9
    assert bytes_s > flops_s  # bound by memory at the published widths
    assert _read("deltarule_roofline", ctx) == pytest.approx(
        100 * bytes_s / (30e-9 / 2))
    # a program without the scopes (the parent, another cell): nothing
    for other in ({"n": 8, "dim": 11, "wire": "f32"},
                  dict(job, inner_scopes={"fusion.1": "draco_attn"})):
        assert _read("deltarule_ms", dict(ctx, job=other)) is None
        assert _read("linear_attn_ms", dict(ctx, job=other)) is None
        assert _read("deltarule_roofline", dict(ctx, job=other)) is None
    assert _read("deltarule_roofline", dict(ctx, peaks=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    """In a fresh interpreter: importing the reference's net and job leaves
    no module of draco_tpu loaded."""
    code = ("import sys; "
            "import benchmark.reference.nets.qwen3_next, "
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
    assert set(sound) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    json.dumps(sound)


@pytest.mark.parametrize("metric", [
    x["name"] for x in manifest.load_manifest()["end_to_end"]])
def test_run_reports_every_end_to_end_metric(sound, metric):
    got = sound["metrics"][metric]
    assert set(got) == {"value", "unit"} and isinstance(got["value"], float)
    if metric != "peak_hbm_gb":  # the CPU backend reports no memory
        assert got["value"] > 0


def test_traced_run_carries_the_nested_scopes_and_leaves_device_metrics_out(
        tmp_path, capsys):
    out = _run(tmp_path, trace=True)
    assert out["correct"] is True
    # no TPU plane in a CPU capture: the new readers find nothing to read
    assert not set(NEW_METRICS) & set(out["metrics"])
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0
    # every window's ledger line carries the model's five counters
    ledger = [line for line in capsys.readouterr().out.splitlines()
              if line.startswith("ledger:")]
    assert ledger
    for key in ("moe_assignments_held", "moe_load_max_over_mean",
                "moe_dropped=0 ", "moe_full_dispatch=0 ",
                "linattn_state_absmax"):
        assert key in ledger[0], key


def test_the_compiled_step_names_the_new_scopes(tmp_path):
    """The route's innermost-scope map of the step it dispatched: the rule
    under ``draco_deltarule``, the rest of the DeltaNet layer under
    ``draco_linattn``, gated attention under ``draco_attn``, and the expert
    layer's and the head's scopes as kanana's cell has them."""
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
    assert {"draco_deltarule", "draco_linattn", "draco_attn", "draco_route",
            "draco_experts", "draco_head"} <= scopes
    for key in ("linattn_state_absmax", "moe_assignments_held",
                "moe_load_max_over_mean", "moe_dropped", "moe_full_dispatch"):
        assert key in rows[0], key


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


def test_a_rule_that_forgets_its_state_comes_out_not_correct(tmp_path,
                                                             monkeypatch):
    """The chunks' pass made to start every chunk from an empty state —
    linear attention over the last 64 tokens only: the loss moves in its
    fourth digit and ``correct`` is false."""
    import jax.numpy as jnp

    from draco_tpu.models import hybrid_moe
    from draco_tpu.ops import delta_rule

    def forgetful(q, k, v, g, beta, chunk):
        t = q.shape[1]
        parts = [delta_rule.chunked_gated_delta_rule(
            q[:, lo:lo + chunk], k[:, lo:lo + chunk], v[:, lo:lo + chunk],
            g[:, lo:lo + chunk], beta[:, lo:lo + chunk], chunk)
            for lo in range(0, t, chunk)]
        return jnp.concatenate([o for o, _ in parts], axis=1), parts[-1][1]

    monkeypatch.setattr(hybrid_moe, "chunked_gated_delta_rule", forgetful)
    out = _run(tmp_path)
    assert out["correct"] is False


def test_lower_precision_control_fails_where_sound_passes():
    import jax

    from benchmark.harness import seeded, trees
    from draco_tpu.models.hybrid_moe import HybridMoeLM

    config, traffic, limits = _files()
    tc = dict(config["train_config"], **traffic["train_config"])
    seed = 79
    data = runner.make_data(config, seed)
    reference = runner.reference_of(config)
    job = reference.make_job(config, {
        "groups": 1, "batch": tc["batch_size"], "lr": tc["lr"],
        "momentum": tc["momentum"]})
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "float32"),
        HybridMoeLM(tc["model_spec"]).param_shapes(),
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
