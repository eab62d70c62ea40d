"""chip_smoke.py rehearsed without the chip, and the device-facing repairs
that came with it (ISSUE 21: chip bring-up).

* the script itself, run as the driver runs it, on this CPU-only host: it
  must fail at once — non-zero, ``"ok": false`` last, nothing measured;
* imported as a module, its phase functions at tiny size on the CPU (kernels
  in interpret mode) and the ``--multichip`` comparison on four virtual
  devices — wrong paths, arguments and control flow are found here, at no
  chip time;
* the compile cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``
  verbatim, else ``<checkout>/.jax_cache``);
* one mesh rule: the CLI builds a folded LM mesh for n=8 on 1 and on 4
  devices for each of sp / tp / pp / ep;
* tools/local_cluster.py refuses anything but the CPU.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_fails_at_once_without_a_tpu():
    """No CPU continuation: under JAX_PLATFORMS=cpu the script exits
    non-zero within seconds, its last line says ``"ok": false`` and names
    the device it found, and no training phase ran."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": lines[-1]["device"]["count"]}}
    assert [ln.get("phase") for ln in lines[:-1]] == ["device"]
    assert lines[0]["failed_checks"] == ["platform_is_tpu"]


def test_fails_alone_in_a_directory(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    the import of the program fails, the script reports it and exits
    non-zero (the driver's check that the script runs the PROGRAM)."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(alone)], capture_output=True,
                          text=True, cwd=tmp_path, timeout=120,
                          env=dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert _last_json(proc.stdout)["ok"] is False
    assert "draco_tpu" in proc.stderr


def _phase_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_phase_device(capsys):
    assert chip_smoke.phase_device("/some/cache", want_count=1)
    line = _phase_line(capsys)
    assert line["phase"] == "device" and line["platform"] == "cpu"
    assert {"jax", "jaxlib", "libtpu", "native_available",
            "native_build_error", "compile_cache_dir"} <= set(line)
    # and it does fail when the machine is short of devices
    assert not chip_smoke.phase_device("/some/cache", want_count=10 ** 6)


def test_phase_kernels_tiny_interpret(capsys):
    """Every kernel check of the chip's `kernels` phase, in interpret mode
    at a ragged tiny d: the references and gates are the ones the chip run
    is held to."""
    assert chip_smoke.phase_kernels(9, 5000, 5, (1, 128, 2, 64),
                                    interpret=True)
    line = _phase_line(capsys)
    assert line["ok"] and "failed_checks" not in line
    for key in ("locator_n9_s1_v_rel_err", "locator_n9_s2_v_rel_err",
                "locator_n9_s1_lam_v_rel_err", "approx_decode_int8_rel_err",
                "cyclic_recombine_bf16_rel_err", "flash_grad_rel_err"):
        assert line[key] < 1e-3, (key, line[key])


def test_phase_reports_every_failed_check(capsys):
    """A phase is ok only if every named check held; the line lists the
    ones that did not, next to what was seen — `a kernel that silently
    became XLA` is one such check (``*_is_tpu_custom_call``)."""
    ph = chip_smoke.Phase("kernels")
    ph.check("flash_fwd_is_tpu_custom_call", False)
    ph.check("flash_fwd_matches_dense", True, flash_fwd_rel_err=1e-6)
    ph.check("flash_grad_matches_dense", float("nan") < 5e-2)
    assert not ph.done()
    line = _phase_line(capsys)
    assert line["ok"] is False and line["flash_fwd_rel_err"] == 1e-6
    assert line["failed_checks"] == ["flash_fwd_is_tpu_custom_call",
                                     "flash_grad_matches_dense"]


TINY_CNN = dict(approach="cyclic", network="FC", dataset="synthetic-mnist",
                num_workers=9, worker_fail=1, err_mode="rev_grad",
                batch_size=4, lr=0.05, momentum=0.9, redundancy="simulate")
TINY_LM = dict(network="TransformerLM", dataset="synthetic-text",
               approach="cyclic", worker_fail=1, err_mode="rev_grad",
               redundancy="shared", compute_dtype="bfloat16",
               attn_impl="flash", lr=0.05, momentum=0.9, model_dim=32,
               model_layers=2, model_heads=4, vocab=64, seq_len=32,
               batch_size=2, num_workers=8)


@pytest.mark.parametrize("name,flags,steps,chunk", [
    ("cnn", TINY_CNN, 6, 3), ("lm", TINY_LM, 4, 2)])
def test_phase_training_tiny(name, flags, steps, chunk, capsys):
    """The four runs of a training phase through draco_tpu.cli.main —
    attacked K=1, attacked K=chunk, clean, uncoded mean — and every check
    between them, at a size the CPU runs in seconds."""
    assert chip_smoke.phase_training(name, chip_smoke._flags(**flags),
                                     steps, chunk)
    line = _phase_line(capsys)
    assert line["phase"] == name and line["ok"]
    assert line["attacked_vs_clean_worst_fraction_of_band"] <= 1.0
    assert line["kn_vs_k1_worst_fraction_of_band"] <= 1.0
    assert len(line["attacked_k1_losses"]) == steps
    assert set(line["ms_per_step"]) == {"attacked_k1", "clean_k1", "mean_k1"}


def test_real_argv_is_the_preset_and_the_measured_lm():
    """main() drives the cyclic-resnet18 preset field for field and the LM
    width PERF.md has measured before — parsed back through the CLI's own
    parser, not compared as strings."""
    import argparse

    from draco_tpu import cli
    from draco_tpu.presets import PRESETS

    def parse(argv):
        ap = cli.add_fit_args(argparse.ArgumentParser())
        return cli.config_from_args(ap.parse_args(argv))

    got = parse(chip_smoke.cnn_argv() + ["--max-steps", "6"])
    want = dataclasses.replace(PRESETS["cyclic-resnet18"], max_steps=6,
                               dataset="synthetic-cifar10")
    assert got == want
    lm = parse(chip_smoke.lm_argv())
    assert (lm.model_dim, lm.model_layers, lm.model_heads, lm.vocab,
            lm.seq_len, lm.batch_size, lm.num_workers) == (
        768, 8, 12, 8192, 512, 2, 8)
    assert (lm.approach, lm.worker_fail, lm.redundancy, lm.compute_dtype,
            lm.attn_impl) == ("cyclic", 1, "shared", "bfloat16", "flash")


def test_multichip_comparison_on_four_virtual_devices(capsys):
    """--multichip's phase on four of the virtual CPU devices: cnn on a
    4-device w axis and lm on w=2 × sp=2, n=8 folded, each against a
    one-device mesh — shards on every device, a worker-axis collective in
    the compiled step, losses equal."""
    cnn, lm = chip_smoke.multichip_configs(4)
    cnn = dataclasses.replace(cnn, network="FC", dataset="synthetic-mnist",
                              batch_size=4)
    lm = dataclasses.replace(lm, model_dim=32, model_layers=2, model_heads=4,
                             vocab=64, seq_len=32)
    assert chip_smoke.phase_multichip(cnn, lm, 4)
    line = _phase_line(capsys)
    assert line["ok"] and line["devices"] == 4
    assert line["cnn_rows_per_device"] == 2 and line["lm_rows_per_device"] == 4
    assert line["cnn_collectives"] > 0 and line["lm_collectives"] > 0
    assert line["cnn_worst_fraction_of_band"] <= 1.0
    assert line["lm_worst_fraction_of_band"] <= 1.0


def test_worker_axis_collectives_reads_both_replica_group_spellings():
    from draco_tpu.parallel import make_mesh_2d

    mesh = make_mesh_2d(2, 2, devices=jax.devices()[:4])  # w groups {0,2},{1,3}
    text = "\n".join([
        "%a = f32[4]{0} all-reduce(%x), replica_groups={{0,2},{1,3}}, to_apply=%add",
        "%b = f32[4]{0} all-gather(%x), replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}",
        "%c = f32[4]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add",
        "%d = f32[4]{0} all-reduce(%x), replica_groups=[2,2]<=[4], to_apply=%add",
        "%e = f32[4]{0} add(%x, %y)",
    ])
    got = chip_smoke._worker_axis_collectives(text, mesh)
    assert [ln[:2] for ln in got] == ["%a", "%b"]  # %c, %d are sp groups


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside(placed, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, enable_compile_cache returns it
    verbatim, sets no directory in code and the entries land directly in
    it; unset, the cache is <checkout>/.jax_cache."""
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from draco_tpu.runtime import enable_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "d = enable_compile_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == (\n"
        "    before if os.environ.get('JAX_COMPILATION_CACHE_DIR') else d)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(7.0)).block_until_ready()\n"
        "print(d)\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=REPO, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    where = proc.stdout.strip().splitlines()[-1]
    if placed:
        assert where == str(tmp_path / "placed")
        entries = os.listdir(where)
        assert entries and all(os.path.isfile(os.path.join(where, e))
                               for e in entries)  # no sub-directory
    else:
        assert where == os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("n_dev,route,flags,want", [
    (1, "sp", [], {"w": 1, "sp": 1}),
    (1, "pp", ["--pp-microbatches", "2"], {"w": 1, "pp": 1}),
    (4, "sp", ["--seq-shards", "2"], {"w": 2, "sp": 2}),
    (4, "sp", [], {"w": 4, "sp": 1}),
    (4, "tp", ["--tensor-shards", "2"], {"w": 2, "tp": 2}),
    (4, "pp", ["--pipeline-shards", "2"], {"w": 2, "pp": 2}),
    (4, "ep", ["--moe-experts", "4", "--expert-shards", "2"],
     {"w": 2, "ep": 2}),
    (8, "tp", ["--tensor-shards", "2"], {"w": 4, "tp": 2}),
])
def test_cli_builds_a_folded_lm_mesh(n_dev, route, flags, want, monkeypatch):
    """The documented LM commands (README quick start) with n=8 and NO
    --cpu-mesh: the CLI folds the eight logical workers onto however many
    devices there are — here the first ``n_dev`` virtual ones, steered in
    the test because the CLI asks jax.devices()."""
    from draco_tpu import cli

    devices = jax.devices()[:n_dev]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    seen = {}

    def recorder(route):
        def fake_train(cfg, mesh, **kw):
            seen.update(route=route, cfg=cfg, mesh=mesh)
            return None, {}
        return fake_train

    for mod in ("sp", "tp", "pp", "ep"):
        monkeypatch.setattr(f"draco_tpu.parallel.{mod}_step.train_{mod}",
                            recorder(mod))
    cli.main(["--network", "TransformerLM", "--approach", "cyclic",
              "--worker-fail", "1", "--num-workers", "8", "--redundancy",
              "shared", "--train-dir", "", *flags])
    assert seen["route"] == route
    assert dict(seen["mesh"].shape) == want
    assert seen["cfg"].num_workers == 8  # logical workers are not cut


def test_model_axis_cannot_shrink():
    from draco_tpu.parallel import make_mesh_wtp

    with pytest.raises(ValueError, match="at least 2 devices"):
        make_mesh_wtp(8, 2, devices=jax.devices()[:1])


def test_local_cluster_refuses_anything_but_the_cpu():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import local_cluster

    with pytest.raises(ValueError, match="one process at a time"):
        local_cluster.launch(2, 1, [sys.executable, "-c", "pass"],
                             env={"JAX_PLATFORMS": "tpu"})
