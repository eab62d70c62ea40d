"""Program-linter tests: the registry covers every route, the fast CLI
subset is green, and — the part that keeps the linter honest — every
seeded-defect negative control trips exactly its rule.

Reference stake: none of these invariants is visible to an output-level
test. The round-5 d-sized-constant regression trained bit-identically and
wedged a 27-minute chip window anyway (PERF_HISTORY.md §4); donation loss doubles
carry HBM silently; an extra all-gather changes the communication
structure the gradient-coding line treats as the algorithm (PAPERS.md).
"""

import json
import os

import pytest

from draco_tpu.analysis import RULE_NAMES, collect, lint_program
from draco_tpu.analysis.controls import control_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.core
class TestNegativeControls:
    """One deliberately-defective program per rule (analysis/controls.py):
    each must trip exactly its rule, with every other rule staying green —
    the proving-the-harness-is-live discipline of the mis-tiled pallas_call
    in tools/tpu_attn_lowering_check.py."""

    @pytest.mark.parametrize(
        "control", control_programs(), ids=lambda c: c.program.name)
    def test_control_trips_exactly_its_rule(self, control):
        row = lint_program(control.program)
        assert row["failed_rules"] == [control.expected_fail], (
            f"{control.program.name} must trip exactly "
            f"[{control.expected_fail}], tripped {row['failed_rules']}: "
            f"{ {n: r for n, r in row['rules'].items() if not r['ok']} }"
        )
        for name, res in row["rules"].items():
            if name != control.expected_fail:
                assert res["ok"], (name, res)

    def test_controls_cover_every_rule(self):
        covered = {c.expected_fail for c in control_programs()}
        assert covered == set(RULE_NAMES)


@pytest.mark.core
def test_registry_covers_every_route():
    """Each route module registers at least its train_step and its K-fused
    scan driver; names are unique (collect() raises on dupes)."""
    programs = collect()
    routes = {p.route for p in programs}
    assert routes >= {"cnn", "sp", "tp", "pp", "ep"}
    names = {p.name for p in programs}
    for route_pair in (("cnn_cyclic_step", "cnn_cyclic_many_k2"),
                       ("lm_sp_ring_step", "lm_sp_ring_many_k2"),
                       ("lm_tp2_step", "lm_tp2_many_k2"),
                       ("lm_pp_step", "lm_pp_many_k2"),
                       ("lm_ep_step", "lm_ep_many_k2")):
        assert names >= set(route_pair), (route_pair, names)
    # the production chunked drivers with device token-gen and the big-d
    # constant-bloat guard are registered too
    assert "lm_fold_devgen_many_k2" in names
    # the kernel-bearing rows (ISSUE 12) ride the fast sweep — their TPU
    # export IS the per-commit Mosaic lowering check
    assert {"kernel_cyclic_locator", "kernel_approx_decode"} <= {
        p.name for p in programs if p.fast}
    # the ISSUE 17 mesh-sub-axis tree combine programs ride the fast
    # sweep — their collectives manifest pins one psum per level
    assert {"tree_combine_g2_l3", "tree_combine_g4_l2"} <= {
        p.name for p in programs if p.fast}
    # out of the --fast budget: the big-d constant-bloat guard (~3.3M
    # params), the ISSUE 12 fused/approx impl VARIANTS of fast-swept
    # step bodies, the ISSUE 16 segmented-wire variants, and the ISSUE 17
    # tree-topology step variants (the full tool + the committed-artifact
    # coverage test still guard them)
    big = {p.name for p in programs if not p.fast}
    assert big == {"lm_fold_big_bf16_many_k2",
                   "cnn_cyclic_layer_step", "cnn_cyclic_layer_pallas_step",
                   "cnn_approx_pallas_step",
                   "lm_sp_ring_approx_pallas_many_k2",
                   "lm_tp2_approx_many_k2", "lm_tp2_approx_pallas_many_k2",
                   "cnn_cyclic_seg2_many_k2",
                   "cnn_cyclic_seg2_wire_bf16_many_k2",
                   "cnn_approx_seg2_step",
                   "cnn_approx_seg2_wire_int8_step",
                   "cnn_cyclic_tree_g4_step", "cnn_cyclic_tree_g4_many_k2",
                   "cnn_cyclic_tree_g4_wire_bf16_many_k2",
                   "cnn_approx_tree_g4_step"}


@pytest.mark.core
def test_fast_subset_all_green(tmp_path):
    """The core-tier wiring of ``tools/program_lint.py --fast``: every fast
    registered program passes all nine rules, through the CLI's own main()
    (controls skipped here — they have their own test above). Runtime is
    the bulk of this module's core budget: 33 programs, each built, traced,
    exported for the TPU and compiled for the host once, about 4 s apiece
    (150 s alone on an idle 8-core host, PR 44)."""
    from tools.program_lint import main

    out = tmp_path / "program_lint.json"
    rc = main(["--fast", "--skip-controls", "--out", str(out)])
    report = json.loads(out.read_text())
    failed = {r["name"]: r.get("failed_rules") or r.get("error")
              for r in report["rows"] if not r["ok"]}
    assert rc == 0 and report["all_ok"], failed
    fast_names = {p.name for p in collect() if p.fast}
    assert {r["name"] for r in report["rows"]} == fast_names
    for row in report["rows"]:
        assert set(RULE_NAMES) <= set(row["rules"]), row["name"]


@pytest.mark.core
def test_committed_artifact_is_consistent_with_registry():
    """baselines_out/program_lint.json (the committed artifact) must cover
    every registered program, be green, and carry live controls — catches
    adding a program without re-running the tool."""
    path = os.path.join(REPO, "baselines_out", "program_lint.json")
    report = json.load(open(path))
    assert report["all_ok"], [r["name"] for r in report["rows"]
                              if not r["ok"]]
    rows = {r["name"]: r for r in report["rows"]}
    missing = {p.name for p in collect()} - set(rows)
    assert not missing, (
        f"programs registered but absent from the committed artifact "
        f"{sorted(missing)} — rerun tools/program_lint.py")
    controls = [r for r in report["rows"] if r.get("control")]
    assert {c["expected_fail"] for c in controls} == set(RULE_NAMES)
    # every registered (non-control) row carries the memory/cost ledger
    # columns the memory_budget rule records (ISSUE 5) — the round-over-
    # round series tools/perf_watch.py diffs. The pallas_call-bearing
    # kernel rows (ISSUE 12, route "decode_kernel") are the one legal
    # exception: tpu_custom_call cannot compile for the CPU host, so they
    # register with the memory-capture opt-out (capture_memory=False,
    # like the chip-tier flash rows) and their memory_budget row reports
    # skipped-with-reason instead of columns.
    from draco_tpu.analysis.registry import collect as _collect

    kernel_rows = {p.name for p in _collect() if p.route == "decode_kernel"}
    for r in report["rows"]:
        if r.get("control"):
            continue
        mb = r["rules"]["memory_budget"]
        if r["name"] in kernel_rows:
            assert mb.get("skipped") and mb.get("ok"), (r["name"], mb)
            continue
        assert not mb.get("skipped"), (r["name"], mb)
        mem = mb["memory"]
        for col in ("argument_bytes", "output_bytes", "temp_bytes",
                    "generated_code_bytes", "alias_bytes", "peak_bytes"):
            assert isinstance(mem.get(col), int), (r["name"], col, mem)
        assert mem["peak_bytes"] > 0
        assert mb["flops"] > 0, (r["name"], mb)
