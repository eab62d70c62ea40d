"""Serialized-program-size guard for chip-facing jits — now a thin call to
the program linter's constant-bloat rule on the registered programs.

A large serialized program costs compile time and memory out of all
proportion (a 638 MB module compiled for ~27 minutes without finishing —
PERF_HISTORY.md §4). Round 5 found the cyclic step closing over the d-length decode projection,
embedding d×4 bytes of CONSTANT into every serialized module. The bespoke
lowering scaffold that used to live here moved into
draco_tpu/analysis (registry + rules); these tests pin the two historical
guard points — the big-d LM program (d ≈ 3.3 M, where a closed-over (d,)
constant would dominate the module) and the CNN cyclic step — against the
same rule every other registered program now passes in
tests/test_program_lint.py / tools/program_lint.py.
"""

import pytest

pytestmark = pytest.mark.core


def _constant_bloat(name):
    from draco_tpu.analysis import get
    from draco_tpu.analysis.rules import rule_constant_bloat, trace_and_export

    prog = get(name)
    art = trace_and_export(prog.build(), platforms=prog.export_platforms)
    res = rule_constant_bloat(art)
    assert not res.get("skipped"), res
    return res


def test_lm_train_program_has_no_d_sized_constants():
    """The registered big-d LM program (the production K-fused chunked
    driver at a config where d > 3M — tp_step.lint_programs asserts the
    guard stays meaningful). A closed-over (d,) f32 would add 4d bytes;
    the honest module is a few hundred KB; the manifest threshold (2d)
    sits far from both."""
    res = _constant_bloat("lm_fold_big_bf16_many_k2")
    assert res["ok"], (
        f"{res} — a d-sized array is being embedded as a program constant "
        f"(rng.random_projection_factors_in_graph docstring / PERF_HISTORY.md §4)"
    )


def test_cnn_train_step_module_has_no_d_sized_constants():
    """Same guard for the CNN cyclic path (training/step.py)."""
    res = _constant_bloat("cnn_cyclic_step")
    assert res["ok"], (
        f"{res} — a d-sized array is being embedded as a program constant"
    )
