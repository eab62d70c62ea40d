"""The comparison that decides ``correct``: what the timed program did in
its first steps against what the plain reference does from the same seeded
weights on the same rows. Each number has a limit of its own, in the cell's
file under benchmark/limits/, and is printed beside it in every run."""

from __future__ import annotations

import math
import statistics


def leaf_gap(program_norms, reference_norms) -> float:
    """Worst leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    if len(program_norms) != len(reference_norms):
        return math.inf
    floor = statistics.median(reference_norms)
    worst = 0.0
    for p, r in zip(program_norms, reference_norms):
        if not math.isfinite(p):
            return math.inf
        worst = max(worst, abs(p - r) / max(r, floor))
    return worst


# Where the twin IS the float32 reference there is no rounding noise to
# measure in; the difference is then read in units of this.
NOISE_FLOOR = 1e-3


def noise_units(program_vs_twin: float, twin_vs_reference: float) -> float:
    """The program's first gradient's distance from the twin (the reference
    at the configuration's stated product precision), in units of the
    twin's own distance from the float32 reference: the rounding noise of
    THIS seed's gradient at the stated precision. Both distances swing
    together from seed to seed (the gradient's conditioning); their ratio
    does not (PERF.md section 6)."""
    return program_vs_twin / max(twin_vs_reference, NOISE_FLOOR)


def unlocated_steps(records, adversaries: int) -> int:
    """Steps on which the decoder did not name exactly the live adversaries
    (every step of the run, the window's included)."""
    if adversaries <= 0:
        return 0
    bad = 0
    for rec in records:
        if not (rec.get("det_adv") == adversaries
                and rec.get("det_tp") == adversaries
                and rec.get("located_errors") == adversaries):
            bad += 1
    return bad


def compare(observed: dict, followed, limits: dict) -> list:
    """Rows of (name, value, limit, ok). ``observed``: the program's
    ``losses`` (first steps), ``grad_norms``, ``delta_norms`` (per leaf),
    ``grad_diff`` (:func:`noise_units`), ``unlocated_steps``,
    ``nonfinite_steps``."""
    rows = []

    def row(name, value, limit):
        ok = math.isfinite(value) and value <= limit
        rows.append((name, value, limit, ok))

    for i, (lp, lr) in enumerate(zip(observed["losses"], followed.losses)):
        row(f"loss_gap_step{i + 1}", abs(lp - lr) / abs(lr),
            limits["loss_gap"])
    row("grad_norm_gap", leaf_gap(observed["grad_norms"],
                                  followed.grad_norms),
        limits["grad_norm_gap"])
    row("grad_diff", observed["grad_diff"], limits["grad_diff"])
    row("delta_norm_gap", leaf_gap(observed["delta_norms"],
                                   followed.delta_norms),
        limits["delta_norm_gap"])
    row("unlocated_steps", float(observed["unlocated_steps"]), 0.0)
    row("nonfinite_steps", float(observed["nonfinite_steps"]), 0.0)
    return rows
