"""Adaptive coding autopilot — incident-driven runtime control (ROADMAP
item 5's payoff; importable WITHOUT jax, like the rest of the host side).

Every run used to execute one fixed (code family, redundancy, wire dtype)
point chosen at launch. The committed straggler study shows why that is
wrong for a time-varying fleet: exact cyclic r=3 wastes ~2× fleet compute
on a quiet fleet, while approx r=1.5 is the ONLY feasible family at 37.5%
drop rates — and neither can defend the other's regime. This module closes
the loop: a host-side policy engine that consumes the typed, attributed
incident stream (obs/incidents.py, PR 13) at chunk boundaries and emits
**remediations**:

  quarantine   a trust-collapsed worker is excluded via the present-mask
               schedule (its rows become erasures at a known position —
               the decode budget absorbs it, the aggregate never sees it)
               and the effective error budget is re-reported
  dial_down    sustained ``straggle``/``starvation`` episodes with the
               adversary signals quiet: swap exact cyclic r=2s+1 down to
               the approx family at ``r_low`` (arXiv:1905.05383 /
               arXiv:2006.09638 ground the residual bound the dial
               accepts — the decode_residual_bound column referees it
               per step)
  dial_up      the straggle evidence stays clear: swap back to the exact
               base family, restoring the Byzantine certificate
  readmit      a quarantined worker earns parole after a sustained clean
               window (its ledger trust resets to ``parole_trust`` so it
               is judged on fresh evidence)
  shadow_off   a ``numerics_drift`` episode drops the shadow wire dtype

Hysteresis both directions, like the detectors: every dial counts
consecutive chunk boundaries of evidence, so a single noisy window can
neither dial down nor dial back up, and ``max_swaps`` hard-caps regime
flapping.

Family/shape changes are **warm program swaps**: the :class:`Autopilot`
caches each regime's built setup, so switching INTO a new regime compiles
exactly that regime's program once (the compile sentinel counts it under
its own ``train_many@<regime>`` label) and returning to a previously-run
regime reuses its jitted executable — steady state within a regime stays
0-retrace under ``compile_guard="raise"``. Quarantine/readmit touch only
host schedule arrays: no program change at all.

Every decision is itself an attributed ``remediation`` event appended to
the run's ``incidents.jsonl`` (same stream, same seq counter — the
decision names the incident episode that triggered it) and a ``control``
block in status.json, so the control loop is as observable as the faults
it reacts to. ``tools/autopilot_study.py`` commits the proof: under a
time-varying adversary + churn scenario the autopilot reaches the target
loss on less fleet compute than every fixed configuration.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, Optional

# boundary-hysteresis policy knobs; every key overridable per run via
# ``cfg.autopilot_policy`` ("key=value,..." — parse_policy validates)
DEFAULT_POLICY: Dict[str, float] = {
    # quarantine: a PRESENT worker whose EW trust (obs/forensics) sits
    # under the floor while a trust incident names it
    "trust_floor": 0.5,
    # max workers quarantined at once; -1 derives it from the code's own
    # erasure budget minus the configured straggler load and one unit of
    # churn headroom (see _quarantine_budget)
    "quarantine_budget": -1.0,
    # boundaries a quarantined worker waits before parole, and the trust
    # its ledger row resets to on re-admission
    "readmit_boundaries": 8.0,
    "parole_trust": 0.75,
    # dial-down: consecutive boundaries with an open straggle/starvation
    # episode AND this many adversary-quiet boundaries
    "dial_down_boundaries": 2.0,
    "clean_boundaries": 2.0,
    # dial-up: consecutive boundaries with the straggle evidence clear
    "dial_up_boundaries": 3.0,
    # the approx redundancy the dial-down accepts (fleet compute per step
    # drops from r=2s+1 to this; the analytic residual bound prices it)
    "r_low": 1.5,
    # hard cap on regime swaps per run — the anti-flap backstop on top of
    # the boundary hysteresis
    "max_swaps": 8.0,
    # boundaries of numerics_drift before the shadow dtype is dropped
    "shadow_off_boundaries": 1.0,
    # REAL-wire dial (ISSUE 15): boundaries of numerics_drift /
    # decode_residual evidence before the wire dtype widens one f32-ward
    # step (int8 → bf16 → f32), and boundaries of clean evidence before it
    # narrows one step back toward the configured dtype
    "wire_widen_boundaries": 1.0,
    "wire_narrow_boundaries": 4.0,
    # streaming-segment dial (ISSUE 16): boundaries of straggle evidence
    # before the wire segment count doubles (decode-on-arrival shortens
    # the tail a slow worker's last byte adds), capped at segments_max;
    # boundaries of straggle-quiet evidence before it halves back toward
    # the configured count (never past it). The segment dial fires BEFORE
    # the family dial-down — it keeps the exactness certificate, so it is
    # the cheap first rung of the straggler escalation ladder.
    "segments_up_boundaries": 1.0,
    "segments_down_boundaries": 4.0,
    "segments_max": 4.0,
    # tree-fanout dial (ISSUE 17): SECOND rung of the straggler ladder —
    # once the segment dial is maxed and straggle evidence persists, the
    # tree fanout halves (each combine node waits on fewer children, so a
    # slow child stalls a smaller subtree), never past fanout_min; sustained
    # straggle-quiet evidence doubles it back toward the configured fanout.
    # Same family, warm cached program swaps under `_g{fanout}` tags. Only
    # live when the run was launched with --topology tree.
    "fanout_down_boundaries": 2.0,
    "fanout_up_boundaries": 4.0,
    "fanout_min": 2.0,
}

# incident types that count as ADVERSARY evidence: any of these open (or
# new accusations landing in the ledger) vetoes a dial-down and resets the
# clean-window counter
_ADVERSARY_TYPES = ("trust", "guard", "nonfinite", "decode_residual")
_STRAGGLE_TYPES = ("straggle", "starvation")


def parse_policy(spec: str) -> Dict[str, float]:
    """``"r_low=1.2,clean_boundaries=3"`` -> override dict; unknown keys
    are config-time errors (DEFAULT_POLICY is the contract)."""
    out: Dict[str, float] = {}
    for item in (spec or "").split(","):
        item = item.strip()
        if not item:
            continue
        try:
            key, val = item.split("=", 1)
            key = key.strip()
            fval = float(val)
        except ValueError:
            raise ValueError(
                f"autopilot policy {item!r} is not '<key>=<float>'")
        if key not in DEFAULT_POLICY:
            raise ValueError(
                f"unknown autopilot policy key {key!r} (known: "
                f"{', '.join(sorted(DEFAULT_POLICY))})")
        out[key] = fval
    return out


@dataclasses.dataclass(frozen=True)
class Regime:
    """One point of the (family, redundancy, wire dtype) dial. For cyclic
    ``redundancy`` is the per-worker load r = 2s+1; for approx it is the
    fractional code_redundancy. ``wire_dtype`` (ISSUE 15) is the REAL
    wire's materialized dtype — the wire_widen/wire_narrow remediations
    move it along the f32 ↔ bf16 ↔ int8 ladder as warm cached program
    swaps, exactly like the family dial."""

    approach: str
    redundancy: float
    shadow_wire: str
    wire_dtype: str = "f32"
    # streaming segmented wire (ISSUE 16): the segments_up/segments_down
    # remediations move this along 1 ↔ 2 ↔ 4 ... (capped by policy
    # segments_max) as warm cached program swaps
    wire_segments: int = 1
    # tree topology (ISSUE 17): the leaf-group fan-in, 0 = flat. The
    # fanout_down/fanout_up remediations halve/double it along
    # base ↔ ... ↔ fanout_min as warm cached program swaps
    tree_fanout: int = 0

    @property
    def tag(self) -> str:
        t = f"{self.approach}_r{self.redundancy:g}"
        if self.shadow_wire != "off":
            t += f"_{self.shadow_wire}"
        if self.wire_dtype != "f32":
            t += f"_wire{self.wire_dtype}"
        if self.wire_segments != 1:
            t += f"_seg{self.wire_segments}"
        if self.tree_fanout:
            t += f"_g{self.tree_fanout}"
        return t

    def as_dict(self) -> dict:
        return {"approach": self.approach, "redundancy": self.redundancy,
                "shadow_wire": self.shadow_wire,
                "wire_dtype": self.wire_dtype,
                "wire_segments": self.wire_segments,
                "tree_fanout": self.tree_fanout, "tag": self.tag}


def base_regime(cfg) -> Regime:
    r = (2 * cfg.worker_fail + 1 if cfg.approach == "cyclic"
         else float(cfg.code_redundancy))
    fanout = (int(cfg.tree_fanout)
              if getattr(cfg, "topology", "flat") == "tree" else 0)
    return Regime(cfg.approach, float(r), cfg.shadow_wire,
                  getattr(cfg, "wire_dtype", "f32"),
                  int(getattr(cfg, "wire_segments", 1)), fanout)


def regime_cfg(base_cfg, regime: Regime, quarantined: int = 0):
    """The TrainConfig a regime's program is built from. Schedule/host
    fault kinds are stripped (they were applied to the host schedules at
    launch and never live inside a compiled program); in-graph kinds stay
    so nan/inf injection survives a swap. The approx regime drops the
    Byzantine knobs (validate: no certificate) and sizes its straggler
    design point to cover the quarantined workers plus churn headroom."""
    from draco_tpu.resilience.faults import INGRAPH_KINDS, plan_from_cfg

    kw = {"approach": regime.approach, "shadow_wire": regime.shadow_wire,
          "wire_dtype": regime.wire_dtype,
          "wire_segments": regime.wire_segments}
    # tree topology rides the regime (ISSUE 17): a dialed fanout keeps the
    # family's tree shape; depth re-derives (auto) when the fanout moved
    # off the launch value, since the pinned level count may be infeasible
    # at the new group count
    if regime.tree_fanout:
        kw["topology"] = "tree"
        kw["tree_fanout"] = regime.tree_fanout
        if regime.tree_fanout != int(getattr(base_cfg, "tree_fanout", 0)):
            kw["tree_levels"] = 0
    else:
        kw["topology"] = "flat"
    plan = plan_from_cfg(base_cfg)
    if plan is not None:
        kw["fault_spec"] = ",".join(ev.spec() for ev in plan.events
                                    if ev.kind in INGRAPH_KINDS)
    if regime.approach == "approx":
        n = base_cfg.num_workers
        alpha = max(
            base_cfg.straggler_alpha,
            min(0.9, (quarantined + base_cfg.straggle_count + 1) / n),
        )
        kw.update(worker_fail=0, adversary_count=0, redundancy="shared",
                  code_redundancy=float(regime.redundancy),
                  assignment_scheme="pairwise", straggler_alpha=alpha)
    elif regime.approach == "cyclic":
        kw.update(worker_fail=base_cfg.worker_fail,
                  adversary_count=base_cfg.adversary_count,
                  redundancy=base_cfg.redundancy)
    return dataclasses.replace(base_cfg, **kw)


class Autopilot:
    """The policy engine: :meth:`act` runs at every chunk-boundary flush
    (control/engine.py), reading the incident engine + accusation ledger
    the heartbeat already feeds, and actuating through the engine's client
    (quarantine/readmit = schedule writes; regime swaps = warm cached
    program switches)."""

    def __init__(self, cfg, heartbeat, policy: Optional[dict] = None,
                 dim: Optional[int] = None):
        self.cfg = cfg
        self.heartbeat = heartbeat
        self.incidents = heartbeat.incidents  # IncidentEngine (required)
        self.policy = dict(DEFAULT_POLICY)
        self.policy.update(policy or {})
        self.base = base_regime(cfg)
        self.regime = self.base
        self.dim = dim
        self._setups: dict = {}  # Regime -> built setup (warm swap cache)
        # worker -> {"step", "boundaries", "trigger"} while quarantined
        self.quarantined: Dict[int, dict] = {}
        # readmitted workers whose restored schedule has not yet SHOWN
        # them present (the engine's two-chunk assembly pipeline lags the
        # remediation): they stay excluded from the straggle detector
        # until a present record lands, else parole would fire a spurious
        # straggle incident
        self._paroled: Dict[int, int] = {}
        self.remediations: list = []
        self.swaps = 0
        self._adv_quiet = 0
        self._strag_hot = 0
        self._strag_quiet = 0
        self._drift_hot = 0
        self._wire_hot = 0
        self._wire_quiet = 0
        self._prev_accused = 0.0

    def attach(self, client) -> None:
        """Engine-construction hook: seed the warm-swap cache with the
        loop's base setup and, when the autopilot already sits in a
        non-base regime (a later run() call on the same Trainer), switch
        the fresh client onto it before the first dispatch."""
        setup = getattr(client, "setup", None)
        if setup is not None:
            self._setups.setdefault(self.base, setup)
        if self.regime != self.base and self.regime in self._setups:
            client.switch_regime(
                self._setups[self.regime],
                f"{client.BASE_LABEL}@{self.regime.tag}")

    # ---- evidence --------------------------------------------------------
    def _quarantine_budget(self) -> int:
        b = self.policy["quarantine_budget"]
        if b >= 0:
            return int(b)
        cfg = self.cfg
        if self.base.approach == "cyclic":
            # erasure-only budget e <= 2s, minus the configured straggler
            # load, minus one unit of churn headroom
            return max(0, 2 * cfg.worker_fail - cfg.straggle_count - 1)
        return max(0, math.ceil(cfg.straggler_alpha * cfg.num_workers)
                   - cfg.straggle_count - 1)

    def _open(self) -> Dict[str, dict]:
        return {e["type"]: e for e in self.incidents.open_episodes()}

    # ---- actuation -------------------------------------------------------
    def act(self, step: int, engine) -> None:
        """One chunk-boundary decision pass. ``engine`` is the live
        ChunkedEngine; its client is the actuation surface."""
        client = engine.client
        # parole completes when the readmitted worker is OBSERVED present
        # again (the newest record's masks) — only then does its absence
        # become telemetry for the straggle detector
        masks = self.incidents.current_masks
        for w in list(self._paroled):
            if masks is not None and masks["present"][w]:
                self.incidents.quarantined.discard(w)
                del self._paroled[w]
        open_eps = self._open()
        ledger = self.incidents.ledger

        # adversary-quiet window: no adversary-class episode open and no
        # NEW accusations since the last boundary
        accused = float(sum(ledger.accused)) if ledger is not None else 0.0
        adversary_evidence = (
            any(t in open_eps for t in _ADVERSARY_TYPES)
            or accused > self._prev_accused)
        self._prev_accused = accused
        self._adv_quiet = 0 if adversary_evidence else self._adv_quiet + 1

        straggle_evidence = any(t in open_eps for t in _STRAGGLE_TYPES)
        self._strag_hot = self._strag_hot + 1 if straggle_evidence else 0
        self._strag_quiet = 0 if straggle_evidence else self._strag_quiet + 1
        self._drift_hot = (self._drift_hot + 1
                           if "numerics_drift" in open_eps else 0)
        # REAL-wire evidence (ISSUE 15): numerics drift on the wire columns
        # or decode-residual drift (residual-near-bound / rel-tol crossing)
        # argues the narrow dtype's noise floor is no longer safe
        wire_evidence = ("numerics_drift" in open_eps
                        or "decode_residual" in open_eps)
        self._wire_hot = self._wire_hot + 1 if wire_evidence else 0
        self._wire_quiet = 0 if wire_evidence else self._wire_quiet + 1

        self._maybe_quarantine(step, client, open_eps, ledger)
        self._maybe_readmit(step, client, ledger)
        if getattr(client, "can_swap", True) \
                and self.swaps < self.policy["max_swaps"]:
            from draco_tpu.obs.numerics import WIRE_WIDEN, narrow_toward

            if (self.regime.wire_dtype != "f32"
                    and self._wire_hot
                    >= self.policy["wire_widen_boundaries"]):
                # wire_widen (ISSUE 15): the dial moves the REAL wire one
                # f32-ward step — a warm cached program swap like every
                # other regime change; the narrow dtype's noise floor is
                # implicated by the open drift/residual episode
                trigger = (open_eps.get("numerics_drift")
                           or open_eps.get("decode_residual"))
                target = dataclasses.replace(
                    self.regime,
                    wire_dtype=WIRE_WIDEN[self.regime.wire_dtype])
                self._swap(step, client, target, "wire_widen", trigger, {
                    "wire_evidence_boundaries": self._wire_hot,
                    "wire_dtype_before": self.regime.wire_dtype,
                    "wire_dtype_after": target.wire_dtype,
                })
            elif (self.regime.wire_dtype != self.base.wire_dtype
                  and self._wire_quiet
                  >= self.policy["wire_narrow_boundaries"]
                  and narrow_toward(self.regime.wire_dtype,
                                    self.base.wire_dtype)
                  != self.regime.wire_dtype):
                # wire_narrow: sustained clean evidence earns one step back
                # toward the configured narrow dtype (never past it)
                trigger = self._last_cleared(("numerics_drift",
                                              "decode_residual"))
                target = dataclasses.replace(
                    self.regime,
                    wire_dtype=narrow_toward(self.regime.wire_dtype,
                                             self.base.wire_dtype))
                self._swap(step, client, target, "wire_narrow", trigger, {
                    "wire_quiet_boundaries": self._wire_quiet,
                    "wire_dtype_before": self.regime.wire_dtype,
                    "wire_dtype_after": target.wire_dtype,
                })
            elif self._drift_hot >= self.policy["shadow_off_boundaries"] \
                    and self.regime.shadow_wire != "off":
                self._swap(step, client,
                           dataclasses.replace(self.regime,
                                               shadow_wire="off"),
                           "shadow_off", open_eps.get("numerics_drift"),
                           {"drift_boundaries": self._drift_hot})
            elif (self.regime.approach in ("cyclic", "approx")
                  and self._strag_hot
                  >= self.policy["segments_up_boundaries"]
                  and self.regime.wire_segments
                  < int(self.policy["segments_max"])):
                # segments_up (ISSUE 16): the first rung of the straggler
                # ladder — double the wire segment count so the aggregator
                # decodes segments on arrival instead of waiting for the
                # slowest worker's LAST byte. Keeps the family (and its
                # exactness certificate); the family dial-down only fires
                # once the segment dial is maxed out.
                trigger = (open_eps.get("straggle")
                           or open_eps.get("starvation"))
                target = dataclasses.replace(
                    self.regime,
                    wire_segments=min(max(2 * self.regime.wire_segments, 2),
                                      int(self.policy["segments_max"])))
                self._swap(step, client, target, "segments_up", trigger, {
                    "straggle_boundaries": self._strag_hot,
                    "wire_segments_before": self.regime.wire_segments,
                    "wire_segments_after": target.wire_segments,
                })
            elif (self.regime.tree_fanout
                  and self._strag_hot
                  >= self.policy["fanout_down_boundaries"]
                  and self.regime.tree_fanout % 2 == 0
                  and self.regime.tree_fanout // 2
                  >= int(self.policy["fanout_min"])
                  and self._fanout_ok(self.regime.tree_fanout // 2)):
                # fanout_down (ISSUE 17): the straggler ladder's SECOND
                # rung — the segment dial is maxed (or spent) and straggle
                # persists, so the tree fanout halves: every combine node
                # waits on half the children, shrinking the subtree one
                # slow worker can stall. Same family, same certificate;
                # a warm cached program swap under the `_g{fanout}` tag.
                trigger = (open_eps.get("straggle")
                           or open_eps.get("starvation"))
                target = dataclasses.replace(
                    self.regime, tree_fanout=self.regime.tree_fanout // 2)
                self._swap(step, client, target, "fanout_down", trigger, {
                    "straggle_boundaries": self._strag_hot,
                    "tree_fanout_before": self.regime.tree_fanout,
                    "tree_fanout_after": target.tree_fanout,
                })
            elif (self.regime.approach == "cyclic"
                  and self._strag_hot >= self.policy["dial_down_boundaries"]
                  and self._adv_quiet >= self.policy["clean_boundaries"]
                  and self._dial_down_allowed(step)):
                trigger = (open_eps.get("straggle")
                           or open_eps.get("starvation"))
                target = Regime("approx", float(self.policy["r_low"]),
                                self.regime.shadow_wire,
                                self.regime.wire_dtype,
                                tree_fanout=self.regime.tree_fanout)
                self._swap(step, client, target, "dial_down", trigger, {
                    "straggle_boundaries": self._strag_hot,
                    "adversary_quiet_boundaries": self._adv_quiet,
                    "fleet_load_before": self.regime.redundancy,
                    "fleet_load_after": target.redundancy,
                    # what the dial accepts: bounded decode error instead
                    # of exactness — refereed per step by the
                    # decode_residual <= decode_residual_bound certificate
                    "accepted_bound": "optimal-decoding residual bound "
                                      "(arXiv:2006.09638), per-step column "
                                      "decode_residual_bound",
                })
            elif (self.regime.approach == "approx"
                  and self.base.approach == "cyclic"
                  and self._strag_quiet >= self.policy["dial_up_boundaries"]):
                trigger = self._last_cleared(_STRAGGLE_TYPES)
                self._swap(step, client,
                           dataclasses.replace(self.base,
                                               shadow_wire=self.regime
                                               .shadow_wire,
                                               wire_dtype=self.regime
                                               .wire_dtype,
                                               wire_segments=self.regime
                                               .wire_segments),
                           "dial_up", trigger, {
                               "straggle_quiet_boundaries":
                                   self._strag_quiet,
                               "restores": "exact decode + Byzantine "
                                           "certificate",
                           })
            elif (self.regime.tree_fanout and self.base.tree_fanout
                  and self.regime.tree_fanout < self.base.tree_fanout
                  and self._strag_quiet
                  >= self.policy["fanout_up_boundaries"]):
                # fanout_up: sustained straggle-quiet evidence doubles the
                # fanout back toward the configured one (never past it) —
                # wider groups restore the per-group budget s_g and cut
                # the level count on a quiet fleet
                trigger = self._last_cleared(_STRAGGLE_TYPES)
                target = dataclasses.replace(
                    self.regime,
                    tree_fanout=min(2 * self.regime.tree_fanout,
                                    self.base.tree_fanout))
                self._swap(step, client, target, "fanout_up", trigger, {
                    "straggle_quiet_boundaries": self._strag_quiet,
                    "tree_fanout_before": self.regime.tree_fanout,
                    "tree_fanout_after": target.tree_fanout,
                })
            elif (self.regime.wire_segments > self.base.wire_segments
                  and self._strag_quiet
                  >= self.policy["segments_down_boundaries"]):
                # segments_down: sustained straggle-quiet evidence halves
                # the segment count back toward the configured one (never
                # past it) — single-message wires pay no per-segment
                # locator overhead on a quiet fleet
                trigger = self._last_cleared(_STRAGGLE_TYPES)
                target = dataclasses.replace(
                    self.regime,
                    wire_segments=max(self.regime.wire_segments // 2,
                                      self.base.wire_segments))
                self._swap(step, client, target, "segments_down", trigger, {
                    "straggle_quiet_boundaries": self._strag_quiet,
                    "wire_segments_before": self.regime.wire_segments,
                    "wire_segments_after": target.wire_segments,
                })
        self.heartbeat.set_control(self.status_block())

    def _fanout_ok(self, fanout: int) -> bool:
        """A dialed fanout must keep a buildable tree (divisibility, ≥2
        groups) and — for cyclic — a per-group budget s_g that still
        carries the DECLARED adversary load (the worst case lands every
        adversary in one leaf group, config.validate's rule mirrored
        dynamically)."""
        from draco_tpu.coding.topology import group_worker_fail, tree_plan

        try:
            tree_plan(self.cfg.num_workers, fanout)
        except ValueError:
            return False
        if self.regime.approach == "cyclic":
            s_g = group_worker_fail(fanout, self.cfg.worker_fail)
            if self.cfg.num_adversaries > s_g:
                return False
        return True

    def _dial_down_allowed(self, step: int) -> bool:
        """The approx family cannot express a Byzantine attack — the
        simulation injects nothing there, which is exactly why
        config.validate rejects adversary/over_budget fault kinds under
        approach=approx. The dial must mirror that rule dynamically: a
        run whose DECLARED scenario still schedules Byzantine activity
        beyond ``step`` (a live seeded adversary count, or a fault-plan
        adversary/over_budget occurrence ahead) may not dial into a
        regime where those events would be silently inert."""
        from draco_tpu.resilience.faults import plan_from_cfg

        if self.cfg.num_adversaries > 0:
            return False
        plan = plan_from_cfg(self.cfg)
        if plan is not None:
            for ev in plan.of_kind("adversary", "over_budget"):
                if ev.last_step > step:
                    return False
        return True

    def _maybe_quarantine(self, step, client, open_eps, ledger) -> None:
        if ledger is None:
            return
        trigger = open_eps.get("trust")
        if trigger is None:
            return  # the decision must have an incident to attribute to
        floor = self.policy["trust_floor"]
        candidates = sorted(
            (w for w in range(ledger.n)
             if ledger.trust[w] < floor and w not in self.quarantined),
            key=lambda w: ledger.trust[w])
        if not candidates:
            return
        if len(self.quarantined) >= self._quarantine_budget():
            return  # out of erasure budget: the guard keeps the run safe
        w = candidates[0]
        client.quarantine(w, from_step=step + 1)
        self.incidents.quarantined.add(w)
        self.quarantined[w] = {"step": step, "boundaries": 0,
                               "trigger": trigger}
        self._remediate("quarantine", step, trigger, worker=w, evidence={
            "trust": round(ledger.trust[w], 4), "trust_floor": floor,
            # the s rebudget: the worker is an erasure now — report the
            # budget the decode is left with
            "quarantined_total": len(self.quarantined),
            "erasure_budget": self._quarantine_budget(),
            # the engine's next chunk was assembled before this boundary:
            # the schedule write lands at effective_step, the wire sees
            # it one chunk later (PERF_HISTORY.md §16)
            "wire_lag": "one assembled chunk",
        })

    def reapply_quarantines(self, schedule) -> None:
        """Re-stamp every ACTIVE quarantine onto a freshly (re)generated
        present-mask schedule — Trainer._ensure_schedules rebuilds the
        tables when a block-wise run() overruns them, and a regenerated
        table must not silently re-admit a worker the policy still holds
        excluded."""
        for w in self.quarantined:
            schedule[:, w] = True

    def _maybe_readmit(self, step, client, ledger) -> None:
        for w in list(self.quarantined):
            info = self.quarantined[w]
            info["boundaries"] += 1
            if info["boundaries"] < self.policy["readmit_boundaries"] \
                    or self._adv_quiet < self.policy["clean_boundaries"]:
                continue
            client.readmit(w, from_step=step + 1)
            # stays in incidents.quarantined until observed present again
            self._paroled[w] = step
            if ledger is not None:
                ledger.forgive(w, self.policy["parole_trust"])
            del self.quarantined[w]
            self._remediate("readmit", step, info["trigger"], worker=w,
                            evidence={
                                "quarantined_boundaries": info["boundaries"],
                                "adversary_quiet_boundaries":
                                    self._adv_quiet,
                                "parole_trust": self.policy["parole_trust"],
                            })

    def _swap(self, step, client, target: Regime, action, trigger,
              evidence) -> None:
        setup = self._setups.get(target)
        warm = setup is not None
        if setup is None:
            # provision the regime for the WORST quarantine load the
            # policy can ever reach (_quarantine_budget), not the current
            # count: the setup is cached per regime, and a later re-entry
            # with more workers quarantined must still sit inside the
            # approx straggler design point it was built with
            setup = client.build_setup(
                regime_cfg(self.cfg, target, self._quarantine_budget()))
            self._setups[target] = setup
        label = (client.BASE_LABEL if target == self.base
                 else f"{client.BASE_LABEL}@{target.tag}")
        client.switch_regime(setup, label)
        # keep the engine's dispatch-span segment tag in step with the
        # regime actually dispatched (segments_up/segments_down swaps)
        client.wire_segments = target.wire_segments
        prev, self.regime = self.regime, target
        self.swaps += 1
        # counters reset so the NEW regime earns its own evidence window
        self._strag_hot = self._strag_quiet = self._drift_hot = 0
        self._wire_hot = self._wire_quiet = 0
        try:
            # the wire ledger is per-family: re-stamp the status block
            from draco_tpu.obs import numerics as numerics_mod

            dim = getattr(setup, "dim", None) or self.dim
            if dim:
                self.heartbeat.set_wire(numerics_mod.wire_ledger(
                    regime_cfg(self.cfg, target, len(self.quarantined)),
                    dim))
        except Exception:
            pass
        ev = dict(evidence or {})
        ev["executable"] = "reused" if warm else "compiled"
        self._remediate(action, step, trigger,
                        regime=target, evidence=ev,
                        regime_from=prev)

    def _last_cleared(self, types) -> Optional[dict]:
        """The most recently CLOSED episode of ``types`` — the attribution
        for a recovery decision (the condition whose clearing earned it)."""
        for ep in reversed(self.incidents.episodes):
            if ep["type"] in types:
                return dict(ep, cleared=True)
        return None

    # ---- reporting -------------------------------------------------------
    def _remediate(self, action, step, trigger, worker=None, regime=None,
                   evidence=None, regime_from=None) -> None:
        rem = {
            "action": action, "step": int(step),
            # wall-clock stamp (ISSUE 19): MTTR = remediation ts − onset
            # ts, joined offline by obs/fleet — stamped here too so the
            # ``control`` status block's ``last`` carries it even though
            # the incidents stream stamps its own copy per line
            "ts": time.time(),
            "effective_step": int(step) + 1,
            "worker": worker,
            "regime": regime.as_dict() if regime is not None else None,
            "regime_from": (regime_from.as_dict()
                            if regime_from is not None else None),
            "trigger": ({
                "type": trigger.get("type"),
                "severity": trigger.get("severity"),
                "onset_step": trigger.get("onset_step"),
                "workers": trigger.get("workers"),
                "cleared": bool(trigger.get("cleared", False)),
            } if trigger else None),
            "evidence": dict(evidence or {}),
        }
        self.remediations.append(rem)
        self.incidents.remediation(rem)
        self.heartbeat.set_control(self.status_block())

    def status_block(self) -> dict:
        """The ``control`` status.json block (additive under schema 4)."""
        return {
            "autopilot": "on",
            "regime": self.regime.as_dict(),
            "base_regime": self.base.tag,
            "swaps": self.swaps,
            "quarantined": sorted(self.quarantined),
            "remediations": len(self.remediations),
            "last": (self.remediations[-1] if self.remediations else None),
        }


def make_autopilot(cfg, heartbeat, dim: Optional[int] = None
                   ) -> Optional[Autopilot]:
    """The one construction rule both production loops share: an autopilot
    only when ``cfg.autopilot == "on"`` AND the incident engine is live on
    this process (the sensing layer it actuates on — config.validate pins
    the dependency, this guards the non-main multihost processes)."""
    if getattr(cfg, "autopilot", "off") != "on" \
            or heartbeat.incidents is None:
        return None
    return Autopilot(cfg, heartbeat,
                     policy=parse_policy(getattr(cfg, "autopilot_policy",
                                                 "")),
                     dim=dim)
