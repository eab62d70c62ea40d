"""Deterministic fault injection — the chaos counterpart of ``attacks.py``.

The adversary schedules (draco_tpu/rng.py) make Byzantine behavior a seeded,
replayable experiment input; this module extends the same discipline to the
faults DRACO's code contract does NOT model (ISSUE 6): non-finite gradients
from faulty-but-honest workers, corruption past the s budget, dead or hung
prefetch threads, and SIGTERM mid-run. A :class:`FaultPlan` is parsed from
``cfg.fault_spec`` — a comma-separated list of ``kind@step`` events — so the
same plan replays bit-for-bit across runs, regimes (eager vs chunked) and
processes, which is what lets ``tools/chaos_run.py`` classify each fault
class as *masked* (final state bitwise-equal to a fault-free run) or
*gracefully degraded* (named error / resumable checkpoint / correct
terminal heartbeat state) instead of "something happened".

Event grammar (``FaultPlan.parse``)::

    kind@step[-end][:w<worker>][:d<seconds>][:every<k>]

    nan_grad@5          worker (seeded draw) emits a NaN gradient at step 5
    inf_grad@5:w2       worker 2 emits an Inf gradient at step 5
    over_budget@7       step 7's adversary row is pushed to s+1 live
                        adversaries (beyond the code's locator budget)
    adversary@5:w2      worker 2 is a LIVE adversary at step 5 (within the
                        code budget — the schedule row is set, the step's
                        cfg.err_mode attack fires through the normal
                        injection path); the declarative time-varying-
                        adversary knob the autopilot scenarios use
    adversary@5-40:w2   ... a sustained adversary EPISODE (steps 5..40)
    drift_grad@5-12     every worker's gradient is scaled by 2^-20 during
                        the window — a finite numerics-drift injection
                        (the whole wire's dynamic range drops a full
                        histogram band, shifting the exponent histogram
                        the ``numerics_drift`` incident detector watches,
                        while staying far from f32/int8-scale underflow;
                        ISSUE 15's autopilot wire_widen chaos cell)
    straggle@5:w3       worker 3 drops (sustained) from step 5 to the end
                        of the run — the heterogeneous-fleet / preempted-
                        worker fault the approx code family (ISSUE 8)
                        absorbs as scheduled erasures, NOT a one-shot
                        crash: the worker's rows simply stop arriving
    straggle@5:w3:d4    ... and recovers after 4 steps (absent 5..8)
    straggle@26-44:w5   ... absent exactly during the window (26..44)
    straggle@20-60:w3:d4:every10
                        CHURN: a recurring episode — a 4-step drop
                        starting at every 10th step of the window
                        (absent 20-23, 30-33, 40-43, 50-53, 60-63)
    prefetch_crash@5    the prefetcher host fn raises InjectedFaultError
                        the first time step 5's data is requested
    prefetch_hang@5:d6  ... sleeps 6 s instead (a stalled worker thread)
    sigterm@5           SIGTERM is raised in-process once step 5 completes
                        (a SECOND due sigterm event while the stop is
                        pending escalates — supervisor.ImmediateStopError)
    ckpt_corrupt@8      consumed by tools/chaos_run.py: flip bytes in the
    ckpt_truncate@8     step-8 checkpoint / truncate it, then resume

Windowed/recurring forms (``@a-b`` + ``:every<k>``) make time-varying
scenarios *declarative*: an event occurs at steps a, a+k, ..., ≤ b
(``:every`` requires a window; a bare ``@a-b`` recurs every step). Every
occurrence behaves exactly like a point event of its kind; host kinds
fire once per occurrence.

In-graph kinds are applied with the same branch-free ``jnp.where`` masking
as ``attacks.inject_plain`` — the fault is part of the compiled program
(config-static: an empty plan compiles the exact unfaulted program, and a
given plan compiles once; no steady-state retraces). Host kinds fire
one-shot through :class:`HostFaultInjector` so a supervised retry
(resilience/supervisor.py) re-executes the request cleanly — exactly how a
transient real-world fault behaves.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional, Tuple

import numpy as np

# in-graph kinds corrupt the step's compiled inputs; schedule kinds mutate
# the seeded host schedules before upload (over_budget → adversary rows,
# straggle → straggler/present rows); host kinds fire in the host loop /
# prefetcher; ckpt kinds are consumed by tools/chaos_run.py
INGRAPH_KINDS = ("nan_grad", "inf_grad", "drift_grad")

# drift_grad's multiplicative payload: 2^-20 moves gradient-scale values
# (~1e-2) down ~6 decades — more than one full exponent-histogram band
# (obs/numerics.EXP_EDGES are 8-16 bins wide), so the numerics_drift
# detector's TV-shift signal goes loud, while every derived quantity
# (int8 per-block scales, squared energies in the decode health) stays in
# the f32 normal range: the injection perturbs NUMERICS, never
# finiteness or decode exactness
DRIFT_GRAD_SCALE = 2.0 ** -20
SCHEDULE_KINDS = ("over_budget", "straggle", "adversary")
HOST_KINDS = ("prefetch_crash", "prefetch_hang", "sigterm")
CKPT_KINDS = ("ckpt_corrupt", "ckpt_truncate")
FAULT_KINDS = INGRAPH_KINDS + SCHEDULE_KINDS + HOST_KINDS + CKPT_KINDS

# kinds whose :d payload is an integer STEP count (dwell), not seconds
_STEP_DWELL_KINDS = ("straggle", "adversary")
# kinds whose target worker is drawn from the seeded stream when no :w
# (drift_grad is fleet-wide — no victim to draw)
_DRAWN_WORKER_KINDS = ("nan_grad", "inf_grad", "straggle", "adversary")

_EVENT_RE = re.compile(r"^(?P<kind>[a-z_]+)@(?P<step>\d+)"
                       r"(?:-(?P<hi>\d+))?"
                       r"(?::w(?P<worker>\d+))?(?::d(?P<dur>[\d.]+))?"
                       r"(?::every(?P<every>\d+))?$")


class InjectedFaultError(RuntimeError):
    """The named error a ``prefetch_crash`` event raises — distinguishable
    from any organic failure, so chaos tests can assert the supervision
    path masked exactly the injected fault and nothing else."""


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    kind: str
    step: int  # 1-based training step the event (window) starts at
    worker: Optional[int] = None  # in-graph/straggle/adversary target row
    # ``:d<n>`` payload. prefetch_hang: seconds the worker thread sleeps
    # (None → 30 s). straggle/adversary: dwell in STEPS per occurrence
    # (None → sustained to the end of the run / a single step).
    duration_s: Optional[float] = None
    # window end (``@a-b``; None = the point event a) and recurrence
    # stride within it (``:every<k>``; 1 = every step of the window)
    step_hi: Optional[int] = None
    every: int = 1
    # position in the parsed spec — keys the one-shot host firing and the
    # seeded worker draw; excluded from equality so a round-tripped spec
    # (with blanks dropped) still compares equal
    index: int = dataclasses.field(default=0, compare=False)

    @property
    def last_step(self) -> int:
        return self.step if self.step_hi is None else self.step_hi

    def occurrences(self, lo: int, hi: int):
        """Occurrence steps within [lo, hi] — a, a+every, ..., <= b."""
        first = self.step
        if lo > first:
            # first occurrence at or after lo on the event's stride grid
            first += ((lo - self.step + self.every - 1)
                      // self.every) * self.every
        return range(first, min(self.last_step, hi) + 1, self.every)

    def occurs_at(self, step: int) -> bool:
        return (self.step <= step <= self.last_step
                and (step - self.step) % self.every == 0)

    def spec(self) -> str:
        """The event's canonical spec token — ``FaultPlan.parse`` of it
        reproduces this event (worker resolved, so the seeded draw is
        pinned explicit on the way out)."""
        tok = f"{self.kind}@{self.step}"
        if self.step_hi is not None:
            tok += f"-{self.step_hi}"
        if self.worker is not None:
            tok += f":w{self.worker}"
        if self.duration_s is not None:
            d = self.duration_s
            tok += f":d{int(d) if float(d).is_integer() else d}"
        if self.every != 1:
            tok += f":every{self.every}"
        return tok


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """An immutable, seed-deterministic set of fault events."""

    events: Tuple[FaultEvent, ...]
    seed: int
    num_workers: int

    @classmethod
    def parse(cls, spec: str, seed: int, num_workers: int) -> "FaultPlan":
        events = []
        for i, tok in enumerate(t.strip() for t in spec.split(",")):
            if not tok:
                continue
            m = _EVENT_RE.match(tok)
            if not m:
                raise ValueError(
                    f"fault_spec event {tok!r} does not match "
                    f"'kind@step[-end][:w<worker>][:d<seconds>]"
                    f"[:every<k>]'"
                )
            kind, step = m.group("kind"), int(m.group("step"))
            if kind not in FAULT_KINDS:
                raise ValueError(
                    f"unknown fault kind {kind!r}; known: "
                    f"{'|'.join(FAULT_KINDS)}"
                )
            if step < 1:
                raise ValueError(f"fault step must be >= 1 in {tok!r}")
            hi = m.group("hi")
            if hi is not None:
                hi = int(hi)
                if hi < step:
                    raise ValueError(
                        f"fault window end {hi} precedes start {step} in "
                        f"{tok!r}"
                    )
                if kind in CKPT_KINDS:
                    raise ValueError(
                        f"{kind} targets one checkpoint; a window makes "
                        f"no sense in {tok!r}"
                    )
            every = m.group("every")
            if every is not None:
                every = int(every)
                if every < 1:
                    raise ValueError(f"every must be >= 1 in {tok!r}")
                if hi is None:
                    raise ValueError(
                        f"':every' without a step window 'a-b' is inert "
                        f"in {tok!r} — recurrence needs a window to recur "
                        f"over"
                    )
            worker = m.group("worker")
            if worker is not None:
                worker = int(worker)
                if worker >= num_workers:
                    raise ValueError(
                        f"fault worker {worker} out of range "
                        f"(num_workers={num_workers}) in {tok!r}"
                    )
            elif kind in _DRAWN_WORKER_KINDS:
                # seeded per-event draw — the same "every participant can
                # recompute it" property as rng.adversary_schedule
                r = np.random.RandomState((seed ^ 0x4641554C) + 7919 * i)
                worker = int(r.randint(num_workers))
            dur = m.group("dur")
            if dur is not None and kind in _STEP_DWELL_KINDS \
                    and float(dur) != int(float(dur)):
                # :d is float SECONDS for host kinds but integer STEPS for
                # straggle/adversary — reject rather than silently floor
                raise ValueError(
                    f"{kind} dwell is a whole number of steps, got "
                    f"d{dur} in {tok!r}"
                )
            events.append(FaultEvent(
                kind=kind, step=step, worker=worker,
                duration_s=float(dur) if dur is not None else None,
                step_hi=hi, every=every or 1, index=i,
            ))
        return cls(events=tuple(events), seed=seed, num_workers=num_workers)

    def spec(self) -> str:
        """Canonical round-trippable spec: ``FaultPlan.parse(plan.spec(),
        seed, n) == plan`` (workers pinned explicit, blanks dropped)."""
        return ",".join(ev.spec() for ev in self.events)

    def of_kind(self, *kinds: str) -> Tuple[FaultEvent, ...]:
        return tuple(e for e in self.events if e.kind in kinds)

    @property
    def ingraph_events(self) -> Tuple[FaultEvent, ...]:
        return self.of_kind(*INGRAPH_KINDS)


@functools.lru_cache(maxsize=64)
def _cached_plan(spec: str, seed: int, num_workers: int) -> FaultPlan:
    return FaultPlan.parse(spec, seed, num_workers)


def plan_from_cfg(cfg) -> Optional[FaultPlan]:
    """The cfg's parsed plan, or None when no faults are configured (the
    common case — every consumer below is an exact no-op then)."""
    if not getattr(cfg, "fault_spec", ""):
        return None
    return _cached_plan(cfg.fault_spec, cfg.seed, cfg.num_workers)


# ---- in-graph injection ----------------------------------------------------


def corrupt_grads(grads, cfg, step):
    """Branch-free NaN/Inf injection into the (n, ...) per-worker gradient
    stack at the plan's in-graph events — IDENTITY (no added ops, no graph
    change) when the plan has none. ``step`` may be a traced scalar (the
    scanned drivers feed it per-iteration), so the comparison runs in-graph
    against the events' tiny static step/worker vectors: the same masked
    ``jnp.where`` discipline as attacks.inject_plain, and no retrace ever
    (the plan is config-static)."""
    plan = plan_from_cfg(cfg)
    if plan is None or not plan.ingraph_events or step is None:
        return grads
    import jax.numpy as jnp

    n = grads.shape[0]
    mask = jnp.zeros((n,), bool)
    payload = jnp.zeros((n,), grads.dtype)
    for ev in plan.ingraph_events:
        s = jnp.asarray(step, jnp.int32)
        if ev.step_hi is None:
            hit = jnp.asarray(ev.step, jnp.int32) == s
        else:
            # windowed/recurring form: occurrence iff inside [a, b] on the
            # event's stride grid — still branch-free, still config-static
            hit = ((s >= ev.step) & (s <= ev.step_hi)
                   & ((s - ev.step) % ev.every == 0))
        if ev.kind == "drift_grad":
            # fleet-wide multiplicative drift (no victim worker): the
            # whole wire's dynamic range collapses during the window
            grads = grads * jnp.where(
                hit, jnp.asarray(DRIFT_GRAD_SCALE, grads.dtype),
                jnp.asarray(1.0, grads.dtype))
            continue
        row = jnp.arange(n) == ev.worker
        mask = mask | (hit & row)
        val = jnp.nan if ev.kind == "nan_grad" else jnp.inf
        payload = jnp.where(hit & row, jnp.asarray(val, grads.dtype),
                            payload)
    shape = (n,) + (1,) * (grads.ndim - 1)
    return jnp.where(mask.reshape(shape), payload.reshape(shape), grads)


def apply_over_budget(adv_schedule: np.ndarray, plan: Optional[FaultPlan],
                      worker_fail: int) -> np.ndarray:
    """Host-side schedule mutation for ``over_budget`` events: the targeted
    steps' adversary rows gain seeded extra workers until s+1 are live —
    one corruption past the code's locator budget, the regime where exact
    recovery is impossible and the guard (resilience/guards.py) is the only
    thing standing between a silently poisoned update and a skipped one.
    Returns the (possibly copied) schedule; the input is never mutated."""
    if plan is None:
        return adv_schedule
    events = plan.of_kind("over_budget")
    if not events:
        return adv_schedule
    adv = np.array(adv_schedule, copy=True)
    n = adv.shape[1]
    want = min(worker_fail + 1, n)
    for ev in events:
        for o in ev.occurrences(1, adv.shape[0] - 1):
            row = adv[o]
            r = np.random.RandomState((plan.seed ^ 0x0B0D6E7) + o)
            order = r.permutation(n)
            for w in order:
                if row.sum() >= want:
                    break
                row[w] = True
            adv[o] = row
    return adv


def apply_adversary(adv_schedule: np.ndarray,
                    plan: Optional[FaultPlan]) -> np.ndarray:
    """Host-side schedule mutation for ``adversary`` events: the targeted
    worker's row goes live-adversarial at every occurrence (for ``:d``
    dwell steps each — default 1), WITHIN the code budget: this is the
    declarative time-varying-adversary knob (an attack EPISODE a fleet
    actually sees), not the beyond-budget ``over_budget`` stressor. The
    step's cfg.err_mode attack then fires through the exact same masked
    injection path as the seeded schedule. Returns the (possibly copied)
    schedule; the input is never mutated."""
    if plan is None:
        return adv_schedule
    events = plan.of_kind("adversary")
    if not events:
        return adv_schedule
    adv = np.array(adv_schedule, copy=True)
    for ev in events:
        dwell = 1 if ev.duration_s is None else int(ev.duration_s)
        for o in ev.occurrences(1, adv.shape[0] - 1):
            adv[o:min(o + dwell, adv.shape[0]), ev.worker] = True
    return adv


def apply_straggle(straggle_schedule: Optional[np.ndarray],
                   plan: Optional[FaultPlan], num_workers: int,
                   n_steps: int) -> Optional[np.ndarray]:
    """Host-side schedule mutation for ``straggle`` events: a SUSTAINED
    per-worker drop — the targeted worker's rows stop arriving from the
    event step until recovery (``:d<dwell>`` steps later; without it, the
    end of the run — the spot/preemptible-instance shape). Unlike the
    one-shot crash kinds this rides the existing seeded straggler/present
    machinery: the drop is an *erasure at a known position* every step it
    lasts, which is exactly the fault surface the approx code family
    (coding/approx.py, ISSUE 8) decodes around with a bounded residual,
    and a scheduled straggler is never an accused worker (obs/forensics).

    ``straggle_schedule``: the seeded (rows, n) drop mask (True = absent)
    or None when cfg configured no stragglers — the mutation materializes
    a fresh all-False table then, sized ``n_steps + 1`` rows like
    rng.straggler_schedule. Passthrough (input returned untouched) when
    the plan has no straggle events."""
    if plan is None:
        return straggle_schedule
    events = plan.of_kind("straggle")
    if not events:
        return straggle_schedule
    if straggle_schedule is None:
        out = np.zeros((n_steps + 1, num_workers), dtype=bool)
    else:
        out = np.array(straggle_schedule, copy=True)
    for ev in events:
        for o in ev.occurrences(1, out.shape[0] - 1):
            if ev.duration_s is not None:
                hi = min(out.shape[0], o + int(ev.duration_s))
            elif ev.step_hi is not None:
                # windowed form without :d — absent exactly DURING the
                # window (each occurrence covers its own step), recovering
                # at window end; only the point form means "to the end of
                # the run" (the spot-instance shape)
                hi = o + 1
            else:
                hi = out.shape[0]
            out[o:hi, ev.worker] = True
    return out


# ---- host-side one-shot triggering ----------------------------------------


class HostFaultInjector:
    """Fires each host fault event exactly once, however many times the
    surrounding request is retried — so a supervised restart
    (resilience/supervisor.py) observes a clean re-execution, the way a
    transient real fault would behave. Inert (every method a cheap no-op)
    when built with ``plan=None``."""

    def __init__(self, plan: Optional[FaultPlan]):
        self._plan = plan
        self._fired: set = set()

    @property
    def active(self) -> bool:
        return self._plan is not None and bool(self._plan.events)

    def _unfired(self, kinds, lo: int, hi: int):
        """(event, occurrence step) of every occurrence of ``kinds`` within
        [lo, hi] that has not fired."""
        if self._plan is None:
            return
        for ev in self._plan.of_kind(*kinds):
            for o in ev.occurrences(lo, hi):
                if (ev.index, o) not in self._fired:
                    yield ev, o

    def _fire(self, kinds, lo: int, hi: Optional[int] = None):
        """First unfired OCCURRENCE of an event of ``kinds`` within
        [lo, hi] (hi defaults to lo), marked fired. Keyed by (event index,
        occurrence step): recurring events fire once per occurrence, and
        two identical point events (e.g. ``sigterm@5,sigterm@5`` — the
        pinned escalation sequence) each fire."""
        for ev, o in self._unfired(kinds, lo, lo if hi is None else hi):
            self._fired.add((ev.index, o))
            return ev
        return None

    def wrap_step_fn(self, fn):
        """Wrap a per-step host data fn (``fn(step) -> x``) so prefetch
        fault events fire when their step's data is first requested."""
        if not self.active:
            return fn

        def wrapped(step):
            self._maybe_prefetch_fault(step, step)
            return fn(step)

        return wrapped

    def wrap_range_fn(self, fn):
        """Wrap a chunk-range host data fn (``fn(start, k) -> x``) so
        prefetch fault events fire when the chunk containing their step is
        first requested."""
        if not self.active:
            return fn

        def wrapped(start, k):
            self._maybe_prefetch_fault(start, start + k - 1)
            return fn(start, k)

        return wrapped

    def _maybe_prefetch_fault(self, lo: int, hi: int) -> None:
        ev = self._fire(("prefetch_crash", "prefetch_hang"), lo, hi)
        if ev is None:
            return
        if ev.kind == "prefetch_crash":
            raise InjectedFaultError(
                f"injected prefetch_crash at step {ev.step} "
                f"(fault plan event)"
            )
        import time

        time.sleep(30.0 if ev.duration_s is None else ev.duration_s)

    def holds(self, step: int) -> bool:
        """True where the plan names ``step`` for a host event that the
        eager loop has to meet with nothing in flight behind it: a sigterm
        still due by ``step`` (the stop then lands on the step the plan
        names) or a prefetch fault on ``step + 1``'s data (it is raised
        with ``step`` booked). Consumes nothing."""
        if self._plan is None:
            return False
        return any(self._unfired(("sigterm",), 1, step)) or any(
            ev.occurs_at(step + 1) for ev in self._plan.of_kind(
                "prefetch_crash", "prefetch_hang"))

    def sigterm_due(self, end_step: int) -> bool:
        """True once, when a sigterm event's step has been reached — the
        loop then raises the real signal in-process so the registered
        GracefulStop handler (resilience/supervisor.py) runs the genuine
        preemption path."""
        return self._fire(("sigterm",), 1, end_step) is not None


NULL_INJECTOR = HostFaultInjector(None)
