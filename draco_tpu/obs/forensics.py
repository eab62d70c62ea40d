"""Per-worker Byzantine forensics: packed accusation masks + the host ledger.

DRACO's value proposition is *identifying and removing* adversarial workers
(PAPER.md), yet until this module the telemetry folded the per-worker
``flagged`` accusation vectors both codes already compute in-graph
(coding/cyclic._locate_v, coding/repetition.majority_vote) down to scalar
detection counts. This module keeps the attribution:

In-graph half — :func:`pack_mask_columns` packs each per-step (n,) bool mask
(the accusation set, the present set, and the seeded-adversary ground truth)
into ``ceil(n/32)`` uint32 words bit-cast to float32, so they ride the
existing (K, m) float32 metric block with ZERO extra device fetches:

  * n <= 32  -> one packed column per mask kind
  * n <= 64  -> two columns per kind (word 0 = workers 0..31, word 1 = 32..63)
  * n  > 64  -> a named error (the schema stays bounded; grow MAX_WORKERS
                together with a third column family when a real mesh needs it)

Host half — the float payload is bit-identical to the uint32 word all the way
to the host fetch (bitcast + pure data movement; XLA never runs arithmetic on
it), but a Python ``float()`` / JSON round trip is NOT bit-safe: words whose
bit pattern is a float32 NaN (any mask with workers 23..30 all accused and
worker 31 variable) would collapse to a payload-free ``NaN`` in
metrics.jsonl. :func:`record_value` therefore re-views mask columns as
integers at record-materialization time (utils/metrics.DeferredMetricWriter
and both eager loops route every record value through it), so the JSONL
carries exact integer words and :func:`unpack_bits` is pure int bit-twiddling
— usable from jax-free tools (tools/forensics_report.py).

:class:`AccusationLedger` folds the per-step masks (at flush boundaries, via
the existing DeferredMetricWriter -> RunHeartbeat observer hook — no new
fetch, no new callback) into per-worker counters (accused / present /
true-positive / false-positive vs the seeded schedule), an
exponentially-weighted trust score, and attack **episodes** — maximal runs of
consecutive accusations per worker, so "worker 3 was adversarial for steps
120..400" is a first-class object. Absence is an erasure, never evidence: an
absent worker is neither accused nor exonerated, so a straggler cannot open,
extend toward closure, or close an episode.

This module is importable WITHOUT jax (the pack side imports it lazily), the
same discipline as the rest of draco_tpu/obs — tools fold committed
artifacts on machines with no accelerator stack.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

MASK_WORD_BITS = 32
MAX_WORKERS = 64

# column-name stem per packed mask kind; a step's forensics columns are
# f"{MASK_PREFIX}{kind}{word}" for word in range(num_mask_words(n))
MASK_PREFIX = "wmask_"
MASK_KINDS = ("accused", "present", "adv")

# EW trust-score step: trust <- (1-alpha)*trust + alpha*(not accused), only
# on steps the worker is present. 0.2 makes ~10 consecutive accusations pull
# a fresh worker below 0.2 and ~10 clean steps pull it back above 0.85 —
# fast enough to rank suspects inside one flush window, slow enough that a
# single false accusation cannot tank a worker
TRUST_ALPHA = 0.2


def num_mask_words(num_workers: int) -> int:
    """ceil(n/32) packed words per mask kind; bounded by MAX_WORKERS."""
    if num_workers < 1:
        raise ValueError(f"num_workers must be >= 1, got {num_workers}")
    if num_workers > MAX_WORKERS:
        raise ValueError(
            f"forensics mask columns support num_workers <= {MAX_WORKERS} "
            f"(got {num_workers}); grow MAX_WORKERS and the column family "
            f"together (PERF_HISTORY.md §10)"
        )
    return (num_workers + MASK_WORD_BITS - 1) // MASK_WORD_BITS


def mask_metric_names(num_workers: int) -> tuple:
    """Column order of the packed forensics block for an n-worker config —
    the single schema source for step bodies and the host flush (same
    contract as parallel/common.token_metric_names)."""
    words = num_mask_words(num_workers)
    return tuple(f"{MASK_PREFIX}{kind}{w}"
                 for kind in MASK_KINDS for w in range(words))


def is_mask_column(name: str) -> bool:
    """True for packed-bitmask metric columns (f32-carried uint32 words) —
    every record-materialization site must route these through
    :func:`record_value` instead of ``float()``."""
    return name.startswith(MASK_PREFIX)


# --------------------------------------------------------------------------
# in-graph packing (lazy jax import: the module stays jax-free for tools)
# --------------------------------------------------------------------------


def pack_bits(mask):
    """(n,) bool -> (num_mask_words(n),) float32 carrying the uint32 words.

    Bit j of word w is worker ``32*w + j``. The float32 is a pure bitcast of
    the uint32 word: no arithmetic ever touches it downstream (stack, scan
    stacking, device->host copy are data movement), so the bits survive to
    the host fetch exactly. In-graph only — the host direction is
    :func:`unpack_bits` on the integer view.

    Deliberately formulated as masked-weight sums over the ORIGINAL (n,)
    axis — no pad-concat, no reshape. The obvious
    ``concat(mask, zeros) -> reshape(words, 32) -> dot(2**j)`` packs a
    mesh-SHARDED mask off by one bit position under the GSPMD partitioner
    (observed on the folded w×tp CPU mesh: worker 3's accusation landed on
    bit 4; the fetched mask itself was correct, only the packed word
    shifted — the pad-concat's per-shard offsets are what go wrong).
    Elementwise ops + a full reduction partition correctly, and the
    equivalence suites + the tp chaos cell pin it per mesh.
    """
    import jax
    import jax.numpy as jnp

    n = int(mask.shape[0])
    words = num_mask_words(n)
    bits = jnp.asarray(mask, jnp.uint32)
    j = jnp.arange(n, dtype=jnp.uint32)
    packed = []
    for w in range(words):
        lo = jnp.uint32(w * MASK_WORD_BITS)
        in_word = (j >= lo) & (j < lo + MASK_WORD_BITS)
        weights = jnp.where(in_word,
                            jnp.left_shift(jnp.uint32(1), j - lo),
                            jnp.uint32(0))
        packed.append(jnp.sum(bits * weights, dtype=jnp.uint32))
    return jax.lax.bitcast_convert_type(jnp.stack(packed), jnp.float32)


def pack_mask_columns(accused, present, adv_mask) -> dict:
    """The per-step packed forensics columns (mask_metric_names order).

    ``accused``: the step's (n,) accusation set — a present-gated union of
    the code's own flag set and the forensic-only signals (loud rows,
    non-finite ingest rows); ``present``: (n,) bool or None (all present);
    ``adv_mask``: the seeded-adversary schedule row, the in-graph ground
    truth. An absent worker is never an accused worker: ``accused`` is
    re-gated by ``present`` here so no call site can forget.
    """
    import jax.numpy as jnp

    accused = jnp.asarray(accused, bool)
    n = int(accused.shape[0])
    pres = (jnp.ones((n,), bool) if present is None
            else jnp.asarray(present, bool))
    cols = {}
    for kind, mask in (("accused", accused & pres), ("present", pres),
                       ("adv", jnp.asarray(adv_mask, bool))):
        packed = pack_bits(mask)
        for w in range(int(packed.shape[0])):
            cols[f"{MASK_PREFIX}{kind}{w}"] = packed[w]
    return cols


def nonfinite_rows(grads):
    """(n, ...) per-worker gradient stack -> (n,) bool: rows containing any
    non-finite value. The ingest-health check a real aggregator runs on
    every received row, evaluated on the RAW per-worker gradients after
    fault injection and BEFORE encode — under ``redundancy="shared"`` the
    algebraic encode smears a NaN across every codeword (0·NaN = NaN in the
    masked matmul), so the wire rows cannot attribute a non-finite fault but
    the ingest rows can (row k <-> worker k in shared mode)."""
    import jax.numpy as jnp

    g = jnp.asarray(grads)
    return ~jnp.all(jnp.isfinite(g).reshape(g.shape[0], -1), axis=1)


# --------------------------------------------------------------------------
# host-side materialization + unpack (numpy/stdlib only)
# --------------------------------------------------------------------------


def record_value(name: str, value):
    """Materialize one metric value for a host record: mask columns become
    the exact integer word (the f32 payload re-viewed as uint32 — safe
    through JSON, where a float NaN would drop its payload), everything else
    the usual float."""
    if not is_mask_column(name):
        return float(value)
    import numpy as np

    arr = np.asarray(value)
    if arr.dtype.kind in "ui":  # already an integer word (re-folded record)
        return int(arr)
    return int(arr.astype(np.float32, copy=False).reshape(()).view(np.uint32))


def unpack_bits(words: Sequence[int], num_workers: int) -> Tuple[bool, ...]:
    """Integer words -> (num_workers,) bools. Pure int bit-twiddling (no
    numpy): usable from jax-free artifact tools."""
    out = []
    for i in range(num_workers):
        w, j = divmod(i, MASK_WORD_BITS)
        word = int(words[w]) if w < len(words) else 0
        out.append(bool((word >> j) & 1))
    return tuple(out)


def record_masks(record: dict, num_workers: int) -> Optional[Dict[str, tuple]]:
    """kind -> (n,) bool tuples from one materialized record, or None when
    the record carries no forensics columns (baseline routes, eval records,
    mixed-route train dirs)."""
    if f"{MASK_PREFIX}accused0" not in record:
        return None
    words = num_mask_words(num_workers)
    out = {}
    for kind in MASK_KINDS:
        vals = [int(record.get(f"{MASK_PREFIX}{kind}{w}", 0))
                for w in range(words)]
        out[kind] = unpack_bits(vals, num_workers)
    return out


# --------------------------------------------------------------------------
# AccusationLedger — the host fold
# --------------------------------------------------------------------------


class AccusationLedger:
    """Folds per-step packed masks into per-worker forensics state.

    Fed one materialized record at a time (:meth:`observe`) — wired through
    the existing DeferredMetricWriter observer / RunHeartbeat hook, so it
    sees exactly the records the flush materializes anyway (every step in
    the chunked regime, the logged steps in the eager LM regime). Records
    without forensics columns are ignored, so mixed-route train dirs cannot
    poison the counters.
    """

    def __init__(self, num_workers: int, trust_alpha: float = TRUST_ALPHA):
        self.n = int(num_workers)
        num_mask_words(self.n)  # validate the bound early
        self.alpha = float(trust_alpha)
        self.steps = 0
        self.accused = [0] * self.n
        self.present = [0] * self.n
        self.tp = [0] * self.n  # accused ∧ adversarial (∧ present)
        self.fp = [0] * self.n  # accused ∧ honest (∧ present)
        self.fn = [0] * self.n  # adversarial ∧ present ∧ not accused
        self.trust = [1.0] * self.n
        self.episodes: List[dict] = []  # closed, in closure order
        self._open: Dict[int, dict] = {}  # worker -> open episode

    # ---- fold ------------------------------------------------------------
    def observe(self, record: dict, masks: Optional[dict] = None) -> bool:
        """Fold one record; returns True iff it carried forensics columns.
        ``masks``: the record's already-unpacked mask dict, when the caller
        holds one (the incident engine's per-record cache) — skips the
        redundant bit-unpack on the hot observer path."""
        if masks is None:
            masks = record_masks(record, self.n)
        if masks is None:
            return False
        step = int(record.get("step", self.steps + 1))
        accused, present, adv = (masks["accused"], masks["present"],
                                 masks["adv"])
        self.steps += 1
        for w in range(self.n):
            if not present[w]:
                # erasure: no vote either way — trust and episodes hold
                continue
            self.present[w] += 1
            if accused[w]:
                self.accused[w] += 1
                if adv[w]:
                    self.tp[w] += 1
                else:
                    self.fp[w] += 1
                ep = self._open.get(w)
                if ep is None:
                    self._open[w] = {"worker": w, "start": step, "end": step,
                                     "steps": 1}
                else:
                    ep["end"] = step
                    ep["steps"] += 1
            else:
                if adv[w]:
                    self.fn[w] += 1
                ep = self._open.pop(w, None)
                if ep is not None:
                    self.episodes.append(ep)
            self.trust[w] = ((1.0 - self.alpha) * self.trust[w]
                             + self.alpha * (0.0 if accused[w] else 1.0))
        return True

    # ---- views -----------------------------------------------------------
    @property
    def active(self) -> bool:
        return self.steps > 0

    def open_episodes(self) -> List[dict]:
        """Episodes still running at the last observed step (sorted by
        worker), marked ``open``."""
        return [dict(self._open[w], open=True) for w in sorted(self._open)]

    def all_episodes(self) -> List[dict]:
        """Closed episodes (closure order) + the still-open tails."""
        return [dict(e, open=False) for e in self.episodes] \
            + self.open_episodes()

    def worker_rows(self) -> List[dict]:
        """One forensics row per worker: counters, detection precision /
        recall vs the seeded schedule (1.0 on the empty-denominator healthy
        states), trust, episode count."""
        rows = []
        n_eps = [0] * self.n
        for ep in self.all_episodes():
            n_eps[ep["worker"]] += 1
        for w in range(self.n):
            adv_seen = self.tp[w] + self.fn[w]
            rows.append({
                "worker": w,
                "present": self.present[w],
                "accused": self.accused[w],
                "tp": self.tp[w],
                "fp": self.fp[w],
                "fn": self.fn[w],
                "precision": (self.tp[w] / self.accused[w]
                              if self.accused[w] else 1.0),
                "recall": (self.tp[w] / adv_seen) if adv_seen else 1.0,
                "trust": round(self.trust[w], 4),
                "episodes": n_eps[w],
            })
        return rows

    def forgive(self, worker: int, trust: float = 0.75) -> None:
        """Re-admission parole (control/autopilot.py): reset the worker's
        EW trust to ``trust`` so a readmitted worker is judged on fresh
        evidence instead of its pre-quarantine collapse — without this the
        trust detector re-fires on the first present step and the
        quarantine/readmit pair would flap forever. Accusation counters
        are NOT reset: the history stays in the ledger."""
        self.trust[worker] = float(trust)

    def summary(self, top: int = 3) -> dict:
        """The compact ``forensics`` block for status.json: top suspects by
        accusation count (ties broken toward lower trust), the per-worker
        trust vector, and the episode counts."""
        order = sorted(range(self.n),
                       key=lambda w: (-self.accused[w], self.trust[w], w))
        suspects = [{"worker": w, "accused": self.accused[w],
                     "trust": round(self.trust[w], 4)}
                    for w in order[:top] if self.accused[w] > 0]
        return {
            "num_workers": self.n,
            "steps": self.steps,
            "top_suspects": suspects,
            "trust": [round(t, 4) for t in self.trust],
            "accused_total": sum(self.accused),
            "open_episodes": len(self._open),
            "episodes_total": len(self.episodes) + len(self._open),
        }

    def to_dict(self) -> dict:
        """The full fold (tools/forensics_report.py's forensics.json body)."""
        return {
            "num_workers": self.n,
            "steps": self.steps,
            "workers": self.worker_rows(),
            "episodes": self.all_episodes(),
            "summary": self.summary(),
        }
