"""Set-up keeps a ledger of its own (ISSUE 37): the two step builders span
what they do before any tracer exists (``setup.model_init``, ``setup.state``,
``setup.step_build``), the loops' schedules are ``setup.schedules``, every
executable build is a ``compile`` or a ``cache_load`` by what it paid, a
tracer made afterwards carries all of it at its true clock reads, each call
of a loop spans its two edges, and the first ``status.json`` says where the
time before the first step went. LeNet and a one-layer LM, on the CPU."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from draco_tpu.config import TrainConfig
from draco_tpu.obs import compile_watch
from draco_tpu.obs import tracer as tracer_mod
from draco_tpu.obs.heartbeat import check_status_schema
from draco_tpu.obs.tracer import (
    NULL_TRACER, make_tracer, record_setup, setup_ledger, setup_span,
    setup_totals,
)
from draco_tpu.parallel import make_mesh_2d
from draco_tpu.parallel.sp_step import build_sp_train_setup
from draco_tpu.parallel.token_loop import run_token_loop
from draco_tpu.runtime import make_mesh
from draco_tpu.training.step import build_train_setup

# the other ledger tests' tiny jobs: one Trainer, one token loop
from test_lm_maj_vote import _Rows, _cfg as _lm_cfg
from test_step_ledger import _trainer, ds  # noqa: F401  (ds: a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("setup.model_init", "setup.state", "setup.step_build")
BUILDS = ("compile", "cache_load")


def _build(kind):
    """One builder's call; the ledger's tuples it recorded."""
    _, mark = setup_ledger()
    if kind == "cnn":
        cfg = TrainConfig(
            network="LeNet", dataset="synthetic-mnist", approach="cyclic",
            num_workers=8, worker_fail=1, batch_size=4, max_steps=8,
            eval_freq=0, train_dir="")
        setup = build_train_setup(cfg, make_mesh(cfg.num_workers))
    else:
        cfg = _lm_cfg("TransformerLM")
        mesh = make_mesh_2d(cfg.num_workers, 1, jax.devices()[:1])
        setup = build_sp_train_setup(cfg, mesh)
    tuples, _ = setup_ledger(mark)
    return cfg, setup, tuples


@pytest.fixture(scope="module", autouse=True)
def no_watch_left_open():
    """A Trainer that an earlier file of this xdist worker built and never
    closed (``single_machine.main``'s; one whose constructor raised after
    its watch had started) keeps its compile watch receiving events, and
    while any watch is active a build goes to that watch's tracer and not
    onto the ledger's list (``compile_watch._dispatch``): the cases here
    that list a build then find none, by the order ``--dist loadfile`` gave
    the files. Watches left open are stopped before this module's first
    builder."""
    for watch in list(compile_watch._ACTIVE):
        watch.stop()


@pytest.fixture(scope="module", params=["cnn", "lm"])
def built(request):
    return _build(request.param)


# ---- the builders' spans -------------------------------------------------

def test_a_builder_spans_its_three_phases_in_order(built):
    _, _, tuples = built
    phases = [t for t in tuples if t[0] in PHASES]
    assert [t[0] for t in phases] == list(PHASES)
    assert all(parent is None for *_, parent in phases)
    for (_, a0, a1, _), (_, b0, _, _) in zip(phases, phases[1:]):
        assert a0 <= a1 <= b0  # ordered, and no two overlap
    # the eager init is work: a span that closes before it is done says
    # nothing (the builders wait for the parameters inside it)
    assert phases[0][2] - phases[0][1] > 0.0


def test_a_build_lies_inside_the_span_that_paid_for_it(built):
    """No watch exists while a builder runs: the dispatcher lists its builds
    (a process that has built the model before finds the init's one-op
    executables in memory and builds nothing: the subprocess case below
    always builds)."""
    _, _, tuples = built
    spans = {t[0]: t for t in tuples if t[0] in PHASES}
    assert {t[0] for t in tuples} <= set(PHASES + BUILDS)
    builds = [t for t in tuples if t[0] in BUILDS]
    for name, t0, t1, parent in builds:
        assert parent in spans, (name, parent)
        _, p0, p1, _ = spans[parent]
        assert p0 <= t0 <= t1 <= p1, (name, parent)
    for (_, _, a1, _), (_, b0, _, _) in zip(builds, builds[1:]):
        assert a1 <= b0 + 1e-6  # one build after the other, never across
    # and one that this process cannot have built before
    _, mark = setup_ledger()
    with setup_span("setup.model_init"):
        jax.jit(lambda x: x * 37.25 + float(mark))(jnp.ones(5))
    *fresh, init = setup_ledger(mark)[0]
    assert fresh and all(t[0] in BUILDS and t[3] == "setup.model_init"
                         and init[1] <= t[1] <= t[2] <= init[2]
                         for t in fresh)


def test_setup_span_names_its_parent_and_survives_an_exception():
    _, mark = setup_ledger()
    with pytest.raises(KeyError):
        with setup_span("setup.outer"):
            with setup_span("setup.inner"):
                raise KeyError("paid all the same")
    with setup_span("setup.after"):
        pass
    tuples, _ = setup_ledger(mark)
    assert [(t[0], t[3]) for t in tuples] == [
        ("setup.inner", "setup.outer"), ("setup.outer", None),
        ("setup.after", None)]


def test_the_ledger_is_bounded_and_its_totals_are_not(monkeypatch):
    # on a ledger of its own: the process's is other tests' to read
    monkeypatch.setattr(tracer_mod, "_SETUP", [("setup.old", 0.0, 1.0, None)])
    monkeypatch.setattr(tracer_mod, "_SETUP_BASE", 7)
    monkeypatch.setattr(tracer_mod, "_SETUP_TOTALS", {})
    before = setup_totals().get("setup.filler", 0.0)
    _, mark = setup_ledger()
    assert mark == 8
    n = 2 * tracer_mod.SETUP_MAX + 10
    for i in range(n):
        record_setup("setup.filler", float(i), float(i) + 0.5)
    kept, end = setup_ledger()
    assert len(kept) <= tracer_mod.SETUP_MAX
    assert end == mark + n  # numbered through, dropped or not
    late, _ = setup_ledger(mark)
    assert 0 < len(late) <= tracer_mod.SETUP_MAX
    assert late[-1][:3] == ("setup.filler", n - 1.0, n - 0.5)
    assert setup_ledger(end) == ([], end)
    assert setup_totals()["setup.filler"] - before == pytest.approx(n * 0.5)


# ---- the tracer adopts the ledger ----------------------------------------

def _x_events(path):
    with open(path) as fh:
        return [e for e in json.load(fh)["traceEvents"] if e["ph"] == "X"]


def test_a_tracer_made_after_the_builder_carries_its_spans(built, tmp_path):
    """The token route's order: build, then make the tracer."""
    _, _, tuples = built
    tr = make_tracer(str(tmp_path))
    epoch = time.perf_counter() - tr.now_us() * 1e-6  # as the routes read it
    with tr.span("dispatch"):
        pass
    with setup_span("setup.schedules"):  # after the tracer: adopted at flush
        pass
    tr.close()
    events = _x_events(tmp_path / "trace.json")
    adopted = [e for e in events if e.get("cat") == "setup"]
    assert all(e["ts"] >= 0.0 for e in events)
    for name, t0, t1, parent in tuples:
        (ev,) = [e for e in adopted if e["name"] == name
                 and abs(epoch + e["ts"] * 1e-6 - t0) < 5e-6]
        assert ev["dur"] * 1e-6 == pytest.approx(t1 - t0, abs=2e-6)
        assert (ev.get("args") or {}).get("parent") == parent
    # one timeline: the builder's spans lie before the tracer's own
    (own,) = [e for e in events if e["name"] == "dispatch"]
    assert max(e["ts"] + e["dur"] for e in adopted
               if e["name"] in PHASES) <= own["ts"]
    assert [e["name"] for e in adopted][-1] == "setup.schedules"
    assert tr.last_span == "dispatch"  # adopted spans are laid late, not last


def test_the_trainers_trace_holds_its_builders_spans(ds, tmp_path):  # noqa
    """The Trainer's order: ``build_train_setup`` eleven lines before
    ``make_tracer``; the schedules after it."""
    tr, _ = _trainer(ds, train_dir="", trace_dir=str(tmp_path))
    tr.run(max_steps=2)
    tr.close()
    events = _x_events(tmp_path / "trace.json")
    at = {e["name"]: e for e in events if e.get("cat") == "setup"}
    assert set(PHASES) | {"setup.schedules"} <= set(at)
    first_step = min(e["ts"] for e in events if e["name"] == "gather+upload")
    for name in PHASES + ("setup.schedules",):
        assert at[name]["ts"] + at[name]["dur"] <= first_step
    assert at["setup.step_build"]["ts"] + at["setup.step_build"]["dur"] \
        <= at["setup.schedules"]["ts"]


def test_the_null_tracer_adopts_nothing_and_allocates_nothing(tmp_path):
    assert make_tracer(None) is NULL_TRACER
    assert make_tracer(str(tmp_path), is_main=False) is NULL_TRACER
    assert type(NULL_TRACER).__slots__ == ()
    with setup_span("setup.unseen"):
        pass
    shared = NULL_TRACER.span("a")
    assert NULL_TRACER.span_since("loop.epilogue", 1.0) is shared
    with NULL_TRACER.span_since("loop.epilogue", None) as s:
        assert s is shared
    NULL_TRACER.flush()
    NULL_TRACER.close()
    assert os.listdir(tmp_path) == []
    # and a tracer built directly adopts only when asked to
    plain = tracer_mod.SpanTracer(str(tmp_path / "trace.json"))
    plain.close()
    assert _x_events(tmp_path / "trace.json") == []


# ---- builds: what they paid ----------------------------------------------

def test_global_stats_keeps_every_key_it_had_and_counts_a_build():
    compile_watch.install()
    before = compile_watch.global_stats()
    assert {"builds", "backend_compiles", "lower_s", "compile_s"} \
        <= set(before)
    assert {"trace_s", "retrieval_s", "cache_hits", "cache_misses"} \
        <= set(before)
    jax.jit(lambda x: x * 37.0 + 1.0)(jnp.ones(11))
    after = compile_watch.global_stats()
    assert after["builds"] > before["builds"]
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]
    assert after["compile_s"] > before["compile_s"]
    paid = (after["backend_compiles"] - before["backend_compiles"]
            + after["cache_hits"] - before["cache_hits"])
    assert paid == after["builds"] - before["builds"]


def test_a_steps_build_keeps_its_own_trace_seconds(built):
    """Lowering a step traces ``jnp`` helpers after the step itself (each a
    trace event of its own): the build takes the one its module is named
    after, not the last."""
    cfg, setup, _ = built
    n = cfg.num_workers
    if hasattr(setup, "train_token_many"):
        args = (setup.state,
                jnp.zeros((n, cfg.batch_size, cfg.seq_len), jnp.int32),
                jnp.zeros((n,), bool))
    else:
        args = (setup.state, jnp.zeros((n, cfg.batch_size, 28, 28, 1)),
                jnp.zeros((n, cfg.batch_size), jnp.int32),
                jnp.zeros((n,), bool))
    before = compile_watch.global_stats()
    t0 = time.perf_counter()
    setup.train_step.lower(*args)
    wall = time.perf_counter() - t0
    after = compile_watch.global_stats()
    assert after["builds"] == before["builds"] + 1
    traced = after["trace_s"] - before["trace_s"]
    lowered = after["lower_s"] - before["lower_s"]
    # tracing a whole step is a fair share of the call, not a helper's 0.1 ms
    assert 0.1 * wall < traced <= wall - lowered + 1e-3, (traced, wall)


def test_a_watched_build_goes_to_the_watchs_tracer_not_the_list(tmp_path):
    tr = tracer_mod.SpanTracer(str(tmp_path / "trace.json"))
    totals = setup_totals()
    _, mark = setup_ledger()
    with compile_watch.CompileWatch(ledger_dir=str(tmp_path),
                                    tracer=tr) as w:
        with w.expect("prog"):
            jax.jit(lambda x: x * 41.0 - 2.0)(jnp.ones(13))
    tr.close()
    assert setup_ledger(mark)[0] == []  # not listed: no second adoption
    rows = [json.loads(line) for line in open(tmp_path / "compiles.jsonl")]
    assert rows and len(rows) == w.builds
    for r in rows:
        assert {"trace_s", "lower_s", "compile_s", "retrieval_s",
                "cache_hits", "cache_misses", "module"} <= set(r)
        assert r["cache_hits"] in (0, 1) and r["cache_misses"] in (0, 1)
    events = [e for e in _x_events(tmp_path / "trace.json")
              if e.get("cat") == "compile"]
    assert len(events) == len(rows)
    for e, r in zip(events, rows):
        assert e["name"] == ("cache_load" if r["cache_hits"] else "compile")
        assert e["dur"] * 1e-6 == pytest.approx(
            r["trace_s"] + r["lower_s"] + r["compile_s"], abs=5e-6)
    # its seconds still reach the totals the heartbeat reads
    gained = sum(setup_totals().get(k, 0.0) - totals.get(k, 0.0)
                 for k in BUILDS)
    assert gained == pytest.approx(
        sum(e["dur"] for e in events) * 1e-6, abs=1e-4)


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from draco_tpu.obs import compile_watch
from draco_tpu.obs.tracer import setup_ledger, setup_span
compile_watch.install()
with setup_span("setup.model_init"):
    jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones(7) * 3).block_until_ready()
print(json.dumps({"spans": [[n, p] for n, _, _, p in setup_ledger()[0]],
                  "stats": compile_watch.global_stats()}))
"""


def test_a_build_reads_compile_cold_and_cache_load_in_the_next_process(
        tmp_path):
    def probe():
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE, str(tmp_path / "cache")],
            cwd=ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold, warm = probe(), probe()
    if not cold["stats"]["cache_misses"]:
        pytest.skip("the CPU backend declined the persistent cache")
    builds = [s for s in cold["spans"] if s[0] in BUILDS]
    assert builds and all(s == ["compile", "setup.model_init"]
                          for s in builds)
    assert cold["stats"]["backend_compiles"] == cold["stats"]["builds"]
    assert cold["stats"]["cache_hits"] == 0
    loads = [s for s in warm["spans"] if s[0] in BUILDS]
    assert len(loads) == len(builds)
    assert all(s == ["cache_load", "setup.model_init"] for s in loads)
    assert warm["stats"]["cache_hits"] == warm["stats"]["builds"]
    assert warm["stats"]["backend_compiles"] == 0
    assert warm["stats"]["retrieval_s"] > 0.0
    assert warm["stats"]["retrieval_s"] <= warm["stats"]["compile_s"]


# ---- the loops' edges ----------------------------------------------------

def _edges(events, t_from_us):
    return {e["name"]: e["dur"] * 1e-6 for e in events
            if e["name"] in ("loop.prologue", "loop.epilogue")
            and e["ts"] >= t_from_us}


def _tiled(rows):
    return sum(r["t_book"] + r["t_fetch"] + r["t_comp"] for r in rows)


def test_edges_and_records_tile_the_trainers_call(ds, tmp_path):  # noqa: F811
    tr, records = _trainer(ds, train_dir="", trace_dir=str(tmp_path))
    tr.run(max_steps=3)  # the compile
    mark = tr.tracer.now_us()
    t0 = time.perf_counter()
    tr.run(max_steps=15)
    wall = time.perf_counter() - t0
    tr.close()
    edges = _edges(_x_events(tmp_path / "trace.json"), mark)
    assert set(edges) == {"loop.prologue", "loop.epilogue"}
    total = edges["loop.prologue"] + _tiled(records.rows[3:]) \
        + edges["loop.epilogue"]
    print(f"edges: {edges} records {_tiled(records.rows[3:]):.6f}s "
          f"wall {wall:.6f}s")
    assert total == pytest.approx(wall, abs=1e-3)
    assert total <= wall


def test_edges_and_records_tile_the_token_loops_call(tmp_path):
    cfg = _lm_cfg("TransformerLM", max_steps=64)
    mesh = make_mesh_2d(cfg.num_workers, 1, jax.devices()[:1])
    setup = build_sp_train_setup(cfg, mesh)
    tr, rows = make_tracer(str(tmp_path)), _Rows()
    state, _ = run_token_loop(setup, cfg, steps=3, quiet=True, writer=rows,
                              tracer=tr, start_step=1)  # the compile
    setup = setup._replace(state=state)
    _, ledger_mark = setup_ledger()
    mark = tr.now_us()
    t0 = time.perf_counter()
    run_token_loop(setup, cfg, steps=12, quiet=True, writer=rows, tracer=tr,
                   start_step=4)
    wall = time.perf_counter() - t0
    tr.close()
    edges = _edges(_x_events(tmp_path / "trace.json"), mark)
    assert set(edges) == {"loop.prologue", "loop.epilogue"}
    total = edges["loop.prologue"] + _tiled(rows.rows[3:]) \
        + edges["loop.epilogue"]
    print(f"edges: {edges} records {_tiled(rows.rows[3:]):.6f}s "
          f"wall {wall:.6f}s")
    assert total == pytest.approx(wall, abs=1e-3)
    assert total <= wall
    # the call rebuilt its schedules, inside its prologue
    assert [t[0] for t in setup_ledger(ledger_mark)[0]
            if t[0].startswith("setup.")] == ["setup.schedules"]


def test_a_chunked_call_has_both_edges(ds, tmp_path):  # noqa: F811
    tr, _ = _trainer(ds, train_dir="", trace_dir=str(tmp_path),
                     steps_per_call=4)
    tr.run(max_steps=8)
    tr.close()
    events = _x_events(tmp_path / "trace.json")
    (pro,) = [e for e in events if e["name"] == "loop.prologue"]
    (epi,) = [e for e in events if e["name"] == "loop.epilogue"]
    dispatches = [e for e in events if e["name"] == "dispatch"]
    assert pro["ts"] + pro["dur"] <= min(e["ts"] for e in dispatches)
    assert epi["ts"] >= max(e["ts"] + e["dur"] for e in dispatches)


def test_schedules_past_the_table_are_a_setup_span(ds):  # noqa: F811
    tr, _ = _trainer(ds, train_dir="")
    _, mark = setup_ledger()
    tr._ensure_schedules(32)  # inside the table made at construction: 64
    assert setup_ledger(mark)[0] == []
    tr._ensure_schedules(96)
    assert [t[0] for t in setup_ledger(mark)[0]] == ["setup.schedules"]
    assert len(tr._adv_schedule) >= 96
    tr.close()


# ---- the operator's reading ----------------------------------------------

def test_the_first_status_json_has_the_setup_block(ds, tmp_path):  # noqa
    tr, _ = _trainer(ds, train_dir=str(tmp_path))
    tr.run(max_steps=2)  # its last step's flush is the run's first beat
    with open(tmp_path / "status.json") as fh:
        first = check_status_schema(json.load(fh))
    block = first["setup"]
    assert set(PHASES) | {"setup.schedules", "cache_hits", "cache_misses"} \
        <= set(block)
    assert set(BUILDS) & set(block)
    assert all(block[k] >= 0 for k in block)
    assert block["setup.model_init"] > 0
    assert isinstance(block["cache_hits"], int)
    tr.run(max_steps=4)  # later beats carry the block as first read
    tr.close()
    with open(tmp_path / "status.json") as fh:
        assert json.load(fh)["setup"] == block
