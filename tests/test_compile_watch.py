"""The host's time inside JAX's runtime gets names (ISSUE 37):
``tpu.profiling.watch_compiles`` turns JAX's own monitoring events into
``xla.compile`` spans and two counters, and the readers of
``benchmark/metrics/`` that split ``setup_s`` read them.

CPU, toy programs: what is checked is which spans and counts appear and the
readers' arithmetic, never how long a compile takes."""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from benchmark.metrics import _compiles  # noqa: E402
from kubeflow_tpu.runtime.metrics import METRICS  # noqa: E402
from kubeflow_tpu.runtime.tracing import TRACER, Span  # noqa: E402
from kubeflow_tpu.tpu import profiling  # noqa: E402

PHASES = ("trace", "lower", "backend", "cache_load")


def counters():
    out = {f"seconds.{p}": METRICS.value("xla_compile_seconds_total", phase=p) for p in PHASES}
    out.update({o: METRICS.value("xla_compiles_total", outcome=o)
                for o in ("compiled", "loaded")})
    return out


def mine(name):
    """The ``xla.compile`` spans of one function, as emitted."""
    return [s for s in TRACER.finished_spans("xla.compile")
            if s.attributes["fun_name"] in (name, f"jit({name})")]


@pytest.fixture()
def watched():
    profiling.watch_compiles()
    return counters()


# -- the watcher -------------------------------------------------------------------

def test_watching_twice_counts_a_compile_once(watched):
    profiling.watch_compiles()
    x = jnp.ones((7,))                    # made eagerly: a program of its own
    seen, then = profiling.compiles_seen(), counters()

    @jax.jit
    def watched_twice(x):
        return x * 3 + 1

    watched_twice(x).block_until_ready()
    assert profiling.compiles_seen() == seen + 1
    now = counters()
    assert now["compiled"] + now["loaded"] == then["compiled"] + then["loaded"] + 1
    assert [s.attributes["phase"] for s in mine("watched_twice")] == ["trace", "lower", "backend"]


def test_a_fresh_jit_gives_its_three_phases_in_order_on_the_wall_clock(watched):
    @jax.jit
    def three_phases(x):
        return jnp.tanh(x) @ x.T

    before = time.time_ns()
    three_phases(jnp.ones((5, 5))).block_until_ready()
    after = time.time_ns()
    spans = mine("three_phases")
    assert [s.attributes["phase"] for s in spans] == ["trace", "lower", "backend"]
    assert [s.attributes["fun_name"] for s in spans] == [
        "three_phases", "jit(three_phases)", "jit(three_phases)"]
    # JAX's float seconds turned into whole nanoseconds: a microsecond of room
    assert before - 1000 <= spans[0].start_ns and spans[-1].end_ns <= after + 1000
    for a, b in zip(spans, spans[1:]):
        assert a.start_ns <= a.end_ns <= b.start_ns + 1000
    assert spans[-1].attributes["outcome"] in ("compiled", "loaded")
    assert "outcome" not in spans[0].attributes and "outcome" not in spans[1].attributes


@pytest.mark.parametrize("phase", ["trace", "lower", "backend"])
def test_each_phase_adds_its_seconds_to_its_counter(watched, phase):
    # the persistent cache is off in the tests: a backend span is a miss
    jax.jit(lambda x: x - 2.5)(jnp.ones((3,))).block_until_ready()
    assert counters()[f"seconds.{phase}"] > watched[f"seconds.{phase}"]


def test_a_call_of_a_compiled_program_emits_nothing_and_moves_no_counter(watched):
    @jax.jit
    def steady(x):
        return x + 1

    x = jnp.ones((4,))
    steady(x).block_until_ready()
    spans, seen, then = len(TRACER.finished_spans("xla.compile")), profiling.compiles_seen(), counters()
    for _ in range(5):
        steady(x).block_until_ready()
    assert len(TRACER.finished_spans("xla.compile")) == spans
    assert profiling.compiles_seen() == seen and counters() == then


def test_the_functions_a_program_calls_are_traced_inside_its_own_span(watched):
    @jax.jit
    def inner_one(x):
        return jnp.sin(x)

    @jax.jit
    def outer_one(x):
        return inner_one(x) + inner_one(x * 2)

    outer_one(jnp.ones((6,))).block_until_ready()
    assert [s.attributes["phase"] for s in mine("outer_one")] == ["trace", "lower", "backend"]
    assert mine("inner_one") == []          # nested events are their caller's time


def test_a_compile_on_another_thread_is_seen_from_this_one(watched):
    x = jnp.ones((9,))
    seen = profiling.compiles_seen()
    worker = threading.Thread(
        target=lambda: jax.jit(lambda x: x * 7.5)(x).block_until_ready())
    worker.start()
    worker.join(timeout=120)
    assert not worker.is_alive() and profiling.compiles_seen() == seen + 1


@pytest.fixture()
def disk_cache(tmp_path):
    """JAX's persistent cache in ``tmp_path``, every entry kept, and the
    settings of the test process put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    old = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path), 0.0, -1, True)):
        jax.config.update(n, v)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for n, v in old.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_the_second_compile_with_a_disk_cache_is_a_load(watched, disk_cache):
    x = jnp.ones((8, 3))

    def fresh():
        @jax.jit
        def cached_on_disk(x):
            return jnp.cos(x) * 11 + x

        cached_on_disk(x).block_until_ready()

    fresh()
    first = mine("cached_on_disk")[-1]
    if not any(disk_cache.iterdir()):
        pytest.skip("this backend wrote no executable to the persistent cache; "
                    "the load is shown on the chip (CHANGES.md, PR 37)")
    assert first.attributes["outcome"] == "compiled"
    mid = counters()
    jax.clear_caches()
    fresh()
    second = mine("cached_on_disk")[-1]
    assert second is not first and second.attributes["outcome"] == "loaded"
    assert second.attributes["retrieval_s"] > 0 and "saved_s" in second.attributes
    now = counters()
    assert now["loaded"] == mid["loaded"] + 1 and now["compiled"] == mid["compiled"]
    assert now["seconds.cache_load"] == pytest.approx(
        mid["seconds.cache_load"] + second.attributes["retrieval_s"])
    assert now["seconds.backend"] == mid["seconds.backend"]      # misses only


# -- the readers, on hand-made spans -------------------------------------------------

MS = 1_000_000


def cspan(phase, start_ms, dur_ms, **attrs):
    return Span("xla.compile", "t" * 32, "s" * 16, start_ns=start_ms * MS,
                end_ns=(start_ms + dur_ms) * MS,
                attributes={"phase": phase, "fun_name": "jit(f)", **attrs})


def compile_obs(kind="serve"):
    """Set-up until 10,000 ms, a window of 1,000 ms, then the reference
    check. One program compiled and one loaded in set-up; a third whose
    tracing starts before the opening and whose lowering starts ON it."""
    spans = [
        cspan("trace", 1000, 500), cspan("lower", 1500, 300),
        cspan("backend", 1800, 3000, outcome="compiled"),
        cspan("trace", 5000, 200), cspan("lower", 5200, 100),
        cspan("backend", 5300, 400, outcome="loaded", retrieval_s=0.25, saved_s=2.0),
        cspan("trace", 9900, 100),                      # before the opening: set-up's
        cspan("lower", 10000, 100),                     # starts on it: the window's
        cspan("backend", 10100, 300, outcome="loaded", retrieval_s=0.1, saved_s=1.0),
        cspan("backend", 20000, 1000, outcome="compiled"),     # the reference check
    ]
    prewarm = [Span("serving.engine.prewarm", "t" * 32, "p" * 16, start_ns=6000 * MS,
                    end_ns=8000 * MS, attributes={"compiled": 1, "loaded": 1}),
               Span("serving.engine.prewarm", "t" * 32, "q" * 16, start_ns=8000 * MS,
                    end_ns=9000 * MS, attributes={"compiled": 0, "loaded": 0})]
    return {"kind": kind, "window_s": 1.0, "window_open_ns": 10000 * MS,
            "compile_spans": spans, "prewarm_spans": prewarm,
            "compiles_counted": {
                "compiles": 4, "seconds": sum(map(_compiles.counted_seconds, spans))}}


SETUP_READERS = {
    "setup_programs_compiled": 1.0,
    "setup_programs_loaded": 1.0,
    "setup_trace_lower_s": 0.5 + 0.3 + 0.2 + 0.1 + 0.1,
    "setup_compile_s": 3.0,
    "setup_cache_load_s": 0.25,
}
SERVE_ONLY_READERS = {
    "setup_prewarm_s.serve": 2.0 + 1.0,
    "window_compiles.serve": 1.0,            # the load that starts inside it
    "window_compile_s.serve": 0.1 + 0.3,     # its lowering and its backend span
}


@pytest.mark.parametrize("metric", sorted(SETUP_READERS) + sorted(SERVE_ONLY_READERS))
def test_compile_reader_on_hand_made_spans(metric):
    read = harness.load_reader(metric)
    want = {**SETUP_READERS, **SERVE_ONLY_READERS}[metric]
    assert read(compile_obs("serve")) == pytest.approx(want)
    got = read(compile_obs("train"))
    assert got is None if metric in SERVE_ONLY_READERS else got == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(SETUP_READERS) + sorted(SERVE_ONLY_READERS))
def test_compile_reader_finds_nothing_in_a_program_without_the_watcher(metric):
    """What the parent commit's program gives: no span, no counter."""
    obs = compile_obs()
    obs.update(compile_spans=[], prewarm_spans=[],
               compiles_counted={"compiles": 0.0, "seconds": 0.0})
    assert harness.load_reader(metric)(obs) is None


@pytest.mark.parametrize("lost", ["a backend span", "a trace span"])
@pytest.mark.parametrize("metric", sorted(SETUP_READERS) + ["window_compiles.serve",
                                                           "window_compile_s.serve"])
def test_no_partial_sum_where_the_ring_lost_a_span(metric, lost):
    obs = compile_obs()
    gone = 2 if lost == "a backend span" else 0      # the ring drops its oldest
    del obs["compile_spans"][gone]
    assert harness.load_reader(metric)(obs) is None


def test_an_event_counts_by_its_start_against_the_opening():
    obs = compile_obs()
    assert [s.attributes["phase"] for s in _compiles.in_window(obs)] == ["lower", "backend"]
    assert _compiles.before_opening(obs)[-1].end_ns == obs["window_open_ns"]
    obs["window_open_ns"] += 1                    # the lowering now starts before it
    assert [s.attributes["phase"] for s in _compiles.in_window(obs)] == ["backend"]
    assert harness.load_reader("setup_trace_lower_s")(obs) == pytest.approx(1.3)


def test_the_opening_is_the_harness_clock_laid_on_the_wall_clock():
    """``setup_s`` counts from the harness's import on ``perf_counter``; the
    readers put the opening on ``time.time()``, the spans' clock."""
    obs = {"kind": "serve", "setup_s": time.perf_counter() - harness._T0 - 5.0}
    assert _compiles.opening_ns(obs) == pytest.approx(time.time_ns() - 5e9, abs=5e7)


def test_the_readers_read_the_program_s_own_ring_and_counters(watched):
    """With no spans handed in they are the tracer's own, checked against
    the registry's counters (here: their rise since the ring was emptied).
    A program compiled after the opening is the window's."""
    x = jnp.ones((2, 9))
    watched = counters()
    TRACER.reset()

    def obs():
        now = counters()
        return {"kind": "serve", "window_s": 600.0, "setup_s": opened_at,
                "compiles_counted": {
                    "compiles": sum(now[o] - watched[o] for o in ("compiled", "loaded")),
                    "seconds": sum(now[k] - watched[k] for k in now if k.startswith("seconds"))}}

    jax.jit(lambda x: x / 3.25)(x).block_until_ready()
    opened_at = time.perf_counter() - harness._T0
    assert harness.load_reader("setup_programs_compiled")(obs()) == 1
    assert harness.load_reader("window_compiles.serve")(obs()) == 0
    jax.jit(lambda x: x / 4.75)(x).block_until_ready()
    assert harness.load_reader("window_compiles.serve")(obs()) == 1
    assert harness.load_reader("window_compile_s.serve")(obs()) > 0
    assert harness.load_reader("setup_programs_compiled")(obs()) == 1
    TRACER.reset()                            # the ring is short of the counters now
    assert harness.load_reader("setup_programs_compiled")(
        {"kind": "serve", "setup_s": opened_at}) is None


def test_the_new_metrics_are_declared_with_their_cells_and_a_layer():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    cells = [w["name"] for w in spec["workloads"]]
    serve = [c for c in cells if ".serve." in c]
    for name in SETUP_READERS:
        assert declared[name]["workloads"] == cells and declared[name]["moves"] == "setup_s"
    for name in SERVE_ONLY_READERS:
        assert declared[name]["workloads"] == serve
    for name in list(SETUP_READERS) + list(SERVE_ONLY_READERS):
        entry = declared[name]
        assert entry["layer"] == "start-up and compile" and entry["better"] == "lower"
        assert entry["source"] == "program_counter"
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists()
