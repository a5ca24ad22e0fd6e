"""The serving engine and the model say what they are doing (ISSUE 26):
``serving.engine.*`` regions in a profiler capture, the ``dequeued`` event
on ``serving.request``, scope names in the lowered programs, names on the
three flash kernels, and the nine per-layer readers of ``benchmark/metrics/``
on hand-made events.

CPU, toy sizes: what is checked is that the names and counts are there and
the readers' arithmetic, never a time."""

from __future__ import annotations

import contextlib
import glob
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness, trace_reduce as tr  # noqa: E402
from benchmark.metrics import _scopes, _spans  # noqa: E402
from kubeflow_tpu.models.gpt import GptConfig, GptLM  # noqa: E402
from kubeflow_tpu.runtime.tracing import TRACER, Span  # noqa: E402
from kubeflow_tpu.serving.continuous import ContinuousBatcher  # noqa: E402

CFG = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=128,
                vocab_size=101)
ENGINE = "serving.engine."
PHASES = ("idle", "drain", "reap", "import", "admit", "prefill_chunk",
          "dispatch", "fetch", "deliver")


@pytest.fixture(scope="module")
def params():
    rng = jax.random.PRNGKey(0)
    return GptLM(CFG).init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def capture(params, tmp_path_factory):
    """One short profiler session over three toy engines: a unified one (a
    short prompt and one long enough to be prefilled in chunks) and a
    prefill -> decode pair (so that a KV import happens). Returns the
    ``serving.*`` spans of the capture and the finished request spans."""
    logdir = str(tmp_path_factory.mktemp("engine_trace"))
    kw = dict(slots=2, chunk=4, pipeline=1)
    TRACER.reset()
    unified = ContinuousBatcher(CFG, params, engine_id="u", prefill_chunk=16, **kw)
    decode = ContinuousBatcher(CFG, params, engine_id="d", role="decode", **kw)
    prefill = ContinuousBatcher(
        CFG, params, engine_id="p", role="prefill",
        handoff_sink=lambda req, blob: decode.submit_handoff(req, blob), **kw)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        # each engine sits in a turn that opened before the session did and
        # is not recorded: one request each takes the engines out of it
        for eng in (unified, prefill):
            eng.submit(np.arange(8, dtype=np.int32), 2).result(timeout=300)
        futs = [unified.submit(np.arange(8, dtype=np.int32), 6),
                unified.submit(np.arange(40, dtype=np.int32) % 97, 6),
                prefill.submit(np.arange(9, dtype=np.int32), 5)]
        outs = [f.result(timeout=300) for f in futs]
    finally:
        # closed inside the session: the idle wait each engine falls back
        # into ends with it, and a region is recorded when it ends
        for eng in (prefill, decode, unified):
            eng.close()
        jax.profiler.stop_trace()
    assert [len(o) for o in outs] == [6, 6, 5]
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = sorted(_spans.spans_in_file(path)[0], key=lambda s: s.start_ns)
    requests = TRACER.finished_spans("serving.request")
    TRACER.reset()
    return spans, requests


# -- the engine loop's regions ---------------------------------------------------

@pytest.mark.parametrize("phase", PHASES)
def test_every_engine_phase_is_a_region_nested_under_a_turn(capture, phase):
    spans, _ = capture
    turns = [s for s in spans if s.name == _spans.TURN]
    mine = [s for s in spans if s.name == ENGINE + phase]
    assert turns and mine, f"no {ENGINE + phase} region in the capture"
    nested = 0
    for s in mine:
        mine_turns = [t for t in turns if t.line == s.line]
        if s.start_ns < min(t.start_ns for t in mine_turns):
            continue    # in the turn that was open when the capture began
        assert any(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns for t in mine_turns), \
            f"{s.name} outside every turn of its thread"
        nested += 1
    assert nested


def test_region_counts_ride_as_event_stats(capture):
    spans, _ = capture
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s.stats)
    dispatch = by[ENGINE + "dispatch"]
    assert all(d["rows"] == 2 * 4 and 1 <= d["live"] <= 2 for d in dispatch)
    # the table's columns each dispatch read: 2 of 8 (blocks of 16) for the
    # short rows, 4 once the 40-token prompt decodes beside them
    assert all(d["max_blocks"] == 8 for d in dispatch)
    assert {4} <= {d["view_blocks"] for d in dispatch} <= {2, 4}
    # a wave's largest batched prompt bucket: 16, or 0 where its one
    # request went the chunked way; 5 requests reached an admission wave
    admit = by[ENGINE + "admit"]
    assert {16} <= {a["bucket"] for a in admit} <= {0, 16}
    assert sum(a["requests"] for a in admit) >= 5
    # 5 submits + 2 imports (the drain that meets the shutdown has no count)
    assert sum(d.get("arrivals", 0) for d in by[ENGINE + "drain"]) == 7
    assert {f["kind"] for f in by[ENGINE + "fetch"]} == {"first", "chunk"}
    deliver = by[ENGINE + "deliver"]
    firsts = [d for d in deliver if d["kind"] == "first"]
    chunks = [d for d in deliver if d["kind"] == "chunk"]
    # unified: three prompts admitted (one in chunks); decode pool: two imports
    assert sum(d["tokens"] for d in firsts) == 5 and all(d["rows"] == 1 for d in firsts)
    assert all(d["rows"] == 2 * 4 and 0 <= d["tokens"] <= d["rows"] for d in chunks)
    # every token a caller got came through a deliver region (the prefill
    # pool's own first token rides the wire and is delivered on import)
    assert sum(d["tokens"] for d in deliver) == 2 + 2 + 6 + 6 + 5
    assert sum(d["retired"] for d in deliver) == 5


def test_turn_self_time_excludes_the_waits(capture):
    spans, _ = capture
    window = (min(s.start_ns for s in spans), max(s.end_ns for s in spans))
    obs = {"kind": "serve", "trace_window": window, "serving_spans": spans}
    ms = harness.load_reader("engine_host_ms_per_turn.serve")(obs)
    turns = _spans.inside(spans, window, _spans.TURN)
    assert 0 < ms < sum(t.dur_ns for t in turns) / len(turns) / 1e6


# -- the launches of device programs (ISSUE 37) ----------------------------------

LAUNCH = ENGINE + "launch"
LAUNCHING_PHASES = ("admit", "prefill_chunk", "dispatch", "import")


def _innermost_phase(spans, launch):
    """The shortest engine region other than the turn that holds a launch."""
    holders = [s for s in spans if s.line == launch.line and s.name != launch.name
               and s.name.startswith(ENGINE) and s.name != _spans.TURN
               and s.start_ns <= launch.start_ns and launch.end_ns <= s.end_ns]
    return min(holders, key=lambda s: s.dur_ns).name[len(ENGINE):] if holders else None


def test_every_launch_names_its_program_and_lies_inside_its_phase(capture):
    spans, _ = capture
    launches = [s for s in spans if s.name == LAUNCH]
    by_phase = {}
    for s in launches:
        by_phase.setdefault(_innermost_phase(spans, s), set()).add(s.stats["program"])
    # no prewarm here, so no view warm-up: every launch has a phase around it
    assert by_phase == {"admit": {"prefill", "adopt"},
                        "prefill_chunk": {"chunk_prefill", "adopt"},
                        "dispatch": {"step"}, "import": {"import"}}
    turns = [s for s in spans if s.name == _spans.TURN]
    for s in launches:
        mine_turns = [t for t in turns if t.line == s.line]
        if s.start_ns >= min(t.start_ns for t in mine_turns):
            assert any(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns for t in mine_turns)
    # the innermost region of its phase: no engine region lies inside a launch
    assert not [s for s in spans for l in launches
                if s is not l and s.line == l.line and s.name.startswith(ENGINE)
                and l.start_ns <= s.start_ns and s.end_ns <= l.end_ns]


@pytest.mark.parametrize("program", ["prefill", "adopt", "chunk_prefill", "step", "import"])
def test_a_launch_that_compiled_says_so_and_a_later_one_does_not(capture, program):
    spans, _ = capture
    by_line = {}
    for s in spans:
        if s.name == LAUNCH and s.stats["program"] == program:
            by_line.setdefault(s.line, []).append(s)
    assert by_line
    for line, launches in by_line.items():
        # every engine builds its own programs: its first call of one
        # compiles (or loads) at least that executable
        assert launches[0].stats.get("compiles", 0) >= 1, (line, program)
        said = [l for l in launches if "compiles" in l.stats]
        if program == "step":
            # ... and so does the first dispatch at each view width (nothing
            # was prewarmed here), and no other
            widths = {d.stats["view_blocks"] for d in spans
                      if d.name == ENGINE + "dispatch" and d.line == line}
            assert len(said) == len(widths) < len(launches), line
        elif program in ("chunk_prefill", "import"):
            assert said == launches[:1], (line, program)      # a repeat says nothing
    if program in ("step", "chunk_prefill", "import"):
        assert max(map(len, by_line.values())) > 1


def test_launch_and_own_time_add_up_to_the_turn_s_self_time(capture):
    spans, _ = capture
    window = (min(s.start_ns for s in spans), max(s.end_ns for s in spans))
    obs = {"kind": "serve", "trace_window": window, "serving_spans": spans}
    host, launch, own = (harness.load_reader(f"engine_{m}_ms_per_turn.serve")(obs)
                         for m in ("host", "launch", "own"))
    assert launch > 0 and own > 0 and launch + own == pytest.approx(host)
    share = harness.load_reader("admit_launch_share.serve")(obs)
    assert 0 < share < 100


@pytest.fixture(scope="module")
def warmed(params, tmp_path_factory):
    """One engine built and prewarmed twice INSIDE a profiler session (its
    first turn opens in it): the capture's spans and the tracer's."""
    logdir = str(tmp_path_factory.mktemp("prewarm_trace"))
    TRACER.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        eng = ContinuousBatcher(CFG, params, engine_id="w", slots=2, chunk=4,
                                pipeline=1, prefill_chunk=16)
        try:
            eng.prewarm(8)
            eng.prewarm(8, group_sizes=[1])
            widths = len(list(eng.kv.warm_tables()))
        finally:
            eng.close()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = sorted(_spans.spans_in_file(path)[0], key=lambda s: s.start_ns)
    tracer = TRACER.finished_spans()
    TRACER.reset()
    return spans, tracer, widths


def test_the_view_warm_up_launches_lie_directly_under_the_turn(warmed):
    spans, _, widths = warmed
    bare = [s for s in spans if s.name == LAUNCH and _innermost_phase(spans, s) is None]
    assert widths > 1 and len(bare) == widths
    turns = [s for s in spans if s.name == _spans.TURN]
    for s in bare:
        assert s.stats["program"] == "step" and s.stats["compiles"] >= 1
        assert any(t.line == s.line and t.start_ns <= s.start_ns and s.end_ns <= t.end_ns
                   for t in turns)
    # all in the turn that took the first wave, ahead of admitting it
    first_admit = min(s.start_ns for s in spans if s.name == ENGINE + "admit")
    assert max(s.end_ns for s in bare) <= first_admit


def test_build_and_prewarm_are_spans_of_the_tracer_with_their_attributes(warmed):
    _, tracer, _ = warmed
    (build,) = [s for s in tracer if s.name == "serving.engine.build"]
    assert build.attributes["replica"] == "w" and build.attributes["slots"] == 2
    first, second = [s for s in tracer if s.name == "serving.engine.prewarm"]
    assert build.start_ns <= build.end_ns <= first.start_ns <= first.end_ns <= second.start_ns
    assert first.attributes["prompt_len"] == 8 and first.attributes["group_sizes"] == [1, 2]
    assert second.attributes["group_sizes"] == [1]
    # the first compiles the engine's programs, a repeat of its shapes none;
    # the tests run with no persistent cache, so nothing is loaded
    backend = [s for s in tracer if s.name == "xla.compile"
               and s.attributes["phase"] == "backend"]
    inside = [s for s in backend if first.start_ns <= s.start_ns <= first.end_ns]
    assert first.attributes["compiled"] == len(inside) > 0
    assert {s.attributes["fun_name"] for s in inside} >= {
        "jit(step)", "jit(prefill)", "jit(paged_adopt)"}
    assert first.attributes["loaded"] == 0
    assert second.attributes["compiled"] == second.attributes["loaded"] == 0
    assert harness.load_reader("setup_prewarm_s.serve")(
        {"kind": "serve", "prewarm_spans": [first, second]}) == pytest.approx(
            first.duration_ms / 1e3 + second.duration_ms / 1e3)


# -- per-request records ---------------------------------------------------------

def test_request_span_has_dequeued_between_enqueued_and_admitted(capture):
    _, requests = capture
    served = [r for r in requests if r.attributes.get("finish_reason") == "ok"]
    assert len(served) >= 5
    for r in served:
        at = {e["name"]: e["timeUnixNano"] for e in r.events}
        assert at["enqueued"] <= at["dequeued"] <= at["admitted"] <= at["first_token"] \
            <= at["retired"]
        assert r.attributes["generated_tokens"] > 0


# -- names on the device ---------------------------------------------------------

def _scoped_and_bare(lower):
    """The lowered text with its scope names, and the text of the same
    program traced with ``jax.named_scope`` turned into a no-op."""
    named = lower()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = lower()
    return named.as_text(debug_info=True), named.as_text(), bare.as_text()


def _names(debug_text, scope):
    """``scope`` is a whole component of some operation's name stack (which
    becomes the HLO instruction's ``op_name``) in the lowered program."""
    return re.search(r'loc\("[^"]*(?<![\w.])' + re.escape(scope) + r'(?![\w.])[^"]*"',
                     debug_text) is not None


@pytest.mark.parametrize("paged", [True, False])
def test_decode_step_carries_its_scopes_and_they_move_no_instruction(params, paged):
    def lower():
        eng = ContinuousBatcher(CFG, params, slots=2, chunk=2, paged=paged)
        try:
            extra = (jnp.asarray(eng.kv.tables),) if paged else ()
            return eng._step_fn.lower(eng.params, eng.cache, eng.last_tok,
                                      eng.temps, eng.rngs, *extra)
        finally:
            eng.close()

    hlo, named, bare = _scoped_and_bare(lower)
    for scope in ("kv_write", "kv_gather", "attn_scores", "lm_head", "sample"):
        assert _names(hlo, scope), scope
    assert named == bare


def test_gpt_train_step_carries_its_scopes_and_they_move_no_instruction():
    import optax

    import bench

    cfg = GptConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
                    max_seq=64, **bench.GPT_TRAIN_KNOBS)
    opt = optax.adamw(3e-4)
    ids = jnp.zeros((2, 64), jnp.int32)

    def lower():
        model, step = bench.gpt_train_step(cfg, opt)
        shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
        return jax.jit(step).lower(shapes, jax.eval_shape(opt.init, shapes), ids)

    hlo, named, bare = _scoped_and_bare(lower)
    for scope in ("jvp(loss)", "transpose(jvp(loss))", "optimizer", "rematted_computation"):
        assert _names(hlo, scope), scope
    assert named == bare


def test_composite_step_carries_its_scopes_and_they_move_no_instruction():
    from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh
    from kubeflow_tpu.parallel.composite import CompositeConfig

    ccfg = CompositeConfig(vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=2, seq=16)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, model=2), devices=jax.devices()[:4])
    tree = composite.init_params(jax.random.PRNGKey(0), ccfg, mesh)
    ids = jnp.zeros((1, 2, 16), jnp.int32)

    def lower():
        return composite.make_train_step(ccfg, mesh, lr=1e-2).lower(tree, ids)

    hlo, named, bare = _scoped_and_bare(lower)
    for scope in ("embed", "attn", "mlp", "unembed", "optimizer"):
        assert _names(hlo, f"jvp({scope})") or _names(hlo, scope), scope
    assert named == bare


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_flash_kernels_carry_their_names(kernel):
    from kubeflow_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    names = _pallas_names(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert names.count(kernel) == 1 and len(names) == 3


# -- the readers, on hand-made events --------------------------------------------

def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def sp(name, start, dur, line="engine", **stats):
    return _spans.Span(ENGINE + name, float(start), float(dur), line, stats)


def request(start, **events):
    span = Span("serving.request", "t" * 32, "s" * 16, start_ns=start)
    span.events = [{"name": k, "timeUnixNano": v, "attributes": {}} for k, v in events.items()]
    return span


def op(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"


WHILE = "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b"


def serve_obs():
    spans = [
        sp("turn", 0, 1000), sp("idle", 0, 100), sp("drain", 100, 10, arrivals=1),
        sp("dispatch", 200, 100, rows=32, live=3, view_blocks=2, max_blocks=8),
        sp("launch", 250, 40, program="step"),
        sp("fetch", 400, 500, kind="chunk"),
        sp("deliver", 900, 50, kind="chunk", rows=32, tokens=20, retired=1),
        sp("turn", 1000, 500), sp("fetch", 1100, 100, kind="first"),
        sp("deliver", 1200, 10, kind="first", rows=2, tokens=2, retired=0),
        sp("deliver", 1250, 10, kind="chunk", rows=32, tokens=12, retired=0),
        sp("admit", 1300, 100, requests=1, bucket=16),
        sp("launch", 1310, 30, program="prefill", compiles=1),
        sp("launch", 1350, 20, program="adopt"),
        sp("turn", 1500, 2000),                       # not wholly inside the window
        sp("fetch", 1100, 100, line="other thread"),  # another thread's is not a child
        sp("launch", 1300, 90, line="other thread", program="step"),
    ]
    ops = {"/device:TPU:0": [
        ev(WHILE, 100, 800), ev(op("gather.1"), 100, 200), ev(op("dot.1"), 300, 500),
        ev(op("convert.1"), 800, 100),                # hoisted out of the scan
        ev(op("gather.1"), 1600, 100),                # outside the decode executions
    ]}
    modules = {"/device:TPU:0": [ev("jit_step(1)", 100, 800), ev("jit_prefill(2)", 1500, 300)]}
    reqs = [request(5, enqueued=5, dequeued=9, admitted=99, first_token=199)]   # lead-in
    reqs += [request(100 + i, enqueued=1_000_000 * i, dequeued=1_000_000 * (i + 1),
                     admitted=1_000_000 * (i + 4), first_token=1_000_000 * (i + 10))
             for i in range(3)]
    reqs.append(request(200, enqueued=0, dequeued=2_000_000))                   # failed early
    return {
        "kind": "serve", "trace": tr.Trace(ops, {}, modules, []), "trace_window": (0.0, 2000.0),
        "busy_by_device": {"/device:TPU:0": 900e-9}, "program_name": "step",
        "serving_spans": spans, "request_spans": reqs, "requests_measured": 4,
        "op_scopes": {op("gather.1"): "jit(step)/while/body/GptLM/block_0/attention/kv_gather/gather",
                      op("dot.1"): "jit(step)/while/body/GptLM/block_0/attention/attn_scores/dot_general",
                      op("convert.1"): "jit(step)/while"},
    }


def train_obs():
    remat = "jit(train_step)/transpose(jvp(GptLM))/while/body/closed_call/checkpoint/" \
            "rematted_computation/blocks/mlp/dot_general"
    scopes = {op("fwd.1"): "jit(train_step)/jvp(GptLM)/while/body/blocks/mlp/dot_general",
              op("refwd.1"): remat, op("adam.1"): "jit(train_step)/optimizer/mul",
              op("optimizer_state_copy.1"): "jit(train_step)/optimizers/copy"}
    per_chip = [ev(WHILE, 0, 700), ev(op("fwd.1"), 0, 300), ev(op("refwd.1"), 300, 200),
                ev(op("adam.1"), 700, 100), ev(op("optimizer_state_copy.1"), 800, 100),
                ev("%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", 900, 100)]
    ops = {"/device:TPU:0": per_chip, "/device:TPU:1": per_chip}
    return {"kind": "train", "trace": tr.Trace(ops, {}, {}, []), "trace_window": (0.0, 1000.0),
            "busy_by_device": {d: 1000e-9 for d in ops}, "op_scopes": scopes}


SERVE_READERS = {
    # turns wholly inside: (1000 - 100 idle - 500 fetch) and (500 - 100 fetch): mean 400 ns
    "engine_host_ms_per_turn.serve": 400e-6,
    # of those, inside calls of device programs: 40 and 30 + 20
    "engine_launch_ms_per_turn.serve": 45e-6,
    "engine_own_ms_per_turn.serve": 355e-6,
    "admit_launch_share.serve": 100.0 * (30 + 20) / 100,        # this thread's alone
    "decode_live_row_share.serve": 100.0 * (20 + 12) / 64,     # chunk events only
    "slot_wait_ms.serve": 3.0,                                 # admitted - dequeued
    "first_token_lag_ms.serve": 6.0,                           # first_token - admitted
    "decode_kv_gather_share.serve": 100.0 * 200 / 800,         # inside jit_step only
    "decode_kv_view_share.serve": 100.0 * (200 + 500) / 800,   # kv_gather or attn_scores
    "decode_unscoped_share.serve": 100.0 * 100 / 800,          # named after the loop alone
    "decode_view_block_share.serve": 100.0 * 2 / 8,            # columns read of the table's
}
TRAIN_READERS = {
    "remat_forward_share.train": 100.0 * 2 * 200 / 2000,       # both chips, over both busy times
    "optimizer_share.train": 100.0 * 2 * 100 / 2000,           # "optimizers" is another scope
}


@pytest.mark.parametrize("metric", sorted(SERVE_READERS) + sorted(TRAIN_READERS))
def test_reader_on_hand_made_events(metric):
    read = harness.load_reader(metric)
    mine, other = (serve_obs(), train_obs()) if metric in SERVE_READERS else \
        (train_obs(), serve_obs())
    want = SERVE_READERS.get(metric, TRAIN_READERS.get(metric))
    assert read(mine) == pytest.approx(want)
    assert read(other) is None


@pytest.mark.parametrize("metric", sorted(SERVE_READERS) + sorted(TRAIN_READERS))
def test_reader_finds_nothing_in_a_program_without_the_names(metric):
    """What the parent commit's program gives: no serving spans, no
    ``dequeued`` event, no scope path on any operation. ``first_token_lag``
    reads events the parent already stamps."""
    obs = serve_obs() if metric in SERVE_READERS else train_obs()
    obs.update(serving_spans=[], op_scopes={})
    for r in obs.get("request_spans", []):
        r.events = [e for e in r.events if e["name"] != "dequeued"]
    got = harness.load_reader(metric)(obs)
    assert got == pytest.approx(6.0) if metric == "first_token_lag_ms.serve" else got is None


def test_request_readers_need_the_whole_measured_set():
    obs = serve_obs()
    obs["requests_measured"] = len(obs["request_spans"]) + 1
    assert harness.load_reader("slot_wait_ms.serve")(obs) is None


def test_new_metrics_are_declared_with_their_cells():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in list(SERVE_READERS) + list(TRAIN_READERS):
        entry = declared[name]
        assert set(entry["workloads"]) <= cells and (ROOT / "benchmark" / "metrics" / f"{name}.py").exists()
        assert entry["moves"] in {m["name"] for m in spec["end_to_end"]}
    assert declared["optimizer_share.train"]["workloads"] == [
        "gpt2-medium.train.b8x1024", "gpt2-large.train4.fsdp2-tp2"]


# -- the two helpers on real files ------------------------------------------------

def test_scope_paths_come_out_of_a_recorded_tpu_trace():
    """``benchmark/tests/small_train.xplane.pb`` (a toy train step on a v5e,
    PR 24): the protobuf walk finds each operation's ``op_name``."""
    scopes = _scopes.scopes_in_file(str(ROOT / "benchmark" / "tests" / "small_train.xplane.pb"))
    kernels = {name: path for name, path in scopes.items() if tr.is_pallas_call(name)}
    assert len(kernels) == 4 and all(p.endswith("/attention/pallas_call") for p in kernels.values())
    assert sum(_scopes.has_scope(p, "rematted_computation") for p in kernels.values()) == 1
    assert not any(tr.is_container(name) for name in scopes)
    assert all(p.startswith("jit(train_step)") for p in scopes.values())


def _message(*fields):
    """A protobuf message from (number, int | bytes) pairs: varints and
    length-delimited fields, all an xplane's metadata needs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    return b"".join(
        varint(number << 3) + varint(value) if isinstance(value, int) else
        varint(number << 3 | 2) + varint(len(value)) + value for number, value in fields)


def test_a_name_that_two_programs_scope_differently_gets_no_scope(tmp_path):
    def record(ident, name, scope):              # one event_metadata map entry
        stat = _message((1, 7), (5, f"{scope}:fusion".encode()))
        return _message((1, ident), (2, _message((1, ident), (2, name.encode()), (5, stat))))

    def plane(name, *records):
        tf_op = _message((1, 7), (2, _message((1, 7), (2, b"tf_op"))))
        return _message((2, name), *[(4, r) for r in records], (5, tf_op))

    step = record(1, op("fusion.1"), "jit(step)/while/body/GptLM/block_0/attention/kv_gather/gather")
    adopt = record(2, op("fusion.1"), "jit(adopt)/dynamic_update_slice")
    mlp = record(3, op("fusion.2"), "jit(step)/while/body/GptLM/block_0/mlp/dot_general")
    path = tmp_path / "two_programs.xplane.pb"
    path.write_bytes(_message((1, plane(b"/device:TPU:0", step, adopt, mlp)),
                              (1, plane(b"/device:TPU:1", step, mlp)),
                              (1, plane(b"/host:CPU", record(4, "serving.engine.turn", "x")))))
    assert _scopes.scopes_in_file(str(path)) == {
        op("fusion.2"): "jit(step)/while/body/GptLM/block_0/mlp/dot_general"}
    assert _scopes.ambiguous_in_file(str(path)) == [op("fusion.1")]


@pytest.mark.parametrize("path,unscoped", [
    ("", True), ("jit(step)/while", True), ("jit(step)/while/body/closed_call", True),
    ("jit(step)/while/body/closed_call/GptLM/embedding/convert_element_type", False),
    ("jit(train_step)/transpose(jvp(GptLM))/while/body/mul", False),
    ("jit(step)/whiled", False),
])
def test_unscoped_is_the_program_and_its_control_flow_alone(path, unscoped):
    assert _scopes.names_no_part(path) is unscoped


@pytest.mark.parametrize("path,name,inside", [
    ("jit(step)/while/body/GptLM/block_0/attention/kv_gather/gather", "kv_gather", True),
    ("jit(step)/while/body/GptLM/block_0/attention/kv_gather", "kv_gather", True),
    ("jit(train_step)/transpose(jvp(loss))/while/body/mul", "loss", True),
    ("jit(train_step)/optimizers/mul", "optimizer", False),
    ("jit(train_step)/my_optimizer/mul", "optimizer", False),
    ("", "optimizer", False),
])
def test_has_scope_matches_whole_components(path, name, inside):
    assert _scopes.has_scope(path, name) is inside


def test_idle_time_goes_to_the_innermost_engine_region():
    obs = serve_obs()
    idle = dict(_spans.idle_by_span(obs["trace"], obs["trace_window"], obs["serving_spans"]))
    # device busy 100-900 and 1600-1700: idle 0-100 (idle region), 900-1600
    # (midpoint 1250: the deliver region there), 1700-2000 (the last turn)
    assert idle == {ENGINE + "idle": pytest.approx(100e-9),
                    ENGINE + "deliver": pytest.approx(700e-9),
                    ENGINE + "turn": pytest.approx(300e-9)}
