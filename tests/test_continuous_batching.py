"""Continuous batching engine (serving/continuous.py, VERDICT r3 #8):
slot admission/retirement on a shared per-slot KV cache, exact greedy
equivalence with the static decode path, and queue overflow behavior."""

import numpy as np
import pytest

import jax

from kubeflow_tpu.models.gpt import GptConfig, GptLM, generate
from kubeflow_tpu.serving.continuous import ContinuousBatcher

CFG = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=128, vocab_size=101)


@pytest.fixture(scope="module")
def params():
    rng = jax.random.PRNGKey(0)
    sample = jax.random.randint(rng, (1, 8), 0, CFG.vocab_size)
    return GptLM(CFG).init(rng, sample)["params"]


def prompt(seed, n):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, CFG.vocab_size))


def test_greedy_tokens_match_static_generate(params):
    """The engine's per-slot cache math is exactly the static decode math —
    different prompt lengths riding the same running batch."""
    p1, p2, p3 = prompt(1, 7), prompt(2, 12), prompt(3, 30)
    refs = [
        np.asarray(generate(CFG, params, p[None, :], max_new_tokens=n))[0, len(p):].tolist()
        for p, n in ((p1, 10), (p2, 6), (p3, 9))
    ]
    eng = ContinuousBatcher(CFG, params, slots=2)  # 3 requests, 2 slots
    try:
        futs = [eng.submit(p1, 10), eng.submit(p2, 6), eng.submit(p3, 9)]
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.close()
    assert got == refs


def test_sequences_join_and_leave_mid_flight(params):
    """A late, short request admitted while a long one decodes must finish
    FIRST — the definition of continuous batching (no drain barrier)."""
    import threading
    import time

    # chunk=1/pipeline=1: one token per engine event, so the 100-token
    # request spans ~100 loop iterations and the short one verifiably
    # joins mid-flight even on a fast backend (a chunked engine can finish
    # the whole long request between two 10ms polls of this test)
    eng = ContinuousBatcher(CFG, params, slots=4, chunk=1, pipeline=1)
    order = []
    lock = threading.Lock()

    def run(name, fut):
        fut.result(timeout=180)
        with lock:
            order.append(name)

    try:
        f_long = eng.submit(prompt(1, 8), 100)
        # admit the short request only once the long one has verifiably
        # started producing tokens (event-based, not sleep-based: the
        # pipelined engine can finish many chunks inside a fixed sleep)
        deadline = time.time() + 120
        while not f_long.tokens and time.time() < deadline:
            time.sleep(0.01)
        assert f_long.tokens, "long request never started"
        f_short = eng.submit(prompt(2, 8), 3)
        threads = [threading.Thread(target=run, args=("long", f_long)),
                   threading.Thread(target=run, args=("short", f_short))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        eng.close()
    assert order and order[0] == "short", order


def test_eos_frees_the_slot_early(params):
    # greedy decode settles into a repeated token; using the static path's
    # 25th token as eos stops the request well before the 50-token budget
    # (derived, not hardcoded — the fixed point is backend-dependent)
    p = prompt(1, 7)
    eos = int(np.asarray(
        generate(CFG, params, p[None, :], max_new_tokens=25))[0, -1])
    eng = ContinuousBatcher(CFG, params, slots=2)
    try:
        f = eng.submit(p, 50, eos_id=eos)
        toks = f.result(timeout=120)
    finally:
        eng.close()
    assert toks[-1] == eos and len(toks) < 50


def test_oversize_prompt_rejected(params):
    eng = ContinuousBatcher(CFG, params, slots=1)
    try:
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(prompt(1, 120), 20)
    finally:
        eng.close()


def test_single_token_budget_completes_at_admit(params):
    eng = ContinuousBatcher(CFG, params, slots=1)
    try:
        toks = eng.submit(prompt(1, 7), 1).result(timeout=60)
    finally:
        eng.close()
    assert len(toks) == 1


def test_generative_model_continuous_predict_surface(params):
    """The HTTP predict surface rides the engine: concurrent requests share
    the running batch and return prompt+generated like the static path."""
    from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

    served = GenerativeModel(name="gpt-cont", apply_fn=None, params=params,
                             cfg=CFG, max_new_tokens=6, continuous=True, slots=2)
    server = ModelServer()
    server.add(served)
    try:
        p = prompt(1, 7)
        ref = np.asarray(generate(CFG, params, p[None, :], max_new_tokens=6))[0].tolist()
        resp = server.app.call(
            "POST", "/v1/models/gpt-cont:predict", {"instances": [p.tolist()]})
        assert resp.status == 200, resp.body
        assert resp.body["predictions"][0] == ref
    finally:
        served.close()


def test_failed_admission_does_not_leak_the_slot(params):
    """A prompt that passes the submit length check but exceeds every
    prefill bucket fails ONLY its own request; the slot stays usable."""
    big_cfg = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                        max_seq=512, vocab_size=101)
    rng = jax.random.PRNGKey(0)
    big_params = GptLM(big_cfg).init(
        rng, jax.random.randint(rng, (1, 8), 0, big_cfg.vocab_size))["params"]
    # prefill_chunk=0: chunked prefill (ISSUE 12) would otherwise SERVE
    # over-bucket prompts; with it disabled the admission fail-fast applies
    eng = ContinuousBatcher(big_cfg, big_params, slots=1, prefill_chunk=0)
    try:
        bad = eng.submit(prompt(1, 300), 32)  # 300 > largest bucket (256)
        with pytest.raises(ValueError, match="exceeds the largest prefill bucket"):
            bad.result(timeout=60)
        good = eng.submit(prompt(2, 7), 3)  # the single slot must still work
        assert len(good.result(timeout=120)) == 3
    finally:
        eng.close()


def test_close_fails_queued_and_future_requests(params):
    eng = ContinuousBatcher(CFG, params, slots=1)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(prompt(1, 7), 3)


def test_concurrent_submitters_and_midflight_close_all_resolve(params):
    """Stress: many threads submitting while close() lands mid-flight —
    every future must resolve (result or error), none may hang."""
    import threading

    eng = ContinuousBatcher(CFG, params, slots=2)
    outcomes = []
    lock = threading.Lock()

    def submitter(seed):
        try:
            f = eng.submit(prompt(seed, 7), 30)
            toks = f.result(timeout=120)
            with lock:
                outcomes.append(("ok", len(toks)))
        except Exception as e:  # record ANY failure — a dead thread would
            with lock:          # fail the count assert with no root cause
                outcomes.append(("err", type(e).__name__))

    try:
        threads = [threading.Thread(target=submitter, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        import time
        time.sleep(0.5)
    finally:
        eng.close()
    for t in threads:
        t.join(timeout=150)
    assert not any(t.is_alive() for t in threads), "a submitter hung"
    assert len(outcomes) == 12, outcomes
    # no TimeoutError: every request was either served or failed FAST
    assert all(o != ("err", "TimeoutError") for o in outcomes), outcomes


def test_mixed_greedy_and_sampled_slots(params):
    """A sampled request and a greedy request share the running batch:
    the greedy slot stays token-exact vs the static path while the sampled
    slot draws distinct sequences across requests."""
    eng = ContinuousBatcher(CFG, params, slots=2)
    try:
        p = prompt(1, 7)
        ref = np.asarray(generate(CFG, params, p[None, :], max_new_tokens=12))[0, 7:].tolist()
        greedy = eng.submit(p, 12)
        s1 = eng.submit(prompt(2, 7), 12, temperature=1.0)
        got_greedy = greedy.result(timeout=120)
        t1 = s1.result(timeout=120)
        # greedy unaffected by the sampled neighbor
        assert got_greedy == ref
        # two sampled requests with the SAME prompt draw different streams
        s2 = eng.submit(prompt(2, 7), 12, temperature=1.0)
        s3 = eng.submit(prompt(2, 7), 12, temperature=1.0)
        t2, t3 = s2.result(timeout=120), s3.result(timeout=120)
        assert t2 != t3 or t1 != t2, (t1, t2, t3)
        assert all(0 <= t < CFG.vocab_size for seq in (t1, t2, t3) for t in seq)
    finally:
        eng.close()


def test_slots_beyond_max_group_chunk_admission_waves(params):
    """An admission wave larger than MAX_GROUP must chunk into several
    prefill groups, not crash the whole wave (round-5 review finding:
    slots=10 + 10 concurrent submits used to fail every request with an
    IndexError from the padded prefill)."""
    from kubeflow_tpu.serving.continuous import MAX_GROUP

    slots = MAX_GROUP + 2
    p = prompt(7, 9)
    ref = np.asarray(generate(CFG, params, p[None, :],
                              max_new_tokens=5))[0, len(p):].tolist()
    eng = ContinuousBatcher(CFG, params, slots=slots)
    try:
        futs = [eng.submit(p, 5) for _ in range(slots)]
        got = [f.result(timeout=300) for f in futs]
    finally:
        eng.close()
    assert got == [ref] * slots


def test_generative_model_long_prompt_falls_back_to_static(params):
    """Prompts beyond the largest prefill bucket serve through the static
    generate() path rather than 413ing — the continuous default must not
    shrink the servable range below cfg.max_seq."""
    from kubeflow_tpu.serving.continuous import PREFILL_BUCKETS
    from kubeflow_tpu.serving.server import GenerativeModel

    big_cfg = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                        max_seq=PREFILL_BUCKETS[-1] + 64, vocab_size=101)
    rng = jax.random.PRNGKey(0)
    big_params = GptLM(big_cfg).init(
        rng, jax.random.randint(rng, (1, 8), 0, big_cfg.vocab_size))["params"]
    model = GenerativeModel(name="g", apply_fn=None, params=big_params,
                            cfg=big_cfg, max_new_tokens=4)
    assert model.continuous
    long_prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (1, PREFILL_BUCKETS[-1] + 16), 0,
        big_cfg.vocab_size))
    try:
        out = model.predict(long_prompt.tolist())
        ref = np.asarray(generate(big_cfg, big_params, long_prompt,
                                  max_new_tokens=4)).tolist()
        assert out == ref
    finally:
        model.close()


# -- paged KV + chunked prefill + speculative decoding (ISSUE 12) ------------

def _run_jobs(cfg, p, jobs, temperature=0.0, **kw):
    """Run [(prompt, budget)] through a fresh engine; returns token lists."""
    eng = ContinuousBatcher(cfg, p, **kw)
    try:
        futs = [eng.submit(pr, b, temperature=temperature) for pr, b in jobs]
        return [f.result(timeout=180) for f in futs]
    finally:
        eng.close()


MIXED_JOBS = [(1, 3, 6), (2, 17, 9), (3, 7, 4), (4, 30, 11), (5, 12, 5),
              (6, 5, 8), (7, 21, 7)]  # (seed, prompt_len, budget)


def test_paged_engine_bit_identical_to_contiguous(params):
    """The tentpole parity contract: the paged (block-arena) engine emits
    BIT-IDENTICAL greedy tokens to the contiguous parity path across mixed
    prompt lengths with retire/re-adopt churn (7 requests over 3 slots)."""
    jobs = [(prompt(s, n), b) for s, n, b in MIXED_JOBS]
    base = _run_jobs(CFG, params, jobs, slots=3, paged=False)
    paged = _run_jobs(CFG, params, jobs, slots=3, paged=True)
    assert base == paged


def test_decode_view_follows_the_longest_granted_row(params, engine_regions):
    """The paged dispatch hands the decode program the block table's first
    ``view_blocks`` columns only (max_seq 128, blocks of 16: widths 2, 4, 6,
    8). A 27-token prompt crosses position 32 in the middle of a 4-step
    chunk (the view widens at the dispatch that grants the block, before
    the crossing); it retires and a 60-token prompt takes its slot and
    crosses 64; when that retires the width falls back to the short rows'.
    The tokens stay the contiguous engine's, ``serving.engine.dispatch``
    says how wide each dispatch read, and the gauge follows it."""
    from kubeflow_tpu.runtime.metrics import METRICS

    jobs = [(prompt(1, 27), 14), (prompt(2, 5), 22), (prompt(3, 60), 10),
            (prompt(4, 6), 24)]
    kw = dict(slots=2, chunk=4, pipeline=1)
    base = _run_jobs(CFG, params, jobs, paged=False, **kw)
    log = engine_regions
    log.clear()                    # the contiguous run's regions
    paged = _run_jobs(CFG, params, jobs, paged=True, engine_id="view", **kw)
    assert base == paged
    dispatch = [stats for name, stats in log if name == "serving.engine.dispatch"]
    views = [d["view_blocks"] for d in dispatch]
    assert all(d["max_blocks"] == 8 and d["view_blocks"] <= 8 for d in dispatch)
    assert {2, 4, 6} <= set(views) <= {2, 4, 6, 8}
    assert views[0] == 2 and views[-1] == 2          # widens, and falls again
    assert METRICS.gauge("serving_decode_view_blocks", replica="view").value == views[-1]
    # the contiguous engine has no table: its dispatches carry neither stat
    log.clear()
    _run_jobs(CFG, params, jobs[:1], paged=False, **kw)
    assert all("view_blocks" not in stats for name, stats in log
               if name == "serving.engine.dispatch")


def test_prewarm_compiles_every_view_width_and_leaves_the_engine_sound(params):
    """Which width a dispatch takes depends on the traffic, so the first
    prewarm of a paged engine runs the decode program at every width (on
    an all-trash table, with no slot active). Afterwards no width is new
    to jit, and requests decode as on an engine never prewarmed."""
    jobs = [(prompt(s, n), b) for s, n, b in MIXED_JOBS[:4]]
    base = _run_jobs(CFG, params, jobs, slots=2, paged=False)
    eng = ContinuousBatcher(CFG, params, slots=2, paged=True)
    try:
        assert eng.kv.view_widths == (2, 4, 6, 8) and eng._view_warmup == "no"
        eng.prewarm(8)
        assert eng._view_warmup == "done"
        compiled = eng._step_fn._cache_size()
        assert compiled == len(eng.kv.view_widths)
        futs = [eng.submit(pr, b) for pr, b in jobs]
        assert [f.result(timeout=180) for f in futs] == base
        eng.prewarm(8)                                # once is enough
        assert eng._step_fn._cache_size() == compiled
    finally:
        eng.close()


def test_tiny_arena_backpressure_completes_all_and_stays_bit_identical(params):
    """An arena far smaller than slots*max_blocks forces admission
    back-pressure (requests wait for retirements to free blocks). Every
    request must still complete, with the SAME tokens — back-pressure may
    delay work but never corrupt a write."""
    jobs = [(prompt(s, n), b) for s, n, b in MIXED_JOBS]
    base = _run_jobs(CFG, params, jobs, slots=3, paged=False)
    # bt=16, max_seq=128 -> 8 blocks/slot capacity; 6 blocks total means
    # at most ~2 mixed requests hold reservations concurrently
    tight = _run_jobs(CFG, params, jobs, slots=3, paged=True, kv_blocks=6)
    assert base == tight


def test_arena_too_small_for_request_fails_fast_at_submit(params):
    """A request whose prompt+budget can NEVER fit the arena must fail at
    submit (waiting on retirements cannot help), not pend forever."""
    eng = ContinuousBatcher(CFG, params, slots=2, paged=True, kv_blocks=2)
    try:
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(prompt(1, 30), 30)  # needs 4 blocks of 16
        # the engine stays fully usable afterwards
        assert len(eng.submit(prompt(2, 7), 3).result(timeout=120)) == 3
    finally:
        eng.close()


def test_chunked_prefill_bit_identical_and_counted(params):
    """prefill_chunk smaller than the prompts: admission runs multiple
    interleaved chunk dispatches, the serving_prefill_chunks_total counter
    ticks, and the tokens stay bit-identical to the contiguous path."""
    from kubeflow_tpu.runtime.metrics import METRICS

    jobs = [(prompt(s, n), b) for s, n, b in MIXED_JOBS]
    base = _run_jobs(CFG, params, jobs, slots=3, paged=False)
    before = METRICS.counter("serving_prefill_chunks_total").value
    chunked = _run_jobs(CFG, params, jobs, slots=3, paged=True,
                        prefill_chunk=16)
    assert base == chunked
    # prompts of 17, 21 and 30 tokens exceed the 16-token chunk budget:
    # 2 chunks each (chunk 16 divides max_seq 128)
    assert METRICS.counter("serving_prefill_chunks_total").value - before >= 6


def test_spec_decode_greedy_bit_identical_and_counted(params):
    """Draft/verify speculative decoding with accept-prefix semantics:
    greedy output is bit-identical to plain decode (every accepted token
    is one plain greedy decode would emit), and the drafted/accepted
    counters expose the accept rate."""
    from kubeflow_tpu.runtime.metrics import METRICS

    draft_cfg = GptConfig(d_model=16, n_layers=1, n_heads=2, d_ff=32,
                          max_seq=128, vocab_size=101)
    rng = jax.random.PRNGKey(42)
    draft_params = GptLM(draft_cfg).init(
        rng, jax.random.randint(rng, (1, 8), 0, 101))["params"]
    jobs = [(prompt(s, n), b) for s, n, b in MIXED_JOBS[:4]]
    base = _run_jobs(CFG, params, jobs, slots=2, paged=False)
    drafted0 = METRICS.counter("serving_spec_tokens_drafted_total").value
    spec = _run_jobs(CFG, params, jobs, slots=2, paged=True,
                     spec_draft=(draft_cfg, draft_params), spec_k=4)
    assert base == spec
    drafted = METRICS.counter("serving_spec_tokens_drafted_total").value
    accepted = METRICS.counter("serving_spec_tokens_accepted_total").value
    assert drafted > drafted0 and accepted >= 0


def test_spec_decode_sampled_slots_respect_budget(params):
    """Sampled requests ride spec rounds one accepted token at a time —
    liveness + budget, not parity (sampling draws fresh keys per engine)."""
    draft_cfg = GptConfig(d_model=16, n_layers=1, n_heads=2, d_ff=32,
                          max_seq=128, vocab_size=101)
    rng = jax.random.PRNGKey(43)
    draft_params = GptLM(draft_cfg).init(
        rng, jax.random.randint(rng, (1, 8), 0, 101))["params"]
    jobs = [(prompt(9, 7), 6), (prompt(11, 12), 4)]
    out = _run_jobs(CFG, params, jobs, temperature=0.8, slots=2, paged=True,
                    spec_draft=(draft_cfg, draft_params), spec_k=3)
    assert [len(t) for t in out] == [6, 4]


def test_overbucket_prompt_serves_via_chunked_prefill(params):
    """Chunked prefill extends the ENGINE's servable range past the
    largest prefill bucket: a 300-token prompt decodes through the engine
    (no static fallback) and matches static generate exactly — while a
    short chatty request admitted behind it still completes (decode
    interleaves between prefill chunks)."""
    from kubeflow_tpu.serving.continuous import PREFILL_BUCKETS

    big_cfg = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                        max_seq=2 * PREFILL_BUCKETS[-1], vocab_size=101)
    rng = jax.random.PRNGKey(0)
    big_params = GptLM(big_cfg).init(
        rng, jax.random.randint(rng, (1, 8), 0, 101))["params"]
    long_p = np.asarray(jax.random.randint(
        jax.random.PRNGKey(8), (PREFILL_BUCKETS[-1] + 44,), 0, 101))
    short_p = prompt(9, 7)
    ref_long = np.asarray(generate(
        big_cfg, big_params, long_p[None, :],
        max_new_tokens=5))[0, len(long_p):].tolist()
    ref_short = np.asarray(generate(
        big_cfg, big_params, short_p[None, :],
        max_new_tokens=5))[0, len(short_p):].tolist()
    eng = ContinuousBatcher(big_cfg, big_params, slots=2, paged=True)
    try:
        f_long = eng.submit(long_p, 5)
        f_short = eng.submit(short_p, 5)
        assert f_long.result(timeout=180) == ref_long
        assert f_short.result(timeout=180) == ref_short
    finally:
        eng.close()


def test_http_unservable_request_is_400_not_500(params):
    """ISSUE-12 regression: a structurally unservable request (needs more
    KV blocks than the arena holds) surfaces as a client-side 400 through
    the HTTP predict surface — never a 500."""
    from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

    served = GenerativeModel(name="gpt-tiny-arena", apply_fn=None,
                             params=params, cfg=CFG, max_new_tokens=30,
                             continuous=True, slots=2, kv_blocks=2)
    server = ModelServer()
    server.add(served)
    try:
        resp = server.app.call(
            "POST", "/v1/models/gpt-tiny-arena:predict",
            {"instances": [prompt(1, 30).tolist()]})
        assert resp.status == 400, resp.body
        assert "KV blocks" in str(resp.body)
    finally:
        served.close()


# -- int8 KV arena + prefill/decode handoff (ISSUE 18) ------------------------


def _self_draft(n_layers=1):
    """The truncated-stack draft serving_bench uses: bottom blocks +
    embeddings of the target."""
    draft_cfg = GptConfig(d_model=CFG.d_model, n_layers=n_layers,
                          n_heads=CFG.n_heads, d_ff=CFG.d_ff,
                          max_seq=CFG.max_seq, vocab_size=CFG.vocab_size)
    return draft_cfg


@pytest.mark.slow
def test_int8_arena_greedy_parity_with_bf16_oracle(params):
    """int8 KV halves arena bytes; greedy decode must stay within the
    tested tolerance of the bf16 oracle — on this config the quantization
    error never flips an argmax, so the tolerance is EXACT token equality
    (any weakening of the quantizer shows up as a diff here)."""
    prompts = [prompt(40 + i, 6 + i) for i in range(4)]
    outs = {}
    for dt in ("bf16", "int8"):
        eng = ContinuousBatcher(CFG, params, slots=2, chunk=2, pipeline=1,
                                kv_dtype=dt, engine_id=f"q-{dt}")
        try:
            outs[dt] = [eng.submit(p, 12).result(timeout=300)
                        for p in prompts]
        finally:
            eng.close()
    assert outs["int8"] == outs["bf16"]
    # bf16 stays the bit-parity ground truth against static decode
    for p, toks in zip(prompts, outs["bf16"]):
        ref = np.asarray(generate(CFG, params, p[None, :], 12))[0, len(p):]
        assert toks == ref.tolist()


def test_int8_rejected_without_paged_arena(params):
    with pytest.raises(ValueError, match="int8"):
        ContinuousBatcher(CFG, params, paged=False, kv_dtype="int8")


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mode", ["plain", "chunked"])
def test_handoff_pair_bit_identical_to_never_moved(params, kv_dtype, mode):
    """An engine pair wired prefill → decode through the KV wire must
    produce byte-identical greedy output to a unified engine that never
    exported anything — for both arena dtypes, with and without chunked
    prefill on the exporting side."""
    kw = dict(slots=2, chunk=2, pipeline=1, kv_dtype=kv_dtype)
    if mode == "chunked":
        kw["prefill_chunk"] = 4
    unified = ContinuousBatcher(CFG, params, engine_id="u", **kw)
    decode = ContinuousBatcher(CFG, params, engine_id="d", role="decode",
                               **kw)
    prefill = ContinuousBatcher(CFG, params, engine_id="p", role="prefill",
                                handoff_sink=lambda req, blob:
                                decode.submit_handoff(req, blob), **kw)
    try:
        prompts = [prompt(50 + i, 5 + 2 * i) for i in range(3)]
        want = [unified.submit(p, 8).result(timeout=300) for p in prompts]
        futs = [prefill.submit(p, 8) for p in prompts]
        assert [f.result(timeout=300) for f in futs] == want
    finally:
        prefill.close()
        decode.close()
        unified.close()


@pytest.mark.slow
def test_handoff_with_speculative_decode_stays_greedy_exact(params):
    """The decode specialist re-prefills its DRAFT locally after an
    import; speculative verification must still commit exactly the
    unified engine's greedy tokens."""
    draft_cfg = _self_draft()
    draft_params = {k: v for k, v in params.items()
                    if not k.startswith("block_")}
    draft_params["block_0"] = params["block_0"]
    kw = dict(slots=2, chunk=2, pipeline=1,
              spec_draft=(draft_cfg, draft_params), spec_k=3)
    unified = ContinuousBatcher(CFG, params, engine_id="su", **kw)
    decode = ContinuousBatcher(CFG, params, engine_id="sd", role="decode",
                               **kw)
    prefill = ContinuousBatcher(CFG, params, engine_id="sp", role="prefill",
                                handoff_sink=lambda req, blob:
                                decode.submit_handoff(req, blob), **kw)
    try:
        p = prompt(60, 7)
        want = unified.submit(p, 10).result(timeout=300)
        assert prefill.submit(p, 10).result(timeout=300) == want
    finally:
        prefill.close()
        decode.close()
        unified.close()


def test_kv_wire_frame_round_trip_and_crc():
    from kubeflow_tpu.serving.kv_wire import pack, unpack

    arrays = {"layer0/k": np.arange(24, dtype=np.float32).reshape(2, 3, 4)}
    blob = pack({"version": 1, "prompt_len": 5}, arrays)
    meta, out = unpack(blob)
    assert meta["prompt_len"] == 5
    np.testing.assert_array_equal(out["layer0/k"], arrays["layer0/k"])
    # a flipped payload byte must fail the per-array crc32, loudly
    bad = bytearray(blob)
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError, match="crc"):
        unpack(bytes(bad))


# -- an event's width: a chunk, a speculative round, the blocks a slot committed ------

def _event_engine(kind, params):
    """(engine, tokens the request's FIRST event brings, prompt)."""
    if kind == "blocks":
        from kubeflow_tpu.models import sdar

        cfg = sdar.SdarConfig.tiny()
        tree = sdar.init_params(cfg, jax.random.PRNGKey(3))
        # a prompt of 10: its tail of 2 opens the first block, which hands out 2
        return (ContinuousBatcher(cfg, tree, slots=2, chunk=5, kv_block_t=4, prefill_chunk=16),
                2, np.arange(1, 11, dtype=np.int32))
    kw = {}
    if kind == "speculative":
        draft_cfg = _self_draft()
        rng = jax.random.PRNGKey(42)
        kw = {"spec_draft": (draft_cfg, GptLM(draft_cfg).init(
            rng, jax.random.randint(rng, (1, 8), 0, CFG.vocab_size))["params"]), "spec_k": 4}
    return ContinuousBatcher(CFG, params, slots=2, chunk=4, **kw), 1, prompt(5, 10)


@pytest.mark.parametrize("kind", ["token_a_step", "speculative", "blocks"])
def test_an_events_width_ttft_and_gaps(params, kind):
    """Whatever the event's width is (a whole chunk, a round's accepted
    prefix, the blocks a slot committed): a request gets exactly its budget
    (the surplus of its last event is discarded), TTFT is stamped once, at
    the event that brings its first token, and every token after that
    event's counts one gap. Only a speculative round counts drafts."""
    from kubeflow_tpu.runtime.metrics import METRICS

    count = lambda name: (METRICS.histogram_counts(name) or (0, 0, 0))[2]
    eng, first, p = _event_engine(kind, params)
    ttft, gaps = count("serving_ttft_seconds"), count("serving_inter_token_seconds")
    out0 = METRICS.total("serving_tokens_out_total")
    drafted0 = METRICS.total("serving_spec_tokens_drafted_total")
    try:
        fut = eng.submit(p, 11)
        toks = fut.result(timeout=600)
    finally:
        eng.close()
    assert len(toks) == 11 and fut.finish_reason == "ok"
    assert count("serving_ttft_seconds") == ttft + 1
    assert count("serving_inter_token_seconds") == gaps + 11 - first
    assert METRICS.total("serving_tokens_out_total") == out0 + 11
    assert (METRICS.total("serving_spec_tokens_drafted_total") > drafted0) == (
        kind == "speculative")
    assert len(fut.reveal_passes) == (11 if kind == "blocks" else 0)


@pytest.mark.parametrize("family", ["blocks", "token_a_step"])
def test_the_reply_carries_the_reveal_passes_when_the_request_asks(params, family):
    """``"reveal_passes": true`` in the body: a row a prompt beside
    ``predictions``, the engine's mark beside each generated token from a
    family that hands one out, empty from one that does not; a body that
    does not ask gets ``predictions`` alone."""
    from kubeflow_tpu.models import sdar
    from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

    if family == "blocks":
        cfg = sdar.SdarConfig.tiny()
        tree, kw = sdar.init_params(cfg, jax.random.PRNGKey(3)), {"kv_block_t": 4,
                                                                  "prefill_chunk": 16}
    else:
        cfg, tree, kw = CFG, params, {}
    served = GenerativeModel(name="m", apply_fn=None, params=tree, cfg=cfg,
                             max_new_tokens=6, slots=2, **kw)
    server = ModelServer()
    server.add(served)
    try:
        body = {"instances": [list(range(1, 8))]}
        plain = server.app.call("POST", "/v1/models/m:predict", body)
        asked = server.app.call("POST", "/v1/models/m:predict", {**body, "reveal_passes": True})
    finally:
        served.close()
    assert plain.status == asked.status == 200, (plain.body, asked.body)
    assert set(plain.body) == {"predictions"}
    assert asked.body["predictions"] == plain.body["predictions"]
    (marks,) = asked.body["reveal_passes"]
    if family == "blocks":
        assert len(marks) == 6 and all(1 <= m <= 4 for m in marks)
    else:
        assert marks == []
