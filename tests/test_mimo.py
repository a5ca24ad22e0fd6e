"""The MiMo model family on the serving path (models/mimo.py, the dropless
expert layer of parallel/moe.py, serving/family.py, the window kind of
cache in serving/paged.py) at a tiny size on the CPU, against the plain
float32 reference of benchmark/reference/mimo.py on seeded weights.

The comparison with the reference is of LOGITS, not of sampled tokens: a
served token is judged by how far its reference logit lies under the
reference's best at that position, in standard deviations of the
position's logits (0: the reference would have chosen it too).
"""

import dataclasses
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import weights_mimo
from benchmark.reference import mimo as ref
from kubeflow_tpu.models import mimo
from kubeflow_tpu.models.gpt import GptConfig, GptLM
from kubeflow_tpu.models.mimo import MimoConfig
from kubeflow_tpu.parallel.moe import held_experts_ffn, sigmoid_top_k
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.paged import KVBlocksExhausted, WindowRings

# hidden 64, 8 query heads, KV heads 2 / 4, qk 24 / v 16, window 8, 16
# experts top 4 (all held), pattern full-window-window-full
CFG = MimoConfig.tiny()
SEED = 2**31 + 5
NEW = 12

#: The program computes in bfloat16 and the reference in float32. At this
#: size the served tokens' worst gap under the reference's best reads
#: 0.00-0.01 sd over these prompts (a flip needs a near-tie); over every
#: position of three 120-token sequences the float8 control reads 0.10-0.17
#: sd, a model that chooses one expert too few 0.036-0.071, one without the
#: sink 1.0-1.3, one without the window 1.6-2.4. The limit sits between the
#: program and the least of those.
GAP_LIMIT_SD = 0.02


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name != "dtype"}


SIZES = sizes_of(CFG)


@pytest.fixture(scope="module")
def params():
    return weights_mimo.program_tree(SEED, SIZES)


@functools.lru_cache(maxsize=None)
def _top():
    return weights_mimo.top_canonical(SEED, SIZES)


def reference_logits(seq, cast=None, fault=None):
    """The reference's full forward pass over one sequence: [len, vocab]."""
    pad = -len(seq) % 8
    x = _top()["embedding"][jnp.asarray(list(seq) + [0] * pad)]
    for i, (kind, moe) in enumerate(zip(CFG.layer_kinds, CFG.moe_layers)):
        w = weights_mimo.layer_canonical(SEED, SIZES, i)
        x = ref.block(SIZES, w, x, window=bool(kind), moe=bool(moe), cast=cast, fault=fault)
    return np.asarray(ref.logits_at(SIZES, _top(), x, cast))[:len(seq)]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).astype(np.int32)


def engine(params, **kw):
    kw = {"slots": 4, "chunk": 4, "kv_block_t": 4, "prefill_chunk": 16, **kw}
    return ContinuousBatcher(CFG, params, **kw)


def served_gaps(p, out):
    logits = reference_logits(list(p) + list(out))
    at = len(p) - 1 + np.arange(len(out))
    return np.asarray(ref.gaps_under_best(jnp.asarray(logits[at]), jnp.asarray(out)))


# -- the served path against the reference --------------------------------------

@pytest.mark.parametrize("n", [5, 12, 16, 37, 70],
                         ids=["under_window", "over_window", "one_chunk", "three_chunks",
                              "five_chunks"])
def test_prefill_then_paged_decode_agrees_with_the_reference(params, n):
    """Prompts shorter and longer than the window (8) and longer than a
    prefill chunk (16), then 12 decode steps through the paged cache of two
    kinds, against the reference's full forward pass."""
    eng = engine(params)
    try:
        p = prompt(n, n)
        out = eng.submit(p, NEW).result(timeout=600)
    finally:
        eng.close()
    assert len(out) == NEW and all(0 <= t < CFG.vocab_size for t in out)
    assert served_gaps(p, out).max() <= GAP_LIMIT_SD


def _wrong_reading(**kw):
    """The worst gap, under the reference, of the tokens a variant of the
    reference puts first, over every position of three sequences."""
    worst = 0.0
    for seed in (1, 2, 3):
        seq = prompt(seed, 120)
        logits = reference_logits(seq)
        wrong = reference_logits(seq, **kw)
        gaps = ref.gaps_under_best(jnp.asarray(logits), jnp.asarray(wrong.argmax(-1)))
        worst = max(worst, float(gaps.max()))
    return worst


def test_the_limit_is_tight_enough_that_float8_operands_fail_it():
    """The tokens the reference puts first when its matmul operands are
    rounded to float8 e4m3 (one scale a tensor): over the limit, with room."""
    assert _wrong_reading(cast=ref.fp8_cast) > 4 * GAP_LIMIT_SD


@pytest.mark.parametrize("fault", ["no_window", "no_sink", "top7"])
def test_a_wrong_model_reads_over_the_limit(fault):
    """The window ignored, the sink left out, one expert fewer chosen: the
    tokens such a model puts first lie over the limit under the reference."""
    assert _wrong_reading(fault=fault) > 2 * GAP_LIMIT_SD


def test_decode_logits_agree_with_the_reference(params):
    """The two device programs by hand, without the engine: one prompt
    prefilled in two chunks into arenas of both kinds, then decoded step
    by step with the reference's own next tokens; every position's logits
    against the reference's (bfloat16 against float32: under 0.05 sd of
    the position's logits, where a wrong mask or position reads over 1)."""
    bt, c, n, steps = 4, 16, 21, 10
    seq = prompt(3, n + steps)
    want = reference_logits(seq)
    rings = WindowRings(1, CFG.window, bt, 1)
    cache = mimo.fresh_cache(CFG, 1, {mimo.FULL: 32, mimo.WINDOW: rings.alloc.n_blocks}, bt)
    rings.attach(0, rings.reserve())
    table = np.arange(32, dtype=np.int32)             # full kind: block b in row b
    chunk = jax.jit(functools.partial(mimo.prefill_chunk, CFG))
    for start in range(0, n, c):
        end = min(start + c, n)
        ids = np.zeros((c,), np.int32)
        ids[:end - start] = seq[start:end]
        read_window = rings.row(0).copy()
        rings.advance(0, end, end)
        first = start // bt
        write_window = np.asarray([rings.block_of(0, first + j) for j in range(c // bt)])
        write_full = np.where(np.arange(first, first + c // bt) < -(-end // bt),
                              table[first:first + c // bt], 32)
        logits, cache, _ = chunk(params, cache, jnp.asarray(ids), start, end - start,
                                 jnp.asarray(table[:16]), jnp.asarray(write_full),
                                 jnp.asarray(read_window), jnp.asarray(write_window))
    got = [np.asarray(logits)]
    cache["cursors"] = jnp.asarray([n], jnp.int32)
    step = jax.jit(functools.partial(mimo.decode_step, CFG,
                                     trash={mimo.FULL: 32, mimo.WINDOW: rings.trash}))
    for t in range(n, n + steps - 1):
        rings.advance(0, t, t + 1)
        logits, cache, _ = step(params, cache, jnp.asarray(seq[t:t + 1]),
                                jnp.asarray(table[None, :16]), jnp.asarray(rings.tables),
                                jnp.ones((1,), bool))
        got.append(np.asarray(logits[0]))
        assert len(rings._held[0]) <= -(-CFG.window // bt) + 1     # one step a dispatch
    got = np.stack(got)
    want = want[n - 1:n - 1 + steps]
    assert np.abs(got - want).max() / want.std(-1).min() < 0.05, np.abs(got - want).max() / want.std(-1).min()


@pytest.fixture(scope="module")
def ragged_batch(params):
    """Rows of 5, 37 and 70 positions and a dead slot, 16 steps a dispatch."""
    eng = engine(params, chunk=16)
    try:
        futs = {n: eng.submit(prompt(n, n), 20) for n in (5, 37, 70)}
        return {n: f.result(timeout=600) for n, f in futs.items()}
    finally:
        eng.close()


@pytest.mark.parametrize("n", [5, 37, 70])
def test_a_dispatch_of_16_steps_over_ragged_rows_agrees_with_the_reference(ragged_batch, n):
    """The engine's decode program as the cell runs it: a scan of 16 steps
    over rows of different lengths in one batch, each full-attention row
    reading its own pages (``ops/paged_attention``)."""
    out = ragged_batch[n]
    assert len(out) == 20 and served_gaps(prompt(n, n), out).max() <= GAP_LIMIT_SD


def test_staggered_arrivals_match_one_at_a_time_while_blocks_come_and_go(params):
    """Greedy tokens of requests that join a running batch at different
    times equal the tokens of the same requests served alone, while the
    window kind gives blocks back and is granted them again."""
    prompts = [prompt(10 + i, n) for i, n in enumerate((9, 40, 18, 55, 6))]
    budgets = [24, 30, 20, 16, 28]
    eng = engine(params, slots=1)
    try:
        alone = [eng.submit(p, b).result(timeout=600) for p, b in zip(prompts, budgets)]
    finally:
        eng.close()
    eng = engine(params, slots=3)
    given_back = []
    orig = eng.kv.rings.alloc.give_back
    eng.kv.rings.alloc.give_back = lambda res, blk: (given_back.append(blk), orig(res, blk))[1]
    try:
        futs = []
        for p, b in zip(prompts, budgets):
            futs.append(eng.submit(p, b))
            time.sleep(0.05)
        together = [f.result(timeout=600) for f in futs]
        assert eng.kv.rings.used() == 0 and eng.kv.alloc.used() == 0
    finally:
        eng.close()
    assert together == alone
    # 5 rows of 30-70 positions on rings of a dozen: blocks went back and
    # the same ids were granted again
    assert len(given_back) > 20 and len(set(given_back)) < len(given_back)


def test_generative_model_serves_the_family_over_the_predict_surface(params):
    """``GenerativeModel(cfg=MimoConfig(...))`` behind ``ModelServer``: the
    same engine class and knobs, the family found from the config's type."""
    from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

    served = GenerativeModel(name="mimo", apply_fn=None, params=params, cfg=CFG,
                             max_new_tokens=NEW, slots=2, kv_block_t=4, prefill_chunk=16,
                             kv_blocks=64)
    server = ModelServer()
    server.add(served)
    try:
        p = prompt(1, 23)
        resp = server.app.call("POST", "/v1/models/mimo:predict", {"instances": [p.tolist()]})
        assert resp.status == 200, resp.body
        reply = resp.body["predictions"][0]
        assert type(served._continuous_engine()) is ContinuousBatcher
    finally:
        served.close()
    assert reply[:23] == p.tolist() and len(reply) == 23 + NEW
    assert served_gaps(p, reply[23:]).max() <= GAP_LIMIT_SD


def test_the_engine_refuses_what_the_family_has_not_built(params):
    for kw in ({"paged": False}, {"kv_dtype": "int8"}, {"role": "prefill"},
               {"prefill_chunk": 0}):
        with pytest.raises(ValueError):
            ContinuousBatcher(CFG, params, slots=2, **kw)


# -- the expert layer --------------------------------------------------------------

def _expert_case(tokens=24, seed=0):
    s = dict(SIZES, held_experts=CFG.n_experts)
    w = weights_mimo.layer_canonical(SEED, s, 1)           # an expert layer, all 16 held
    h = jax.random.normal(jax.random.PRNGKey(seed), (tokens, CFG.d_model), jnp.float32)
    return s, w, h


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """4 shares of 4 experts: the parts of the result that all shares give
    add up to what the reference gives for the whole layer."""
    s, w, h = _expert_case()
    whole = ref.expert_layer(s, w, h, None, None)
    idx, wts = sigmoid_top_k(h, w["router"], w["router_bias"], CFG.experts_per_token)
    total, on_held = 0.0, 0
    for share in range(4):
        held = slice(4 * share, 4 * share + 4)
        part, stats = held_experts_ffn(h, idx, wts, w["w_gate"][held], w["w_up"][held],
                                       w["w_down"][held], first_held=4 * share)
        total = total + part
        on_held += int(stats[0])
        # the reference, given the same share, gives the same part
        cut = {**w, **{k: w[k][held] for k in ("w_gate", "w_up", "w_down")}}
        np.testing.assert_allclose(part, ref.expert_layer(s, cut, h, None, None, 4 * share),
                                   atol=2e-5 * float(jnp.abs(whole).max()))
    assert on_held == h.shape[0] * CFG.experts_per_token        # every assignment, once
    np.testing.assert_allclose(total, whole, atol=2e-5 * float(jnp.abs(whole).max()))


def test_dropless_when_every_token_goes_to_one_held_expert():
    """Total skew: a router bias that sends every token's first choice to
    expert 2. No capacity, so nothing is dropped: the result is the
    reference's, and the busiest expert's count is the token count."""
    s, w, h = _expert_case(tokens=40)
    w = dict(w, router_bias=w["router_bias"].at[2].set(10.0))
    idx, wts = sigmoid_top_k(h, w["router"], w["router_bias"], CFG.experts_per_token)
    assert bool((idx == 2).any(axis=1).all())
    got, stats = held_experts_ffn(h, idx, wts, w["w_gate"][:4], w["w_up"][:4], w["w_down"][:4])
    cut = {**w, **{k: w[k][:4] for k in ("w_gate", "w_up", "w_down")}}
    want = ref.expert_layer(s, cut, h, None, None)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    assert int(stats[1]) == 40 and int(stats[2]) >= 1


def test_no_assignment_on_a_held_expert_gives_zero_and_divides_by_nothing():
    s, w, h = _expert_case()
    idx, wts = sigmoid_top_k(h, w["router"], w["router_bias"], CFG.experts_per_token)
    got, stats = held_experts_ffn(h, idx, wts, w["w_gate"][:4], w["w_up"][:4], w["w_down"][:4],
                                  first_held=CFG.n_experts + 3)          # nobody's ids
    assert np.array_equal(np.asarray(got), np.zeros_like(got)) and stats.tolist() == [0, 0, 0]
    dead, stats = held_experts_ffn(h, idx, wts, w["w_gate"], w["w_up"], w["w_down"],
                                   live=jnp.zeros((h.shape[0],), bool))   # no live row
    assert np.array_equal(np.asarray(dead), np.zeros_like(dead)) and stats.tolist() == [0, 0, 0]


def test_router_bias_enters_the_choice_only():
    _, w, h = _expert_case()
    idx0, w0 = sigmoid_top_k(h, w["router"], jnp.zeros_like(w["router_bias"]), 4)
    idx1, w1 = sigmoid_top_k(h, w["router"], w["router_bias"].at[5].add(10.0), 4)
    assert bool((idx1 == 5).any(axis=1).all()) and not bool((idx0 == 5).any(axis=1).all())
    np.testing.assert_allclose(w1.sum(-1), 1.0, atol=1e-6)
    scores = jax.nn.sigmoid(h @ w["router"])                 # weights are the raw scores'
    picked = jnp.take_along_axis(scores, idx1, axis=-1)
    np.testing.assert_allclose(w1, picked / picked.sum(-1, keepdims=True), atol=1e-5)


# -- two kinds of cache, accounted by kind ------------------------------------------

@pytest.mark.parametrize("lookahead,cols", [(1, 3), (4, 4), (16, 7)])
def test_a_ring_never_holds_more_than_its_columns(lookahead, cols):
    """window 8, blocks of 4: ``ceil((8 + lookahead - 1) / 4) + 1`` blocks a
    slot, which with one step a dispatch is ``ceil(window / block_t) + 1``."""
    rings = WindowRings(2, 8, 4, lookahead)
    assert rings.cols == cols
    if lookahead == 1:
        assert rings.cols == -(-8 // 4) + 1
    rings.attach(0, rings.reserve())
    cursor, most = 0, 0
    for _ in range(40):
        rings.advance(0, cursor, cursor + lookahead)
        held = rings._held[0]
        most = max(most, len(held))
        # every position a dispatch reads or writes has its block
        for p in range(max(0, cursor - 7), cursor + lookahead):
            assert rings.tables[0, (p // 4) % cols] == held[p // 4]
        cursor += lookahead
    assert most <= cols and rings.used() == len(rings._held[0])
    assert rings.unreleased() == -(-cursor // 4)
    rings.release(0)
    assert rings.used() == 0 and rings.alloc.available() == rings.alloc.n_blocks
    assert (rings.tables == rings.trash).all()


def test_a_ring_goes_to_trash_before_its_blocks_return():
    """Retire ordering, for a block given back and for a whole ring: the
    table no longer names a block when the free list gets it."""
    rings = WindowRings(1, 8, 4, 4)
    rings.attach(0, rings.reserve())
    seen = []
    give_back, release = rings.alloc.give_back, rings.alloc.release
    rings.alloc.give_back = lambda res, blk: (seen.append(blk in rings.tables), give_back(res, blk))
    rings.alloc.release = lambda res: (
        seen.extend(b in rings.tables for b in res.granted), release(res))
    for cursor in range(0, 40, 4):
        rings.advance(0, cursor, cursor + 4)
    rings.release(0)
    assert seen and not any(seen)


def test_admission_reserves_both_kinds_and_waits_for_a_ring(params):
    """Rings for two slots and three requests: the third stays pending
    (back-pressure, not an error) until a ring comes back with its slot;
    all finish, and both free lists are whole afterwards."""
    eng = engine(params, slots=2)
    assert eng.kv.rings.cols == 4 and eng.kv.rings.alloc.n_blocks == 8
    room = []
    reserve = eng.kv.rings.reserve
    eng.kv.rings.reserve = lambda: (room.append(eng.kv.rings.alloc.available()), reserve())[1]
    try:
        futs = [eng.submit(prompt(1, 20), 16), eng.submit(prompt(2, 9), 8),
                eng.submit(prompt(3, 13), 6)]
        assert [len(f.result(timeout=600)) for f in futs] == [16, 8, 6]
        assert eng.kv.rings.used() == 0 and eng.kv.alloc.used() == 0
        assert eng.kv.rings.alloc.available() == 8
        full = eng.kv.alloc.blocks_for(20 + 16)
        assert full == 9                                         # ceil((prompt + budget) / 4)
    finally:
        eng.close()
    # every admission found its ring free: a slot and its ring come together
    assert len(room) == 3 and min(room) >= 4
    with pytest.raises(KVBlocksExhausted):
        rings = WindowRings(2, 8, 4, 4)
        rings.reserve(), rings.reserve(), rings.reserve()


@pytest.mark.parametrize("slots,chunk,bt,cols", [(1, 1, 4, 3), (2, 4, 4, 4), (3, 8, 2, 9)])
def test_the_window_arena_is_a_whole_ring_for_every_slot(params, slots, chunk, bt, cols):
    """No knob sizes the window kind: ``cols = ceil((w + chunk - 1) / bt) + 1``
    blocks a slot (w = 8), ``slots * cols`` in the arena and one trash block
    more in every window layer's arenas on the device."""
    eng = engine(params, slots=slots, chunk=chunk, kv_block_t=bt)
    try:
        assert eng.kv.rings.cols == cols == -(-(CFG.window + chunk - 1) // bt) + 1
        assert eng.kv.rings.alloc.n_blocks == slots * cols
        shapes = {leaf.shape[0] for leaf in jax.tree.leaves(eng.cache) if leaf.ndim == 3}
        assert shapes == {eng.kv.alloc.n_blocks + 1, slots * cols + 1}
    finally:
        eng.close()


def test_the_engine_reports_blocks_by_kind(params):
    from kubeflow_tpu.runtime.metrics import METRICS

    eng = engine(params, slots=2, engine_id="by-kind")
    try:
        eng.submit(prompt(1, 30), 12).result(timeout=600)
        for kind in ("full", "window"):
            assert METRICS.value("serving_kv_blocks_used", replica="by-kind", kind=kind) == 0
            assert METRICS.value("serving_kv_blocks_free", replica="by-kind", kind=kind) > 0
        assert METRICS.value("serving_moe_assignments_total", held="true") > 0
    finally:
        eng.close()


def test_a_dispatch_says_which_full_kind_pages_its_live_rows_read(params, engine_regions):
    """``full_blocks_read`` on ``serving.engine.dispatch``: the pages the
    live rows hold at the dispatch's last step, a row its own and a dead
    slot none. One row of 30 positions, blocks of 4, 4 steps a dispatch:
    34 positions are 9 pages, and every dispatch needs one more, of a
    table of 3 slots x 32 columns."""
    eng = engine(params, slots=3)
    try:
        eng.submit(prompt(1, 30), 12).result(timeout=600)
    finally:
        eng.close()
    dispatch = [stats for name, stats in engine_regions
                if name == "serving.engine.dispatch"]
    read = [d["full_blocks_read"] for d in dispatch]
    assert read[:3] == [9, 10, 11] and all(r <= d["view_blocks"] for r, d in zip(read, dispatch))
    # one live row and nothing prefilling: all the full kind holds (a
    # dispatch in flight past the row's budget reads on, into trash)
    assert [d["full_blocks"] for d in dispatch[:3]] == read[:3]
    assert all(d["max_blocks"] == 32 and d["rows"] == 3 * 4 for d in dispatch)


# -- the other family's device programs did not move ---------------------------------

GPT = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=128, vocab_size=101)


@pytest.fixture(scope="module")
def gpt_params():
    rng = jax.random.PRNGKey(0)
    return GptLM(GPT).init(rng, jax.random.randint(rng, (1, 8), 0, GPT.vocab_size))["params"]


def _parent_step(model, chunk):
    """The decode program as the engine lowered it before it knew families
    (PR 27's ``ContinuousBatcher._build_step``), written out."""
    @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
    def step(params, cache, tok, temps, rngs, *tables):
        def one(carry, _):
            cache, tok, rngs = carry
            logits, updated = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                mutable=["cache"], block_tables=tables[0])
            with jax.named_scope("sample"):
                lg = logits[:, -1]
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                pairs = jax.vmap(jax.random.split)(rngs)
                rngs, keys = pairs[:, 0], pairs[:, 1]
                sampled = jax.vmap(
                    lambda k, l, t: jax.random.categorical(k, l / jnp.maximum(t, 1e-6))
                )(keys, lg, temps).astype(jnp.int32)
                nxt = jnp.where(temps > 0.0, sampled, greedy)
            return (updated["cache"], nxt, rngs), nxt

        (cache, tok, rngs), toks = jax.lax.scan(one, (cache, tok, rngs), None, length=chunk)
        return cache, tok, rngs, jnp.moveaxis(toks, 0, 1)

    return step


def _parent_prefill(model):
    @jax.jit
    def prefill(params, cache, ids, true_lens, temperatures, keys):
        logits, updated = model.apply({"params": params, "cache": cache}, ids,
                                      mutable=["cache"])
        lg = jnp.take_along_axis(logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        sampled = jax.vmap(
            lambda k_, l, t: jax.random.categorical(k_, l / jnp.maximum(t, 1e-6))
        )(keys, lg, temperatures).astype(jnp.int32)
        first = jnp.where(temperatures > 0.0, sampled, greedy)
        return updated["cache"], first

    return prefill


def _parent_chunk_prefill(model):
    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk_prefill(params, cache, ids, first_idx, temperature, key):
        logits, updated = model.apply({"params": params, "cache": cache}, ids,
                                      mutable=["cache"])
        lg = logits[0, first_idx]
        greedy = jnp.argmax(lg).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, lg / jnp.maximum(temperature, 1e-6)).astype(jnp.int32)
        return updated["cache"], jnp.where(temperature > 0.0, sampled, greedy)

    return chunk_prefill


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk_prefill"])
def test_gpt_device_programs_are_what_the_parent_engine_lowered(gpt_params, program):
    """StableHLO text of the GPT family's decode, batched-prefill and
    chunked-prefill programs, lowered from the engine, equal to the text of
    the parent's functions over ``GptLM`` built with the parent's
    arguments: the refactor into families moved no instruction."""
    eng = ContinuousBatcher(GPT, gpt_params, slots=2, chunk=2, kv_block_t=16)
    try:
        n_blocks, bt = eng.kv.alloc.n_blocks, eng.kv_block_t
        if program == "decode":
            model = GptLM(GPT, decode=True, per_slot=True, paged=True,
                          kv_blocks=n_blocks + 1, kv_block_t=bt, kv_dtype="bf16")
            args = (eng.params, eng.cache, eng.last_tok, eng.temps, eng.rngs,
                    jnp.asarray(eng.kv.tables))
            ours, theirs = eng._step_fn.lower(*args), _parent_step(model, 2).lower(*args)
        else:
            model = GptLM(GPT, decode=True)
            if program == "prefill":
                keys = jnp.zeros((1, 2), jnp.uint32)
                eng._prefill_group([np.arange(1, 8, dtype=np.int32)], [0.0], keys)
                fn = eng._prefill_fns[(16, eng._group_pad, False)]
                n = eng._group_pad
                args = (eng.params, eng._zero_small[(n, False)], jnp.zeros((n, 16), jnp.int32),
                        jnp.ones((n,), jnp.int32), jnp.zeros((n,), jnp.float32),
                        jnp.zeros((n, 2), jnp.uint32))
                ours, theirs = fn.lower(*args), _parent_prefill(model).lower(*args)
            else:
                args = (eng.params, eng.family.prefill_cache(1),
                        jnp.zeros((1, eng.prefill_chunk), jnp.int32), jnp.asarray(0, jnp.int32),
                        jnp.asarray(0.0, jnp.float32), jnp.zeros((2,), jnp.uint32))
                ours = eng._build_chunk_prefill().lower(*args)
                theirs = _parent_chunk_prefill(model).lower(*args)
    finally:
        eng.close()
    assert ours.as_text() == theirs.as_text()


def _parent_mimo_step(cfg, trash, chunk):
    """``MimoFamily.build_step`` as PR 31's tree had it."""
    from kubeflow_tpu.serving.family import sample_next

    @functools.partial(jax.jit, donate_argnums=(1, 2, 4))
    def step(params, cache, tok, temps, rngs, full_table, window_table, live):
        def one(carry, _):
            cache, tok, rngs, stats = carry
            logits, cache, st = mimo.decode_step(
                cfg, params, cache, tok, full_table, window_table, live, trash)
            with jax.named_scope("sample"):
                nxt, rngs = sample_next(logits, temps, rngs)
            return (cache, nxt, rngs, stats + st), nxt

        (cache, tok, rngs, stats), toks = jax.lax.scan(
            one, (cache, tok, rngs, jnp.zeros((3,), jnp.int32)), None, length=chunk)
        return cache, tok, rngs, jnp.moveaxis(toks, 0, 1), stats

    return step


def _parent_mimo_chunk_prefill(cfg):
    """``MimoFamily.build_chunk_prefill`` as PR 31's tree had it."""
    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, cache, ids, start, n_valid, temperature, key,
                      read_full, write_full, read_window, write_window):
        logits, cache, stats = mimo.prefill_chunk(
            cfg, params, cache, ids, start, n_valid,
            read_full, write_full, read_window, write_window)
        greedy = jnp.argmax(logits).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6)).astype(jnp.int32)
        return cache, jnp.where(temperature > 0.0, sampled, greedy), stats

    return prefill_chunk


@pytest.mark.parametrize("program", ["decode", "chunk_prefill"])
def test_mimo_device_programs_are_what_the_parent_engine_lowered(params, program):
    """The GPT test's twin, since PR 32 put a stride and a third and fourth
    kind of cache behind ``SlotKV``'s verbs and a second output mode into
    the decode kernel: StableHLO text of the MiMo family's decode and
    chunk-prefill programs, lowered from the engine with the tables its
    ``SlotKV`` hands out, equal to the text of the parent's builders given
    the parent's arguments. (PR 32 also lowered both trees' programs side by
    side, the kernel's file included: byte-equal.)"""
    eng = engine(params)
    try:
        trash = {mimo.FULL: eng.kv.alloc.n_blocks, mimo.WINDOW: eng.kv.rings.alloc.n_blocks}
        if program == "decode":
            args = (eng.params, eng.cache, eng.last_tok, eng.temps, eng.rngs,
                    *eng.kv.warm_tables()[1])
            ours = eng._step_fn.lower(*args)
            theirs = _parent_mimo_step(CFG, trash, eng.chunk).lower(*args)
        else:
            eng.kv.hold(0, eng.kv.reserve(40))
            tables = eng.kv.chunk_tables(0, 0, 16, 16)
            assert len(tables) == 4
            args = (eng.params, eng.cache, jnp.zeros((16,), jnp.int32), jnp.asarray(0, jnp.int32),
                    jnp.asarray(16, jnp.int32), jnp.asarray(0.0, jnp.float32),
                    jnp.zeros((2,), jnp.uint32), *map(jnp.asarray, tables))
            ours = eng.family.build_chunk_prefill().lower(*args)
            theirs = _parent_mimo_chunk_prefill(CFG).lower(*args)
            eng.kv.release(0)
    finally:
        eng.close()
    assert ours.as_text() == theirs.as_text()


@pytest.mark.parametrize("family", ["gpt", "mimo", "gpt_contiguous"])
def test_the_engine_keeps_the_names_the_benchmark_reads(params, gpt_params, family):
    """``benchmark/runners/{gpt,mimo}_serve.py`` read the engine by name:
    ``params`` (and assign it), ``prefill_chunk``, ``prewarm``,
    ``_group_pad``, ``kv_block_t``, ``chunk``, ``engine_id``. Their values
    are the constructor's, whoever owns the slots' KV; and the constructor
    takes sixteen arguments beside the configuration and its weights."""
    import inspect

    cfg, tree, kw, block_t, prefill_chunk = {
        "gpt": (GPT, gpt_params, {"kv_block_t": 16}, 16, 128),
        "mimo": (CFG, params, {"kv_block_t": 4, "prefill_chunk": 16}, 4, 16),
        "gpt_contiguous": (GPT, gpt_params, {"paged": False}, 0, 128),
    }[family]
    eng = ContinuousBatcher(cfg, tree, slots=3, chunk=2, engine_id="names", **kw)
    try:
        assert eng.params is tree
        assert (eng.prefill_chunk, eng._group_pad, eng.kv_block_t, eng.chunk,
                eng.engine_id) == (prefill_chunk, 3, block_t, 2, "names")
        assert eng.kv.block_t == eng.kv_block_t
        eng.params = tree                      # the runners hand it fresh weights
        assert list(inspect.signature(eng.prewarm).parameters)[:2] == [
            "prompt_len", "group_sizes"]
    finally:
        eng.close()
    arguments = list(inspect.signature(ContinuousBatcher.__init__).parameters)
    assert arguments[:3] == ["self", "cfg", "params"] and len(arguments[3:]) == 16
