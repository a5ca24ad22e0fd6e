"""The EvaByte model family on the serving path (models/evabyte.py,
serving/family.py, the local and the summary kind of cache in
serving/paged.py, the decode kernel's two sources under one softmax) at a
tiny size on the CPU, against the plain float32 reference of
benchmark/reference/evabyte.py on seeded weights.

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

from benchmark import weights_eva
from benchmark.reference import evabyte as ref
from kubeflow_tpu.models import evabyte
from kubeflow_tpu.models.evabyte import EvaConfig
from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.family import EvaFamily, family_for
from kubeflow_tpu.serving.paged import AlignedWindows

# hidden 64, 4 heads of 16, window 32, chunks of 4, 256 positions: a window
# is 8 chunks and 8 blocks of 4, a row at most 8 windows
CFG = EvaConfig.tiny()
SEED = 2**31 + 5
NEW = 40            # more than a window: every decode crosses a window's end

#: The program computes in bfloat16 and the reference in float32. At this
#: size the served tokens' worst gap under the reference's best reads
#: 0.000-0.009 sd over these prompts (a flip needs a near-tie); over every
#: position of three 200-token sequences the float8 control reads 0.10-0.21
#: sd, a model that pools a chunk to its plain mean 0.21-0.29, one whose
#: newest window's summaries are not visible 1.4-2.9, one without summaries
#: 2.6-2.9. The limit sits between the program and the least of those.
GAP_LIMIT_SD = 0.03


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name != "dtype"}


SIZES = sizes_of(CFG)


@pytest.fixture(scope="module")
def params():
    return weights_eva.program_tree(SEED, SIZES)


@functools.lru_cache(maxsize=None)
def _top():
    return weights_eva.top_canonical(SEED, SIZES)


def reference_logits(seq, cast=None, fault=None):
    """The reference's full forward pass over one sequence: [len, vocab]."""
    pad = -len(seq) % CFG.window
    x = _top()["embedding"][jnp.asarray(list(seq) + [0] * pad)]
    for i in range(CFG.n_layers):
        x = ref.block(SIZES, weights_eva.layer_canonical(SEED, SIZES, i), x,
                      cast=cast, fault=fault)
    return np.asarray(ref.logits_at(SIZES, _top(), x, cast))[:len(seq)]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).astype(np.int32)


def engine(params, **kw):
    kw = {"slots": 4, "chunk": 4, "kv_block_t": 4, "prefill_chunk": 16, "kv_blocks": 64, **kw}
    return ContinuousBatcher(CFG, params, **kw)


def served_gaps(p, out):
    logits = reference_logits(list(p) + list(out))
    at = len(p) - 1 + np.arange(len(out))
    return np.asarray(ref.gaps_under_best(jnp.asarray(logits[at]), jnp.asarray(out)))


# -- the served path against the reference --------------------------------------

@pytest.mark.parametrize("n", [5, 27, 28, 29, 31, 32, 33, 64, 70, 100], ids=[
    "in_the_first_chunk", "before_a_chunk_line", "on_a_chunk_line", "after_a_chunk_line",
    "before_the_window_line", "on_the_window_line", "after_the_window_line",
    "on_the_second_line", "three_windows", "seven_chunks"])
def test_prefill_then_paged_decode_agrees_with_the_reference(params, n):
    """Prompts that end before, on and after a chunk's line (every 4) and a
    window's line (every 32), prefilled in chunks of 16, then 40 decode
    steps through the paged cache of two kinds (every row completes chunks
    and leaves at least one window while it decodes), against the
    reference's full forward pass."""
    eng = engine(params)
    try:
        p = prompt(n, n)
        out = eng.submit(p, NEW).result(timeout=600)
        assert eng.kv.alloc.used() == eng.kv.rings.used() == 0
    finally:
        eng.close()
    assert len(out) == NEW and all(0 <= t < CFG.vocab_size for t in out)
    assert served_gaps(p, out).max() <= GAP_LIMIT_SD


def _wrong_reading(**kw):
    """The worst gap, under the reference, of the tokens a variant of the
    reference puts first, over every position of three sequences."""
    worst = 0.0
    for seed in (1, 2, 3):
        seq = prompt(seed, 200)
        logits = reference_logits(seq)
        wrong = reference_logits(seq, **kw)
        gaps = ref.gaps_under_best(jnp.asarray(logits), jnp.asarray(wrong.argmax(-1)))
        worst = max(worst, float(gaps.max()))
    return worst


@pytest.mark.parametrize("variant", ["control_fp8", "no_remote", "mean_pool", "stale_rollover"])
def test_the_limit_is_tight_enough_that_the_control_and_every_fault_read_over_it(variant):
    """The tokens the reference puts first with its matmul operands rounded
    to float8 e4m3, with the summaries left out, with mu and phi ignored,
    and with the newest completed window's summaries not visible: each over
    the limit under the reference, with room (3x)."""
    kw = {"cast": ref.fp8_cast} if variant == "control_fp8" else {"fault": variant}
    assert _wrong_reading(**kw) > 3 * GAP_LIMIT_SD


def test_decode_logits_agree_with_the_reference(params):
    """The two device programs by hand, without the engine: one prompt
    prefilled in two chunks into arenas of both kinds, then decoded step by
    step with the reference's own next tokens over a chunk's and a window's
    line; every position's logits against the reference's (bfloat16 against
    float32: under 0.05 sd of the position's logits, where a wrong mask, a
    stale summary or a wrong position reads over 1)."""
    bt, c, n, steps = 4, 16, 21, 50
    seq = prompt(3, n + steps)
    want = reference_logits(seq)
    rings = AlignedWindows(1, CFG.window, bt, 1)
    assert rings.cols == CFG.window // bt                  # one step a dispatch: no spare column
    cache = evabyte.fresh_cache(CFG, 1, rings.alloc.n_blocks, 16, bt)
    rings.attach(0, rings.reserve())
    table = np.arange(4, dtype=np.int32)                   # summary kind: block b in row b
    chunk = jax.jit(functools.partial(evabyte.prefill_chunk, CFG, summary_trash=16))
    for start in range(0, n, c):
        end = min(start + c, n)
        ids = np.zeros((c,), np.int32)
        ids[:end - start] = seq[start:end]
        read_local = rings.row(0).copy()
        rings.advance(0, end, end)
        write_local = np.asarray([rings.block_of(0, start // bt + j) for j in range(c // bt)])
        logits, cache, stats = chunk(params, cache, jnp.asarray(ids), start, end - start,
                                     jnp.asarray(table), jnp.asarray(read_local),
                                     jnp.asarray(write_local))
        assert list(np.asarray(stats)) == [end // 4 - start // 4, 0, 0]
    got = [np.asarray(logits)]
    cache["cursors"] = jnp.asarray([n], jnp.int32)
    step = jax.jit(functools.partial(evabyte.decode_step, CFG, summary_trash=16))
    written = 0
    for t in range(n, n + steps - 1):
        rings.advance(0, t, t + 1)
        logits, cache, stats = step(params, cache, jnp.asarray(seq[t:t + 1]),
                                    jnp.asarray(table[None]), jnp.asarray(rings.tables),
                                    jnp.ones((1,), bool))
        got.append(np.asarray(logits[0]))
        written += int(stats[0])
        assert list(np.asarray(stats)[1:]) == [int((t + 1) % CFG.window == 0), 0]
        assert len(rings._held[0]) == t % CFG.window // bt + 1       # the current window only
    assert written == (n + steps - 1) // 4 - n // 4
    got = np.stack(got)
    want = want[n - 1:n - 1 + steps]
    assert np.abs(got - want).max() / want.std(-1).min() < 0.05


@pytest.fixture(scope="module")
def ragged_batch(params):
    """Rows of 5, 27 and 70 positions and a dead slot, 16 steps a dispatch:
    the row of 27 crosses the window's end (32) in the MIDDLE of its first
    dispatch, the row of 70 in its second."""
    eng = engine(params, chunk=16)
    assert eng.kv.rings.cols == 8 + 4                   # 32 / 4 + ceil(15 / 4)
    try:
        futs = {n: eng.submit(prompt(n, n), 24) for n in (5, 27, 70)}
        return {n: f.result(timeout=600) for n, f in futs.items()}
    finally:
        eng.close()


@pytest.mark.parametrize("n", [5, 27, 70])
def test_a_dispatch_of_16_steps_over_ragged_rows_agrees_with_the_reference(ragged_batch, n):
    """The engine's decode program as the cell runs it: a scan of 16 steps
    over rows of different lengths in one batch, each reading its own pages
    of both kinds; a roll-over inside the dispatch neither reads a returned
    block nor writes into one."""
    out = ragged_batch[n]
    assert len(out) == 24 and served_gaps(prompt(n, n), out).max() <= GAP_LIMIT_SD


def test_staggered_arrivals_match_one_at_a_time_while_local_blocks_come_and_go(params):
    """Greedy tokens of requests that join a running batch at different
    times equal the tokens of the same requests served alone, while the
    local kind gives whole windows back and is granted their blocks again."""
    prompts = [prompt(10 + i, n) for i, n in enumerate((9, 40, 18, 55, 31))]
    budgets = [40, 30, 50, 16, 36]
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
    # every row leaves a window or two while it decodes: whole windows went
    # back and the same ids were granted again
    assert len(given_back) > 20 and len(set(given_back)) < len(given_back)


def test_generative_model_serves_the_family_over_the_predict_surface(params):
    """``GenerativeModel(cfg=EvaConfig(...))`` behind ``ModelServer``: the
    same engine class and knobs, the family found from the config's type."""
    from kubeflow_tpu.serving.server import GenerativeModel, ModelServer

    served = GenerativeModel(name="eva", apply_fn=None, params=params, cfg=CFG,
                             max_new_tokens=NEW, slots=2, kv_block_t=4, prefill_chunk=16,
                             kv_blocks=32)
    server = ModelServer()
    server.add(served)
    try:
        p = prompt(1, 23)
        resp = server.app.call("POST", "/v1/models/eva:predict", {"instances": [p.tolist()]})
        assert resp.status == 200, resp.body
        reply = resp.body["predictions"][0]
        eng = served._continuous_engine()
        assert type(eng) is ContinuousBatcher and type(eng.family) is EvaFamily
    finally:
        served.close()
    assert reply[:23] == p.tolist() and len(reply) == 23 + NEW
    assert served_gaps(p, reply[23:]).max() <= GAP_LIMIT_SD


def test_the_engine_refuses_what_the_family_has_not_built(params):
    for kw in ({"paged": False}, {"kv_dtype": "int8"}, {"role": "prefill"},
               {"prefill_chunk": 0}):
        with pytest.raises(ValueError):
            ContinuousBatcher(CFG, params, slots=2, **kw)
    # a prefill chunk that straddles windows (48 of 32) cannot be a program
    with pytest.raises(ValueError, match="must divide the window"):
        jax.eval_shape(functools.partial(evabyte.prefill_chunk, CFG, summary_trash=4),
                       params, evabyte.fresh_cache(CFG, 1, 8, 4, 4), jnp.zeros((48,), jnp.int32),
                       0, 48, jnp.zeros((2,), jnp.int32), jnp.zeros((8,), jnp.int32),
                       jnp.zeros((12,), jnp.int32))


# -- two kinds of cache, accounted by kind ------------------------------------------

def test_the_family_is_found_from_the_configuration_and_sizes_both_kinds(params):
    """No knob sizes the local kind (a whole ring for every slot, ``window
    / block_t + ceil((chunk - 1) / block_t)`` blocks); ``kv_blocks`` is the
    summary kind's, by default a whole row a slot: a row a chunk, so
    ``max_seq / (block_t * chunk_size)`` blocks."""
    fam = family_for(CFG, slots=3, paged=True, kv_blocks=0, kv_block_t=4)
    assert type(fam) is EvaFamily and fam.kv_stride == 4 and fam.kv_blocks == 3 * 16
    assert fam.prefills_in_arena and fam.has_stats
    eng = engine(params, slots=3, chunk=8, kv_blocks=0)
    try:
        assert eng.kv.rings.cols == 8 + 2 and eng.kv.rings.alloc.n_blocks == 3 * 10
        assert eng.kv.max_blocks == 16 and eng.kv.alloc.n_blocks == 48
        shapes = {leaf.shape[0] for leaf in jax.tree.leaves(eng.cache) if leaf.ndim == 3}
        assert shapes == {48 + 1, 30 + 1}
    finally:
        eng.close()


def test_the_engine_says_what_each_kind_holds_reads_and_writes(params, engine_regions):
    """One row of 30 positions, blocks of 4, chunks of 4, 4 steps a
    dispatch, window 32: the stats on ``serving.engine.dispatch`` and
    ``.deliver``, the gauges by kind and the windows' counter."""
    eng = engine(params, slots=3, engine_id="by-kind")
    try:
        eng.submit(prompt(1, 30), 12).result(timeout=600)
        for kind in ("local", "summary"):
            assert METRICS.value("serving_kv_blocks_used", replica="by-kind", kind=kind) == 0
            assert METRICS.value("serving_kv_blocks_free", replica="by-kind", kind=kind) > 0
    finally:
        eng.close()
    dispatch = [s for name, s in engine_regions if name == "serving.engine.dispatch"]
    # cursors 30 -> 34 -> 38 -> 42: the first dispatch crosses the line at 32
    assert [d["rollovers"] for d in dispatch[:3]] == [1, 0, 0]
    # its last step (position 33) reads 2 positions of the new window: one
    # page, and the old window's 8 summaries: 2 pages of 4 rows
    assert [d["local_blocks_read"] for d in dispatch[:3]] == [1, 2, 3]
    assert [d["summary_blocks_read"] for d in dispatch[:3]] == [2, 2, 2]
    # the crossing dispatch holds the old window whole and the new one's first
    # block; the next gave the old window back
    assert [d["local_blocks"] for d in dispatch[:3]] == [9, 2, 3]
    # frontiers 34, 38, 42 have written 8, 9, 10 whole chunks: 2, 3, 3 blocks of 4 rows
    assert [d["summary_blocks"] for d in dispatch[:3]] == [2, 3, 3]
    assert all(d["max_blocks"] == 16 and d["view_blocks"] == 4 for d in dispatch[:3])
    deliver = [s for name, s in engine_regions if name == "serving.engine.deliver"]
    first = next(d for d in deliver if d["kind"] == "first")
    assert first["summaries_written"] == 30 // 4
    chunks = [d for d in deliver if d["kind"] == "chunk"]
    assert [d["summaries_written"] for d in chunks[:3]] == [1, 1, 1]
    assert METRICS.value("serving_eva_windows_summarised_total") >= 1


def test_the_engine_keeps_the_names_the_benchmark_s_runner_reads(params):
    """``benchmark/runners/eva_serve.py`` reads the engine by name, as the
    other families' runners do (``tests/test_mimo.py``), and besides:
    ``kv.rings.alloc.n_blocks`` (the local arena, for
    ``local_kv_block_share.serve``), and ``cache`` / ``family.fresh_cache``
    (limit readings drop the arenas between two windows and make them anew)."""
    eng = engine(params, slots=3, chunk=2, engine_id="names")
    try:
        assert eng.params is params
        assert (eng.prefill_chunk, eng.kv_block_t, eng.chunk, eng.engine_id) == (16, 4, 2, "names")
        assert eng.kv.block_t == 4 and eng.kv.rings.alloc.n_blocks == 3 * (8 + 1)
        first = eng.submit(prompt(1, 40), 8).result(timeout=600)
        eng.params = eng.cache = None
        eng.params, eng.cache = params, eng.family.fresh_cache()
        assert eng.submit(prompt(1, 40), 8).result(timeout=600) == first
    finally:
        eng.close()
