"""The SDAR model family on the serving path (models/sdar.py, SdarFamily in
serving/family.py, a block in flight past the cursor in serving/paged.py,
events of a variable width in serving/continuous.py, ``block_len x heads``
query rows a slot through the decode kernel) at a tiny size on the CPU,
against the plain float32 reference of benchmark/reference/sdar.py on seeded
weights.

Two kinds of comparison. LOGITS of the two device programs by hand (prefill
in chunks, then block passes through the paged cache) against the
reference's uncached forward under the block mask. And the generation
TRAJECTORY through the engine, ids and the pass that revealed each, against
the reference's loop: in float32 both sides put the same id first and the
same position forward, so the trajectories are equal, not close.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import weights_sdar
from benchmark.reference import sdar as ref
from kubeflow_tpu.models import sdar
from kubeflow_tpu.models.evabyte import EvaConfig
from kubeflow_tpu.models.gpt import GptConfig
from kubeflow_tpu.models.mimo import MimoConfig
from kubeflow_tpu.models.sdar import SdarConfig
from kubeflow_tpu.parallel.moe import held_experts_ffn, softmax_top_k
from kubeflow_tpu.runtime.metrics import METRICS
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.family import SdarFamily, family_for

# hidden 64, 8 query and 2 KV heads of 16, 2 layers, 16 experts of 32 (4 a
# token, all held), blocks of 4 over 4 steps, 128 positions, mask id 95
CFG = SdarConfig.tiny()
SEED = 2**31 + 7
B = CFG.block_len


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name != "dtype"}


SIZES = sizes_of(CFG)


@functools.lru_cache(maxsize=None)
def canon(head_scale=1.0):
    """The reference's float32 arrays: (layers, top)."""
    top = dict(weights_sdar.top_canonical(SEED, SIZES))
    top["head"] = top["head"] * head_scale
    return ([weights_sdar.layer_canonical(SEED, SIZES, i) for i in range(CFG.n_layers)], top)


def tree(dtype, head_scale=1.0):
    """The program's tree from the same draws, in ``dtype`` (the router
    float32 either way)."""
    layers, top = canon(head_scale)
    moe = ("router", "w_gate", "w_up", "w_down")
    cast = lambda k, a: a if k == "router" else a.astype(dtype)
    return {**{k: a.astype(dtype) for k, a in top.items()},
            "layers": [{**{k: cast(k, a) for k, a in w.items() if k not in moe},
                        "moe": {k: cast(k, w[k]) for k in moe}} for w in layers]}


F32 = dataclasses.replace(CFG, dtype=jnp.float32)


def prompt(seed, n):
    """Ids in [1, vocab) without the mask id."""
    ids = np.random.default_rng(seed).integers(1, CFG.vocab_size - 1, n)
    return (ids + (ids >= CFG.mask_id)).astype(np.int32)


def engine(cfg=F32, head_scale=1.0, **kw):
    kw = {"slots": 3, "chunk": 5, "kv_block_t": 4, "prefill_chunk": 16, **kw}
    return ContinuousBatcher(cfg, tree(cfg.dtype, head_scale), **kw)


def counter(name, **labels):
    return METRICS.value(name, **labels) if labels else METRICS.total(name)


def observations(name):
    """How many values a histogram has taken."""
    counts = METRICS.histogram_counts(name)
    return counts[2] if counts else 0


# -- the two device programs by hand against the reference's forward ---------------

def by_hand(cfg, params, p, block_inputs):
    """Prefills ``p`` in chunks of 16 into a paged arena, then runs one
    block pass an entry of ``block_inputs`` ([B] ids, commit: bool) for one
    live slot among three. Yields each pass's logits [B, vocab]."""
    family = SdarFamily(cfg, slots=3, kv_blocks=40, kv_block_t=4)
    cache, trash, slot = family.fresh_cache(), 40, 1
    blocks = np.arange(7, 7 + 32)                     # the row's pages, in order
    table = np.full((3, 32), trash, np.int32)
    chunk, n = 16, len(p)
    for start in range(0, n // B * B, chunk):
        end = min(start + chunk, n)
        ids = np.zeros((chunk,), np.int32)
        ids[:end - start] = p[start:end]
        held = -(-end // 4)
        write = np.full((chunk // 4,), trash, np.int32)
        write[:held - start // 4] = blocks[start // 4:held]
        opening, cache, _ = sdar.prefill_chunk(
            cfg, params, cache, jnp.asarray(ids), jnp.asarray(start), jnp.asarray(end - start),
            jnp.asarray(blocks[:8 * -(-held // 8)]), jnp.asarray(write))
    table[slot, :32] = blocks
    cursor = n // B * B
    for ids, commit in block_inputs:
        cache = dict(cache, cursors=cache["cursors"].at[slot].set(cursor),
                     block_ids=cache["block_ids"].at[slot].set(jnp.asarray(ids)))
        logits, cache, live, _ = sdar.block_logits(cfg, params, cache, jnp.asarray(table), trash)
        assert list(np.asarray(live)) == [False, True, False]
        yield np.asarray(logits[slot])
        cursor += B * commit


def reference_logits(seq, **kw):
    layers, top = canon()
    return np.asarray(ref.forward(SIZES, layers, top, np.asarray(seq, np.int64), **kw))


#: float32 on both sides: the kernels' online softmax and the grouped
#: products sum in another order than the reference's whole-row softmax and
#: per-expert products; over these passes the worst logit differs by 1e-6
#: of the logits' standard deviation. bfloat16 program against the float32
#: reference: rounding of every matmul operand, 0.016-0.040 sd over these
#: passes (the reference itself at bfloat16 reads 0.017), where the
#: reference with its operands rounded to float8 reads 0.27.
TOLERANCE_SD = {"float32": 1e-4, "bfloat16": 0.08}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_block_passes_agree_with_the_reference(dtype):
    """A prompt of 27 (two chunks; a tail of 3 that opens the first block),
    then five passes: two denoising passes of the first block, its commit,
    and two passes of the next, each against the reference's uncached
    forward over everything before it and the pass's own block."""
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype))
    p = prompt(3, 27)
    M = CFG.mask_id
    first = [int(p[24]), int(p[25]), int(p[26]), M]
    done = first[:3] + [17]
    nxt = [M, 9, M, M]
    passes = [(first, False), (done, True), (nxt, False), ([5, 9, M, 11], False)]
    seqs = [list(p[:24]) + first, list(p[:24]) + done, list(p[:24]) + done + nxt,
            list(p[:24]) + done + [5, 9, M, 11]]
    worst = 0.0
    for got, seq in zip(by_hand(cfg, tree(cfg.dtype), p, passes), seqs):
        want = reference_logits(seq)[-B:]
        worst = max(worst, float((np.abs(got - want).max(-1) / want.std(-1)).max()))
    assert worst <= TOLERANCE_SD[dtype], worst


def test_the_bfloat16_tolerance_is_one_the_float8_control_fails():
    """The reference with its matmul operands rounded to float8 e4m3 reads
    over the tolerance the bfloat16 program is held to, and the reference
    at bfloat16 under it."""
    seq = list(prompt(3, 24)) + [CFG.mask_id, 9, CFG.mask_id, CFG.mask_id]
    want = reference_logits(seq)
    err = lambda cast: float((np.abs(reference_logits(seq, cast=cast) - want).max(-1)
                              / want.std(-1)).max())
    assert err(ref.fp8_cast) > 2 * TOLERANCE_SD["bfloat16"] > 2 * err(ref.bf16_cast)


@pytest.mark.parametrize("fault", ["causal_in_block", "top7", "no_qk_norm"])
def test_every_fault_of_the_forward_moves_the_logits(fault):
    seq = list(prompt(4, 20)) + [CFG.mask_id] * 4
    want, wrong = reference_logits(seq), reference_logits(seq, fault=fault)
    # the least, one expert of 4 too few, reads 0.076 sd; float32 noise 2e-6
    assert (np.abs(wrong - want).max(-1) / want.std(-1)).max() > 0.05


def test_the_block_mask_sees_the_whole_own_block_and_no_later_one():
    """Changing the LAST id of a block moves the logits of the block's
    first position and of nothing before the block."""
    seq = list(prompt(5, 16))
    other = seq[:11] + [seq[11] % 90 + 1] + seq[12:]
    a, b = reference_logits(seq), reference_logits(other)
    assert np.abs(a[:8] - b[:8]).max() == 0 and np.abs(a[8] - b[8]).max() > 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_greedy_pass_reveals_what_choose_over_the_written_logits_reveals(dtype):
    """``block_pass`` at temperature 0 takes candidates and confidences
    straight out of the head (``ops.head_choice``); the block state and the
    arenas it leaves are those of ``choose`` and ``reveal`` over
    ``block_logits``: a slot denoising, a dead one, one committing."""
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype))
    params = tree(cfg.dtype)
    family = SdarFamily(cfg, slots=3, kv_blocks=40, kv_block_t=4)
    trash, M = 40, CFG.mask_id
    table = np.full((3, 8), trash, np.int32)
    table[0, :2], table[2, :3] = [5, 6], [9, 3, 11]
    cache = dict(family.fresh_cache(),
                 cursors=jnp.asarray([4, 0, 8], jnp.int32),
                 block_ids=jnp.asarray([[17, M, M, 40], [M] * 4, [3, 70, 9, 21]], jnp.int32),
                 masked=jnp.asarray([[0, 1, 1, 0], [1] * 4, [0] * 4], bool),
                 passes=jnp.asarray([2, 0, 4], jnp.int32))
    temps, keys = jnp.zeros((3,), jnp.float32), jnp.zeros((3, 2), jnp.uint32)
    out, commit, ids, _, _, stats = sdar.block_pass(
        cfg, params, cache, jnp.asarray(table), temps, keys, trash)
    logits, want_cache, live, _ = sdar.block_logits(cfg, params, cache, jnp.asarray(table), trash)
    x0, conf = sdar.choose(cfg, logits.reshape(3 * B, -1), temps, keys)
    shown = np.asarray(sdar.reveal(cfg, cache["masked"], conf))
    assert list(np.asarray(live)) == [True, False, True]
    assert list(np.asarray(commit)) == [False, False, True] and shown[0].sum() == 1
    at = int(np.argmax(shown[0]))
    want_ids = np.asarray(cache["block_ids"]).copy()
    want_ids[0, at], want_ids[2] = int(x0[0, at]), M
    assert np.array_equal(np.asarray(out["block_ids"]), want_ids)
    assert np.array_equal(np.asarray(out["masked"]),
                          [[j in (1, 2) and j != at for j in range(B)], [True] * B, [True] * B])
    assert int(out["revealed_at"][0, at]) == 3 and list(np.asarray(out["cursors"])) == [4, 0, 12]
    for i in range(cfg.n_layers):
        for kind in ("k", "v"):
            assert np.array_equal(np.asarray(out[f"layer_{i}"][kind], np.float32),
                                  np.asarray(want_cache[f"layer_{i}"][kind], np.float32))
    # one denoising pass, one commit; nobody samples: no logits written
    assert list(np.asarray(stats[3:7])) == [1, 1, 1, 1] and int(stats[8]) == 0
    # a dead slot's lingering temperature draws nothing; a live one's does
    for hot, drew in ((1, 0), (0, 1)):
        *_, stats = sdar.block_pass(cfg, params, cache, jnp.asarray(table),
                                    temps.at[hot].set(0.7), keys, trash)
        assert int(stats[8]) == drew


# -- the generation trajectory through the engine ----------------------------------

def reference_run(p, new, head_scale=1.0, fault=None):
    layers, top = canon(head_scale)
    return ref.generate(SIZES, layers, top, [int(t) for t in p], new, fault=fault)


@pytest.mark.parametrize("n,new", [(8, 8), (9, 7), (10, 5), (11, 9), (2, 6), (3, 1), (23, 6),
                                   (40, 13)],
                         ids=["tail_0", "tail_1", "tail_2", "tail_3", "shorter_than_a_block",
                              "one_token", "two_chunks", "three_chunks"])
def test_generation_agrees_with_the_reference_loop(n, new):
    """Ids AND the pass that revealed each, on flat weights (no confidence
    nears the threshold: the static schedule, one position a pass), for
    prompts whose tail is 0, 1, 2 and 3 positions, a prompt shorter than a
    block, budgets that are no multiple of 4 (the last block's surplus is
    discarded), prompts of several prefill chunks."""
    eng = engine()
    try:
        p = prompt(n, n)
        fut = eng.submit(p, new)
        toks = fut.result(timeout=600)
        assert eng.kv.alloc.used() == 0
    finally:
        eng.close()
    want, marks = reference_run(p, new)
    assert toks == want and fut.reveal_passes == marks
    assert all(1 <= m <= CFG.denoise_steps for m in marks)
    assert fut.first_token_at is not None and fut.finish_reason == "ok"


def test_a_peaked_head_crosses_the_threshold_and_takes_fewer_passes():
    """With the head scaled up confidences pass 0.9: several positions are
    revealed in one pass, a block takes fewer than 4 denoising passes, and
    the trajectory is still the reference's (whose loop has the same
    rule)."""
    scale, p, new = 300.0, prompt(11, 13), 24
    before = {k: counter("serving_block_forwards_total", kind=k) for k in ("denoise", "commit")}
    revealed = counter("serving_tokens_revealed_total")
    eng = engine(head_scale=scale, slots=1, chunk=2)
    try:
        fut = eng.submit(p, new)
        toks = fut.result(timeout=600)
    finally:
        eng.close()
    want, marks = reference_run(p, new, head_scale=scale)
    assert toks == want and fut.reveal_passes == marks
    by_block = [marks[max(i, 0):i + B] for i in range(-(len(p) % B), new, B)]
    assert any(len(set(b)) < len(b) for b in by_block)      # two positions in one pass
    assert max(marks) <= CFG.denoise_steps and min(max(b) for b in by_block) < CFG.denoise_steps
    denoise = counter("serving_block_forwards_total", kind="denoise") - before["denoise"]
    commit = counter("serving_block_forwards_total", kind="commit") - before["commit"]
    assert commit >= len(by_block) and denoise < CFG.denoise_steps * commit
    # every position of every committed block but the prompt's tail was revealed once
    assert counter("serving_tokens_revealed_total") - revealed >= new


def test_left_to_right_is_another_trajectory():
    """The fault the cell's second number is set against: revealing by
    position gives other reveal passes (and so other ids) than revealing by
    confidence."""
    p = prompt(8, 8)
    assert reference_run(p, 12)[1] != reference_run(p, 12, fault="left_to_right")[1]


def test_two_slots_out_of_lockstep_and_a_retirement_mid_dispatch():
    """Three requests of different tails and budgets over two slots, a
    dispatch of 5 passes: the slots' blocks never line up, a request ends
    inside a dispatch (its slot's later blocks are computed for nobody and
    counted as discarded) and its slot is taken by the third; each
    trajectory is the reference's."""
    discarded = counter("serving_discarded_tail_tokens_total")
    eng = engine(slots=2)
    try:
        asks = [(prompt(21, 9), 5), (prompt(22, 14), 18), (prompt(23, 7), 10)]
        futs = [eng.submit(p, new) for p, new in asks]
        outs = [f.result(timeout=600) for f in futs]
        assert eng.kv.alloc.used() == 0
    finally:
        eng.close()
    for (p, new), fut, out in zip(asks, futs, outs):
        want, marks = reference_run(p, new)
        assert out == want and fut.reveal_passes == marks
    assert counter("serving_discarded_tail_tokens_total") > discarded


def test_sampled_slots_draw_their_own_streams():
    """Temperature above 0: ids inside the vocabulary, a reveal pass each,
    and two requests with one prompt differ (each slot samples on its own
    key); a greedy request beside them still matches the reference."""
    eng = engine()
    try:
        p = prompt(31, 10)
        hot = [eng.submit(p, 16, temperature=1.0) for _ in range(2)]
        cold = eng.submit(p, 16)
        a, b = (f.result(timeout=600) for f in hot)
        assert cold.result(timeout=600) == reference_run(p, 16)[0]
    finally:
        eng.close()
    assert a != b and all(0 <= t < CFG.vocab_size for t in a + b)
    assert all(1 <= m <= CFG.denoise_steps for f in hot for m in f.reveal_passes)


def test_the_choice_counts_its_dispatches_by_path():
    """``serving_block_choice_dispatches_total``: a dispatch in which no live
    slot samples takes the streamed head, one with a sampling slot writes
    the logits out; a prefill's event counts on neither."""
    paths = ("streamed", "materialised")
    read = lambda: [counter("serving_block_choice_dispatches_total", path=p) for p in paths]
    for temperature, moved in ((0.0, 0), (0.8, 1)):
        before = read()
        eng = engine(slots=1)
        try:
            eng.submit(prompt(51, 9), 3, temperature=temperature).result(timeout=600)
        finally:
            eng.close()
        rose = [b - a for a, b in zip(before, read())]
        assert rose[moved] >= 1 and rose[1 - moved] == 0, (temperature, rose)


def test_ttft_is_stamped_at_the_first_block_and_gaps_count_the_later_tokens():
    """A prefill yields no token: ``first_token`` and
    ``serving_ttft_seconds`` come with the first block, whose tokens arrive
    together and count no gap; every later token counts one."""
    ttft = observations("serving_ttft_seconds")
    itl = observations("serving_inter_token_seconds")
    eng = engine(slots=1)
    try:
        fut = eng.submit(prompt(41, 10), 11)          # a tail of 2: blocks of 2, 4, 4, 1
        fut.result(timeout=600)
    finally:
        eng.close()
    assert observations("serving_ttft_seconds") == ttft + 1
    assert observations("serving_inter_token_seconds") == itl + 11 - 2
    assert fut.first_token_at is not None and fut.last_token_at >= fut.first_token_at


# -- the pieces ---------------------------------------------------------------------

def test_the_family_is_found_by_the_configuration_and_answers_the_engine():
    family = family_for(CFG, slots=2, paged=True, kv_blocks=0, kv_block_t=4, kv_dtype="bf16")
    assert isinstance(family, SdarFamily)
    assert (family.prefill_yields_token, family.prefills_in_arena, family.has_stats,
            family.kv_stride, family.kv_ahead) == (False, True, True, 1, 4)
    assert family.rings(16) is None
    # a block takes a denoising pass and a commit at least
    assert [family.cursor_moves(c) for c in (1, 2, 5, 16)] == [4, 4, 12, 32]
    with pytest.raises(ValueError):
        family_for(CFG, slots=2, paged=False, kv_blocks=0, kv_block_t=0, kv_dtype="bf16")
    for cfg in (GptConfig.tiny(), MimoConfig.tiny(), EvaConfig.tiny()):
        other = family_for(cfg, slots=2, paged=True, kv_blocks=0, kv_block_t=4, kv_dtype="bf16")
        assert other.prefill_yields_token and other.kv_ahead == 0
        assert other.cursor_moves(16) == 16


def test_softmax_top_k_against_numpy():
    h = np.random.default_rng(1).normal(size=(9, 12)).astype(np.float32)
    router = np.random.default_rng(2).normal(size=(12, 10)).astype(np.float32)
    idx, w = softmax_top_k(jnp.asarray(h), jnp.asarray(router), 3)
    logits = h.astype(np.float64) @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.argsort(-probs, axis=-1)[:, :3]
    assert (np.asarray(idx) == want).all()
    picked = np.take_along_axis(probs, want, -1)
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def test_eight_shares_of_sixteen_experts_add_up_to_the_uncut_layer():
    """The cut's tie to the model: the router over all 128 experts, 8 a
    token; eight chips' ``held_experts_ffn(first_held=16 i)`` over their
    own 16 experts add up to what the uncut reference layer gives."""
    d, f, E, k, T = 32, 8, 128, 8, 24
    rng = np.random.default_rng(5)
    w = {"router": rng.normal(size=(d, E)).astype(np.float32),
         "w_gate": rng.normal(size=(E, d, f)).astype(np.float32) * 0.2,
         "w_up": rng.normal(size=(E, d, f)).astype(np.float32) * 0.2,
         "w_down": rng.normal(size=(E, f, d)).astype(np.float32) * 0.2}
    h = jnp.asarray(rng.normal(size=(T, d)).astype(np.float32))
    idx, weights = softmax_top_k(h, jnp.asarray(w["router"]), k)
    total, held = 0.0, 0
    for share in range(8):
        mine = slice(16 * share, 16 * share + 16)
        y, stats = held_experts_ffn(h, idx, weights, *(jnp.asarray(w[n][mine]) for n in
                                                      ("w_gate", "w_up", "w_down")),
                                    first_held=16 * share)
        total, held = total + y, held + int(stats[0])
    want = ref.expert_layer({"experts_per_token": k}, {n: jnp.asarray(a) for n, a in w.items()},
                            h, None, None)
    assert held == T * k                                  # every assignment on one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    # all held at once is the same layer
    whole, _ = held_experts_ffn(h, idx, weights, *(jnp.asarray(w[n]) for n in
                                                   ("w_gate", "w_up", "w_down")))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("conf,masked,want", [
    ([0.2, 0.5, 0.3, 0.1], [1, 1, 1, 1], [0, 1, 0, 0]),       # the most confident
    ([0.2, 0.99, 0.3, 0.1], [1, 0, 1, 1], [0, 0, 1, 0]),      # among the MASKED
    ([0.95, 0.5, 0.93, 0.1], [1, 1, 1, 1], [1, 0, 1, 0]),     # all over the threshold
    ([0.5, 0.5, 0.5, 0.5], [0, 1, 1, 1], [0, 1, 0, 0]),       # a tie: the earlier
    ([0.5, 0.5, 0.5, 0.95], [0, 0, 0, 0], [0, 0, 0, 0]),      # nothing masked
], ids=["most_confident", "among_the_masked", "over_the_threshold", "tie", "none_masked"])
def test_the_unmasking_rule(conf, masked, want):
    shown = sdar.reveal(CFG, jnp.asarray([masked], bool), jnp.asarray([conf], jnp.float32))
    assert list(np.asarray(shown[0]).astype(int)) == want
    assert list(ref.choose_reveal(SIZES, np.asarray(conf), np.asarray(masked, bool)
                                  ).astype(int)) == want


def test_two_quota_reveals_two_a_pass():
    cfg = dataclasses.replace(CFG, denoise_steps=2)
    shown = sdar.reveal(cfg, jnp.ones((1, 4), bool), jnp.asarray([[0.1, 0.4, 0.2, 0.3]]))
    assert list(np.asarray(shown[0]).astype(int)) == [0, 1, 0, 1]


def test_passes_of_gives_back_every_pass_from_the_reveal_marks():
    """The cell's check rebuilds each pass's input from the served ids and
    marks: the reference's own loop, replayed, sees exactly those inputs."""
    p, new = prompt(51, 10), 9                 # a tail of 2; the last block served in part
    toks, marks = reference_run(p, new)
    got = ref.passes_of(SIZES, [int(t) for t in p], toks, marks)
    assert list(got["final"][:len(p) + new]) == [int(t) for t in p] + toks
    # first block: 2 masked, so 2 passes; then 4; of the last block (3 of 4
    # positions never served) only the first pass is known
    assert list(got["block"]) == [2, 2, 3, 3, 3, 3, 4]
    assert list(got["step"]) == [1, 2, 1, 2, 3, 4, 1]
    first = got["ids"][0]
    assert list(first[:2]) == [int(p[8]), int(p[9])] and (first[2:] == CFG.mask_id).all()
    assert (got["masked"].sum(-1) == [2, 1, 4, 3, 2, 1, 4]).all()
    assert (got["shown"].sum(-1)[:6] == 1).all()
    # a generated block's stale rows are its LAST denoising pass's
    assert list(got["stale"][12:16]) == [20, 21, 22, 23] and (got["stale"][:8] == -1).all()
