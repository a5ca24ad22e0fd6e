"""The Nemotron model family on the serving path (models/nemotron.py, the
state-space ops of ops/ssm.py, the relu² form of parallel/moe.py and
ops/grouped_matmul.py, the state kind of serving/paged.py,
serving/family.NemotronFamily) at a tiny size on the CPU, against the plain
float32 reference of benchmark/reference/nemotron.py (the recurrence one
position at a time over the whole sequence) on seeded weights.

A float32 program must choose what the reference chooses (equal tokens)
and give its logits within float32's rounding of a different order of
sums; the bfloat16 program is judged by how far a served token's reference
logit lies under the reference's best, in standard deviations of that
position's logits.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import weights_nemotron
from benchmark.reference import nemotron as ref
from kubeflow_tpu.models import nemotron
from kubeflow_tpu.models.nemotron import NemotronConfig
from kubeflow_tpu.ops.grouped_matmul import grouped_matmul, grouped_swiglu
from kubeflow_tpu.ops.ssm import ssd_scan, ssm_decode_update
from kubeflow_tpu.parallel.moe import held_experts_ffn, shared_expert_layer, sigmoid_top_k
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.family import NemotronFamily, family_for
from kubeflow_tpu.serving.paged import SlotKV, SlotStates

# hidden 64, pattern MEM*E: two Mamba-2 mixers of 8 heads of 8 over a state
# of 16 in 2 groups, one attention layer of 4 heads over 2 KV heads of 16,
# two latent expert layers of 8 held of 16 experts (top 4) in a latent of 16
CFG = NemotronConfig.tiny()
F32 = dataclasses.replace(CFG, dtype=jnp.float32)
SEED = 2**31 + 5
NEW = 12
#: tokens of a bfloat16 answer of 12 that may lie over ``TIE_SD`` under the
#: reference's best: the program reads 0 and 1 over these two prompts (the
#: one is the answer's last token, where the float8 control picks the same
#: token), the control 3 and 7; the widest gap alone cannot tell them apart
#: at this size (0.70 both)
OFF_LIMIT, TIE_SD = 1, 0.01
#: float32 logits of the program against the reference's, over the
#: reference's spread: the chunked scan and the kernels sum in other orders
#: than the recurrence at ``highest`` (1e-6 read here)
LOGIT_TOL = 1e-4


def sizes_of(cfg):
    s = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name != "dtype"}
    return {**s, "time_step_min": 1e-3, "time_step_max": 0.1, "time_step_floor": 1e-4}


SIZES = sizes_of(CFG)


@functools.lru_cache(maxsize=None)
def _canonical():
    return ([weights_nemotron.layer_canonical(SEED, SIZES, i) for i in range(CFG.n_layers)],
            weights_nemotron.top_canonical(SEED, SIZES))


@pytest.fixture(scope="module")
def params():
    return weights_nemotron.program_tree(SEED, SIZES)


@pytest.fixture(scope="module")
def params_f32():
    """The canonical float32 draws in the program's tree."""
    layers, top = _canonical()
    return {**top, "layers": [weights_nemotron.program_layer(w, kind)
                              for w, kind in zip(layers, CFG.pattern)]}


def reference_logits(seq, cast=None, prompt=0, chunk=0, fault=None):
    """The reference's full forward pass over one sequence: [len, vocab]."""
    layers, top = _canonical()
    n = -(-len(seq) // 64) * 64
    x = top["embedding"][jnp.asarray(list(seq) + [0] * (n - len(seq)))]
    k = ref.knobs(SIZES, n, prompt, chunk, fault)
    for w, kind in zip(layers, CFG.pattern):
        x = ref.block_jit(ref.frozen(SIZES), w, x, k, kind=kind, cast=cast)
    return np.asarray(ref.logits_at(SIZES, top, x, cast))[:len(seq)]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).astype(np.int32)


def engine(cfg, params, **kw):
    kw = {"slots": 4, "chunk": 4, "kv_block_t": 4, "prefill_chunk": 16, **kw}
    return ContinuousBatcher(cfg, params, **kw)


def served_gaps(p, out):
    logits = reference_logits(list(p) + list(out))
    at = len(p) - 1 + np.arange(len(out))
    return np.asarray(ref.gaps_under_best(jnp.asarray(logits[at]), jnp.asarray(out)))


def serve(cfg, tree, prompts, new=NEW, **kw):
    eng = engine(cfg, tree, **kw)
    try:
        futures = [eng.submit(p, new) for p in prompts]
        out = [f.result(timeout=600) for f in futures]
        assert eng.kv.alloc.used() == 0
    finally:
        eng.close()
    return out


# -- the model against the reference ---------------------------------------------

@pytest.mark.parametrize("n", [5, 16, 37, 70],
                         ids=["inside_a_page", "one_chunk", "three_chunks", "five_chunks"])
def test_float32_prefill_then_decode_chooses_what_the_reference_does(params_f32, n):
    """Chunked prefill (the states carried chunk to chunk, the attention
    layer's keys into the arena), then 12 decode steps, in float32: every
    token the reference's best."""
    p = prompt(n, n)
    (out,) = serve(F32, params_f32, [p])
    assert len(out) == NEW and served_gaps(p, out).max() == 0.0


@pytest.mark.parametrize("n", [9, 45])
def test_bfloat16_prefill_then_decode_agrees_with_the_reference(params, n):
    p = prompt(100 + n, n)
    (out,) = serve(CFG, params, [p])
    assert all(0 <= t < CFG.vocab_size for t in out)
    assert np.sum(served_gaps(p, out) > TIE_SD) <= OFF_LIMIT


def _prefill(cfg, tree, ids, chunk, slot=1, slots=2, bt=4, blocks=64):
    """A prompt through ``prefill_chunk`` in chunks of ``chunk`` on a fresh
    cache, into ``slot``, its blocks 0.. in order. Returns (the logits of its
    last position, the cache)."""
    cache = nemotron.fresh_cache(cfg, slots, blocks, bt)
    n = len(ids)
    for start in range(0, n, chunk):
        end = min(start + chunk, n)
        piece = np.zeros((chunk,), np.int32)
        piece[:end - start] = ids[start:end]
        held = -(-end // bt)
        read = np.full((blocks // slots,), blocks, np.int32)
        read[:held] = np.arange(held)
        write = np.full((chunk // bt,), blocks, np.int32)
        write[:held - start // bt] = np.arange(start // bt, held)
        logits, cache, _ = jax.jit(functools.partial(nemotron.prefill_chunk, cfg))(
            tree, cache, jnp.asarray(piece), start, end - start, jnp.asarray(read),
            jnp.asarray(write), slot)
    return logits, cache


def test_the_logits_agree_with_the_reference_and_one_chunk_with_four(params_f32):
    """The same 64-token prompt prefilled in one chunk and in four: the last
    position's logits agree with each other and with the reference's, and
    the states each leaves in its slot agree."""
    ids = prompt(3, 64)
    one, cache_one = _prefill(F32, params_f32, ids, 64)
    four, cache_four = _prefill(F32, params_f32, ids, 16)
    want = reference_logits(ids)[-1]
    spread = want.std()
    assert np.abs(np.asarray(one) - want).max() < LOGIT_TOL * spread
    assert np.abs(np.asarray(four) - np.asarray(one)).max() < LOGIT_TOL * spread
    for i, kind in enumerate(CFG.pattern):
        if kind == nemotron.MAMBA:
            for leaf in ("ssm", "conv"):
                a, b = cache_one[f"layer_{i}"][leaf], cache_four[f"layer_{i}"][leaf]
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
                assert not np.any(np.asarray(a[0]))     # the other slot: untouched


def test_a_decode_step_leaves_a_dead_row_s_state_as_it_was(params_f32):
    """Slot 1 holds a prompt's states half-way through its prefill; a
    decode step in which only slot 0 is live must not move them by a bit."""
    _, cache = _prefill(F32, params_f32, prompt(4, 32), 16)
    table = jnp.full((2, 4), 64, jnp.int32)
    live = jnp.asarray([True, False])
    _, after, stats = jax.jit(functools.partial(nemotron.decode_step, F32, trash=64))(
        params_f32, cache, jnp.asarray([5, 6], jnp.int32), table, live)
    for i, kind in enumerate(CFG.pattern):
        if kind == nemotron.MAMBA:
            for leaf in ("ssm", "conv"):
                np.testing.assert_array_equal(after[f"layer_{i}"][leaf][1],
                                              cache[f"layer_{i}"][leaf][1])
                assert np.any(np.asarray(after[f"layer_{i}"][leaf][0]
                                         != cache[f"layer_{i}"][leaf][0]))
    assert int(stats[3]) == CFG.pattern.count(nemotron.MAMBA)   # one live row a layer


def test_chunks_interleaved_with_other_rows_decoding_give_what_they_give_alone(params_f32):
    """A long prompt's five chunks run between decode dispatches of a row
    that is already decoding (one chunk a turn): its states sit through
    those dispatches and must come out unchanged, so its tokens are those
    it gets alone, and the reference's."""
    busy, long = prompt(20, 9), prompt(21, 70)
    alone = serve(F32, params_f32, [long])[0]
    # the short prompt is one chunk and decodes from the next turn on; the
    # long one's chunks follow, one a turn, between its dispatches
    _, together = serve(F32, params_f32, [busy, long], new=NEW)
    assert list(together) == list(alone)
    assert served_gaps(long, together).max() == 0.0


def test_a_reused_slot_serves_what_a_fresh_one_does(params_f32):
    """One slot: a second request's first chunk zeroes the states the first
    left, so it gets what it gets on a fresh engine."""
    a, b = prompt(30, 37), prompt(31, 23)
    (fresh,) = serve(F32, params_f32, [b], slots=1)
    _, reused = serve(F32, params_f32, [a, b], slots=1)
    assert list(reused) == list(fresh)


def test_the_parent_fails_no_family_and_the_family_is_found():
    assert isinstance(family_for(CFG, slots=2, paged=True, kv_block_t=4), NemotronFamily)
    with pytest.raises(ValueError):
        NemotronFamily(CFG, slots=2, paged=False)


# -- the state kind of the cache owner --------------------------------------------

def test_the_state_kind_hands_the_slot_to_a_chunk_and_live_rows_to_a_dispatch():
    kv = SlotKV(3, 32, 4, 24, engine_id="states", states=SlotStates(3))
    res = kv.reserve(10)
    kv.hold(2, res)
    read, write, slot = kv.chunk_tables(2, 0, 8, 8)
    assert int(slot) == 2 and len(write) == 2
    kv.bind([2], [res], [8])
    tables, _ = kv.dispatch_tables([2])
    assert list(np.asarray(tables[-1])) == [False, False, True]
    assert all(not np.asarray(t[-1]).any() for t in kv.warm_tables())
    kv.release(2)
    assert kv.alloc.used() == 0


# -- the ops -------------------------------------------------------------------------

def _recurrence(x, dt, A, B, C, S):
    """The state a position at a time: y_t = S_t C_t."""
    hg = x.shape[1] // B.shape[1]

    def step(S, t):
        xt, dtt, bt, ct = t
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * jnp.repeat(bt, hg, 0)[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, jnp.repeat(ct, hg, 0), precision="highest")

    return jax.lax.scan(step, S, (x, dt, B, C))


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_the_chunked_scan_equals_the_recurrence(chunk):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    T, H, P, G, N = 32, 8, 16, 2, 32
    x = jax.random.normal(k[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H))) * 0.3
    dt = dt.at[-5:].set(0.0)                       # positions that are not real
    A = -jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.7))
    B, C = jax.random.normal(k[3], (T, G, N)), jax.random.normal(k[4], (T, G, N))
    S0 = jax.random.normal(k[5], (H, P, N))
    want_last, want = _recurrence(x, dt, A, B, C, S0)
    y, last = ssd_scan(x, dt, A, B, C, S0, chunk)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pattern", [
    [False] * 5, [False, False, True, False, False], [False, False, False, True, True],
    [True] * 5, [True, False, True, True, False]],
    ids=["none_live", "one_live", "live_at_the_end", "every_row_live", "scattered"])
def test_the_decode_update_kernel_equals_the_update_and_skips_dead_rows(pattern):
    """Interpreted off the TPU: live rows' states decayed, updated and
    contracted as a plain update does; dead rows' states bit for bit as
    they were, and their outputs zero (with no row live the walk is empty:
    every state comes back as it was and ``y`` is all zeros)."""
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    S, G, hg, P, N = 5, 2, 4, 16, 128
    state = jax.random.normal(k[0], (S, G, hg, P, N))
    decay = jnp.exp(-jax.random.uniform(k[1], (S, G * hg)))
    u = jax.random.normal(k[2], (S, G * hg, P))
    B, C = jax.random.normal(k[3], (S, G, N)), jax.random.normal(k[4], (S, G, N))
    live = jnp.asarray(pattern)
    new, y = ssm_decode_update(state, decay, u, B, C, live, interpret=True)
    flat = state.reshape(S, G * hg, P, N)
    want = (decay[:, :, None, None] * flat
            + u[..., None] * jnp.repeat(B, hg, 1)[:, :, None, :])
    want_y = jnp.einsum("shpn,shn->shp", want, jnp.repeat(C, hg, 1), precision="highest")
    keep = live[:, None, None, None]
    np.testing.assert_allclose(new, jnp.where(keep, want, flat).reshape(state.shape),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new)[~np.asarray(live)],
                                  np.asarray(state)[~np.asarray(live)])
    np.testing.assert_allclose(y, jnp.where(live[:, None, None], want_y, 0.0),
                               rtol=1e-5, atol=1e-5)


def _experts(seed, held, d, L, f, fs, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda i, *shape, sd=0.2: (jax.random.normal(k[i], shape) * sd).astype(dtype)
    return {"router": n(0, d, 16, sd=1.0).astype(jnp.float32),
            "router_bias": n(1, 16, sd=0.01).astype(jnp.float32),
            "latent_down": n(2, d, L), "latent_up": n(3, L, d),
            "w_up": n(4, held, L, f), "w_down": n(5, held, f, L),
            "shared": {"w_up": n(6, d, fs), "w_down": n(6, fs, d)}}


def test_the_relu2_walk_equals_a_plain_expert_loop():
    p = _experts(0, 8, 32, 16, 24, 40)
    h = jax.random.normal(jax.random.PRNGKey(9), (10, 16))
    idx, w = sigmoid_top_k(h, p["router"][:16], p["router_bias"], 4)
    live = jnp.arange(10) < 8
    got, stats = held_experts_ffn(h, idx, w, None, p["w_up"], p["w_down"], first_held=4,
                                  live=live)
    want = jnp.zeros_like(h)
    for e in range(8):
        mine = jnp.sum(jnp.where(idx == 4 + e, w, 0.0), axis=-1) * live
        mid = jnp.square(jax.nn.relu(jnp.dot(h, p["w_up"][e], precision="highest")))
        want = want + mine[:, None] * jnp.dot(mid, p["w_down"][e], precision="highest")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(stats[0]) == int(jnp.sum(((idx >= 4) & (idx < 12)) & live[:, None]))


def test_four_shares_of_the_routed_experts_add_up_to_the_uncut_layer():
    """A deployment's four chips each hold a quarter of the 16 experts and
    compute the shared expert whole: their routed parts (scaled, back
    through the linear up-projection) add up to the uncut layer's, with the
    shared expert counted once."""
    whole = _experts(1, 16, 32, 16, 24, 40)
    h = jax.random.normal(jax.random.PRNGKey(2), (12, 32))
    idx, w = sigmoid_top_k(h, whole["router"], whole["router_bias"], 4)
    run = lambda p, first, live=None: shared_expert_layer(
        h, idx, w, p, routed_scale=5.0, first_held=first, live=live)[0]
    uncut = run(whole, 0)
    shared = run(whole, 0, jnp.zeros((12,), bool))         # no routed part
    shares = [run({**whole, "w_up": whole["w_up"][4 * q:4 * q + 4],
                   "w_down": whole["w_down"][4 * q:4 * q + 4]}, 4 * q) - shared
              for q in range(4)]
    np.testing.assert_allclose(shared + sum(shares), uncut, rtol=1e-4, atol=1e-4)


# the SwiGLU walk as the parent commit wrote it: the grouped products, then
# the combine, with nothing of the relu² form
def _parent_held_experts_ffn(h, idx, weights, w_gate, w_up, w_down, first_held, live):
    T, k = idx.shape
    n_held = w_gate.shape[0]
    local = idx.reshape(-1) - first_held
    held = (local >= 0) & (local < n_held) & jnp.repeat(live, k)
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0, dtype=jnp.int32)
    rows = h[order // k]
    in_group = jnp.arange(T * k) < jnp.sum(sizes)
    mid = grouped_swiglu(rows, w_gate, w_up, sizes)
    out = grouped_matmul(mid, w_down, sizes, preferred_element_type=jnp.float32)
    out = jnp.where(in_group[:, None], out, 0.0)
    per_choice = out[jnp.argsort(order)].reshape(T, k, -1)
    y = jnp.einsum("tkd,tk->td", per_choice,
                   jnp.where(held.reshape(T, k), weights, 0.0).astype(jnp.float32))
    return y.astype(h.dtype)


@pytest.mark.parametrize("d,f,experts,held,k", [(64, 32, 16, 16, 4), (64, 32, 16, 8, 4)],
                         ids=["mimo_tiny", "sarvam_tiny"])
def test_the_swiglu_path_gives_the_parent_s_results_bit_for_bit(d, f, experts, held, k):
    keys = jax.random.split(jax.random.PRNGKey(d + held), 7)
    n = lambda i, *shape: (jax.random.normal(keys[i], shape) * 0.2).astype(jnp.bfloat16)
    wg, wu, wd = n(0, held, d, f), n(1, held, d, f), n(2, held, f, d)
    h = n(3, 24, d)
    router = jax.random.normal(keys[4], (d, experts))
    idx, w = sigmoid_top_k(h.astype(jnp.float32), router, jnp.zeros((experts,)), k)
    live = jnp.arange(24) < 21
    got, _ = jax.jit(lambda *a: held_experts_ffn(*a, first_held=0, live=live))(
        h, idx, w, wg, wu, wd)
    want = jax.jit(lambda *a: _parent_held_experts_ffn(*a, 0, live))(h, idx, w, wg, wu, wd)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    shared = {"w_gate": n(5, d, 2 * f), "w_up": n(6, d, 2 * f), "w_down": n(5, 2 * f, d)}
    p = {"w_gate": wg, "w_up": wu, "w_down": wd, "shared": shared}
    got = jax.jit(lambda: shared_expert_layer(h, idx, w, p, routed_scale=2.5, live=live)[0])()

    @jax.jit
    def parent():
        routed = _parent_held_experts_ffn(h, idx, w, wg, wu, wd, 0, live)
        mid = jax.nn.silu(jnp.dot(h, shared["w_gate"])) * jnp.dot(h, shared["w_up"])
        return (jnp.dot(mid, shared["w_down"], preferred_element_type=jnp.float32)
                + 2.5 * routed.astype(jnp.float32))

    np.testing.assert_array_equal(np.asarray(got), np.asarray(parent()))
