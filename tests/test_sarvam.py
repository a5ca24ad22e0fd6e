"""The Sarvam model family on the serving path (models/sarvam.py, the latent
attention kernels of ops/latent_attention.py, the shared expert of
parallel/moe.py, serving/family.SarvamFamily) at a tiny size on the CPU,
against the plain float32 reference of benchmark/reference/sarvam.py (the
expanded form over the whole sequence) on seeded weights.

A float32 program must choose what the reference chooses (equal tokens);
the bfloat16 program is judged by how far a served token's reference logit
lies under the reference's best at its position, in standard deviations of
that position's logits (0: the reference would have chosen it too).
"""

import dataclasses
import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import weights_sarvam
from benchmark.reference import sarvam as ref
from kubeflow_tpu.models import sarvam
from kubeflow_tpu.models.sarvam import SarvamConfig
from kubeflow_tpu.ops.latent_attention import (_tile_ranges, latent_decode_attention,
                                               latent_prefill_attention)
from kubeflow_tpu.parallel.moe import shared_expert_layer, sigmoid_top_k
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.family import SarvamFamily, family_for

# hidden 64, 4 heads of 16 + 8 over a latent of 32, values 16; a dense layer
# then two expert layers of 8 held of 16 experts (top 4) and a shared expert
CFG = SarvamConfig.tiny()
F32 = dataclasses.replace(CFG, dtype=jnp.float32)
SEED = 2**31 + 5
NEW = 12
#: the bfloat16 program's worst served gap at this size reads 0.00-0.03 sd
#: over these prompts (a flip needs a near-tie); the float8 control and every
#: fault read 0.1 or more over three 120-token sequences
GAP_LIMIT_SD = 0.06


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name != "dtype"}


SIZES = sizes_of(CFG)


@pytest.fixture(scope="module")
def params():
    return weights_sarvam.program_tree(SEED, SIZES)


@pytest.fixture(scope="module")
def params_f32():
    """The canonical float32 draws in the program's tree."""
    return {**_top(), "layers": [
        weights_sarvam.program_layer(weights_sarvam.layer_canonical(SEED, SIZES, i), bool(m))
        for i, m in enumerate(CFG.moe_layers)]}


@functools.lru_cache(maxsize=None)
def _top():
    return weights_sarvam.top_canonical(SEED, SIZES)


def reference_logits(seq, cast=None, fault=None):
    """The reference's full forward pass over one sequence: [len, vocab]."""
    pad = -len(seq) % 64
    x = _top()["embedding"][jnp.asarray(list(seq) + [0] * pad)]
    for i, moe in enumerate(CFG.moe_layers):
        w = weights_sarvam.layer_canonical(SEED, SIZES, i)
        x = ref.block(SIZES, w, x, moe=bool(moe), cast=cast, fault=fault)
    return np.asarray(ref.logits_at(SIZES, _top(), x, cast))[:len(seq)]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).astype(np.int32)


def engine(cfg, params, **kw):
    kw = {"slots": 4, "chunk": 4, "kv_block_t": 4, "prefill_chunk": 16, **kw}
    return ContinuousBatcher(cfg, params, **kw)


def served_gaps(p, out):
    logits = reference_logits(list(p) + list(out))
    at = len(p) - 1 + np.arange(len(out))
    return np.asarray(ref.gaps_under_best(jnp.asarray(logits[at]), jnp.asarray(out)))


# -- the model against the reference -----------------------------------------------

@pytest.mark.parametrize("n", [5, 16, 37, 70],
                         ids=["inside_a_page", "one_chunk", "three_chunks", "five_chunks"])
def test_float32_prefill_then_paged_decode_chooses_what_the_reference_does(params_f32, n):
    """Chunked prefill into the latent arena (expanded), then 12 absorbed
    decode steps through its pages, in float32: every token the reference's
    best."""
    eng = engine(F32, params_f32)
    try:
        p = prompt(n, n)
        out = eng.submit(p, NEW).result(timeout=600)
    finally:
        eng.close()
    assert len(out) == NEW and served_gaps(p, out).max() == 0.0


@pytest.mark.parametrize("n", [9, 45])
def test_bfloat16_prefill_then_paged_decode_agrees_with_the_reference(params, n):
    eng = engine(CFG, params)
    try:
        p = prompt(100 + n, n)
        out = eng.submit(p, NEW).result(timeout=600)
        assert eng.kv.alloc.used() == 0
    finally:
        eng.close()
    assert all(0 <= t < CFG.vocab_size for t in out)
    assert served_gaps(p, out).max() <= GAP_LIMIT_SD


def test_ragged_rows_in_one_dispatch_match_one_at_a_time(params_f32):
    """Three prompts of different lengths admitted together, decoded in
    dispatches of 16 steps: each row reads its own pages only."""
    eng = engine(F32, params_f32, chunk=16)
    try:
        prompts = [prompt(7, 7), prompt(33, 33), prompt(61, 61)]
        reqs = [eng.submit(p, NEW) for p in prompts]
        together = [r.result(timeout=600) for r in reqs]
    finally:
        eng.close()
    for p, out in zip(prompts, together):
        assert served_gaps(p, out).max() == 0.0


def _wrong_reading(**kw):
    """The worst gap, under the reference, of the tokens a variant of the
    reference puts first, over every position of three sequences."""
    worst = 0.0
    for seed in (1, 2, 3):
        seq = prompt(seed, 120)
        gaps = ref.gaps_under_best(jnp.asarray(reference_logits(seq)),
                                   jnp.asarray(reference_logits(seq, **kw).argmax(-1)))
        worst = max(worst, float(gaps.max()))
    return worst


@pytest.mark.parametrize("variant", [{"cast": ref.fp8_cast}, {"fault": "no_mscale"},
                                     {"fault": "no_yarn"}, {"fault": "no_latent_norm"},
                                     {"fault": "no_routed_scaling"}, {"fault": "no_shared"}],
                         ids=["fp8", "no_mscale", "no_yarn", "no_latent_norm",
                              "no_routed_scaling", "no_shared"])
def test_the_control_and_each_fault_read_over_the_limit(variant):
    assert _wrong_reading(**variant) > GAP_LIMIT_SD


# -- the two forms of the attention ------------------------------------------------

def _layer_f32(i=1):
    return weights_sarvam.program_layer(weights_sarvam.layer_canonical(SEED, SIZES, i), True)


def test_the_absorbed_decode_equals_the_expanded_form_at_one_step():
    """One position attending to 11 earlier rows: the decode step's absorbed
    attention (query folded through W_uk, context out through W_uv) against
    the prefill's expanded kernel and the reference's textbook form."""
    cfg, layer = F32, _layer_f32()
    T, bt, at = 12, 4, 11
    h = jax.random.normal(jax.random.PRNGKey(3), (T, cfg.d_model), jnp.float32)
    pos = jnp.arange(T)
    rows = sarvam._latent_rows(cfg, layer, h, pos)
    arena = jnp.zeros((5, bt, cfg.row), jnp.float32).at[:3].set(rows.reshape(3, bt, -1))
    table = jnp.asarray([[0, 1, 2]], jnp.int32)
    absorbed, _ = sarvam._decode_attention(
        cfg, layer, arena.at[2, 3].set(0.0), h[at:at + 1], jnp.asarray([at]), table,
        jnp.asarray([True]), trash=4)
    expanded, _ = sarvam._chunk_attention(
        cfg, layer, jnp.zeros((5, bt, cfg.row), jnp.float32), jnp.pad(h, ((0, 4), (0, 0))),
        0, jnp.asarray([0, 1, 2, 3]), jnp.asarray([0, 1, 2, 4]))
    textbook = ref.attention(SIZES, layer, h, None, None)
    np.testing.assert_allclose(absorbed[0], expanded[at], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(absorbed[0], textbook[at], rtol=1e-4, atol=1e-4)


def _plain_decode(q, arena, table, lengths, scale, latent):
    out = []
    for s in range(q.shape[0]):
        rows = arena[table[s]].reshape(-1, arena.shape[-1])[:int(lengths[s])]
        if rows.shape[0] == 0:
            out.append(jnp.zeros((q.shape[1], latent), jnp.float32))
            continue
        sc = jnp.dot(q[s].astype(jnp.float32), rows.astype(jnp.float32).T) * scale
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.dot(p, rows[:, :latent].astype(jnp.float32)))
    return jnp.stack(out)


@pytest.mark.parametrize("pages", [1, 2, 32])
def test_the_decode_kernel_interpreted_against_a_plain_reading(pages):
    """Rows of 0, 1, 5 and 23 positions over pages of 4 (a group of
    ``pages`` pages a DMA wave): each slot over its own pages, nothing past
    its length, zeros where it holds nothing."""
    heads, width, latent, bt = 4, 128, 96, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    arena = jax.random.normal(keys[0], (20, bt, width), jnp.float32).astype(jnp.bfloat16)
    q = jax.random.normal(keys[1], (4, heads, width), jnp.float32).astype(jnp.bfloat16)
    table = jax.random.randint(keys[2], (4, 6), 0, 20, jnp.int32)
    lengths = jnp.asarray([0, 1, 5, 23], jnp.int32)
    got = latent_decode_attention(q, arena, table, lengths, scale=0.1, latent=latent,
                                  pages=pages, interpret=True)
    want = _plain_decode(q, arena, table, lengths, 0.1, latent)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert float(jnp.abs(got[0]).max()) == 0.0


def _prefill_operands(start, keys, chunk, dtype):
    """A kernel call's operands and the plain float32 reading of the same
    numbers at a scale of 0.2."""
    heads, nope, rope_w, latent, dv = 3, 16, 16, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    f = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    q = f(ks[0], (heads, chunk, nope + rope_w))
    view, wuk, wuv = (f(ks[2], (keys, latent + rope_w)), f(ks[3], (heads, latent, nope)) * 0.2,
                      f(ks[4], (heads, latent, dv)) * 0.2)
    q32, view32, wuk32, wuv32 = (a.astype(jnp.float32) for a in (q, view, wuk, wuv))
    k = jnp.einsum("jc,hcn->hjn", view32[:, :latent], wuk32)
    v = jnp.einsum("jc,hcv->hjv", view32[:, :latent], wuv32)
    sc = (jnp.einsum("hqn,hjn->hqj", q32[..., :nope], k)
          + jnp.einsum("hqr,jr->hqj", q32[..., nope:], view32[:, latent:])) * 0.2
    mask = jnp.arange(keys)[None, :] <= (start + jnp.arange(chunk))[:, None]
    want = jnp.einsum("hqj,hjv->hqv", jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1), v)
    return (q, view, wuk, wuv), want


@pytest.mark.parametrize("start,keys,chunk", [
    pytest.param(0, 16, 16, id="0-16"), pytest.param(8, 24, 16, id="8-24"),
    pytest.param(24, 64, 16, id="24-64"),
    pytest.param(16, 40, 16, id="two_blocks_in"),
    pytest.param(13, 29, 16, id="start_on_neither_grid"),
    pytest.param(10, 14, 4, id="one_tile"),
    pytest.param(5, 45, 16, id="keys_padded_past_the_chunk")])
def test_the_prefill_kernel_interpreted_against_a_plain_reading(start, keys, chunk):
    """A chunk of ``chunk`` queries at ``start`` against the rows of
    ``keys`` positions, in key blocks of 8 and query tiles of 4: causal by
    position, every block expanded where the chunk reaches it, the tiles
    across the diagonal masked and those below it not (every case has
    both)."""
    tiles = [_by_hand(start, chunk, j, 4, 8) for j in range(-(-keys // 8))]
    assert any(m for _, m in tiles) and any(set(c) - set(m) for c, m in tiles)
    (q, view, wuk, wuv), want = _prefill_operands(start, keys, chunk, jnp.float32)
    got = latent_prefill_attention(q, view, wuk, wuv, jnp.asarray(start), scale=0.2,
                                   block_q=4, block_k=8, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_bfloat16_prefill_kernel_lies_within_the_served_tolerance():
    """In bfloat16 (keys, values and probabilities rounded to it, scores,
    maxima and sums float32), against the float32 reading of the same
    operands: within ``GAP_LIMIT_SD`` standard deviations of the output."""
    (q, view, wuk, wuv), want = _prefill_operands(13, 45, 16, jnp.bfloat16)
    got = latent_prefill_attention(q, view, wuk, wuv, jnp.asarray(13), scale=0.2, block_q=4,
                                   block_k=8, interpret=True).astype(jnp.float32)
    assert float(jnp.abs(got - want).max() / want.std()) <= GAP_LIMIT_SD


def _by_hand(start, chunk, j, bq, bk):
    """Of key block ``j``, the query tiles that hold some key at or before
    some query (computed), and of those the ones that also hold some key
    past some query (masked)."""
    k = j * bk + np.arange(bk)
    computed, masked = [], []
    for i in range(chunk // bq):
        q = start + i * bq + np.arange(bq)
        if (k[:, None] <= q[None, :]).any():
            computed.append(i)
            if (k[:, None] > q[None, :]).any():
                masked.append(i)
    return computed, masked


def _ranges(start, chunk, j, bq, bk):
    first, inner = (int(x) for x in _tile_ranges(j, start, chunk // bq, bq, bk))
    return list(range(first, chunk // bq)), list(range(first, inner))


@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_the_tile_ranges_split_where_a_brute_force_reading_does(chunk):
    """Every key block the kernel expands (some query of the chunk reaches
    it): the tiles it computes and the ones it masks."""
    for start in range(0, 41, 3):
        for j in range((start + chunk - 1) // 8 + 1):
            assert _ranges(start, chunk, j, 4, 8) == _by_hand(start, chunk, j, 4, 8), (start, j)


def test_the_tile_ranges_at_the_cell_s_chunk():
    """Chunks of 4,096 in the module's tiles of 512 x 1,024: each of the
    chunk's own 4 blocks masks 2 tiles; a block wholly below the chunk
    masks none and computes all 8."""
    from kubeflow_tpu.ops.latent_attention import BLOCK_K, BLOCK_Q

    assert (BLOCK_Q, BLOCK_K) == (512, 1024)
    for start in (0, 28672):
        for j in range(start // 1024, start // 1024 + 4):
            assert _ranges(start, 4096, j, 512, 1024) == (
                list(range(2 * (j - start // 1024), 8)),
                list(range(2 * (j - start // 1024), 2 * (j - start // 1024) + 2)))
    assert _ranges(28672, 4096, 27, 512, 1024) == (list(range(8)), [])


# -- YaRN at the published sizes, computed here -------------------------------------

def test_yarn_ramp_bounds_and_softmax_scale_at_the_published_sizes():
    """Base 1e4, rotary 64, original length 4,096, beta 32 / 1: the ramp
    runs between pair indices 10 and 23; the scale is 192^-0.5 x (0.1 ln 40
    + 1)^2 = 0.1352; both computed here from the formulas, independently of
    the model's and the reference's code."""
    dim, base, orig = 64, 1e4, 4096

    def bound(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    lo, hi = math.floor(bound(32)), math.ceil(bound(1))
    assert (lo, hi) == (10, 23)
    scale = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert round(scale, 4) == 0.1352
    cfg = SarvamConfig()
    assert cfg.softmax_scale == pytest.approx(scale, rel=1e-12)
    inv = np.asarray(sarvam.yarn_inv_freq(cfg))
    f = base ** (-np.arange(0, dim, 2) / dim)
    # below the lower bound the original frequencies, above the upper one a
    # fortieth of them, between a straight line in the pair index
    np.testing.assert_allclose(inv[:lo + 1], f[:lo + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[hi:], f[hi:] / 40, rtol=1e-6)
    r = (np.arange(dim // 2) - lo) / (hi - lo)
    np.testing.assert_allclose(inv[lo:hi], f[lo:hi] / 40 * r[lo:hi] + f[lo:hi] * (1 - r[lo:hi]),
                               rtol=1e-6)
    ref_sizes = sizes_of(cfg)
    assert ref.correction_bounds(ref_sizes) == (10, 23)
    assert ref.softmax_scale(ref_sizes) == pytest.approx(scale, rel=1e-12)
    np.testing.assert_allclose(np.asarray(ref.inv_freq(ref_sizes)), inv, rtol=1e-6)


# -- the expert layer: a share of a four-chip layer -----------------------------------

def test_the_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """An uncut expert layer (all 16 experts) against four chips' shares of
    4 each, the shared expert counted once: the reference's and the
    program's (``parallel/moe.shared_expert_layer``) alike."""
    sizes = dict(SIZES, held_experts=16)
    w = weights_sarvam.layer_canonical(SEED, sizes, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.d_model), jnp.float32)
    uncut = ref.expert_layer(sizes, w, h, None, None)
    shared = ref.swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], None)
    share = {k: w[k] for k in ("router", "router_bias", "shared_gate", "shared_up",
                               "shared_down")}
    parts = [ref.routed(sizes, {**share, **{k: w[k][4 * c:4 * c + 4] for k in
                                            ("w_gate", "w_up", "w_down")}}, h, None, 4 * c)
             for c in range(4)]
    np.testing.assert_allclose(shared + CFG.routed_scaling * sum(parts), uncut,
                               rtol=1e-5, atol=1e-5)
    idx, weights = sigmoid_top_k(h, w["router"], w["router_bias"], CFG.experts_per_token)
    program = []
    for c in range(4):
        layer = weights_sarvam.program_layer(
            {**share, **{k: w[k][4 * c:4 * c + 4] for k in ("w_gate", "w_up", "w_down")}},
            True)["moe"]
        out, stats = shared_expert_layer(h, idx, weights, layer, routed_scale=CFG.routed_scaling,
                                         first_held=4 * c)
        program.append(out)
    np.testing.assert_allclose(sum(program) - 3 * shared, uncut, rtol=1e-4, atol=1e-4)


# -- the family in the engine --------------------------------------------------------

def test_the_family_is_found_by_the_configuration_type_and_refuses_what_it_lacks():
    assert isinstance(family_for(CFG, slots=2), SarvamFamily)
    for kw in ({"paged": False}, {"kv_dtype": "int8"}):
        with pytest.raises(ValueError):
            SarvamFamily(CFG, slots=2, **kw)
    assert family_for(CFG, slots=2).rings(4) is None


def test_the_arena_is_one_latent_row_a_position_and_layer():
    cache = SarvamFamily(CFG, slots=2, kv_blocks=10, kv_block_t=4).fresh_cache()
    assert set(cache) == {"cursors"} | {f"layer_{i}" for i in range(CFG.n_layers)}
    assert cache["layer_0"].shape == (11, 4, 128) and CFG.row == 128
    assert SarvamConfig().row == 640


def test_a_dispatch_counts_the_latent_pages_its_live_rows_read(params_f32):
    """The decode step's counter: a live row at cursor ``c`` fetches
    ``c // block_t + 1`` pages a layer; a dead row none."""
    cache = sarvam.fresh_cache(F32, 3, 12, 4)
    cache["cursors"] = jnp.asarray([5, 0, 9], jnp.int32)
    table = jnp.asarray([[0, 1, 2], [12, 12, 12], [3, 4, 5]], jnp.int32)
    _, _, stats = sarvam.decode_step(F32, params_f32, cache, jnp.asarray([1, 2, 3]), table,
                                     trash=12)
    assert int(stats[3]) == (5 // 4 + 1) + (9 // 4 + 1)
    assert int(stats[0]) <= 2 * CFG.experts_per_token * sum(CFG.moe_layers)


def test_a_prompt_leaves_no_block_held_and_the_counters_ride_home(params):
    from kubeflow_tpu.runtime.metrics import METRICS

    before = METRICS.value("serving_latent_pages_read_total")
    eng = engine(CFG, params)
    try:
        eng.submit(prompt(3, 21), NEW).result(timeout=600)
        assert eng.kv.alloc.used() == 0
    finally:
        eng.close()
    assert METRICS.value("serving_latent_pages_read_total") > before
