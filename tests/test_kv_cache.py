"""ops/kv_cache.py — the KV write of the paged serving path (one XLA
scatter a token through the block table), int8 KV quantization, and the
contiguous per-slot cache's where-select write in models/gpt.py."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


def test_per_slot_decode_past_the_end_writes_nothing():
    """Idle/retired rows keep stepping past their end in the engine (static
    shapes: every row computes every chunk). The contiguous cache's
    where-select write must leave such a row untouched — no position
    compares equal to a cursor at or beyond ``max_seq`` — instead of
    corrupting the last KV position (T-1 may hold a live token for a row at
    exactly full length)."""
    from kubeflow_tpu.models.gpt import GptConfig, GptLM

    cfg = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                    max_seq=24, vocab_size=128)
    T = cfg.max_seq
    params = GptLM(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))["params"]
    model = GptLM(cfg, decode=True, per_slot=True)
    S = 4
    kv = (S, T, cfg.n_heads, cfg.head_dim)
    cursors = jnp.asarray([0, T, T + 5, 3], jnp.int32)
    cache = {f"block_{i}": {"attention": {
        "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
        "cursors": cursors}} for i in range(cfg.n_layers)}
    _, upd = model.apply({"params": params, "cache": cache},
                         jnp.asarray([[3], [7], [11], [5]], jnp.int32),
                         mutable=["cache"])
    for layer in upd["cache"].values():
        for name in ("k", "v"):
            out = np.asarray(layer["attention"][name], np.float32)
            assert np.abs(out[0, 0]).sum() > 0 and np.abs(out[3, 3]).sum() > 0
            assert np.abs(out[0, 1:]).sum() == 0 and np.abs(out[3, :3]).sum() == 0
            assert np.abs(out[1]).sum() == 0 and np.abs(out[2]).sum() == 0
        np.testing.assert_array_equal(np.asarray(layer["attention"]["cursors"]),
                                      np.asarray(cursors) + 1)


# -- paged (block-table) variants — ISSUE 12 ---------------------------------

from kubeflow_tpu.ops.kv_cache import kv_block_update
from kubeflow_tpu.serving.paged import KVBlockAllocator, KVBlocksExhausted


def _paged_reference(arena, seg, cursors, tables, max_seq):
    """Plain-numpy oracle: write seg[s, j] at the block-table-mapped
    position cursors[s] + j; out-of-range positions land in the trash row
    (arena's last)."""
    out = np.array(arena, copy=True)
    N, bt = out.shape[:2]
    for s in range(seg.shape[0]):
        for j in range(seg.shape[1]):
            pos = int(cursors[s]) + j
            blk = int(tables[s, pos // bt]) if pos < max_seq else N - 1
            out[blk, pos % bt] = seg[s, j]
    return out


def test_block_update_matches_reference():
    """The XLA scatter == the numpy oracle, over random cursors and a
    shuffled (non-identity) block table."""
    S, MB, bt, H, D = 5, 4, 8, 2, 4
    max_seq = MB * bt
    n_blocks = S * MB
    rng = np.random.default_rng(7)
    arena_np = rng.normal(size=(n_blocks + 1, bt, H, D)).astype(np.float32)
    new_np = rng.normal(size=(S, H, D)).astype(np.float32)
    cursors = rng.integers(0, max_seq, S).astype(np.int32)
    perm = rng.permutation(n_blocks)[: S * MB].reshape(S, MB).astype(np.int32)
    want = _paged_reference(arena_np, new_np[:, None], cursors, perm, max_seq)
    out = kv_block_update(jnp.asarray(arena_np), jnp.asarray(new_np)[:, None],
                          jnp.asarray(cursors), jnp.asarray(perm),
                          max_seq=max_seq)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_block_update_out_of_range_writes_only_trash():
    """Cursors at/past max_seq: the scatter redirects the write into the
    trash row, so no real data can be corrupted by a retired/idle row
    stepping past its end."""
    S, MB, bt, H, D = 3, 2, 4, 2, 4
    max_seq = MB * bt
    n_blocks = S * MB
    arena = jnp.zeros((n_blocks + 1, bt, H, D), jnp.float32)
    new = jnp.ones((S, H, D), jnp.float32)
    tables = jnp.arange(S * MB, dtype=jnp.int32).reshape(S, MB)
    cursors = jnp.asarray([max_seq, max_seq + 3, 1], jnp.int32)
    out = np.asarray(kv_block_update(arena, new[:, None], cursors, tables,
                                     max_seq=max_seq))
    assert out[tables[2, 0], 1].all()          # in-range row wrote
    assert out[: n_blocks].sum() == H * D      # ...and ONLY that row
    # multi-token segment straddling max_seq: the tail goes to trash
    seg = jnp.ones((1, 3, H, D), jnp.float32)
    out = np.asarray(kv_block_update(
        arena, seg, jnp.asarray([max_seq - 1], jnp.int32), tables[:1],
        max_seq=max_seq))
    assert out[: n_blocks].sum() == H * D          # one real write
    assert out[n_blocks].sum() == 2 * H * D        # two trash writes


def test_block_allocator_accounting_and_backpressure():
    alloc = KVBlockAllocator(8, 16)
    assert alloc.trash == 8 and alloc.available() == 8 and alloc.used() == 0
    assert alloc.blocks_for(1) == 1 and alloc.blocks_for(16) == 1
    assert alloc.blocks_for(17) == 2
    res = alloc.reserve(5)
    # reserved-but-ungranted blocks count against available, not used
    assert alloc.available() == 3 and alloc.used() == 0
    got = alloc.grant(res, 2)
    assert len(got) == 2 and res.granted == got
    assert alloc.used() == 2 and alloc.available() == 3
    assert alloc.grant(res, 2) == []               # idempotent up-to
    # exhaustion -> FleetSaturated-family back-pressure, never corruption
    with pytest.raises(KVBlocksExhausted):
        alloc.reserve(4)
    from kubeflow_tpu.serving.errors import FleetSaturated
    assert issubclass(KVBlocksExhausted, FleetSaturated)
    res2 = alloc.reserve(3)
    alloc.grant(res2, 3)
    assert alloc.available() == 0 and alloc.used() == 5
    # impossible request fails fast (waiting can never help)
    with pytest.raises(ValueError):
        alloc.reserve(9)
    # release returns granted AND promised blocks
    alloc.release(res)
    assert alloc.available() == 5 and alloc.used() == 3
    alloc.release(res2)
    assert alloc.available() == 8 and alloc.used() == 0
    # grant caps at the reservation total; trash is never handed out
    res3 = alloc.reserve(2)
    granted = alloc.grant(res3, 99)
    assert len(granted) == 2 and alloc.trash not in granted


def test_block_allocator_publishes_gauges():
    from kubeflow_tpu.runtime.metrics import METRICS

    alloc = KVBlockAllocator(4, 8, engine_id="gauge-test")
    res = alloc.reserve(3)
    alloc.grant(res, 3)
    free = METRICS.gauge("serving_kv_blocks_free", replica="gauge-test")
    used = METRICS.gauge("serving_kv_blocks_used", replica="gauge-test")
    assert free.value == 1 and used.value == 3
    alloc.release(res)
    assert free.value == 4 and used.value == 0


# -- int8 KV quantization (ISSUE 18) ------------------------------------------


def test_quantize_dequantize_round_trip_within_half_scale():
    from kubeflow_tpu.ops.kv_cache import dequantize_kv, quantize_kv

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 7, 8, 16)).astype(np.float32))
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1] + (1,)
    err = np.abs(np.asarray(dequantize_kv(q, scale)) - np.asarray(x))
    # symmetric rounding: every element lands within half a scale step
    bound = np.asarray(scale) / 2.0 + 1e-7
    assert (err <= bound).all(), f"max quant error {err.max()} exceeds bound"


def test_quantize_all_zero_rows_are_exact():
    from kubeflow_tpu.ops.kv_cache import dequantize_kv, quantize_kv

    x = jnp.zeros((2, 3, 4, 8), jnp.float32)
    q, scale = quantize_kv(x)
    assert np.asarray(q).sum() == 0 and np.asarray(scale).sum() == 0
    assert np.asarray(dequantize_kv(q, scale)).sum() == 0


def test_quantize_is_deterministic_across_jit_contexts():
    """The KV-handoff parity contract: the wire exporter and the local
    store path must produce the same int8 codes for identical inputs,
    jitted or not. (Scales may drift one ULP under XLA's reciprocal
    fusion — harmless, the wire ships the exporter's scales verbatim so
    import never recomputes them.)"""
    from kubeflow_tpu.ops.kv_cache import quantize_kv

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(3, 4, 2, 8)).astype(np.float32))
    q0, s0 = quantize_kv(x)
    q1, s1 = jax.jit(quantize_kv)(x)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-6)


def test_quantized_write_puts_code_and_scale_at_the_same_mapped_position():
    """The int8 arena's write as ``GptAttention`` makes it: quantize, then
    the same scatter through the same table for the codes and for the
    scales; a row past ``max_seq`` touches no real block of either."""
    from kubeflow_tpu.ops.kv_cache import quantize_kv

    S, MB, block_t, H, D = 3, 4, 4, 2, 8
    N = S * MB + 1  # one arena block per table entry + the trash row
    max_seq = block_t * MB
    rng = np.random.default_rng(5)
    arena = jnp.asarray(rng.integers(-127, 128, (N, block_t, H, D)), jnp.int8)
    scales = jnp.asarray(rng.random((N, block_t, H, 1)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(S, H, D)).astype(np.float32))
    cursors = jnp.asarray([0, 5, max_seq], jnp.int32)  # last row out of range
    tables = jnp.asarray(np.arange(S * MB).reshape(S, MB), jnp.int32)
    q, s = quantize_kv(new)
    got_q = kv_block_update(arena, q[:, None], cursors, tables, max_seq=max_seq)
    got_s = kv_block_update(scales, s[:, None], cursors, tables, max_seq=max_seq)
    want_q = np.array(arena, copy=True)
    want_s = np.array(scales, copy=True)
    for row in range(S):
        pos = int(cursors[row])
        if pos >= max_seq:
            continue  # out-of-range rows go to the trash row (N - 1)
        blk = int(tables[row, pos // block_t])
        want_q[blk, pos % block_t] = np.asarray(q[row])
        want_s[blk, pos % block_t] = np.asarray(s[row])
    np.testing.assert_array_equal(np.asarray(got_q)[:N - 1], want_q[:N - 1])
    np.testing.assert_array_equal(np.asarray(got_s)[:N - 1], want_s[:N - 1])


# -- the decode view bounded to the granted columns — ISSUE 27 ----------------

def test_block_update_beyond_a_bounded_table_writes_only_trash():
    """A caller may pass only the table's first columns (the decode view
    bounded to the longest granted row). A row whose cursor lies beyond
    them — dead, or stepping past its budget — must write to trash, never
    through the clamped last column into a live block; rows inside the
    columns write as with the whole table."""
    from kubeflow_tpu.ops.kv_cache import quantize_kv

    S, MB, bt, H, D = 3, 4, 4, 2, 4
    max_seq = MB * bt
    n_blocks = S * MB
    whole = jnp.arange(n_blocks, dtype=jnp.int32).reshape(S, MB)
    bounded = whole[:, :2]                      # positions 0..7
    cursors = jnp.asarray([8, 13, 5], jnp.int32)    # two beyond, one inside
    new = jnp.ones((S, H, D), jnp.float32)
    arena = jnp.zeros((n_blocks + 1, bt, H, D), jnp.float32)
    out = np.asarray(kv_block_update(arena, new[:, None], cursors, bounded,
                                     max_seq=max_seq))
    assert out[whole[2, 1], 1].all()            # the row inside wrote
    assert out[:n_blocks].sum() == H * D        # ...and no other real block
    assert out[n_blocks].sum() > 0              # the others went to trash
    codes, scales = quantize_kv(new)
    q = kv_block_update(jnp.zeros(arena.shape, jnp.int8), codes[:, None],
                        cursors, bounded, max_seq=max_seq)
    s = kv_block_update(jnp.zeros(arena.shape[:3] + (1,)), scales[:, None],
                        cursors, bounded, max_seq=max_seq)
    assert np.abs(np.asarray(q)[:n_blocks]).sum() == 127 * H * D
    assert np.asarray(s)[:n_blocks].sum() == pytest.approx(H / 127.0)


@pytest.mark.parametrize("dead_row", [False, True])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("seg_len", [1, 4])
def test_paged_decode_bounded_table_matches_whole_table(seg_len, kv_dtype, dead_row):
    """``GptLM.apply`` with the block table cut to its live columns gives
    the whole table's logits for every live row (to float32 rounding: the
    positions left out carry exactly zero weight, only the sums' order
    changes), and the same arena and cursors. The dead row's cursor lies
    beyond the bounded table: its write must land in trash."""
    from kubeflow_tpu.models.gpt import GptConfig, GptLM

    cfg = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                    max_seq=64, vocab_size=128)
    S, bt, mb = 3, 8, 8
    n_blocks = S * mb
    quant = kv_dtype == "int8"
    params = GptLM(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))["params"]
    model = GptLM(cfg, decode=True, per_slot=True, paged=True,
                  kv_blocks=n_blocks + 1, kv_block_t=bt, kv_dtype=kv_dtype)
    rng = np.random.default_rng(27)
    granted = [2, 3, 0 if dead_row else 1]       # blocks per row: a prefix
    cursors = [9, 17, 40 if dead_row else 2]     # the segment stays inside them
    tables = np.full((S, mb), n_blocks, np.int32)
    free = list(rng.permutation(n_blocks))
    for row, n in enumerate(granted):
        tables[row, :n] = [free.pop() for _ in range(n)]
    arena = (n_blocks + 1, bt, cfg.n_heads, cfg.head_dim)

    def layer():
        if quant:
            att = {name: jnp.asarray(rng.integers(-127, 128, arena), jnp.int8)
                   for name in ("k_arena", "v_arena")}
            att.update({name: jnp.asarray(rng.uniform(0.001, 0.02, arena[:3] + (1,)),
                                          jnp.float32)
                        for name in ("k_scale", "v_scale")})
        else:
            att = {name: jnp.asarray(rng.normal(size=arena), cfg.dtype)
                   for name in ("k_arena", "v_arena")}
        att["cursors"] = jnp.asarray(cursors, jnp.int32)
        return {"attention": att}

    cache = {f"block_{i}": layer() for i in range(cfg.n_layers)}
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, seg_len)), jnp.int32)

    def run(table):
        return jax.jit(lambda t: model.apply(
            {"params": params, "cache": cache}, ids, mutable=["cache"],
            block_tables=t))(jnp.asarray(table))

    whole_logits, whole = run(tables)
    view = max(granted) + 1                       # a width above the longest row
    cut_logits, cut = run(tables[:, :view])
    live = [row for row, n in enumerate(granted) if n]
    np.testing.assert_allclose(np.asarray(cut_logits)[live],
                               np.asarray(whole_logits)[live], rtol=2e-5, atol=2e-5)
    for name, att in whole["cache"].items():
        for key, value in att["attention"].items():
            got = np.asarray(cut["cache"][name]["attention"][key])
            want = np.asarray(value)
            if key != "cursors":                  # dead rows share the trash block
                got, want = got[:n_blocks], want[:n_blocks]
            np.testing.assert_array_equal(got, want, err_msg=f"{name}/{key}")
