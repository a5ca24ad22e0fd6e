"""``ops/grouped_matmul.py`` against a plain float32 loop over the groups, on
the CPU in interpret mode: every way the groups can lie over the row tiles
(128 rows a tile), at widths under a tile and no multiple of one."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import grouped_matmul as gm

# name: (rows in the buffer, k, n, rows of each group)
CASES = {
    "empty_groups_between": (300, 40, 36, [90, 0, 0, 120, 0, 60]),
    "boundary_inside_a_tile": (256, 24, 20, [70, 186]),
    "several_groups_in_one_tile": (128, 20, 12, [3, 5, 1, 40, 2, 9, 30]),
    "one_group_spans_tiles": (400, 16, 24, [10, 330, 20]),
    "all_rows_in_one_group": (260, 16, 8, [0, 0, 260, 0]),
    "rows_past_the_last_group": (384, 32, 16, [40, 50, 30]),
    "one_group_only": (200, 12, 20, [150]),
    "no_rows_in_groups": (300, 24, 16, [0, 0, 0]),
    "buffer_under_a_tile": (24, 20, 12, [3, 0, 5, 0, 7]),
    "groups_end_on_tile_lines": (384, 16, 136, [128, 128, 128]),
    "column_tiles": (200, 48, 384, [60, 0, 100, 30]),
}


def _by_group(x, w, sizes):
    """Row r of group g times w[g], float32; the rows in groups only."""
    out, at = [], 0
    for g, size in enumerate(sizes):
        out.append(np.asarray(x[at:at + size], np.float32) @ np.asarray(w[g], np.float32))
        at += size
    return np.concatenate(out) if out else np.zeros((0, w.shape[2]), np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("fused", [False, True], ids=["matmul", "swiglu"])
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_products_match_a_loop_over_groups(case, fused, dtype, monkeypatch):
    m, k, n, sizes = CASES[case]
    if case == "column_tiles":            # three column blocks of 128 a matrix
        monkeypatch.setattr(gm, "_RHS_BLOCK_BYTES", k * 128 * jnp.dtype(dtype).itemsize)
        assert gm._column_tile(k, n, jnp.dtype(dtype).itemsize) == 128
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    x = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    w = [(jax.random.normal(key, (len(sizes), k, n), jnp.float32) / np.sqrt(k)).astype(dtype)
         for key in keys[1:]]
    group_sizes = jnp.asarray(sizes, jnp.int32)
    held = sum(sizes)
    if fused:
        got = gm.grouped_swiglu(x, w[0], w[1], group_sizes)
        gate, up = _by_group(x, w[0], sizes), _by_group(x, w[1], sizes)
        want = gate / (1.0 + np.exp(-gate)) * up
        assert got.dtype == dtype
    else:
        got = gm.grouped_matmul(x, w[0], group_sizes)
        want = _by_group(x, w[0], sizes)
        assert got.dtype == jnp.float32
    assert got.shape == (m, n)
    # operands as they are and float32 sums: only the fused result is rounded
    tol = 2e-2 if (fused and dtype == jnp.bfloat16) else 1e-4
    np.testing.assert_allclose(np.asarray(got[:held], np.float32), want, rtol=tol, atol=tol)
    # a visited tile's rows past the groups are zeros (an unvisited tile's
    # are whatever the buffer held: the caller masks them)
    tile = min(m, gm._ROW_TILE)
    visited_end = min(m, -(-held // tile) * tile)
    assert not np.asarray(got[held:visited_end], np.float32).any()


def test_visits_are_the_tile_group_pairs_that_hold_a_row():
    sizes, visits = [90, 0, 0, 120, 0, 60], 3 + 6 - 1
    plan = np.asarray(gm._visits(jnp.asarray(sizes, jnp.int32), 300, 128))
    assert plan.shape == (1 + 7 + 2 * visits,)
    n, offsets = plan[0], plan[1:8]
    group, tile = plan[8:8 + visits], plan[8 + visits:]
    assert list(zip(tile[:n], group[:n])) == [(0, 0), (0, 3), (1, 3), (1, 5), (2, 5)]
    assert list(offsets) == [0, 90, 90, 90, 210, 210, 270]
    assert gm._visits(jnp.zeros(4, jnp.int32), 300, 128)[0] == 0


def test_tiles_follow_the_shapes():
    # SDAR's matrices are taken whole, MiMo's in column blocks of 512
    assert gm._column_tile(2048, 768, 2) == 768 and gm._column_tile(768, 2048, 2) == 2048
    assert gm._column_tile(4096, 2048, 2) == 512 and gm._column_tile(2048, 4096, 2) == 1024
    assert gm._column_tile(40, 36, 4) == 36           # under a lane tile: whole


def test_mismatched_shapes_are_refused():
    x, w = jnp.zeros((8, 4)), jnp.zeros((2, 5, 3))
    with pytest.raises(ValueError, match="do not match"):
        gm.grouped_matmul(x, w, jnp.zeros(2, jnp.int32))
    with pytest.raises(ValueError, match="do not match"):
        gm.grouped_matmul(jnp.zeros((8, 5)), w, jnp.zeros(3, jnp.int32))
