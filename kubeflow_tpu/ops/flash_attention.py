"""Fused blockwise attention (FlashAttention-2 style) as a Pallas TPU kernel.

Replaces the materialized [lq, lk] score matrix with an online-softmax over
k/v blocks streamed through VMEM: O(block_q x block_k) live scores, f32
accumulators, bf16-friendly inputs, MXU-shaped (128-lane) tiles. Forward and
backward are both Pallas kernels wired through ``jax.custom_vjp`` with the
log-sum-exp residual, so training steps never allocate the full score
matrix either.

``q_offset``/``k_offset`` shift the *global* positions used for causal
masking, which is exactly what ring attention needs: each ring step holds a
k/v block from another device and masks by that block's global position
(parallel/ring_attention.py). Grid iteration on TPU is sequential over the
minor-most grid dim, so accumulators live in VMEM scratch across k-block
steps (the canonical Pallas accumulation pattern).

The grid's tiles stay large (up to 1,024 a side, ``_block_sizes``), so at a
causal length of 1,024 one grid step holds a head's whole square. The two
backward kernels classify each grid step by the diagonal (``_walk``): a step
wholly above it is skipped, one wholly below it runs whole and unmasked, and
only a step that the diagonal crosses walks its tile in square sub-tiles of
``_SUB``, skipping those above the diagonal and masking only those it
crosses. The forward runs each step it cannot skip whole and masked: walked
in sub-tiles it measured slower, since each row of sub-tiles pays the online
softmax's running state (``_block_sizes``). Non-causal calls run every step
whole and unmasked.

The reference has no kernels of any kind (SURVEY.md §2.9: its only compiled
code is five Go control-plane binaries); this module is part of the
in-workload compute path the TPU-native build adds.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_LANE = 128
#: Side of the square sub-tiles the backward kernels walk a grid step that
#: the causal diagonal crosses in (see ``_block_sizes`` for how it was chosen).
_SUB = 256

#: ``checkpoint_name``s of the two residuals the forward kernel makes: its
#: output and the log-sum-exp. A ``jax.checkpoint`` whose policy saves both
#: keeps the forward ``pallas_call`` out of its backward (the other three
#: residuals, q, k and v, are the caller's to keep or rebuild). Outside a
#: ``jax.checkpoint`` the names compute nothing, and only a differentiated
#: program traces them (``_flash_fwd``): no forward-only program holds one.
SAVED_RESIDUALS = ("flash_out", "flash_lse")


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _auto_block(length: int, cap: int) -> int:
    """Largest 128-aligned divisor of ``length`` up to ``cap`` (whole length
    when it is shorter than a lane tile). Lengths with no 128-aligned
    divisor fall back to the largest 8-aligned divisor >= 64 (Mosaic
    sublane tiling), and failing that to the whole length as ONE block —
    which ``flash_attention`` then rejects on the TPU path when it is not
    8-aligned (clear error instead of an opaque Mosaic failure)."""
    if length <= 128:
        return length
    best = 0
    d = 128
    while d <= min(cap, length):
        if length % d == 0:
            best = d
        d += 128
    if best:
        return best
    # No 128-aligned divisor: largest 8-aligned divisor, floored at 64 — a
    # tiny block would explode the grid (lq/bq × lk/bk steps), and Mosaic
    # rejects block shapes whose sublane dim isn't a multiple of 8, so
    # non-8-aligned divisors would only fail later with an opaque compile
    # error (ADVICE r3). Below the floor, run the whole length as ONE
    # block: always a divisor, grid of 1, just more VMEM (the caller
    # rejects it on the TPU path if it isn't 8-aligned).
    for d in range(min(cap, length) & ~7, 63, -8):
        if length % d == 0:
            return d
    return length


def _auto_tile_cap() -> int:
    # The 1024 cap budgets ~4 MiB of f32 scores plus accumulators/iotas
    # against the ~128 MiB VMEM of v4/v5/v6-class chips; v2/v3 (~16 MiB)
    # get a 256 cap so the auto default stays within what the old 128x128
    # tiles compiled under (ADVICE r3: the big cap was a silent portability
    # regression for earlier generations).
    kind = jax.devices()[0].device_kind.lower()
    return 256 if ("v2" in kind or "v3" in kind) else 1024


def _block_sizes(lq: int, lk: int, block_q: Optional[int], block_k: Optional[int]) -> Tuple[int, int]:
    # Auto-tiling: measured on v5e at GPT shapes (b8 h16 L1024 d64,
    # fwd+bwd), (block_q, block_k) = (128,128) sustains 8.1 TF/s, (512,1024)
    # 22.8, (1024,1024) 23.7 — bigger tiles amortize the softmax VPU work
    # against MXU dots and cut grid-step overhead ~3x (GPT-2-medium step:
    # 20.9% -> 41.2% MFU). Scores VMEM is bq*bk*4B = 4 MiB at the caps, far
    # under the 128 MiB budget even with q/k/v/o blocks alongside.
    # Round-4 note: an ISOLATED grad-chain probe preferred (512,1024) by
    # 13%, but the full GPT-2-medium train step measured consistently WORSE
    # with a 512 q-cap (41.4 vs 42.4% MFU, two runs each) — in-model, XLA
    # overlaps the flash bwd with surrounding matmuls differently than any
    # attention-only microbenchmark. The 1024 cap stands on the end-to-end
    # number; tune via explicit block_q/block_k, not the auto default.
    # The causal work is cut inside a grid step instead: the tile stays at
    # up to 1,024, and the backward walks a step the diagonal crosses in
    # sub-tiles of _SUB, skipping those above it and masking only those on
    # it (_walk). Measured on v5e at b8 h16 L1024 d64 bfloat16 (ms a call,
    # forward / dq / dkv), the whole square 0.457 / 0.586 / 0.832; walked
    # in sub-tiles of 128 0.491 / 0.419 / 1.060, of 256 0.642 / 0.403 /
    # 0.618, of 512 0.622 / 0.458 / 0.654. The forward is slower walked at
    # every size (each row of sub-tiles pays the online softmax's running
    # state), so it runs its step whole. The backward is fastest at 256,
    # and so is the training cell (gpt2-medium.train.b8x1024, tokens/s,
    # the forward whole): 128 -> 32,050, 256 -> 33,492, 512 -> 33,195,
    # against 32,237 for the whole square.
    cap = _auto_tile_cap()
    bq = _auto_block(lq, cap) if block_q is None else min(block_q, lq)
    bk = _auto_block(lk, cap) if block_k is None else min(block_k, lk)
    if lq % bq or lk % bk:
        raise ValueError(
            f"block sizes ({bq}, {bk}) must divide sequence lengths ({lq}, {lk})"
        )
    return bq, bk


# ---------------------------------------------------------------------------
# causal sub-tiles
# ---------------------------------------------------------------------------


def _sub_side(block: int) -> int:
    """Side of the sub-tiles a grid step walks its block in: ``_SUB`` where
    it divides the block, else the whole block."""
    return _SUB if block % _SUB == 0 else block


def _sub_tile_class(gap, rows: int, cols: int):
    """Of ``rows`` queries against ``cols`` keys, the first query ``gap``
    positions after the first key: whether some query sees some key
    (``seen``; else the tile is skipped) and whether every query sees every
    key (``full``; then it needs no mask). Python bools for a Python
    ``gap``, traced ones where a program id enters."""
    return gap + rows - 1 >= 0, gap >= cols - 1


def _gap(q_offset: int, k_offset: int, block_q: int, block_k: int,
         nq: int, nk: int, q_axis: int, k_axis: int):
    """The grid step's first query position less its first key position,
    and that gap in a step that crosses the diagonal. Both are Python ints
    where the grid has one block along each axis. Where program ids enter,
    square blocks whose offsets differ by whole blocks cross only at a gap
    of 0; any other grid gives ``None``: its crossing steps run masked whole."""
    base = q_offset - k_offset
    if nq == nk == 1:
        return base, base
    gap = base
    if nq > 1:
        gap = gap + pl.program_id(q_axis) * block_q
    if nk > 1:
        gap = gap - pl.program_id(k_axis) * block_k
    return gap, (0 if block_q == block_k and base % block_q == 0 else None)


def _mask(s, gap):
    """Scores ``s`` of queries whose first lies ``gap`` positions after the
    first key, ``_NEG_BIG`` where a key lies past its query."""
    rows, cols = s.shape
    q_pos = gap + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_BIG)


def _walk(group, causal: bool, gap, crossing, block_q: int, block_k: int,
          *, keys_outer: bool = False):
    """Call ``group(o0, size, parts)`` over a ``block_q x block_k`` grid
    step: ``parts`` lists as ``(i0, size, mask)`` the spans along the inner
    axis (queries with ``keys_outer``, the dk/dv accumulation; else keys)
    that the rows ``o0 .. o0 + size - 1`` of the outer axis see, where
    ``mask`` is ``None`` or the span's gap to pass to ``_mask``.

    A step the causal mask leaves whole (or a call that is not causal) is
    one group of one unmasked span; a step wholly above the diagonal is
    skipped. Only a step that crosses it walks its sub-tiles: by rows of
    sub-tiles, skipping those above the diagonal, a run of those below as
    one unmasked span, masking those crossing it. ``gap`` and ``crossing``
    come from ``_gap``; where ``gap`` is traced, the step's class is
    guarded with ``pl.when``."""
    outer_block, inner_block = (block_k, block_q) if keys_outer else (block_q, block_k)
    whole = functools.partial(group, 0, outer_block)
    if not causal:
        whole([(0, inner_block, None)])
        return
    seen, full = _sub_tile_class(gap, block_q, block_k)
    if isinstance(seen, bool):
        if full:
            whole([(0, inner_block, None)])
        elif seen:
            _walk_sub_tiles(group, gap, block_q, block_k, keys_outer)
        return
    pl.when(full)(functools.partial(whole, [(0, inner_block, None)]))
    if crossing is None:
        pl.when(seen & ~full)(functools.partial(whole, [(0, inner_block, gap)]))
    else:
        pl.when(seen & ~full)(functools.partial(
            _walk_sub_tiles, group, crossing, block_q, block_k, keys_outer))


def _walk_sub_tiles(group, gap: int, block_q: int, block_k: int, keys_outer: bool):
    sq, sk = _sub_side(block_q), _sub_side(block_k)
    outer_block, inner_block = (block_k, block_q) if keys_outer else (block_q, block_k)
    outer, inner = (sk, sq) if keys_outer else (sq, sk)
    for o0 in range(0, outer_block, outer):
        parts = []
        for i0 in range(0, inner_block, inner):
            r0, c0 = (i0, o0) if keys_outer else (o0, i0)
            seen, full = _sub_tile_class(gap + r0 - c0, sq, sk)
            if full and parts and parts[-1][2] is None and sum(parts[-1][:2]) == i0:
                parts[-1] = (parts[-1][0], parts[-1][1] + inner, None)
            elif seen:
                parts.append((i0, inner, None if full else gap + r0 - c0))
        if parts:
            group(o0, outer, parts)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
    *, scale: float, causal: bool, q_offset: int, k_offset: int,
    block_q: int, block_k: int, nk: int, dot_dtype,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)

    iq = pl.program_id(2)
    q_lo = q_offset + iq * block_q
    k_lo = k_offset + ik * block_k

    def _body():
        q = q_ref[0, 0].astype(dot_dtype)
        k = k_ref[0, 0].astype(dot_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_BIG)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            # When every entry of a row is masked, m_new == _NEG_BIG and
            # exp(s - m_new) == 1 for masked entries; zero them explicitly.
            p = jnp.where(s > 0.5 * _NEG_BIG, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        v = v_ref[0, 0].astype(dot_dtype)
        pv = jax.lax.dot_general(
            p.astype(dot_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        acc[:] = acc[:] * alpha[:, None] + pv
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    if causal:
        # Skip blocks strictly above the diagonal (no query attends there).
        pl.when(q_lo + block_q - 1 >= k_lo)(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0, 0] = (acc[:] / l_safe[:, None]).astype(o_ref.dtype)
        m = m_scr[:, 0]
        lse = jnp.where(l == 0.0, _NEG_BIG, m + jnp.log(l_safe))
        lse_ref[0, 0] = lse[:, None]


def _fwd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool, scale: float, q_offset: int, k_offset: int,
    block_q: int, block_k: int, interpret: bool, bf16_dots: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    b, lq, h, d = q.shape
    lk = k.shape[1]
    bq, bk = _block_sizes(lq, lk, block_q, block_k)
    nq, nk = lq // bq, lk // bk
    # [b, l, h, d] -> [b, h, l, d]: heads become a grid dim, seq x head_dim
    # are the (sublane, lane) tile dims the MXU wants.
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        q_offset=q_offset, k_offset=k_offset,
        block_q=bq, block_k=bk, nk=nk,
        dot_dtype=jnp.bfloat16 if bf16_dots else jnp.float32,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            # lse rides in a trailing unit lane dim: TPU blocks need their
            # last two dims (sublane, lane) tileable, so [b, h, lq] row
            # vectors are stored as [b, h, lq, 1].
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale: float, causal: bool, q_offset: int, k_offset: int,
    block_q: int, block_k: int, nq: int, nk: int, dot_dtype,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _row(r0, rows, parts):
        at = slice(r0, r0 + rows)
        q = q_ref[0, 0, at, :].astype(dot_dtype)
        do = do_ref[0, 0, at, :].astype(dot_dtype)
        lse, delta = lse_ref[0, 0, at, :], delta_ref[0, 0, at, :]
        dq = dq_acc[at, :]
        for c0, cols, gap in parts:
            k = k_ref[0, 0, c0:c0 + cols, :].astype(dot_dtype)
            v = v_ref[0, 0, c0:c0 + cols, :].astype(dot_dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            if gap is not None:
                s = _mask(s, gap)
            p = jnp.exp(s - lse)
            if gap is not None:
                p = jnp.where(s > 0.5 * _NEG_BIG, p, 0.0)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta) * scale
            dq = dq + jax.lax.dot_general(
                ds.astype(dot_dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
            )
        dq_acc[at, :] = dq

    gap, crossing = _gap(q_offset, k_offset, block_q, block_k, nq, nk, 2, 3)
    _walk(_row, causal, gap, crossing, block_q, block_k)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, causal: bool, q_offset: int, k_offset: int,
    block_q: int, block_k: int, nq: int, nk: int, dot_dtype,
):
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _col(c0, cols, parts):
        ks = slice(c0, c0 + cols)
        k = k_ref[0, 0, ks, :].astype(dot_dtype)
        v = v_ref[0, 0, ks, :].astype(dot_dtype)
        dk, dv = dk_acc[ks, :], dv_acc[ks, :]
        for r0, rows, gap in parts:
            at = slice(r0, r0 + rows)
            q = q_ref[0, 0, at, :].astype(dot_dtype)
            do = do_ref[0, 0, at, :].astype(dot_dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            if gap is not None:
                s = _mask(s, gap)
            p = jnp.exp(s - lse_ref[0, 0, at, :])  # [rows, cols]
            if gap is not None:
                p = jnp.where(s > 0.5 * _NEG_BIG, p, 0.0)
            dv = dv + jax.lax.dot_general(
                p.astype(dot_dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta_ref[0, 0, at, :]) * scale  # [rows, cols]
            dk = dk + jax.lax.dot_general(
                ds.astype(dot_dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
            )
        dk_acc[ks, :] = dk
        dv_acc[ks, :] = dv

    gap, crossing = _gap(q_offset, k_offset, block_q, block_k, nq, nk, 3, 2)
    _walk(_col, causal, gap, crossing, block_q, block_k, keys_outer=True)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(
    q, k, v, out, lse, do,
    *, causal: bool, scale: float, q_offset: int, k_offset: int,
    block_q: int, block_k: int, interpret: bool, bf16_dots: bool = False,
):
    dot_dtype = jnp.bfloat16 if bf16_dots else jnp.float32
    b, lq, h, d = q.shape
    lk = k.shape[1]
    bq, bk = _block_sizes(lq, lk, block_q, block_k)
    nq, nk = lq // bq, lk // bk

    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.swapaxes(delta, 1, 2)[..., None]  # [b, h, lq, 1]

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot = jnp.swapaxes(do, 1, 2)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    k_spec = pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk, nq=nq, nk=nk,
            dot_dtype=dot_dtype,
        ),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)

    # k-major grid: the q loop is the accumulating (minor) dim for dk/dv.
    q_spec2 = pl.BlockSpec((1, 1, bq, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    k_spec2 = pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk, nq=nq, nk=nk,
            dot_dtype=dot_dtype,
        ),
        grid=(b, h, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, lk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)

    return (
        jnp.swapaxes(dq, 1, 2),
        jnp.swapaxes(dk, 1, 2),
        jnp.swapaxes(dv, 1, 2),
    )


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
           bf16_dots):
    out, _ = _fwd(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset, k_offset=k_offset,
        block_q=block_q, block_k=block_k, interpret=interpret, bf16_dots=bf16_dots,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret,
               bf16_dots):
    out, lse = _fwd(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset, k_offset=k_offset,
        block_q=block_q, block_k=block_k, interpret=interpret, bf16_dots=bf16_dots,
    )
    out = checkpoint_name(out, SAVED_RESIDUALS[0])
    lse = checkpoint_name(lse, SAVED_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, q_offset, k_offset, block_q, block_k, interpret,
               bf16_dots, res, do):
    q, k, v, out, lse = res
    return _bwd(
        q, k, v, out, lse, do,
        causal=causal, scale=scale, q_offset=q_offset, k_offset=k_offset,
        block_q=block_q, block_k=block_k, interpret=interpret, bf16_dots=bf16_dots,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int = 0,
    k_offset: int = 0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    bf16_dots: bool = False,
) -> jax.Array:
    """Fused attention. q: [b, lq, h, d]; k/v: [b, lk, h, d] -> [b, lq, h, d].

    Differentiable (custom VJP, both passes Pallas). ``q_offset``/``k_offset``
    are the global positions of element 0 of q/k for causal masking — ring
    attention passes the rotating block's ring position here. On non-TPU
    backends the kernel runs in interpreter mode (tests); pass
    ``interpret=False`` to force compilation.

    ``block_q``/``block_k`` default to auto-tiling (_block_sizes): the
    largest 128-aligned divisors up to 1024 each — measured ~3x faster than
    the old fixed 128x128 tiles at GPT shapes on v5e (see _block_sizes).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("expected [batch, seq, heads, head_dim] inputs")
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    interpret = _interpret_default() if interpret is None else interpret
    bq, bk = _block_sizes(q.shape[1], k.shape[1], block_q, block_k)
    if not interpret and (bq % 8 or bk % 8):
        # Mosaic requires sublane dims to be multiples of 8; fail fast with
        # a clear message instead of an opaque TPU compile error (ADVICE r3).
        raise ValueError(
            f"block sizes ({bq}, {bk}) are not 8-aligned; sequence lengths "
            f"({q.shape[1]}, {k.shape[1]}) have no TPU-tileable divisor — "
            "pad the sequence or pass explicit block_q/block_k"
        )
    return _flash(
        q, k, v, causal, scale, int(q_offset), int(k_offset),
        bq, bk, interpret, bool(bf16_dots),
    )


def auto_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Pick the fused kernel when the backend/shapes support it.

    Drop-in ``attention_fn`` for kubeflow_tpu.models: Pallas flash attention
    on TPU for 128-tileable sequence lengths, exact XLA attention otherwise
    (CPU tests, ragged prototype shapes).
    """
    lq, lk = q.shape[1], k.shape[1]
    if jax.default_backend() == "tpu":
        if lq % 128 == 0 and lk % 128 == 0:
            return flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=False)
        # Same eligibility cliff as the bq%8/bk%8 fail-fast above, but here
        # the miss used to be silent: the model quietly ran the O(l^2)
        # materialized path on TPU. Make the MFU loss visible.
        from kubeflow_tpu.ops.fallback import record_fallback

        record_fallback(
            "flash_attention",
            f"sequence lengths ({lq}, {lk}) are not 128-tileable; "
            "pad the sequence to recover the fused path")
    from kubeflow_tpu.parallel.ring_attention import full_attention

    return full_attention(q, k, v, causal=causal, scale=scale)
