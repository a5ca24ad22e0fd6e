"""Fused ResNet bottleneck (1x1 -> 3x3 -> 1x1 + residual) Pallas kernels.

The round-4 conv decomposition (BASELINE.md) pinned ResNet-50's MFU ceiling
on v5e to the 1x1 projection convs: at stage-1 shapes they are HBM-bound at
~39 TF/s (52 F/B arithmetic intensity against a ~770 GB/s part), and they
carry ~2/3 of bottleneck FLOPs. The only remaining lever is cross-op fusion
that keeps the 256-channel activations in VMEM across the whole block —
these kernels are that lever, built to measure (VERDICT r4 #1).

Per grid step (one image), entirely in VMEM:
    x[hw,hw,cin] -> h1 = relu(x @ W1 * s1 + b1)          # 1x1 reduce
                 -> h2 = relu(im2col(h1) @ W2 * s2 + b2) # 3x3 implicit GEMM
                 -> y  = relu(sc + (h2 @ W3 * s3 + b3))  # 1x1 expand + shortcut
HBM traffic: read x once + write y once (the XLA composite moves x, h1,
h2, y through HBM ~6 passes). Norms are folded scale/bias ("frozen norm",
the same setting the round-4 composite measured at 42.6 TF/s — batch-stat
BatchNorm needs a cross-image reduction no per-image kernel can fuse).

Two kernel families cover all 16 ResNet-50 blocks at 224x224:

- ``fused_bottleneck``: identity-shortcut, stride-1 blocks. Row dims that
  are not 8-aligned (14x14 -> 196 rows, 7x7 -> 49) go through sublane-padded
  dots (``_pdot``), so every spatial stage qualifies — not just the %8 ones.
- ``fused_transition``: the stage-head blocks (stride-2 3x3 + 1x1 projection
  shortcut, or the stride-1 channel-expanding stage1 head). The projection
  runs in the same VMEM residency as the main path.

``folded_bottleneck`` is the XLA epilogue-fusion fallback for shapes neither
kernel takes (non-square, odd strided inputs): same folded-norm math, each
conv+scale+relu a single XLA fusion, checkpoint-identical params.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mxu(w):
    """Weights cross into the kernel already in the MXU dtype: the kernels
    cast to bf16 before every dot anyway, so casting outside is the same
    math with half the weight DMA and VMEM — as f32 the stage-4 blocks'
    weights alone (17-25 MB) overflow the compiler's 16 MiB VMEM scope."""
    return w.astype(jnp.bfloat16)


def _pdot(a, b):
    """Row-dim sublane-padded matmul: ``a @ b`` with f32 accumulation.

    Mosaic wants (8, 128)-tileable f32 operands; row counts like 196
    (14x14 images) or 49 (7x7) are not. Pad the rows with zeros for the
    MXU pass and slice the product back — zero rows contribute nothing,
    and on 8-aligned shapes both branches are no-ops so the original
    kernels' numerics are untouched.
    """
    m = a.shape[0]
    mp = -(-m // 8) * 8
    if mp != m:
        a = jnp.pad(a, ((0, mp - m), (0, 0)))
    out = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return out[:m] if mp != m else out


def _expand_rows_per_chunk(hw: int) -> int:
    """Row-group size for the 1x1 expand stage.

    The f32 [rows, cout] intermediate is the VMEM peak (3.2 MiB whole-image
    at stage-1 shapes, x2 with the shortcut operand), so large images chunk
    by 8 rows as before; 28x28 chunks by 4 (sublane-aligned: 4*28 = 112);
    14x14/7x7 fit whole-image (<1 MiB) and lean on ``_pdot`` padding.
    """
    if hw % 8 == 0:
        return 8
    if hw % 4 == 0 and hw > 16:
        return 4
    return hw


def _kernel(x_ref, w1_ref, s1_ref, w2_ref, s2_ref, w3_ref, s3_ref, o_ref,
            *, hw: int, cin: int, cmid: int, dot_dtype):
    x = x_ref[0]                                    # [hw, hw, cin] bf16
    xm = x.reshape(hw * hw, cin)
    w1 = w1_ref[...].astype(dot_dtype)              # [cin, cmid]
    h1 = _pdot(xm.astype(dot_dtype), w1)
    h1 = jnp.maximum(h1 * s1_ref[0] + s1_ref[1], 0.0)  # bn1 folded + relu

    # 3x3 as ONE implicit-GEMM dot: im2col built in VMEM (9 shifted views
    # of the zero-padded h1 concatenated on the lane dim). K=9*cmid=576
    # feeds the 128-wide MXU contraction far better than 9 K=64 tap dots
    # (measured: tap-dots 28.6 TF/s vs XLA composite 33.5 at stage-1) —
    # and unlike the round-4 HBM im2col experiment, the 9x data blowup
    # lives only in VMEM.
    h1p = jnp.pad(h1.reshape(hw, hw, cmid).astype(dot_dtype),
                  ((1, 1), (1, 1), (0, 0)))
    cols = jnp.concatenate(
        [h1p[di:di + hw, dj:dj + hw, :].reshape(hw * hw, cmid)
         for di in range(3) for dj in range(3)], axis=1)     # [hw*hw, 9*cmid]
    w2m = w2_ref[...].astype(dot_dtype).reshape(9 * cmid, cmid)
    acc = _pdot(cols, w2m)
    h2 = jnp.maximum(acc * s2_ref[0] + s2_ref[1], 0.0)      # bn2 folded + relu
    h2 = h2.astype(dot_dtype)

    # Expand stage in row chunks: the f32 [hw*hw, cin] intermediate would
    # be the VMEM peak (3.2 MiB at stage-1 shapes, x2 with the residual
    # operand — over the 16 MiB scoped stack); chunking keeps the peak at
    # one row-group while h1/h2 (cmid-wide) stay whole-image.
    w3 = w3_ref[...].astype(dot_dtype)              # [cmid, cin]
    rows_per_chunk = _expand_rows_per_chunk(hw)
    n_chunks = hw // rows_per_chunk
    m = rows_per_chunk * hw
    for r in range(n_chunks):
        h2_r = h2[r * m:(r + 1) * m]  # static slice (Mosaic-lowerable)
        y = _pdot(h2_r, w3)
        y = y * s3_ref[0] + s3_ref[1]               # bn3 folded
        x_r = x_ref[0, r * rows_per_chunk:(r + 1) * rows_per_chunk]
        y = jnp.maximum(y + x_r.reshape(m, cin).astype(jnp.float32), 0.0)
        o_ref[0, r * rows_per_chunk:(r + 1) * rows_per_chunk] = (
            y.reshape(rows_per_chunk, hw, cin).astype(o_ref.dtype))


def fused_bottleneck(
    x: jax.Array,          # [n, hw, hw, cin]
    w1: jax.Array,         # [cin, cmid]
    scale1: jax.Array, bias1: jax.Array,   # [cmid] folded bn1
    w2: jax.Array,         # [3, 3, cmid, cmid]
    scale2: jax.Array, bias2: jax.Array,   # [cmid]
    w3: jax.Array,         # [cmid, cin]
    scale3: jax.Array, bias3: jax.Array,   # [cin]
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """relu(x + bn3(conv1x1(relu(bn2(conv3x3(relu(bn1(conv1x1(x)))))))))
    with folded scale/bias norms, one image per grid step, everything
    between the input read and output write resident in VMEM."""
    n, hw, hw2, cin = x.shape
    assert hw == hw2, x.shape
    cmid = w1.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s1 = jnp.stack([scale1, bias1]).astype(jnp.float32)   # [2, cmid]
    s2 = jnp.stack([scale2, bias2]).astype(jnp.float32)
    s3 = jnp.stack([scale3, bias3]).astype(jnp.float32)
    w2r = w2.reshape(9, cmid, cmid)

    kernel = functools.partial(
        _kernel, hw=hw, cin=cin, cmid=cmid, dot_dtype=jnp.bfloat16)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, hw, hw, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((cin, cmid), lambda i: (0, 0)),
            pl.BlockSpec((2, cmid), lambda i: (0, 0)),
            pl.BlockSpec((9, cmid, cmid), lambda i: (0, 0, 0)),
            pl.BlockSpec((2, cmid), lambda i: (0, 0)),
            pl.BlockSpec((cmid, cin), lambda i: (0, 0)),
            pl.BlockSpec((2, cin), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hw, hw, cin), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, _mxu(w1), s1, _mxu(w2r), s2, _mxu(w3), s3)


@jax.custom_vjp
def fused_bottleneck_block(x, w1, scale1, bias1, w2, scale2, bias2,
                           w3, scale3, bias3):
    """Differentiable fused bottleneck: Pallas forward, XLA backward.

    The kernel has no Pallas backward; the VJP recomputes the block through
    ``reference_bottleneck`` (same math, compiler-scheduled) and uses ITS
    cotangents — forward-only fusion, rematerialized backward. Residuals are
    the primal inputs, so the fused path holds no extra activations between
    fwd and bwd (the remat trade the models already make per-block).
    """
    return fused_bottleneck(x, w1, scale1, bias1, w2, scale2, bias2,
                            w3, scale3, bias3)


def _fused_block_fwd(x, w1, scale1, bias1, w2, scale2, bias2, w3, scale3, bias3):
    out = fused_bottleneck(x, w1, scale1, bias1, w2, scale2, bias2,
                           w3, scale3, bias3)
    return out, (x, w1, scale1, bias1, w2, scale2, bias2, w3, scale3, bias3)


def _composite_f32(x, w1, scale1, bias1, w2, scale2, bias2, w3, scale3, bias3):
    """All-f32 twin of ``reference_bottleneck`` for the VJP: the mixed
    bf16-input/f32-accumulate convs the reference uses hit a conv-transpose
    dtype mismatch under ``jax.vjp``; a uniform-dtype composite transposes
    cleanly and gives f32-accurate cotangents."""
    conv = functools.partial(
        jax.lax.conv_general_dilated,
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    h1 = jnp.maximum(conv(x, w1[None, None]) * scale1 + bias1, 0.0)
    h2 = jnp.maximum(conv(h1, w2) * scale2 + bias2, 0.0)
    y = conv(h2, w3[None, None]) * scale3 + bias3
    return jnp.maximum(y + x, 0.0)


def _fused_block_bwd(residuals, g):
    primals_f32 = tuple(r.astype(jnp.float32) for r in residuals)
    _, vjp = jax.vjp(_composite_f32, *primals_f32)
    grads = vjp(g.astype(jnp.float32))
    return tuple(dr.astype(r.dtype) for dr, r in zip(grads, residuals))


fused_bottleneck_block.defvjp(_fused_block_fwd, _fused_block_bwd)


def reference_bottleneck(x, w1, scale1, bias1, w2, scale2, bias2,
                         w3, scale3, bias3):
    """The XLA composite the kernel must match (and beat): same math,
    scheduled by the compiler through HBM."""
    f32 = jnp.float32
    h1 = jax.lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w1[None, None].astype(jnp.bfloat16),
        (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=f32)
    h1 = jnp.maximum(h1 * scale1 + bias1, 0.0)
    h2 = jax.lax.conv_general_dilated(
        h1.astype(jnp.bfloat16), w2.astype(jnp.bfloat16),
        (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=f32)
    h2 = jnp.maximum(h2 * scale2 + bias2, 0.0)
    y = jax.lax.conv_general_dilated(
        h2.astype(jnp.bfloat16), w3[None, None].astype(jnp.bfloat16),
        (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=f32)
    y = y * scale3 + bias3
    return jnp.maximum(y + x.astype(f32), 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Transition blocks: stride-2 (or stride-1 channel-expanding) heads with a
# 1x1 projection shortcut — the top hbm-bound sinks in the r6 attribution.
# ---------------------------------------------------------------------------


def _lane_group_scratch(h: int, w: int, c: int):
    """f32 VMEM scratch holding an [h, w, c] image as [groups, h, w, width]
    channel slabs. Mosaic's strided load wants a base ref whose minor dim is
    exactly one 128-lane tile, so ResNet's widths (all multiples of 128 at
    the stride-2 heads) park as c/128 slabs. Other widths stay one slab:
    fine under the interpreter, refused by the chip's compiler."""
    groups, width = (c // 128, 128) if c % 128 == 0 else (1, c)
    return pltpu.VMEM((groups, h, w, width), jnp.float32)


def _store_lane_groups(ref, v, hw: int):
    """Write ``v`` [hw, hw, c] into ``ref`` [groups, >=hw, >=hw, width]."""
    groups, width = ref.shape[0], ref.shape[-1]
    for g in range(groups):
        ref[g, 0:hw, 0:hw, :] = v[:, :, g * width:(g + 1) * width]


def _strided_taps(ref, r0: int, c0: int, rows: int, cols: int):
    """``[r0::2, c0::2]`` (``rows`` x ``cols`` taps) of the [h, w, c] image
    parked in ``ref`` as lane groups -> [rows, cols, c]."""
    return jnp.concatenate(
        [ref[g, pl.ds(r0, rows, stride=2), pl.ds(c0, cols, stride=2), :]
         for g in range(ref.shape[0])], axis=-1)


def _transition_kernel(x_ref, w1_ref, s1_ref, w2_ref, s2_ref, w3_ref, s3_ref,
                       wp_ref, sp_ref, o_ref, *scratch,
                       hw: int, ho: int, cin: int, cmid: int, cout: int,
                       stride: int, dot_dtype):
    x = x_ref[0]                                    # [hw, hw, cin]
    xm = x.reshape(hw * hw, cin)
    w1 = w1_ref[...].astype(dot_dtype)              # [cin, cmid]
    h1 = _pdot(xm.astype(dot_dtype), w1)
    h1 = jnp.maximum(h1 * s1_ref[0] + s1_ref[1], 0.0)

    # Strided implicit-GEMM 3x3. XLA SAME padding for stride 2, kernel 3 on
    # an even input is (lo=0, hi=1): out(i,j) taps in_pad[2i+di, 2j+dj].
    h1sq = h1.reshape(hw, hw, cmid)
    if stride == 1:
        h1p = jnp.pad(h1sq.astype(dot_dtype), ((1, 1), (1, 1), (0, 0)))
        views = [h1p[di:di + ho, dj:dj + ho, :]
                 for di in range(3) for dj in range(3)]
        xs_ref = None
    else:
        # Mosaic has no strided slice of a VALUE (it lowers to a gather it
        # refuses), but it does have strided loads from a REF, for 32-bit
        # data only: park the padded h1 and x in f32 VMEM scratch and read
        # each tap as one stride-2 load. bf16 -> f32 -> bf16 is exact.
        h1p_ref, xs_ref = scratch
        groups, _, _, width = h1p_ref.shape
        h1p_ref[:, hw:hw + 2, :, :] = jnp.zeros(
            (groups, 2, hw + 2, width), jnp.float32)
        h1p_ref[:, 0:hw, hw:hw + 2, :] = jnp.zeros(
            (groups, hw, 2, width), jnp.float32)
        _store_lane_groups(h1p_ref, h1sq, hw)
        views = [_strided_taps(h1p_ref, di, dj, ho, ho).astype(dot_dtype)
                 for di in range(3) for dj in range(3)]
        _store_lane_groups(xs_ref, x.astype(jnp.float32), hw)
    cols = jnp.concatenate(
        [v.reshape(ho * ho, cmid) for v in views], axis=1)   # [ho*ho, 9*cmid]
    w2m = w2_ref[...].astype(dot_dtype).reshape(9 * cmid, cmid)
    acc = _pdot(cols, w2m)
    h2 = jnp.maximum(acc * s2_ref[0] + s2_ref[1], 0.0)
    h2 = h2.astype(dot_dtype)

    # Expand + projection in row chunks (same VMEM-peak argument as the
    # identity kernel, with the projection dot riding the same row group).
    # Projection shortcut input: a 1x1 stride-s SAME conv reads every s-th
    # pixel of x.
    w3 = w3_ref[...].astype(dot_dtype)              # [cmid, cout]
    wp = wp_ref[...].astype(dot_dtype)              # [cin, cout]
    rows_per_chunk = _expand_rows_per_chunk(ho)
    n_chunks = ho // rows_per_chunk
    m = rows_per_chunk * ho
    for r in range(n_chunks):
        y = _pdot(h2[r * m:(r + 1) * m], w3)
        y = y * s3_ref[0] + s3_ref[1]               # bn3 folded (zero-init)
        r0 = r * rows_per_chunk
        if stride == 1:
            xs_r = x[r0:r0 + rows_per_chunk]
        else:
            xs_r = _strided_taps(xs_ref, 2 * r0, 0, rows_per_chunk, ho)
        proj = _pdot(xs_r.reshape(m, cin).astype(dot_dtype), wp)
        proj = proj * sp_ref[0] + sp_ref[1]         # bn_proj folded
        o_ref[0, r0:r0 + rows_per_chunk] = (
            jnp.maximum(proj + y, 0.0)
            .reshape(rows_per_chunk, ho, cout).astype(o_ref.dtype))


def fused_transition(
    x: jax.Array,          # [n, hw, hw, cin]
    w1: jax.Array,         # [cin, cmid]
    scale1: jax.Array, bias1: jax.Array,
    w2: jax.Array,         # [3, 3, cmid, cmid]
    scale2: jax.Array, bias2: jax.Array,
    w3: jax.Array,         # [cmid, cout]
    scale3: jax.Array, bias3: jax.Array,
    wp: jax.Array,         # [cin, cout] 1x1 projection shortcut
    scalep: jax.Array, biasp: jax.Array,
    *,
    stride: int = 2,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """relu(proj(x) + bn3(conv1x1(relu(bn2(conv3x3_s(relu(bn1(conv1x1(x)))))))))
    — the downsampling/channel-expanding stage head, fully VMEM-resident,
    projection shortcut included. ``stride`` in {1, 2}; stride 2 requires an
    even spatial dim (SAME padding is then (0, 1))."""
    n, hw, hw2, cin = x.shape
    assert hw == hw2, x.shape
    assert stride in (1, 2), stride
    assert stride == 1 or hw % 2 == 0, (hw, stride)
    cmid = w1.shape[1]
    cout = w3.shape[1]
    ho = hw if stride == 1 else hw // 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s1 = jnp.stack([scale1, bias1]).astype(jnp.float32)
    s2 = jnp.stack([scale2, bias2]).astype(jnp.float32)
    s3 = jnp.stack([scale3, bias3]).astype(jnp.float32)
    sp = jnp.stack([scalep, biasp]).astype(jnp.float32)
    w2r = w2.reshape(9, cmid, cmid)

    kernel = functools.partial(
        _transition_kernel, hw=hw, ho=ho, cin=cin, cmid=cmid, cout=cout,
        stride=stride, dot_dtype=jnp.bfloat16)
    scratch_shapes = () if stride == 1 else (
        _lane_group_scratch(hw + 2, hw + 2, cmid),   # zero-padded h1
        _lane_group_scratch(hw, hw, cin))            # x, for the proj taps
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, hw, hw, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((cin, cmid), lambda i: (0, 0)),
            pl.BlockSpec((2, cmid), lambda i: (0, 0)),
            pl.BlockSpec((9, cmid, cmid), lambda i: (0, 0, 0)),
            pl.BlockSpec((2, cmid), lambda i: (0, 0)),
            pl.BlockSpec((cmid, cout), lambda i: (0, 0)),
            pl.BlockSpec((2, cout), lambda i: (0, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((2, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ho, ho, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, ho, cout), x.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(x, _mxu(w1), s1, _mxu(w2r), s2, _mxu(w3), s3, _mxu(wp), sp)


def _transition_composite_f32(stride, x, w1, scale1, bias1, w2, scale2, bias2,
                              w3, scale3, bias3, wp, scalep, biasp):
    """All-f32 XLA twin of ``fused_transition`` — the VJP recompute target
    (same role as ``_composite_f32`` for the identity kernel)."""
    conv = functools.partial(
        jax.lax.conv_general_dilated, padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    h1 = jnp.maximum(conv(x, w1[None, None], (1, 1)) * scale1 + bias1, 0.0)
    h2 = jnp.maximum(conv(h1, w2, (stride, stride)) * scale2 + bias2, 0.0)
    y = conv(h2, w3[None, None], (1, 1)) * scale3 + bias3
    proj = conv(x, wp[None, None], (stride, stride)) * scalep + biasp
    return jnp.maximum(proj + y, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _transition_block(stride, x, w1, scale1, bias1, w2, scale2, bias2,
                      w3, scale3, bias3, wp, scalep, biasp):
    return fused_transition(x, w1, scale1, bias1, w2, scale2, bias2,
                            w3, scale3, bias3, wp, scalep, biasp,
                            stride=stride)


def _transition_fwd(stride, *primals):
    return _transition_block(stride, *primals), primals


def _transition_bwd(stride, residuals, g):
    primals_f32 = tuple(r.astype(jnp.float32) for r in residuals)
    _, vjp = jax.vjp(
        functools.partial(_transition_composite_f32, stride), *primals_f32)
    grads = vjp(g.astype(jnp.float32))
    return tuple(dr.astype(r.dtype) for dr, r in zip(grads, residuals))


_transition_block.defvjp(_transition_fwd, _transition_bwd)


def fused_transition_block(x, w1, scale1, bias1, w2, scale2, bias2,
                           w3, scale3, bias3, wp, scalep, biasp,
                           *, stride: int = 2):
    """Differentiable fused transition block: Pallas forward, XLA backward
    via ``_transition_composite_f32`` cotangents (forward-only fusion,
    rematerialized backward — same contract as ``fused_bottleneck_block``)."""
    return _transition_block(stride, x, w1, scale1, bias1, w2, scale2, bias2,
                             w3, scale3, bias3, wp, scalep, biasp)


def reference_transition(x, w1, scale1, bias1, w2, scale2, bias2,
                         w3, scale3, bias3, wp, scalep, biasp,
                         *, stride: int = 2):
    """The XLA composite the transition kernel must match: bf16 convs with
    f32 accumulation, compiler-scheduled through HBM."""
    f32 = jnp.float32
    bf16 = jnp.bfloat16
    conv = functools.partial(
        jax.lax.conv_general_dilated, padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=f32)
    h1 = conv(x.astype(bf16), w1[None, None].astype(bf16), (1, 1))
    h1 = jnp.maximum(h1 * scale1 + bias1, 0.0)
    h2 = conv(h1.astype(bf16), w2.astype(bf16), (stride, stride))
    h2 = jnp.maximum(h2 * scale2 + bias2, 0.0)
    y = conv(h2.astype(bf16), w3[None, None].astype(bf16), (1, 1))
    y = y * scale3 + bias3
    proj = conv(x.astype(bf16), wp[None, None].astype(bf16), (stride, stride))
    proj = proj * scalep + biasp
    return jnp.maximum(proj + y, 0.0).astype(x.dtype)


def folded_bottleneck(x, w1, scale1, bias1, w2, scale2, bias2,
                      w3, scale3, bias3,
                      *, strides: Tuple[int, int] = (1, 1), proj=None):
    """Epilogue-fused XLA fallback for block shapes neither kernel takes.

    Folding the norm into scale/bias turns each conv+norm+relu into a
    single XLA fusion (conv with a scale/bias/relu epilogue) — batch-stat
    BatchNorm would force a cross-batch reduction pass between convs.
    Computed in f32 throughout so it transposes cleanly under ``jax.vjp``.
    ``proj`` is ``(wp, scalep, biasp)`` for a projection shortcut, or None
    for an identity shortcut.
    """
    f32 = jnp.float32
    conv = functools.partial(
        jax.lax.conv_general_dilated, padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    xf = x.astype(f32)
    h1 = jnp.maximum(
        conv(xf, w1[None, None].astype(f32), (1, 1))
        * scale1 + bias1, 0.0)
    h2 = jnp.maximum(
        conv(h1, w2.astype(f32), tuple(strides)) * scale2 + bias2, 0.0)
    y = conv(h2, w3[None, None].astype(f32), (1, 1)) * scale3 + bias3
    if proj is None:
        shortcut = xf
    else:
        wp, scalep, biasp = proj
        shortcut = (conv(xf, wp[None, None].astype(f32), tuple(strides))
                    * scalep + biasp)
    return jnp.maximum(shortcut + y, 0.0).astype(x.dtype)
