"""Ring attention: exact long-context attention over the ``seq`` mesh axis.

Queries stay put; key/value blocks rotate around the ring of devices via
``lax.ppermute`` (one ICI hop per step, overlapping compute with transfer),
while an online-softmax accumulator keeps the result exact — attention over
sequences far larger than one chip's HBM, with per-device memory O(L/N).

The reference has no long-context machinery at all (SURVEY.md §5 — it
schedules pods); this is the in-workload half of "long-context is
first-class". Causal masking is computed from global positions derived from
the device's ring index, so block-skipping keeps the causal case ~2x cheap.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_MODEL, AXIS_SEQ, BATCH_AXES

_NEG_BIG = -1e30


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool,
    scale: Optional[float],
    vary_axes: tuple = (),
) -> jax.Array:
    """Per-device body. q/k/v: [batch, seq_local, heads, head_dim]."""
    orig_dtype = q.dtype
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    axis_size = lax.psum(1, axis_name)
    axis_idx = lax.axis_index(axis_name)

    q_pos = axis_idx * lq + jnp.arange(lq)  # global query positions

    # Accumulators in f32 regardless of input dtype (bf16-safe softmax).
    # pcast-to-varying marks them device-varying over the ring axis so the
    # fori_loop carry type stays fixed once ppermute'd blocks mix in.
    vary = vary_axes or (BATCH_AXES + (axis_name,))
    o = lax.pcast(jnp.zeros((b, h, lq, d), jnp.float32), vary, to="varying")
    m = lax.pcast(jnp.full((b, h, lq), _NEG_BIG, jnp.float32), vary, to="varying")
    l = lax.pcast(jnp.zeros((b, h, lq), jnp.float32), vary, to="varying")

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (axis_idx - i) % axis_size  # ring index this k/v block came from
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur, preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            k_pos = src * lk + jnp.arange(lk)
            mask = q_pos[:, None] >= k_pos[None, :]  # [lq, lk]
            s = jnp.where(mask[None, None], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, axis_size, step, (o, m, l, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (shouldn't occur) -> 0 output
    out = (o / l[..., None]).astype(orig_dtype)
    return jnp.einsum("bhqd->bqhd", out)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = AXIS_SEQ,
) -> jax.Array:
    """Sequence-parallel attention over ``mesh``'s ``seq`` axis.

    Inputs are globally [batch, seq, heads, head_dim] with seq sharded over
    ``axis_name`` and batch over the batch axes; output matches q's layout.
    Works with seq axis size 1 (degrades to one local softmax pass).

    When the mesh has a non-trivial ``model`` axis the heads dimension is
    sharded over it too (heads are independent in attention), composing
    tensor parallelism with the ring; head count must then divide the axis.
    """
    model_size = mesh.shape.get(AXIS_MODEL, 1)
    heads = q.shape[2]
    head_axes = AXIS_MODEL if model_size > 1 and heads % model_size == 0 else None
    spec = P(BATCH_AXES, axis_name, head_axes, None)
    vary_axes = BATCH_AXES + (axis_name,) + ((head_axes,) if head_axes else ())
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local,
            axis_name=axis_name,
            causal=causal,
            scale=scale,
            vary_axes=vary_axes,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-device exact reference (tests + short-sequence fast path)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))
    return out.astype(q.dtype)
