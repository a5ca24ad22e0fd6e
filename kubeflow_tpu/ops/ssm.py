"""State-space layers (Mamba-2) on the serving path of models/nemotron.py.

A head ``h`` of a Mamba-2 layer keeps a state ``S[h]`` of ``P x N`` (head
dim x state size) that every position updates and reads:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,      y_t = S_t C_t

with ``dt_t`` and ``A`` one number a head, ``x_t`` [P] the head's own, and
``B_t``, ``C_t`` [N] shared by the heads of a group. A slot's state has the
same size whatever the slot's length: it is carried, not paged.

- :func:`ssd_scan` — a chunk of ONE prompt (the state-space dual form, SSD):
  the chunk is cut into sub-chunks of ``chunk`` positions; inside one, the
  quadratic (attention-like) form ``y_t = sum_{s <= t} exp(cum_t - cum_s)
  (C_t . B_s) dt_s x_s``; between them the state, carried by a scan over the
  sub-chunks and seen by each position as ``exp(cum_t) S_in C_t``. It starts
  from the state it is given (the slot's, carried from the prompt's previous
  chunk, or zeros) and returns the last one. ``jnp`` einsums in float32 at
  ``highest`` precision: a chunk's scan is a few GFLOP a layer beside the
  projections' hundreds.
- :func:`ssm_decode_update` — one position for every slot, a Pallas kernel
  (``ssm_decode_update``): each LIVE slot's state is fetched from HBM once,
  decayed, updated, contracted with ``C``, and written back once, in place
  (the output aliases the input); a row that is not live is neither read nor
  written. The live rows are put first (``order``, in SMEM with their
  count) and the kernel is one step: a loop over the ``count`` live rows,
  each row's groups in turn, that fetches the next live group's state while
  it works on this one, so no work at all falls to a dead row. ``decay``,
  ``u``, ``B``, ``C`` and ``y`` sit whole in VMEM, laid out so a row is a
  leading index and a head a lane (``y`` zeroed once, a live row's entries
  written). Bound by bytes: 4.2 MB a slot and layer at the published widths,
  read and written.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

_HI = jax.lax.Precision.HIGHEST


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
             state: jax.Array, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """x [T, H, P]; dt [T, H] (after the softplus; 0 at a position that is not
    real, which then leaves the state as it found it); A [H] (negative); B, C
    [T, G, N]; state [H, P, N]: float32, ``T`` a multiple of ``chunk`` (or
    under it). Returns (y [T, H, P] = ``S_t C_t``, the skip term ``D x`` left
    to the caller; the state after the last position [H, P, N])."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    Q = min(chunk, T)
    n, hg = T // Q, H // G
    cum = jnp.cumsum((dt * A).reshape(n, Q, G, hg), axis=1)          # log decay, inclusive
    u = (x * dt[..., None]).reshape(n, Q, G, hg, P)
    Bc, Cc = B.reshape(n, Q, G, N), C.reshape(n, Q, G, N)
    # inside a sub-chunk: position t sees s <= t, decayed from s to t
    t = jnp.arange(Q)
    causal = (t[:, None] >= t[None, :])[None, :, :, None, None]
    seg = cum[:, :, None] - cum[:, None, :]                            # [n, Qt, Qs, G, hg]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("ctgk,csgk->ctsg", Cc, Bc, precision=_HI)
    y = jnp.einsum("ctsgj,ctsg,csgjp->ctgjp", decay, cb, u, precision=_HI)
    # what each sub-chunk adds to the state at its end, and how much of the
    # state entering it survives it
    tail = jnp.exp(cum[:, -1:] - cum)                                  # [n, Q, G, hg]
    own = jnp.einsum("csgj,csgjp,csgk->cgjpk", tail, u, Bc, precision=_HI)
    whole = jnp.exp(cum[:, -1])                                        # [n, G, hg]

    def carry(s, step):
        w, o = step
        return w[..., None, None] * s + o, s

    last, entering = jax.lax.scan(carry, state.reshape(G, hg, P, N), (whole, own))
    y = y + jnp.einsum("ctgk,cgjpk,ctgj->ctgjp", Cc, entering, jnp.exp(cum), precision=_HI)
    return y.reshape(T, H, P), last.reshape(H, P, N)


def _decode_kernel(order_ref, count_ref, decay_ref, u_ref, b_ref, c_ref, state_hbm,
                   state_out, y_ref, buf, sems):
    groups, hg = b_ref.shape[1], buf.shape[1]
    total = count_ref[0] * groups                                # live (row, group) pairs
    y_ref[...] = jnp.zeros_like(y_ref)

    def place(kk):
        return order_ref[kk // groups], kk % groups

    def fetch(kk, slot):
        row, grp = place(kk)
        return pltpu.make_async_copy(state_hbm.at[row, grp], buf.at[slot], sems.at[0, slot])

    def store(kk, slot):
        row, grp = place(kk)
        return pltpu.make_async_copy(buf.at[slot], state_out.at[row, grp], sems.at[1, slot])

    def group(k, row, g, decay, u, b, c):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < total)
        def _next():
            # the other buffer held the group before this one: its state
            # must be home before the next one comes in over it
            @pl.when(k > 0)
            def _():
                store(k - 1, 1 - slot).wait()
            fetch(k + 1, 1 - slot).start()

        fetch(k, slot).wait()
        for j in range(hg):
            h = g * hg + j
            s = buf[slot, j] * decay[:, h:h + 1] + u[:, h:h + 1] * b   # [P, N]
            buf[slot, j] = s
            y_ref[row, :, h:h + 1] = jnp.sum(s * c, axis=-1, keepdims=True)
        store(k, slot).start()

    def live_row(i, carry):
        row = order_ref[i]
        decay, u = decay_ref[row], u_ref[row]                      # [1, H], [P, H]
        b, c = b_ref[row], c_ref[row]                              # [G, N]
        for g in range(groups):
            group(i * groups + g, row, g, decay, u, b[g:g + 1], c[g:g + 1])
        return carry

    @pl.when(total > 0)
    def _walk():
        fetch(0, 0).start()
        jax.lax.fori_loop(0, count_ref[0], live_row, 0)
        last = total - 1

        @pl.when(last > 0)
        def _():
            store(last - 1, 1 - jax.lax.rem(last, 2)).wait()
        store(last, jax.lax.rem(last, 2)).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(state, decay, u, B, C, live, interpret: bool):
    S, G, hg, P, N = state.shape
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)      # live rows first
    count = jnp.sum(live, dtype=jnp.int32)[None]
    smem, vmem = pltpu.MemorySpace.SMEM, pltpu.MemorySpace.VMEM
    state, y = pl.pallas_call(
        _decode_kernel,
        in_specs=[pl.BlockSpec(memory_space=smem), pl.BlockSpec(memory_space=smem)]
                 + [pl.BlockSpec(memory_space=vmem)] * 4
                 + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=vmem)],
        scratch_shapes=[pltpu.VMEM((2, hg, P, N), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((S, P, G * hg), jnp.float32)],
        input_output_aliases={6: 0},
        interpret=interpret,
        name="ssm_decode_update",
    )(order, count, decay.reshape(S, 1, G * hg), jnp.swapaxes(u, 1, 2), B, C, state)
    return state, jnp.swapaxes(y, 1, 2)


def ssm_decode_update(state: jax.Array, decay: jax.Array, u: jax.Array, B: jax.Array,
                      C: jax.Array, live: jax.Array, *,
                      interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """One position for every slot. ``state`` [S, G, H / G, P, N] float32
    (each slot's, a group's heads together); ``decay`` [S, H] = ``exp(dt A)``;
    ``u`` [S, H, P] = ``dt x``; ``B``, ``C`` [S, G, N]; ``live`` [S] bool.
    Returns (the state, updated in place where live and untouched where not:
    the buffer is donated, and a dead row's bytes are never fetched or
    written; y [S, H, P] = ``S_t C_t`` of the live rows, zeros elsewhere).
    The walk's trip count is the live count: a dead row costs no loop trip,
    no DMA and no store; what grows with the slots is one copy a call of the
    small inputs and of ``y`` (about 4.6 MB at 64 slots of the published widths)."""
    return _decode_call(state, decay.astype(jnp.float32), u.astype(jnp.float32),
                        B.astype(jnp.float32), C.astype(jnp.float32), live.astype(bool),
                        _interpret_default() if interpret is None else interpret)
