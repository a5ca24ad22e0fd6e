"""Fused-bottleneck kernel evidence: build, verify, measure (VERDICT r4 #1).

Round 4's conv decomposition named one remaining ResNet lever: a Pallas
kernel fusing the whole bottleneck (1x1 -> 3x3 -> 1x1 + residual) so the
256-channel activations never touch HBM, estimated "+8-10 MFU points".
This probe is the measured answer (run: ``python -m e2e.fused_bottleneck_probe``):

1. ``fused``   — the real kernel (ops/fused_bottleneck.py, parity-tested)
   at stage-1 shapes, one image per grid step, auto-pipelined.
2. ``xla``     — the XLA composite of the same math (frozen norm), the
   thing the kernel must beat.
3. ``copy_*``  — pure-streaming probes that pin the mechanism: Pallas
   block-pipelined HBM streaming vs XLA's own elementwise streaming, plus
   a hand-rolled double-buffered DMA kernel (the fastest Pallas can go).

The question the copy probes answer is whether Pallas streams HBM as fast
as XLA's own elementwise code does: a fused kernel that moves half the
bytes at half the rate saves nothing. Rates on today's chip: not measured.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from e2e.ceiling import CHAIN, _timed

N, HW, CIN, CMID = 256, 56, 256, 64


def _inputs():
    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(N, HW, HW, CIN), jnp.bfloat16) * 0.3
    w1 = jnp.asarray(rng.randn(CIN, CMID) * 0.05, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(3, 3, CMID, CMID) * 0.05, jnp.bfloat16)
    w3 = jnp.asarray(rng.randn(CMID, CIN) * 0.05, jnp.bfloat16)
    s1, b1 = jnp.ones(CMID), jnp.zeros(CMID) + 0.01
    s2, b2 = jnp.ones(CMID) * 1.1, jnp.zeros(CMID) - 0.01
    s3, b3 = jnp.ones(CIN) * 0.9, jnp.zeros(CIN)
    return x0, (w1, s1, b1, w2, s2, b2, w3, s3, b3)


def _bench_block(fn, x0, weights, label) -> Dict[str, Any]:
    flops = 2.0 * N * HW * HW * (CIN * CMID + 9 * CMID * CMID + CMID * CIN)

    @jax.jit
    def run(x):
        def body(x, _):
            for _ in range(CHAIN):
                y = fn(x, *weights)
                x = (y * jnp.bfloat16(0.97)).astype(jnp.bfloat16)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=8)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0,), 8 * CHAIN)
    return {"probe": label, "ms_per_pass": round(dt * 1e3, 3),
            "tflops": round(flops / dt / 1e12, 1)}


def _bench_copy(fn, x0, label) -> Dict[str, Any]:
    nbytes = x0.size * 2

    @jax.jit
    def run(x):
        def body(x, _):
            for _ in range(4):
                x = fn(x)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=8)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0,), 32)
    return {"probe": label, "ms_per_pass": round(dt * 1e3, 3),
            "gbps_rw": round(2 * nbytes / dt / 1e9)}


def _pallas_copy(shape, block):
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * jnp.bfloat16(0.97)

    n_blocks = shape[0] // block[0]
    return pl.pallas_call(
        kern, grid=(n_blocks,),
        in_specs=[pl.BlockSpec(block, lambda i: (i,) + (0,) * (len(block) - 1))],
        out_specs=pl.BlockSpec(block, lambda i: (i,) + (0,) * (len(block) - 1)),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.bfloat16), interpret=False)


def _manual_dma_copy(m, c, bm=4096):
    nb = m // bm

    def kern(x_hbm, o_hbm, buf, obuf, in_sems, out_sems):
        def get(i, slot):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(i * bm, bm), :], buf.at[slot], in_sems.at[slot])

        def put(i, slot):
            return pltpu.make_async_copy(
                obuf.at[slot], o_hbm.at[pl.ds(i * bm, bm), :], out_sems.at[slot])

        get(0, 0).start()

        def body(i, _):
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < nb)
            def _():
                get(i + 1, nxt).start()

            get(i, slot).wait()

            @pl.when(i >= 2)
            def _():
                put(i - 2, slot).wait()

            obuf[slot] = buf[slot] * jnp.bfloat16(0.97)
            put(i, slot).start()
            return 0

        jax.lax.fori_loop(0, nb, body, 0)
        put(nb - 2, jax.lax.rem(nb - 2, 2)).wait()
        put(nb - 1, jax.lax.rem(nb - 1, 2)).wait()

    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((m, c), jnp.bfloat16),
        scratch_shapes=[
            pltpu.VMEM((2, bm, c), jnp.bfloat16),
            pltpu.VMEM((2, bm, c), jnp.bfloat16),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=False,
    )


def main() -> int:
    from kubeflow_tpu.ops.fused_bottleneck import fused_bottleneck, reference_bottleneck

    rows: List[Dict[str, Any]] = []
    x0, weights = _inputs()
    rows.append(_bench_block(reference_bottleneck, x0, weights, "xla_composite"))
    rows.append(_bench_block(
        functools.partial(fused_bottleneck, interpret=False), x0, weights,
        "fused_pallas"))

    flat = x0.reshape(N * HW * HW, CIN)
    rows.append(_bench_copy(lambda x: x * jnp.bfloat16(0.97), flat, "xla_copy_2d"))
    rows.append(_bench_copy(_pallas_copy(flat.shape, (3136, CIN)), flat,
                            "pallas_copy_auto_2d"))
    rows.append(_bench_copy(_pallas_copy(x0.shape, (1, HW, HW, CIN)), x0,
                            "pallas_copy_auto_4d"))
    rows.append(_bench_copy(_manual_dma_copy(N * HW * HW, CIN), flat,
                            "pallas_copy_manual_dma"))

    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"metric": "fused_bottleneck_probe", "rows": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
