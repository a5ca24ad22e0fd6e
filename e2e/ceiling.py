"""Device-ceiling probe: what this chip actually sustains.

A single-dispatch matmul measures per-dispatch latency, not the MXU. This
probe measures every kernel the way the bench does: all iterations inside
ONE jitted ``lax.scan`` executable, results kept live by a fetched checksum,
a device→host fetch as the barrier.

Kernels:
- bf16 matmul chain (y <- y @ W) at several sizes — the MXU roofline.
- ResNet-dominant 3x3 convs at the real per-stage shapes — conv roofline.
- f32 elementwise triad (y <- a*x + y) — HBM bandwidth roofline.

Output: per-kernel sustained TFLOP/s (or GB/s) + the sweep max, printed as a
table and one JSON line. The sweep max IS the measured ceiling: MFU-at-
ceiling = step_flops / (step_time * ceiling) tells whether the training step
leaves real headroom on the table or the device is the limit.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

# Chaining CHAIN ops inside each scan body amortizes the fixed cost of a
# scan iteration out of the kernel-rate measurement.
CHAIN = int(os.environ.get("CEILING_CHAIN", "8"))


def _timed(fn, args, iters: int) -> float:
    """Seconds per iteration: compile+warm once, then time one scanned run
    with a host fetch as the barrier. All arrays are passed as ARGUMENTS:
    a closure-captured device array becomes a constant of the program."""
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: float(jnp.sum(x.astype(jnp.float32))), out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: float(jnp.sum(x.astype(jnp.float32))), out)
    return (time.perf_counter() - t0) / iters


def matmul_sustained(n: int, iters: int = 20) -> Dict[str, Any]:
    """bf16 y <- y @ W chained n×n matmul; sustained TFLOP/s."""
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (n, n), jnp.bfloat16) * (1.0 / n) ** 0.5
    y0 = jax.random.normal(key, (n, n), jnp.bfloat16)

    @jax.jit
    def run(y, w):
        def body(y, _):
            # scaled init keeps values finite across the chained multiplies
            for _i in range(CHAIN):
                y = y @ w
            return y, ()
        y, _ = jax.lax.scan(body, y, None, length=iters)
        return jnp.sum(y.astype(jnp.float32))

    dt = _timed(run, (y0, w), iters * CHAIN)
    flops = 2.0 * n * n * n
    return {"kernel": f"matmul_bf16_{n}", "tflops": flops / dt / 1e12, "iter_s": dt}


def conv_sustained(batch: int, hw: int, cin: int, cout: int, iters: int = 20) -> Dict[str, Any]:
    """bf16 3x3 stride-1 SAME conv at a ResNet-stage shape; sustained TFLOP/s."""
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (batch, hw, hw, cin), jnp.bfloat16)
    k = jax.random.normal(key, (3, 3, cin, cout), jnp.bfloat16) * 0.05
    # cout -> cin projection so the loop composes when cin != cout
    proj = jax.random.normal(key, (1, 1, cout, cin), jnp.bfloat16) * 0.05
    dn = jax.lax.conv_dimension_numbers(x0.shape, k.shape, ("NHWC", "HWIO", "NHWC"))
    y_shape = (batch, hw, hw, cout)
    dn_proj = jax.lax.conv_dimension_numbers(y_shape, proj.shape, ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    def run(x, k, proj):
        def body(x, _):
            for _i in range(CHAIN):
                y = jax.lax.conv_general_dilated(x, k, (1, 1), "SAME", dimension_numbers=dn)
                x = jax.lax.conv_general_dilated(y, proj, (1, 1), "SAME",
                                                 dimension_numbers=dn_proj) * (1.0 / hw)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=iters)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0, k, proj), iters * CHAIN)
    flops = 2.0 * batch * hw * hw * (3 * 3 * cin * cout + cout * cin)
    return {"kernel": f"conv3x3_bf16_b{batch}_{hw}x{hw}x{cin}->{cout}",
            "tflops": flops / dt / 1e12, "iter_s": dt}


def flash_seq_sustained(batch: int, seq: int, heads: int = 16, head_dim: int = 64,
                        iters: int = 8) -> Dict[str, Any]:
    """Pallas flash attention fwd+bwd at long sequence lengths — the
    long-context kernel evidence (8192 tokens held constant across the
    sweep; the quadratic score work grows with seq while the token count
    stays fixed, so rates show how the kernel scales with context)."""
    from kubeflow_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    shape = (batch, seq, heads, head_dim)
    q0 = jax.random.normal(key, shape, jnp.bfloat16) * 0.1

    def loss(q, k, v):
        return jnp.sum(jnp.abs(
            flash_attention(q, k, v, causal=True, interpret=False).astype(jnp.float32)))

    @jax.jit
    def run(q):
        def body(q, _):
            for _i in range(CHAIN):
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, q, q)
                q = (jnp.abs(dq) * 0.1 + (jnp.abs(dk) + jnp.abs(dv))
                     * jnp.bfloat16(1e-3)).astype(jnp.bfloat16) * 0.3
            return q, ()
        q, _ = jax.lax.scan(body, q, None, length=iters)
        return jnp.sum(q.astype(jnp.float32))

    dt = _timed(run, (q0,), iters * CHAIN)
    # causal fwd = 2 matmuls over the lower triangle ~ 2*2*b*h*L^2*d/2;
    # flash bwd recomputes scores + 4 more matmuls ~ 2.5x fwd
    fwd = 2.0 * b_h_l2_d(batch, heads, seq, head_dim)
    flops = 3.5 * fwd
    return {"kernel": f"flash_attn_fwd_bwd_b{batch}_L{seq}",
            "tflops": flops / dt / 1e12, "iter_s": dt}


def b_h_l2_d(b: int, h: int, l: int, d: int) -> float:
    return b * h * float(l) * l * d  # one causal-triangle matmul's MACs*2/2


def hbm_triad(mib: int = 512, iters: int = 20) -> Dict[str, Any]:
    """f32 y <- |y|*0.9999 + x : 2 reads + 1 write per element -> GB/s.
    abs() makes each chain step non-linear so XLA cannot algebraically
    collapse the chain into one op (a plain a*y+x chain measured 1.9 TB/s
    on an 0.8 TB/s part — the compiler had folded it)."""
    n = mib * 1024 * 1024 // 4
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n,), jnp.float32)
    y0 = jax.random.normal(key, (n,), jnp.float32)

    @jax.jit
    def run(y, x):
        def body(y, _):
            for _i in range(CHAIN):
                y = jnp.abs(y) * jnp.float32(0.9999) + x
            return y, ()
        y, _ = jax.lax.scan(body, y, None, length=iters)
        return jnp.sum(y)

    # XLA fuses the whole chain into one elementwise kernel, so the real
    # HBM traffic per scan ITERATION is 3 array passes (y in, x in, y out)
    # no matter how long the chain is — count exactly that.
    dt = _timed(run, (y0, x), iters)
    gbytes = 3.0 * n * 4 / 1e9
    return {"kernel": f"hbm_triad_f32_{mib}MiB", "gbs": gbytes / dt, "iter_s": dt}


def sweep() -> Dict[str, Any]:
    results: List[Dict[str, Any]] = []
    for n in (2048, 4096, 8192):
        results.append(matmul_sustained(n))
    # ResNet-50's conv budget by stage (batch matches the bench)
    for shape in ((256, 56, 64, 64), (256, 28, 128, 128), (256, 14, 256, 256)):
        results.append(conv_sustained(*shape))
    bw = hbm_triad()
    ceiling = max(r["tflops"] for r in results)
    return {"kernels": results, "hbm": bw, "ceiling_tflops": ceiling}


def flash_sweep() -> List[Dict[str, Any]]:
    """Long-context flash rows (8192 tokens held constant) —
    ``python -m e2e.ceiling --flash``."""
    return [flash_seq_sustained(b, L)
            for b, L in ((8, 1024), (4, 2048), (2, 4096), (1, 8192))]


def main(argv: Optional[List[str]] = None) -> None:
    import sys

    from kubeflow_tpu.tpu.env import enable_compile_cache
    from kubeflow_tpu.training.flops import detect_generation, peak_flops_per_chip

    enable_compile_cache()
    argv = sys.argv[1:] if argv is None else argv
    gen = detect_generation()
    peak = peak_flops_per_chip(gen) / 1e12
    if "--flash" in argv:
        rows = flash_sweep()
        for r in rows:
            print(f"{r['kernel']:45s} {r['tflops']:9.1f} TF {100 * r['tflops'] / peak:7.1f}%")
        print(json.dumps({"metric": f"flash_seq_sweep_{gen}", "rows": rows}))
        return
    out = sweep()
    print(f"{'kernel':45s} {'sustained':>12s} {'of peak':>8s}")
    for r in out["kernels"]:
        print(f"{r['kernel']:45s} {r['tflops']:9.1f} TF {100 * r['tflops'] / peak:7.1f}%")
    b = out["hbm"]
    print(f"{b['kernel']:45s} {b['gbs']:9.1f} GB/s")
    print(json.dumps({
        "metric": f"kernel_ceiling_{gen}",
        "value": round(out["ceiling_tflops"], 1),
        "unit": "tflops_sustained",
        "peak_tflops": peak,
        "of_peak": round(out["ceiling_tflops"] / peak, 4),
        "hbm_gbs": round(b["gbs"], 1),
    }))


if __name__ == "__main__":
    main()
