"""Conv-ceiling attack experiments.

The claim under attack: ResNet's 64-channel convs are bound by the op MIX (a
128-wide MXU half-idle below 128 contraction/output channels), not by the
framework. That claim was measured only via
``jax.lax.conv_general_dilated`` — i.e. via XLA's chosen formulation. These
probes attack the bound directly by measuring the SAME arithmetic in every
formulation a custom kernel could choose, using the honest harness from
e2e/ceiling.py (all iterations inside one ``lax.scan`` executable, chained
bodies, host-fetch barrier).

Stage-1 conv3x3 (batch 256, 56x56, 64->64, bf16) as a GEMM is
[M=256*56*56=802816, K=9*64=576] @ [K, N=64]:

1. ``gemm_conv_style``   — [M, 576] @ [576, 64]: XLA-conv-like orientation,
   output channels (64) in the minor/lane dim -> half the MXU lanes idle.
2. ``gemm_spatial_lanes``— [64, 576] @ [576, M]: the transposed orientation a
   Pallas kernel can pick — spatial in lanes (full width), c_out streamed as
   rows. Same FLOPs.
3. ``gemm_tap_dots``     — 9 x ([64, 64] @ [64, M]): the no-im2col variant
   (one dot per 3x3 tap); contraction depth 64 halves MXU depth utilization.
4. ``conv_xla``          — the actual ``conv_general_dilated`` at the stage
   shape (control).
5. ``conv_xla_fused``    — conv + BN-apply + ReLU, measuring whether the
   epilogue is free (XLA fusion) or a separate HBM pass.
6. ``conv_stem`` / ``conv_stem_s2d`` — the 7x7/2 stem on 224x224x3 vs the
   space-to-depth repack (112x112x12, 4x4/1 kernel = identical arithmetic,
   4x the input channels feeding the MXU).

Run:  python -m e2e.conv_experiments [--probe NAME]
Prints one line per probe + a JSON summary. BASELINE.md keeps what the
July 2026 runs taught; rates on today's chip are not measured.
"""

from __future__ import annotations

import argparse
import json
import os

from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

# Harness shared with the ceiling probe so rates stay comparable under the
# same CEILING_CHAIN knob (one copy of the scan/amortization rationale).
from e2e.ceiling import CHAIN, _timed  # noqa: E402

ITERS = int(os.environ.get("CEILING_ITERS", "20"))

# Stage-1 conv3x3 as GEMM
B, HW, C = 256, 56, 64
M = B * HW * HW          # 802816
K = 9 * C                # 576


def _gemm_probe(m: int, k: int, n: int, name: str) -> Dict[str, Any]:
    """y <- (x @ w) folded back into x's shape via a cheap projection, chained
    so every dot stays live. x is a jit ARGUMENT (a closure capture would
    become a constant of the program)."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.bfloat16) * 0.05
    w = jax.random.normal(key, (k, n), jnp.bfloat16) * 0.05
    proj = jax.random.normal(key, (n, k), jnp.bfloat16) * 0.05

    @jax.jit
    def run(x, w, proj):
        def body(x, _):
            for _i in range(CHAIN):
                y = jax.lax.dot(x, w)            # [m, n]
                x = jnp.abs(jax.lax.dot(y, proj)) * 0.05  # back to [m, k], non-linear
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=ITERS)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x, w, proj), ITERS * CHAIN)
    flops = 2.0 * m * k * n * 2  # two dots per chain step
    return {"kernel": name, "tflops": flops / dt / 1e12, "iter_s": dt}


def gemm_conv_style() -> Dict[str, Any]:
    return _gemm_probe(M, K, C, f"gemm[{M}x{K}]@[{K}x{C}] (cout in lanes)")


def gemm_spatial_lanes() -> Dict[str, Any]:
    return _gemm_probe(C, K, M, f"gemm[{C}x{K}]@[{K}x{M}] (spatial in lanes)")


def gemm_tap_dots() -> Dict[str, Any]:
    """9 tap-dots of K=64: w9[9,64,64] x x[64,M] -> summed [64,M]."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (C, M), jnp.bfloat16) * 0.05
    w9 = jax.random.normal(key, (9, C, C), jnp.bfloat16) * 0.05

    @jax.jit
    def run(x, w9):
        def body(x, _):
            for _i in range(CHAIN):
                y = jnp.zeros((C, M), jnp.float32)
                for t in range(9):
                    y = y + jax.lax.dot(w9[t].T, x, preferred_element_type=jnp.float32)
                x = jnp.abs(y).astype(jnp.bfloat16) * 0.05
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=ITERS)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x, w9), ITERS * CHAIN)
    flops = 2.0 * C * C * M * 9
    return {"kernel": "9 tap-dots [64x64]@[64xM] (K=64)", "tflops": flops / dt / 1e12, "iter_s": dt}


def _conv_probe(batch: int, hw: int, cin: int, cout: int, ksz: int, stride: int,
                name: str, fuse_bn_relu: bool = False) -> Dict[str, Any]:
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (batch, hw, hw, cin), jnp.bfloat16)
    k = jax.random.normal(key, (ksz, ksz, cin, cout), jnp.bfloat16) * 0.05
    ohw = hw // stride
    proj = jax.random.normal(key, (1, 1, cout, cin), jnp.bfloat16) * 0.05
    scale = jax.random.normal(key, (cout,), jnp.bfloat16) * 0.1
    bias = jax.random.normal(key, (cout,), jnp.bfloat16) * 0.1
    dn = jax.lax.conv_dimension_numbers(x0.shape, k.shape, ("NHWC", "HWIO", "NHWC"))
    dn_proj = jax.lax.conv_dimension_numbers((batch, ohw, ohw, cout), proj.shape,
                                             ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    def run(x, k, proj, scale, bias):
        def body(x, _):
            for _i in range(CHAIN):
                y = jax.lax.conv_general_dilated(x, k, (stride, stride), "SAME",
                                                 dimension_numbers=dn)
                if fuse_bn_relu:
                    y = jnp.maximum(y * scale + bias, 0.0)
                z = jax.lax.conv_general_dilated(y, proj, (1, 1), "SAME",
                                                 dimension_numbers=dn_proj) * (1.0 / hw)
                if stride != 1:
                    z = jnp.repeat(jnp.repeat(z, stride, 1), stride, 2)  # back to hw
                x = jnp.abs(z).astype(jnp.bfloat16)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=ITERS)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0, k, proj, scale, bias), ITERS * CHAIN)
    flops = 2.0 * batch * ohw * ohw * (ksz * ksz * cin * cout + cout * cin)
    return {"kernel": name, "tflops": flops / dt / 1e12, "iter_s": dt}


def conv_xla() -> Dict[str, Any]:
    return _conv_probe(B, HW, C, C, 3, 1, f"conv3x3 b{B} {HW}x{HW}x{C}->{C} (XLA)")


def conv_xla_fused() -> Dict[str, Any]:
    return _conv_probe(B, HW, C, C, 3, 1,
                       f"conv3x3+bn+relu b{B} {HW}x{HW}x{C}->{C} (XLA)", fuse_bn_relu=True)


def conv_stem() -> Dict[str, Any]:
    # 7x7/2 on 224x224x3: K = 49*3 = 147 contraction, 3 input channels of a
    # 128-lane load -> the classic worst case.
    return _conv_probe(B, 224, 3, 64, 7, 2, f"stem conv7x7/2 b{B} 224x224x3->64 (XLA)")


def conv_stem_s2d() -> Dict[str, Any]:
    # Space-to-depth: x[224,224,3] -> [112,112,12] (2x2 blocks into channels);
    # the 7x7/2 conv becomes a 4x4/1 conv on the repacked grid (the 7x7
    # kernel zero-padded to 8x8 and regrouped — MLPerf-style stem packing).
    # 16*12=192 taps vs 147: 31% more nominal FLOPs, but 4x the input
    # channels feeding the MXU. Compare iter_s against conv_stem — both
    # compute the full stem from the same input information.
    return _conv_probe(B, 112, 12, 64, 4, 1, f"stem-s2d conv4x4 b{B} 112x112x12->64 (XLA)")


def _conv_bwd_probe(which: str, cin: int = C, hw: int = HW) -> Dict[str, Any]:
    """Backward-pass decomposition at the stage-1 3x3 shape: time
    fwd+grad-wrt-x ('x'), fwd+grad-wrt-w ('w'), or the full training shape
    ('both'). The loss is sum(abs(conv)) so dY depends on x (a plain sum
    would make dY constant-foldable); grads feed the next chain step so
    nothing is dead."""
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (B, hw, hw, cin), jnp.bfloat16)
    k0 = jax.random.normal(key, (3, 3, cin, cin), jnp.bfloat16) * 0.05
    dn = jax.lax.conv_dimension_numbers(x0.shape, k0.shape, ("NHWC", "HWIO", "NHWC"))

    def loss(x, k):
        y = jax.lax.conv_general_dilated(x, k, (1, 1), "SAME", dimension_numbers=dn)
        return jnp.sum(jnp.abs(y.astype(jnp.float32)))

    @jax.jit
    def run(x, k):
        def body(x, _):
            for _i in range(CHAIN):
                if which == "x":
                    dx = jax.grad(loss, argnums=0)(x, k)
                    x = (jnp.abs(dx) * 0.01).astype(jnp.bfloat16)
                elif which == "w":
                    dw = jax.grad(loss, argnums=1)(x, k)
                    # dw is tiny [3,3,cin,cin]; keep it live through x
                    x = x * (1.0 + jnp.sum(jnp.abs(dw)) * jnp.bfloat16(1e-30))
                else:
                    dx, dw = jax.grad(loss, argnums=(0, 1))(x, k)
                    x = (jnp.abs(dx) * 0.01
                         + jnp.sum(jnp.abs(dw)) * jnp.bfloat16(1e-30)).astype(jnp.bfloat16)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=ITERS)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0, k0), ITERS * CHAIN)
    conv_f = 2.0 * B * hw * hw * 9 * cin * cin
    flops = conv_f * (3.0 if which == "both" else 2.0)  # fwd + 1-2 grad convs
    return {"kernel": f"conv3x3 {hw}x{hw}x{cin} fwd+grad_{which}",
            "tflops": flops / dt / 1e12, "iter_s": dt}


def _conv1x1_bwd_probe(cin: int, cout: int, hw: int = HW) -> Dict[str, Any]:
    """fwd+bwd of the bottleneck's 1x1 convs (projection GEMMs)."""
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (B, hw, hw, cin), jnp.bfloat16)
    k0 = jax.random.normal(key, (1, 1, cin, cout), jnp.bfloat16) * 0.05
    dn = jax.lax.conv_dimension_numbers(x0.shape, k0.shape, ("NHWC", "HWIO", "NHWC"))

    def loss(x, k):
        y = jax.lax.conv_general_dilated(x, k, (1, 1), "SAME", dimension_numbers=dn)
        return jnp.sum(jnp.abs(y.astype(jnp.float32)))

    @jax.jit
    def run(x, k):
        def body(x, _):
            for _i in range(CHAIN):
                dx, dw = jax.grad(loss, argnums=(0, 1))(x, k)
                x = (jnp.abs(dx) * 0.01
                     + jnp.sum(jnp.abs(dw)) * jnp.bfloat16(1e-30)).astype(jnp.bfloat16)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=ITERS)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0, k0), ITERS * CHAIN)
    flops = 3.0 * 2.0 * B * hw * hw * cin * cout
    return {"kernel": f"conv1x1 {hw}x{hw} {cin}->{cout} fwd+grad_both",
            "tflops": flops / dt / 1e12, "iter_s": dt}


def conv1x1_grad_reduce() -> Dict[str, Any]:
    return _conv1x1_bwd_probe(256, 64)


def conv1x1_grad_expand() -> Dict[str, Any]:
    return _conv1x1_bwd_probe(64, 256)


def bottleneck_block_fwd_bwd() -> Dict[str, Any]:
    """The WHOLE stage-1 bottleneck (1x1 256->64, 3x3 64->64, 1x1 64->256 +
    relu + residual; frozen scale/bias norm) fwd+bwd — isolates whether the
    stage tower's deficit is the conv mix itself or the BN/elementwise
    interleave around it."""
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (B, HW, HW, 256), jnp.bfloat16) * 0.1
    ks = {
        "k1": jax.random.normal(key, (1, 1, 256, 64), jnp.bfloat16) * 0.05,
        "k2": jax.random.normal(key, (3, 3, 64, 64), jnp.bfloat16) * 0.05,
        "k3": jax.random.normal(key, (1, 1, 64, 256), jnp.bfloat16) * 0.05,
        "s1": jnp.ones((64,), jnp.bfloat16), "b1": jnp.zeros((64,), jnp.bfloat16),
        "s2": jnp.ones((64,), jnp.bfloat16), "b2": jnp.zeros((64,), jnp.bfloat16),
        "s3": jnp.ones((256,), jnp.bfloat16), "b3": jnp.zeros((256,), jnp.bfloat16),
    }

    def block(x, p):
        def conv(x, k):
            dn = jax.lax.conv_dimension_numbers(x.shape, k.shape, ("NHWC", "HWIO", "NHWC"))
            return jax.lax.conv_general_dilated(x, k, (1, 1), "SAME", dimension_numbers=dn)
        y = jnp.maximum(conv(x, p["k1"]) * p["s1"] + p["b1"], 0)
        y = jnp.maximum(conv(y, p["k2"]) * p["s2"] + p["b2"], 0)
        y = conv(y, p["k3"]) * p["s3"] + p["b3"]
        return jnp.maximum(x + y, 0)

    def loss(x, p):
        return jnp.sum(jnp.abs(block(x, p).astype(jnp.float32)))

    @jax.jit
    def run(x, p):
        def body(x, _):
            for _i in range(CHAIN):
                dx, dp = jax.grad(loss, argnums=(0, 1))(x, p)
                dpsum = sum(jnp.sum(jnp.abs(g)) for g in jax.tree_util.tree_leaves(dp))
                x = (jnp.abs(dx) * 0.05 + dpsum * jnp.bfloat16(1e-30)).astype(jnp.bfloat16)
            return x, ()
        x, _ = jax.lax.scan(body, x, None, length=ITERS)
        return jnp.sum(x.astype(jnp.float32))

    dt = _timed(run, (x0, ks), ITERS * CHAIN)
    conv_f = 2.0 * B * HW * HW * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    flops = 3.0 * conv_f  # fwd + dX + dW
    return {"kernel": "bottleneck(256->64->64->256) fwd+bwd frozen-norm",
            "tflops": flops / dt / 1e12, "iter_s": dt}


def conv_grad_x() -> Dict[str, Any]:
    return _conv_bwd_probe("x")


def conv_grad_w() -> Dict[str, Any]:
    return _conv_bwd_probe("w")


def conv_grad_both() -> Dict[str, Any]:
    return _conv_bwd_probe("both")


def conv_grad_both_128() -> Dict[str, Any]:
    return _conv_bwd_probe("both", cin=128, hw=28)


PROBES: Dict[str, Callable[[], Dict[str, Any]]] = {
    "gemm_conv_style": gemm_conv_style,
    "gemm_spatial_lanes": gemm_spatial_lanes,
    "gemm_tap_dots": gemm_tap_dots,
    "conv_xla": conv_xla,
    "conv_xla_fused": conv_xla_fused,
    "conv_stem": conv_stem,
    "conv_stem_s2d": conv_stem_s2d,
    "conv_grad_x": conv_grad_x,
    "conv_grad_w": conv_grad_w,
    "conv_grad_both": conv_grad_both,
    "conv_grad_both_128": conv_grad_both_128,
    "conv1x1_grad_reduce": conv1x1_grad_reduce,
    "conv1x1_grad_expand": conv1x1_grad_expand,
    "bottleneck_block_fwd_bwd": bottleneck_block_fwd_bwd,
}


def main(argv=None) -> int:
    from kubeflow_tpu.tpu.env import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", choices=sorted(PROBES), action="append",
                    help="run only these probes (default: all)")
    args = ap.parse_args(argv)
    names = args.probe or list(PROBES)
    rows: List[Dict[str, Any]] = []
    for name in names:
        try:
            r = PROBES[name]()
        except Exception as e:  # record, keep sweeping
            r = {"kernel": name, "tflops": 0.0, "error": str(e)[:160]}
        rows.append(r)
        print(f"{r['kernel']:55s} {r['tflops']:9.1f} TF/s"
              + (f"  ERROR {r['error']}" if r.get("error") else ""), flush=True)
    print(json.dumps({"metric": "conv_experiments", "rows": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
