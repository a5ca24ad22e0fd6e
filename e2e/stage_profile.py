"""Per-stage ResNet-50 train-time decomposition (VERDICT r3 #1 follow-up).

The step-level profile (e2e/profile_step.py) attributes time to
fwd/bwd/optimizer but not to STAGES, and the isolated-kernel rates in
e2e/ceiling.py turned out to mispredict in-model cost (the 7x7 stem probe
measured 5.7 TF/s standalone, yet swapping in the 44-TF/s space-to-depth
stem moved the full step by <1% — XLA treats the conv differently in
context). This probe times each stage AS TRAINED: one fwd+bwd (wrt params
and input) over just that stage's blocks at its real activation shape,
BN in train mode, scanned inside one executable with the standard
anti-hoist carry perturbation and host-fetch barrier.

Output: ms and TF/s per stage + the sum vs the measured full step, i.e.
which stage is leaving MFU on the table and how much of the step the
stage model explains.

Run:  python -m e2e.stage_profile [--batch 256] [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import time
from functools import partial
from typing import Any, Dict, List

import flax.linen as nn
import jax
import jax.numpy as jnp


class StemTower(nn.Module):
    """conv7x7/2 (or s2d) + BN + ReLU + maxpool, exactly as ResNet runs it."""

    stem: str = "conv7x7"

    @nn.compact
    def __call__(self, x, train: bool = True):
        from kubeflow_tpu.models.resnet import space_to_depth

        conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32)
        norm = partial(nn.BatchNorm, use_running_average=not train, momentum=0.9,
                       epsilon=1e-5, dtype=jnp.bfloat16, param_dtype=jnp.float32)
        x = x.astype(jnp.bfloat16)
        if self.stem == "s2d":
            x = space_to_depth(x, 2)
            x = conv(64, (4, 4), (1, 1), padding=[(2, 1), (2, 1)], name="conv_init_s2d")(x)
        else:
            x = conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = nn.relu(norm(name="bn_init")(x))
        return nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))


class _ScaleBias(nn.Module):
    """BN stand-in: per-channel scale+bias with NO batch statistics — the
    'norm=frozen' variant that isolates what the stats reductions cost."""

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        return x * scale.astype(x.dtype) + bias.astype(x.dtype)


class StageTower(nn.Module):
    """One ResNet-50 bottleneck stage at its real shapes.

    ``norm_mode``: 'train' = real BN batch stats (what training runs);
    'eval' = running-average BN (no stats reduction); 'frozen' = scale+bias
    only (no reduction, no stats memory traffic).
    """

    filters: int
    blocks: int
    first_stride: int
    norm_mode: str = "train"

    @nn.compact
    def __call__(self, x, train: bool = True):
        from kubeflow_tpu.models.resnet import BottleneckBlock

        conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32)
        if self.norm_mode == "frozen":
            def norm(name=None, scale_init=None):
                return _ScaleBias(name=name)
        else:
            norm = partial(nn.BatchNorm,
                           use_running_average=(self.norm_mode == "eval") or not train,
                           momentum=0.9, epsilon=1e-5, dtype=jnp.bfloat16,
                           param_dtype=jnp.float32)
        x = x.astype(jnp.bfloat16)
        for j in range(self.blocks):
            strides = (self.first_stride, self.first_stride) if j == 0 else (1, 1)
            x = BottleneckBlock(filters=self.filters, strides=strides, conv=conv,
                                norm=norm, act=nn.relu, name=f"block{j + 1}")(x)
        return x


def _flops_of(fn, *args) -> float:
    try:
        comp = jax.jit(fn).lower(*args).compile()
        fl = comp.cost_analysis()
        fl = fl[0] if isinstance(fl, (list, tuple)) else fl
        return float(fl.get("flops", 0.0))
    except Exception:
        return 0.0


def time_tower(module: nn.Module, x_shape, steps: int) -> Dict[str, Any]:
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, x_shape, jnp.float32)
    variables = module.init(rng, x)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    def fwd_bwd(params, batch_stats, x):
        def loss_fn(p, xx):
            out, updates = module.apply(
                {"params": p, "batch_stats": batch_stats}, xx, train=True,
                mutable=["batch_stats"])
            return jnp.sum(out.astype(jnp.float32)) * 1e-6, updates
        (loss, updates), grads = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(params, x)
        return loss, grads, updates

    @jax.jit
    def run(params, batch_stats, x):
        def body(c, _):
            xx = x + c * jnp.float32(1e-30)  # anti-hoist: body depends on carry
            loss, grads, _ = fwd_bwd(params, batch_stats, xx)
            gsum = sum(jnp.sum(g.astype(jnp.float32))
                       for g in jax.tree_util.tree_leaves(grads))
            return c + loss + gsum * jnp.float32(1e-30), ()
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=steps)
        return c

    def one_step(params, batch_stats, x):
        # return grads too — a loss-only analysis target lets XLA dead-code
        # the entire backward and undercounts FLOPs ~3x (round-4 bug)
        loss, grads, _ = fwd_bwd(params, batch_stats, x)
        gsum = sum(jnp.sum(g.astype(jnp.float32))
                   for g in jax.tree_util.tree_leaves(grads))
        return loss, gsum

    flops = _flops_of(one_step, params, batch_stats, x)
    out = run(params, batch_stats, x)
    float(out)  # compile + warm
    t0 = time.perf_counter()
    float(run(params, batch_stats, x))
    dt = (time.perf_counter() - t0) / steps
    return {"ms": dt * 1e3, "tflops": flops / dt / 1e12 if flops else None,
            "gflops": flops / 1e9}


def main(argv=None) -> int:
    from kubeflow_tpu.tpu.env import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--stem", default="conv7x7", choices=["conv7x7", "s2d"])
    ap.add_argument("--norm", default="train", choices=["train", "eval", "frozen"],
                    help="BN variant for the stage towers (isolates BN-stats cost)")
    ap.add_argument("--stage", action="append",
                    help="run only towers whose name contains this substring")
    args = ap.parse_args(argv)
    b = args.batch
    nm = args.norm

    towers = [
        ("stem", StemTower(stem=args.stem), (b, 224, 224, 3)),
        ("stage1 (3x bottleneck 64, 56x56)", StageTower(64, 3, 1, nm), (b, 56, 56, 64)),
        ("stage2 (4x bottleneck 128, 28x28)", StageTower(128, 4, 2, nm), (b, 56, 56, 256)),
        ("stage3 (6x bottleneck 256, 14x14)", StageTower(256, 6, 2, nm), (b, 28, 28, 512)),
        ("stage4 (3x bottleneck 512, 7x7)", StageTower(512, 3, 2, nm), (b, 14, 14, 1024)),
    ]
    if args.stage:
        towers = [t for t in towers if any(s in t[0] for s in args.stage)]
    rows: List[Dict[str, Any]] = []
    total_ms = 0.0
    for name, module, shape in towers:
        r = {"stage": name, **time_tower(module, shape, args.steps)}
        rows.append(r)
        total_ms += r["ms"]
        rate = f"{r['tflops']:.1f} TF/s" if r["tflops"] else "n/a"
        print(f"{name:38s} {r['ms']:8.2f} ms  {r['gflops']:9.1f} GF  {rate}", flush=True)
    print(f"{'sum of stages (fwd+bwd, no opt/head)':38s} {total_ms:8.2f} ms")
    print(json.dumps({"metric": "resnet_stage_profile", "batch": b,
                      "stem": args.stem, "rows": rows,
                      "sum_ms": round(total_ms, 2)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
