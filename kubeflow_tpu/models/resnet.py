"""ResNet for TPU: the platform's flagship/MFU-benchmark model.

TPU-first choices:
- NHWC layout throughout (XLA's native conv layout on TPU; MXU-friendly),
- bf16 activations/compute with f32 parameters and f32 BatchNorm statistics
  (bf16 matmul/conv inputs hit the MXU at full rate; f32 running stats keep
  train/eval parity),
- static shapes only; no Python control flow in the forward pass, so the
  whole step compiles to one XLA program.

Reference context: the reference's only "model" content is CUDA notebook
images (example-notebook-servers/jupyter-pytorch/cuda.Dockerfile); the
BASELINE north-star is ResNet-50 ≥60% MFU on a v5e slice.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class _ConvKernel(nn.Module):
    """Parameter holder with ``nn.Conv``'s exact tree ({kernel}) — the fused
    block reads the weight directly instead of applying the conv, while the
    checkpoint layout stays interchangeable with the unfused path."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(), self.shape,
                          jnp.float32)


class _FoldedNorm(nn.Module):
    """Parameter/stat holder with ``nn.BatchNorm``'s exact tree (params
    {scale, bias}, batch_stats {mean, var}); returns the inference-form norm
    folded to a single (scale, bias) affine: y*s + b == (y - mean)/sqrt(var
    + eps) * gamma + beta."""

    features: int
    epsilon: float = 1e-5
    scale_init: Callable = nn.initializers.ones

    @nn.compact
    def __call__(self):
        scale = self.param("scale", self.scale_init, (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((self.features,), jnp.float32)
        )
        var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((self.features,), jnp.float32)
        )
        inv = scale * jax.lax.rsqrt(var.value + self.epsilon)
        return inv, bias - mean.value * inv


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut when needed.

    ``fused=True`` routes every square-input application through a Pallas
    kernel: identity-shortcut blocks through ``fused_bottleneck`` (any
    spatial size — non-8-aligned rows go through sublane-padded dots) and
    the stage heads (stride-2 and/or projection shortcut) through
    ``fused_transition``. The whole block runs as MXU matmuls with
    activations resident in VMEM, norms folded from the running statistics
    ("frozen norm" — matches the unfused path exactly in eval mode; in
    train mode fused blocks normalize by running stats instead of batch
    stats and do not update them). Backward stays XLA
    (ops.fused_bottleneck_block / fused_transition_block). The rare
    leftover shapes (non-square, odd strided inputs) take the epilogue-
    fused XLA ``folded_bottleneck`` path and tick
    ``ops_fused_fallback_total``; all paths declare an identical variable
    tree, so checkpoints move freely between fused and unfused models.
    """

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    fused: bool = False

    def _fusable(self, x) -> bool:
        """Identity-shortcut Pallas kernel eligibility (stride 1, square)."""
        return (
            self.strides == (1, 1)
            and x.ndim == 4
            and x.shape[-1] == self.filters * 4
            and x.shape[1] == x.shape[2]
            and x.shape[1] >= 4
        )

    def _fusable_transition(self, x) -> bool:
        """Transition-block Pallas kernel eligibility: a stage head (needs a
        projection shortcut for channels and/or stride), square input,
        stride in {1, 2}; stride 2 needs an even spatial dim (SAME pad is
        then (0, 1), which the kernel's strided im2col reproduces)."""
        if not (x.ndim == 4 and x.shape[1] == x.shape[2] and x.shape[1] >= 4):
            return False
        if self.strides == (1, 1):
            return x.shape[-1] != self.filters * 4  # stride-1 channel head
        return self.strides == (2, 2) and x.shape[1] % 2 == 0

    def _fused_params(self, cin: int, cmid: int, cout: int, proj: bool):
        w1 = _ConvKernel((1, 1, cin, cmid), name="conv1")()
        s1, b1 = _FoldedNorm(cmid, name="bn1")()
        w2 = _ConvKernel((3, 3, cmid, cmid), name="conv2")()
        s2, b2 = _FoldedNorm(cmid, name="bn2")()
        w3 = _ConvKernel((1, 1, cmid, cout), name="conv3")()
        # Zero-init bn3's scale, mirroring the unfused path below.
        s3, b3 = _FoldedNorm(cout, scale_init=nn.initializers.zeros, name="bn3")()
        main = (w1[0, 0], s1, b1, w2, s2, b2, w3[0, 0], s3, b3)
        if not proj:
            return main, None
        wp = _ConvKernel((1, 1, cin, cout), name="conv_proj")()
        sp, bp = _FoldedNorm(cout, name="bn_proj")()
        return main, (wp[0, 0], sp, bp)

    @nn.compact
    def __call__(self, x):
        if self.fused and self._fusable(x):
            from kubeflow_tpu.ops.fused_bottleneck import fused_bottleneck_block

            cin, cmid = self.filters * 4, self.filters
            main, _ = self._fused_params(cin, cmid, cin, proj=False)
            return fused_bottleneck_block(x, *main)
        if self.fused and self._fusable_transition(x):
            from kubeflow_tpu.ops.fused_bottleneck import fused_transition_block

            cin, cmid, cout = x.shape[-1], self.filters, self.filters * 4
            main, proj = self._fused_params(cin, cmid, cout, proj=True)
            return fused_transition_block(
                x, *main, *proj, stride=self.strides[0])
        if self.fused and x.ndim == 4:
            # Neither kernel takes this shape: keep the folded-norm math in
            # an epilogue-fused XLA composite so the variable tree (and the
            # frozen-norm semantics of fused=True) stay uniform, and make
            # the kernel miss visible.
            from kubeflow_tpu.ops.fallback import record_fallback
            from kubeflow_tpu.ops.fused_bottleneck import folded_bottleneck

            record_fallback(
                "fused_bottleneck",
                f"input shape {tuple(x.shape)} with strides "
                f"{tuple(self.strides)} is not kernel-fusable; using the "
                "epilogue-fused XLA path")
            cin, cmid, cout = x.shape[-1], self.filters, self.filters * 4
            out_hw = tuple(
                -(-d // s) for d, s in zip(x.shape[1:3], self.strides))
            needs_proj = cin != cout or out_hw != tuple(x.shape[1:3])
            main, proj = self._fused_params(cin, cmid, cout, proj=needs_proj)
            return folded_bottleneck(
                x, *main, strides=self.strides, proj=proj)
        residual = x
        y = self.conv(self.filters, (1, 1), name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides, name="conv2")(y)
        y = self.norm(name="bn2")(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1), name="conv3")(y)
        # Zero-init the last BN's scale: identity-ish residual at init
        # (standard ResNet-v1.5 trick; improves large-batch training).
        y = self.norm(name="bn3", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="bn_proj")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides, name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), name="conv2")(y)
        y = self.norm(name="bn2", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="bn_proj")(residual)
        return self.act(residual + y)


def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """[N, H, W, C] -> [N, H/b, W/b, b*b*C]: 2x2 pixel blocks folded into
    channels. A pure reshape/transpose — XLA compiles it to a cheap copy."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // block, w // block, block * block * c)


class ResNet(nn.Module):
    """stem="s2d" folds the input 2x2 space-to-depth and runs the stem as a
    4x4/1 conv on 12 channels instead of 7x7/2 on 3 — the same receptive
    field (the 7x7 kernel zero-padded to 8x8 and regrouped onto the
    half-res grid), but with 4x the channels feeding the MXU. In isolation
    (a standalone conv probe, BASELINE.md) the s2d form has measured several
    times the 3-channel 7x7's rate; in the full train step the win was ~1% (XLA
    treats the in-model stem better than the standalone probe suggests —
    BASELINE.md, older findings). Default stays "conv7x7":
    the s2d stem renames/reshapes conv_init in the param tree, which would
    silently break existing checkpoints and torchvision weight-shape
    parity; perf-sensitive callers (bench.py) opt in explicitly."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    stem: str = "conv7x7"  # "s2d" | "conv7x7"
    # fused_blocks: route bottlenecks through the Pallas fused kernels
    # (ops/fused_bottleneck.py) — identity blocks AND the stage heads, so
    # all 16 of ResNet-50's blocks fuse at 224x224. Same variable tree as
    # the unfused model; frozen-norm semantics in those blocks (see
    # BottleneckBlock). Opt-in like the s2d stem; bench.py decides per
    # backend.
    fused_blocks: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32)
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
            param_dtype=jnp.float32,
        )
        act = nn.relu

        x = x.astype(self.dtype)
        if self.stem == "s2d" and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            x = space_to_depth(x, 2)
            # padding (2,1): the s2d window spans cells i-2..i+1, covering
            # the 7x7/2 receptive field (rows 2i-4..2i+3 vs 2i-3..2i+3).
            x = conv(self.num_filters, (4, 4), (1, 1),
                     padding=[(2, 1), (2, 1)], name="conv_init_s2d")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        # BasicBlock has no fused kernel; the flag only reaches bottlenecks.
        fused_kw = (
            {"fused": True}
            if self.fused_blocks and self.block_cls is BottleneckBlock
            else {}
        )
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    act=act,
                    name=f"stage{i + 1}_block{j + 1}",
                    **fused_kw,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        # Final classifier in f32: logits feed a softmax cross-entropy that is
        # numerically touchy in bf16.
        x = nn.Dense(self.num_classes, dtype=jnp.float32, param_dtype=jnp.float32, name="classifier")(
            x.astype(jnp.float32)
        )
        return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)
