"""Pallas flash-attention kernel vs the exact XLA reference.

Interpreter mode on CPU (conftest forces JAX_PLATFORMS=cpu); the same code
compiles on TPU. Mirrors the reference's tier-1 table-driven style
(SURVEY.md §4) over shapes/causality/dtype/offsets.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import flash_attention
from kubeflow_tpu.parallel.ring_attention import full_attention


def _rand_qkv(key, b, lq, lk, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, lq, h, d), dtype)
    k = jax.random.normal(kk, (b, lk, h, d), dtype)
    v = jax.random.normal(kv, (b, lk, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,lq,lk,h,d,causal,block",
    [
        (1, 128, 128, 1, 64, False, 64),
        (2, 256, 256, 2, 32, False, 128),
        (1, 256, 256, 2, 32, True, 64),
        (2, 128, 256, 1, 64, False, 128),  # cross-attention lq != lk
    ],
)
def test_forward_matches_reference(b, lq, lk, h, d, causal, block):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, lq, lk, h, d)
    got = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 128, 128, 2, 64, jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    want = full_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), atol=2e-2, rtol=2e-2
    )


def test_position_offsets_shift_causal_mask():
    """With k_offset = lk the whole k block is 'in the future' of low queries."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 128, 1, 32)
    lk = k.shape[1]
    # Same global layout expressed two ways: one call over concat(k, k2) vs
    # two offset calls combined would need online-softmax; here just check
    # q_offset makes everything visible (q positions >= all k positions).
    shifted = flash_attention(q, k, v, causal=True, q_offset=lk)
    unmasked = full_attention(q, k, v, causal=False)
    np.testing.assert_allclose(shifted, unmasked, atol=2e-5, rtol=2e-5)
    # And k entirely in the future -> fully-masked rows give zeros.
    future = flash_attention(q, k, v, causal=True, k_offset=10 * lk)
    np.testing.assert_allclose(future, np.zeros_like(future), atol=1e-6)


def test_grad_matches_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 128, 128, 2, 32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


def test_jit_and_vmap_compose():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 2, 128, 128, 1, 32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    np.testing.assert_allclose(f(q, k, v), full_attention(q, k, v), atol=2e-5, rtol=2e-5)


def test_indivisible_block_rejected():
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 96, 96, 1, 32)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_bert_with_flash_attention():
    """flash_attention drops in as the models' injectable attention_fn."""
    from kubeflow_tpu.models import BertConfig, BertForMaskedLM

    cfg = BertConfig.tiny()
    model = BertForMaskedLM(cfg, attention_fn=lambda q, k, v: flash_attention(q, k, v))
    ref = BertForMaskedLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(1), ids)
    got = model.apply(variables, ids)
    want = ref.apply(variables, ids)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)  # bf16 model compute


def test_auto_attention_cpu_falls_back():
    from kubeflow_tpu.ops import auto_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 64, 64, 1, 16)
    np.testing.assert_allclose(
        auto_attention(q, k, v, causal=True), full_attention(q, k, v, causal=True),
        atol=1e-6,
    )


def _offset_reference(q, k, v, q_offset, k_offset, scale=None):
    """Exact attention with global-position causal mask (ring-step semantics)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(q.shape[1])
    k_pos = k_offset + jnp.arange(k.shape[1])
    mask = q_pos[:, None] >= k_pos[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> zero output
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype)).astype(q.dtype)


def test_partial_offset_fully_masked_rows():
    """k_offset=lk/2: low query rows see no keys and must output exact zeros.

    Regression test — the soft -1e30 mask used to degenerate to uniform
    attention (p=1) when a row's running max was itself -1e30.
    """
    b, l, h, d = 1, 128, 2, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b, l, l, h, d)
    k_offset = 64
    got = flash_attention(q, k, v, causal=True, k_offset=k_offset)
    want = _offset_reference(q, k, v, 0, k_offset)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got[:, :k_offset], 0.0, atol=1e-6)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, k_offset=k_offset) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_offset_reference(q, k, v, 0, k_offset) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, r, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, r, atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


class TestAutoTiling:
    """_block_sizes auto-tiling (round-3: fixed 128x128 tiles ran the
    attention core at 8 TF/s on v5e; (512,1024) reaches ~23 TF/s)."""

    def test_auto_block_picks_largest_aligned_divisor(self):
        from kubeflow_tpu.ops.flash_attention import _auto_block

        assert _auto_block(1024, 512) == 512
        assert _auto_block(1024, 1024) == 1024
        assert _auto_block(768, 512) == 384   # 512 does not divide 768
        assert _auto_block(1280, 512) == 256  # largest 128-aligned divisor
        assert _auto_block(64, 512) == 64     # shorter than a lane tile
        assert _auto_block(128, 512) == 128
        assert _auto_block(192, 512) == 192   # no 128-aligned divisor: 8-aligned
        assert _auto_block(960, 512) == 480   # largest 8-aligned divisor <= cap
        assert _auto_block(1021, 512) == 1021  # prime: ONE whole-length block
        # Fallback divisors must be 8-aligned (Mosaic sublane tiling): 1250's
        # divisors (250, 125, ...) are all rejected -> whole length, which the
        # TPU path then refuses with a clear error (ADVICE r3).
        assert _auto_block(1250, 512) == 1250
        assert _auto_block(1255, 512) == 1255  # 251 not 8-aligned
        assert _auto_block(1216, 512) == 304   # 8-aligned non-128 divisor kept
        # lengths either tile 8-aligned >= 64 or run as one whole block
        from kubeflow_tpu.ops.flash_attention import _auto_block as ab
        for length in (1021, 1031, 2047, 1250, 254):
            b = ab(length, 512)
            assert (b >= 64 and b % 8 == 0) or b == length, (length, b)
            assert length % b == 0

    def test_non_tileable_length_rejected_on_tpu_path(self):
        import pytest
        from kubeflow_tpu.ops.flash_attention import flash_attention

        q = jnp.zeros((1, 1021, 2, 64), jnp.float32)
        # interpret=False takes the TPU path; the 8-alignment check fires
        # before any pallas_call, so this is testable on CPU.
        with pytest.raises(ValueError, match="8-aligned"):
            flash_attention(q, q, q, interpret=False)
        # interpret mode still runs whole-length blocks of any size
        out = flash_attention(q, q, q, interpret=True)
        assert out.shape == q.shape

    def test_auto_block_always_divides(self):
        from kubeflow_tpu.ops.flash_attention import _auto_block

        for length in (128, 192, 256, 384, 512, 640, 768, 960, 1024, 1536,
                       2048, 4096, 8192):
            for cap in (128, 256, 512, 1024):
                b = _auto_block(length, cap)
                assert length % b == 0, (length, cap, b)
                assert b <= max(cap, 128) or b == length

    def test_auto_tiling_handles_odd_lengths(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 192, 192, 2, 32)
        got = flash_attention(q, k, v, causal=True)  # auto: single 192 block
        want = _offset_reference(q, k, v, 0, 0)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_auto_tiles_match_fixed_tiles_numerically(self):
        """Defaults (auto) must equal explicit 128-tiles bit-for-bit in
        interpret mode — tiling is a schedule, not a math change."""
        q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 256, 256, 2, 32)
        auto = flash_attention(q, k, v, causal=True)
        fixed = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        np.testing.assert_allclose(auto, fixed, atol=1e-6, rtol=1e-6)

    def test_explicit_blocks_still_validated(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 192, 192, 2, 32)
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, k, v, block_q=128, block_k=128)


class TestFusedBottleneck:
    """Parity of the fused bottleneck kernel (ops/fused_bottleneck.py)
    against the XLA composite of the same math (on the chip the kernels
    compile in tests/test_chip_compile.py and run in chip_smoke.py)."""

    def test_parity_vs_xla_composite(self):
        import numpy as np

        from kubeflow_tpu.ops.fused_bottleneck import (
            fused_bottleneck, reference_bottleneck,
        )

        rng = np.random.RandomState(0)
        n, hw, cin, cmid = 2, 16, 256, 64
        x = jnp.asarray(rng.randn(n, hw, hw, cin), jnp.bfloat16) * 0.3
        w1 = jnp.asarray(rng.randn(cin, cmid) * 0.05, jnp.float32)
        w2 = jnp.asarray(rng.randn(3, 3, cmid, cmid) * 0.05, jnp.float32)
        w3 = jnp.asarray(rng.randn(cmid, cin) * 0.05, jnp.float32)
        s1, b1 = jnp.ones(cmid), jnp.zeros(cmid) + 0.01
        s2, b2 = jnp.ones(cmid) * 1.1, jnp.zeros(cmid) - 0.01
        s3, b3 = jnp.ones(cin) * 0.9, jnp.zeros(cin)
        got = np.asarray(
            fused_bottleneck(x, w1, s1, b1, w2, s2, b2, w3, s3, b3),
            np.float32)
        want = np.asarray(
            reference_bottleneck(x, w1, s1, b1, w2, s2, b2, w3, s3, b3),
            np.float32)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert err < 2e-2, f"fused bottleneck diverges: rel err {err}"

    def test_relu_and_residual_active(self):
        """The kernel's epilogue really applies residual+relu (zeros with a
        negative bias everywhere except where the residual wins)."""
        import numpy as np

        from kubeflow_tpu.ops.fused_bottleneck import fused_bottleneck

        n, hw, cin, cmid = 1, 8, 256, 64
        x = jnp.ones((n, hw, hw, cin), jnp.bfloat16)
        w1 = jnp.zeros((cin, cmid))
        w2 = jnp.zeros((3, 3, cmid, cmid))
        w3 = jnp.zeros((cmid, cin))
        zero = jnp.zeros(cmid)
        out = fused_bottleneck(
            x, w1, jnp.ones(cmid), zero, w2, jnp.ones(cmid), zero,
            w3, jnp.ones(cin), jnp.full((cin,), -3.0))
        # y = relu(x + (-3)) = 0 ; with bias +3: relu(1+3) = 4
        assert np.allclose(np.asarray(out, np.float32), 0.0)
        out2 = fused_bottleneck(
            x, w1, jnp.ones(cmid), zero, w2, jnp.ones(cmid), zero,
            w3, jnp.ones(cin), jnp.full((cin,), 3.0))
        assert np.allclose(np.asarray(out2, np.float32), 4.0)


class TestFusedBottleneckBlock:
    """The differentiable wrapper (Pallas forward, XLA-composite backward)
    and its wiring into ResNet behind ``fused_blocks=True``."""

    def _inputs(self, n=2, hw=8, cin=32, cmid=8):
        import numpy as np

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(n, hw, hw, cin), jnp.bfloat16) * 0.3
        w1 = jnp.asarray(rng.randn(cin, cmid) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.randn(3, 3, cmid, cmid) * 0.1, jnp.float32)
        w3 = jnp.asarray(rng.randn(cmid, cin) * 0.1, jnp.float32)
        s1, b1 = jnp.ones(cmid) * 1.1, jnp.zeros(cmid) + 0.02
        s2, b2 = jnp.ones(cmid) * 0.9, jnp.zeros(cmid) - 0.02
        s3, b3 = jnp.ones(cin) * 0.8, jnp.zeros(cin) + 0.01
        return (x, w1, s1, b1, w2, s2, b2, w3, s3, b3)

    def test_forward_is_the_kernel(self):
        import numpy as np

        from kubeflow_tpu.ops.fused_bottleneck import (
            fused_bottleneck, fused_bottleneck_block,
        )

        args = self._inputs()
        np.testing.assert_array_equal(
            np.asarray(fused_bottleneck_block(*args), np.float32),
            np.asarray(fused_bottleneck(*args), np.float32))

    def test_gradients_match_f32_composite(self):
        """custom_vjp backward == differentiating the f32 composite directly
        (same math, same cotangents)."""
        import numpy as np

        from kubeflow_tpu.ops.fused_bottleneck import (
            _composite_f32, fused_bottleneck_block,
        )

        args = self._inputs()

        def loss_fused(*a):
            return jnp.sum(fused_bottleneck_block(*a).astype(jnp.float32) ** 2)

        def loss_ref(*a):
            a32 = tuple(t.astype(jnp.float32) for t in a)
            return jnp.sum(_composite_f32(*a32) ** 2)

        g_fused = jax.grad(loss_fused, argnums=tuple(range(10)))(*args)
        g_ref = jax.grad(loss_ref, argnums=tuple(range(10)))(*args)
        for i, (a, b) in enumerate(zip(g_fused, g_ref)):
            # the fused forward computes in bf16, so its cotangent g differs
            # at bf16 resolution before the (f32) backward propagates it
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=0.15, rtol=0.08, err_msg=f"grad argnum {i}")
            assert np.isfinite(np.asarray(a, np.float32)).all()

    def _small_resnet(self, fused: bool):
        from kubeflow_tpu.models.resnet import BottleneckBlock, ResNet

        # stage of two blocks: block1 has a projection shortcut (handled by
        # the fused transition kernel), block2 is the canonical stride-1
        # identity block the original kernel takes over.
        return ResNet(stage_sizes=[2], block_cls=BottleneckBlock,
                      num_classes=10, num_filters=8, fused_blocks=fused)

    def test_resnet_variable_trees_identical(self):
        """fused_blocks must not change the checkpoint layout — the same
        variables dict serves both paths."""
        x = jnp.ones((1, 32, 32, 3), jnp.float32)
        v_plain = self._small_resnet(False).init(jax.random.PRNGKey(0), x)
        v_fused = self._small_resnet(True).init(jax.random.PRNGKey(0), x)
        assert (jax.tree_util.tree_structure(v_plain)
                == jax.tree_util.tree_structure(v_fused))
        assert all(a.shape == b.shape for a, b in zip(
            jax.tree_util.tree_leaves(v_plain),
            jax.tree_util.tree_leaves(v_fused)))

    def test_resnet_eval_parity_fused_vs_unfused(self):
        """Eval mode: folded running stats == use_running_average BatchNorm,
        so the two paths are the same function (up to kernel bf16)."""
        import numpy as np

        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        variables = self._small_resnet(False).init(jax.random.PRNGKey(0), x)
        out_plain = self._small_resnet(False).apply(variables, x, train=False)
        out_fused = self._small_resnet(True).apply(variables, x, train=False)
        np.testing.assert_allclose(
            np.asarray(out_plain, np.float32), np.asarray(out_fused, np.float32),
            atol=0.05, rtol=0.05)

    def test_resnet_fused_train_step_produces_finite_grads(self):
        import numpy as np

        x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
        labels = jnp.asarray([1, 3])
        model = self._small_resnet(True)
        variables = model.init(jax.random.PRNGKey(0), x)

        def loss_fn(params):
            logits, _ = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            onehot = jax.nn.one_hot(labels, 10)
            return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

        loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
        assert np.isfinite(float(loss))
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in leaves)
        # the fused blocks' weights actually receive gradient
        flat = jax.tree_util.tree_leaves_with_path(grads)
        block2 = [np.abs(np.asarray(v, np.float32)).max()
                  for p, v in flat if "stage1_block2" in str(p)]
        assert block2 and max(block2) > 0.0
