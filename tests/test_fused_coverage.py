"""Full-coverage fused bottlenecks (ISSUE 17): parity for every newly
fusable shape — the 28/14/7 identity stages the padded tiling admits, the
stride-2/stride-1 transition kernel, the folded XLA fallback — plus the
checkpoint contract (bit-exact round trip unfused <-> fused) and the
fallback-visibility counter."""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.fused_bottleneck import (
    _composite_f32,
    _transition_composite_f32,
    folded_bottleneck,
    fused_bottleneck,
    fused_bottleneck_block,
    fused_transition,
    fused_transition_block,
    reference_bottleneck,
    reference_transition,
)


def _identity_inputs(hw, cin=64, cmid=16, n=2, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, hw, hw, cin), jnp.bfloat16) * 0.3
    w1 = jnp.asarray(rng.randn(cin, cmid) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(3, 3, cmid, cmid) * 0.1, jnp.float32)
    w3 = jnp.asarray(rng.randn(cmid, cin) * 0.1, jnp.float32)
    s1, b1 = jnp.ones(cmid) * 1.1, jnp.zeros(cmid) + 0.02
    s2, b2 = jnp.ones(cmid) * 0.9, jnp.zeros(cmid) - 0.02
    s3, b3 = jnp.ones(cin) * 0.8, jnp.zeros(cin) + 0.01
    return (x, w1, s1, b1, w2, s2, b2, w3, s3, b3)


def _transition_inputs(hw, cin=32, cmid=16, cout=64, n=2, seed=3):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, hw, hw, cin), jnp.bfloat16) * 0.3
    w1 = jnp.asarray(rng.randn(cin, cmid) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(3, 3, cmid, cmid) * 0.1, jnp.float32)
    w3 = jnp.asarray(rng.randn(cmid, cout) * 0.1, jnp.float32)
    wp = jnp.asarray(rng.randn(cin, cout) * 0.1, jnp.float32)
    s1, b1 = jnp.ones(cmid) * 1.1, jnp.zeros(cmid) + 0.02
    s2, b2 = jnp.ones(cmid) * 0.9, jnp.zeros(cmid) - 0.02
    s3, b3 = jnp.ones(cout) * 0.8, jnp.zeros(cout) + 0.01
    sp, bp = jnp.ones(cout) * 1.05, jnp.zeros(cout) - 0.01
    return (x, w1, s1, b1, w2, s2, b2, w3, s3, b3, wp, sp, bp)


class TestIdentityKernelNewShapes:
    """The padded tiling admits every spatial size ResNet-50 produces at
    224x224 — 56 was always tileable; 28/14/7 are the new ones."""

    @pytest.mark.parametrize("hw", [28, 14, 7])
    def test_forward_parity(self, hw):
        args = _identity_inputs(hw)
        got = np.asarray(fused_bottleneck(*args), np.float32)
        want = np.asarray(reference_bottleneck(*args), np.float32)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert err < 2e-2, f"hw={hw}: rel err {err}"

    @pytest.mark.parametrize("hw", [28, 14, 7])
    def test_grad_parity_1e5(self, hw):
        # linear loss: the cotangent entering the block is a constant, so
        # the custom_vjp backward and differentiating the f32 composite
        # directly must agree to float32 resolution (<= 1e-5), regardless
        # of the bf16 forward. The constant is bf16-representable so the
        # fused path's bf16 output cast loses nothing of it.
        args = _identity_inputs(hw)
        rng = np.random.RandomState(7)
        c = jnp.asarray(rng.randn(*args[0].shape),
                        jnp.bfloat16).astype(jnp.float32)

        def loss_fused(*a):
            return jnp.sum(fused_bottleneck_block(*a).astype(jnp.float32) * c)

        def loss_ref(*a):
            return jnp.sum(_composite_f32(
                *(t.astype(jnp.float32) for t in a)) * c)

        g_fused = jax.grad(loss_fused, argnums=tuple(range(10)))(*args)
        g_ref = jax.grad(loss_ref, argnums=tuple(range(10)))(*args)
        for i, (a, b) in enumerate(zip(g_fused, g_ref)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5, rtol=1e-5, err_msg=f"hw={hw} grad argnum {i}")


class TestTransitionKernel:
    """The stride-2 + 1x1-projection kernel covering ResNet's four former
    unfused downsampling sinks (and stage1's stride-1 channel head)."""

    @pytest.mark.parametrize("hw,stride", [(14, 2), (28, 2), (8, 2), (14, 1)])
    def test_forward_parity(self, hw, stride):
        args = _transition_inputs(hw)
        got = np.asarray(fused_transition(*args, stride=stride), np.float32)
        want = np.asarray(
            reference_transition(*args, stride=stride), np.float32)
        assert got.shape == want.shape
        assert got.shape[1] == (hw if stride == 1 else hw // 2)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert err < 2e-2, f"hw={hw} stride={stride}: rel err {err}"

    @pytest.mark.parametrize("stride", [1, 2])
    def test_grad_parity_1e5(self, stride):
        args = _transition_inputs(8)
        n, hw = args[0].shape[0], args[0].shape[1]
        ho = hw if stride == 1 else hw // 2
        cout = args[7].shape[1]
        rng = np.random.RandomState(11)
        c = jnp.asarray(rng.randn(n, ho, ho, cout),
                        jnp.bfloat16).astype(jnp.float32)

        def loss_fused(*a):
            out = fused_transition_block(*a, stride=stride)
            return jnp.sum(out.astype(jnp.float32) * c)

        def loss_ref(*a):
            return jnp.sum(_transition_composite_f32(
                stride, *(t.astype(jnp.float32) for t in a)) * c)

        g_fused = jax.grad(loss_fused, argnums=tuple(range(13)))(*args)
        g_ref = jax.grad(loss_ref, argnums=tuple(range(13)))(*args)
        for i, (a, b) in enumerate(zip(g_fused, g_ref)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5, rtol=1e-5,
                err_msg=f"stride={stride} grad argnum {i}")

    def test_odd_hw_stride2_rejected(self):
        args = _transition_inputs(7)
        with pytest.raises(AssertionError):
            fused_transition(*args, stride=2)


class TestFoldedFallback:
    """The epilogue-fused XLA fallback for shapes neither kernel takes
    (e.g. non-square inputs): same math as the reference composite."""

    def test_matches_reference_with_projection(self):
        args = _transition_inputs(10)
        got = np.asarray(
            folded_bottleneck(*args[:10], strides=(2, 2), proj=args[10:]),
            np.float32)
        want = np.asarray(
            reference_transition(*args, stride=2), np.float32)
        np.testing.assert_allclose(got, want, atol=0.05, rtol=0.05)

    def test_matches_reference_identity(self):
        args = _identity_inputs(12)
        got = np.asarray(folded_bottleneck(*args), np.float32)
        want = np.asarray(reference_bottleneck(*args), np.float32)
        np.testing.assert_allclose(got, want, atol=0.05, rtol=0.05)


class TestModelCoverage:
    """Model-level contract: every bottleneck routes through a fused path,
    checkpoints are interchangeable bit-for-bit between the two modes."""

    def _resnet(self, fused):
        from kubeflow_tpu.models.resnet import BottleneckBlock, ResNet

        return ResNet(stage_sizes=[2, 2], block_cls=BottleneckBlock,
                      num_classes=10, num_filters=8, fused_blocks=fused)

    def test_variable_trees_identical(self):
        x = jnp.ones((1, 32, 32, 3), jnp.float32)
        v_plain = self._resnet(False).init(jax.random.PRNGKey(0), x)
        v_fused = self._resnet(True).init(jax.random.PRNGKey(0), x)
        assert (jax.tree_util.tree_structure(v_plain)
                == jax.tree_util.tree_structure(v_fused))

    def test_checkpoint_round_trip_bit_exact(self):
        # serialize under one mode, restore under the other, both ways —
        # the param-holder contract means the bytes are interchangeable
        from flax import serialization

        x = jnp.ones((1, 32, 32, 3), jnp.float32)
        v_plain = self._resnet(False).init(jax.random.PRNGKey(0), x)
        v_fused = self._resnet(True).init(jax.random.PRNGKey(1), x)
        blob = serialization.to_bytes(v_plain)
        restored = serialization.from_bytes(v_fused, blob)
        for a, b in zip(jax.tree_util.tree_leaves(v_plain),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and back: fused-written bytes restore into the plain tree
        blob2 = serialization.to_bytes(restored)
        back = serialization.from_bytes(v_plain, blob2)
        for a, b in zip(jax.tree_util.tree_leaves(restored),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_eval_parity_across_modes(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        variables = self._resnet(False).init(jax.random.PRNGKey(0), x)
        out_plain = self._resnet(False).apply(variables, x, train=False)
        out_fused = self._resnet(True).apply(variables, x, train=False)
        np.testing.assert_allclose(
            np.asarray(out_plain, np.float32),
            np.asarray(out_fused, np.float32), atol=0.05, rtol=0.05)

    def test_full_coverage_at_224(self):
        # acceptance: >= 14/16 bottlenecks fused at 224x224, verified
        # through the model's own predicates via attribute_resnet
        from kubeflow_tpu.training.attribution import (
            attribute_resnet, attribution_report)

        costs = attribute_resnet(batch=1, image=224, generation="v5e")
        report = attribution_report(costs, step_seconds=0.1,
                                    generation="v5e")
        cov = report.coverage()
        assert cov["total"] == 16
        assert cov["fused"] >= 14
        assert cov["fused"] == 16  # the transition kernel closes the gap


class TestFallbackVisibility:
    """Silent fallbacks become one-time warnings + a counter (satellite 1)."""

    def test_record_fallback_counts_and_warns_once(self):
        from kubeflow_tpu.ops.fallback import (
            record_fallback, reset_fallback_warnings)
        from kubeflow_tpu.runtime.metrics import METRICS

        reset_fallback_warnings()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            record_fallback("test_kernel", "because reasons")
            record_fallback("test_kernel", "because reasons")
        assert len(caught) == 1  # deduplicated per (kernel, reason)
        assert "test_kernel" in str(caught[0].message)
        text = METRICS.render()
        assert 'ops_fused_fallback_total{kernel="test_kernel"}' in text

    def test_auto_attention_records_tpu_eligibility_cliff(self, monkeypatch):
        import importlib

        from kubeflow_tpu.ops import auto_attention
        from kubeflow_tpu.ops import fallback as fb

        # the ops package re-exports a `flash_attention` FUNCTION, so the
        # module itself must come from importlib
        fa = importlib.import_module("kubeflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        fb.reset_fallback_warnings()
        q = jnp.ones((1, 100, 2, 8), jnp.float32)  # 100: not 128-tileable
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            out = auto_attention(q, q, q, causal=True)
        assert out.shape == q.shape
        msgs = [str(w.message) for w in caught
                if issubclass(w.category, RuntimeWarning)]
        assert any("flash_attention" in m for m in msgs)
        from kubeflow_tpu.runtime.metrics import METRICS

        assert 'kernel="flash_attention"' in METRICS.render()

    def test_model_folded_path_counts_a_fallback(self):
        # a fused-mode model hitting a shape neither kernel takes must
        # route through folded_bottleneck AND count the fallback
        from kubeflow_tpu.models.resnet import BottleneckBlock
        from kubeflow_tpu.ops import fallback as fb
        from kubeflow_tpu.runtime.metrics import METRICS

        import functools

        import flax.linen as nn

        fb.reset_fallback_warnings()
        conv = functools.partial(nn.Conv, use_bias=False,
                                 dtype=jnp.bfloat16, param_dtype=jnp.float32)
        norm = functools.partial(nn.BatchNorm, momentum=0.9, epsilon=1e-5,
                                 dtype=jnp.bfloat16, param_dtype=jnp.float32)
        block = BottleneckBlock(filters=8, strides=(1, 1), conv=conv,
                                norm=norm, act=nn.relu, fused=True)
        # non-square input: _fusable and _fusable_transition both refuse
        x = jnp.ones((1, 12, 16, 32), jnp.bfloat16)
        variables = block.init(jax.random.PRNGKey(0), x)
        out = block.apply(variables, x)
        assert out.shape == (1, 12, 16, 32)
        assert 'kernel="fused_bottleneck"' in METRICS.render()
