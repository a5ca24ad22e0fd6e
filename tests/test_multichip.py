"""8-device multichip fast-path parity (slow tier; run by the multichip CI job).

These are the expensive end-to-end checks behind the multi-chip fast path:
the interleaved schedule and the overlapped/amortized gather modes must be
arithmetic-identical to the GPipe + eager baseline on the full composed
dp x fsdp x tp x pp train step — not just on toy MLP stages — and the
multichip bench must emit its throughput row with every field the scaling
dashboards read.
"""

import jax
import numpy as np
import pytest

from kubeflow_tpu.parallel import MeshConfig, deinterleave_stage_params, make_mesh
from kubeflow_tpu.parallel.composite import (
    GATHER_MODES,
    CompositeConfig,
    batch_sharding,
    init_params,
    make_train_step,
)

pytestmark = pytest.mark.slow

CFG = CompositeConfig(vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=4, seq=16)


def _mesh():
    return make_mesh(MeshConfig(data=1, fsdp=2, model=2, pipe=2))


def _ids(mesh, micro=4, mb=8):
    return jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (micro, mb, CFG.seq), 0, CFG.vocab_size),
        batch_sharding(mesh),
    )


def _canonical_stages(stages, pp, virtual_stages):
    """Stage params in per-layer order [n_layers, ...], mesh-layout-free."""
    nat = (
        deinterleave_stage_params(stages, pp, virtual_stages)
        if virtual_stages > 1
        else stages
    )
    return jax.tree_util.tree_map(
        lambda p: np.asarray(p).reshape((CFG.n_layers,) + p.shape[2:]), nat
    )


def test_interleaved_loss_and_grads_match_gpipe():
    """Loss AND gradients: the post-SGD-step params encode the grads, so
    comparing params after one step at matched init checks the whole
    backward schedule, not just the forward."""
    mesh = _mesh()
    ids = _ids(mesh)
    out = {}
    for v in (1, 2):
        params = init_params(jax.random.PRNGKey(0), CFG, mesh, virtual_stages=v)
        step = make_train_step(CFG, mesh, virtual_stages=v)
        params, loss = step(params, ids)
        out[v] = (float(loss), params)
    l1, p1 = out[1]
    l2, p2 = out[2]
    assert abs(l2 - l1) <= 1e-5 * max(1.0, abs(l1))
    np.testing.assert_allclose(
        np.asarray(p2["embed"]), np.asarray(p1["embed"]), rtol=1e-5, atol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        _canonical_stages(p2["stages"], 2, 2),
        _canonical_stages(p1["stages"], 2, 1),
    )


@pytest.mark.parametrize("virtual_stages", [1, 2])
def test_gather_modes_match_eager(virtual_stages):
    """overlap (double-buffered prefetch) and amortized (once-per-step
    stage_prepare gather) reorder collectives but must not change the math."""
    mesh = _mesh()
    ids = _ids(mesh)
    losses = {}
    for mode in GATHER_MODES:
        params = init_params(
            jax.random.PRNGKey(0), CFG, mesh, virtual_stages=virtual_stages
        )
        step = make_train_step(
            CFG, mesh, virtual_stages=virtual_stages, gather_mode=mode
        )
        ls = []
        for _ in range(3):
            params, loss = step(params, ids)
            ls.append(float(loss))
        losses[mode] = ls
    assert all(np.isfinite(l) for l in losses["eager"])
    np.testing.assert_allclose(losses["overlap"], losses["eager"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(losses["amortized"], losses["eager"], rtol=1e-5, atol=1e-5)


def test_bench_multichip_emits_throughput_row(monkeypatch):
    """The bench row the dashboards consume: tokens/sec/chip, weak-scaling
    efficiency, bubble fraction (strictly below GPipe's), per-axis comm
    bytes, and a step-time breakdown."""
    for k, v in {
        "BENCH_MC_DMODEL": "32",
        "BENCH_MC_FF": "64",
        "BENCH_MC_LAYERS": "8",
        "BENCH_MC_SEQ": "32",
        "BENCH_MC_VOCAB": "128",
        "BENCH_MC_MICRO": "8",
        "BENCH_MC_MB": "8",
        "BENCH_MC_STEPS": "2",
        "BENCH_REPEATS": "1",
    }.items():
        monkeypatch.setenv(k, v)
    from bench import _run_multichip

    row = _run_multichip("cpu", "v5e")
    assert "error" not in row, row
    assert row["metric"] == "multichip_composite_tokens_per_sec_per_chip_8dev"
    assert row["value"] > 0
    assert row["n_devices"] == 8
    assert row["virtual_stages"] == 2 and row["gather_mode"] == "overlap"
    assert row["bubble_fraction"] < row["bubble_fraction_gpipe"]
    assert set(row["comm_bytes_per_step"]) == {"pipe", "fsdp", "model", "data", "total"}
    assert all(v >= 0 for v in row["comm_bytes_per_step"].values())
    assert row["scaling_efficiency"] is not None and row["scaling_efficiency"] > 0
    assert np.isfinite(row["loss"])
    assert "device_compute_s_per_step" in row["step_breakdown"]
