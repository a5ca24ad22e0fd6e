"""CPU rehearsal of chip_smoke.py's control flow, and the two helpers in
kubeflow_tpu/tpu/env.py it starts with. The chip itself is asked by
``python chip_smoke.py`` through the chip tool; here the phases run at toy
sizes with the device requirement satisfied by the test."""

from __future__ import annotations

import json
import os

import jax
import pytest

import chip_smoke
from kubeflow_tpu.tpu import env


def _toy_sizes():
    import dataclasses

    import optax

    from kubeflow_tpu.models.bert import BertConfig
    from kubeflow_tpu.models.gpt import GptConfig
    from kubeflow_tpu.models.resnet import BottleneckBlock, ResNet
    from kubeflow_tpu.parallel.composite import CompositeConfig
    from kubeflow_tpu.training import ClassifierTask

    def resnet_step():
        model = ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                       num_classes=1000, num_filters=8, stem="s2d",
                       fused_blocks=True)
        task = ClassifierTask(model=model, optimizer=optax.sgd(0.1))
        return task, task.make_train_step()

    return {
        # max_seq 64 leaves two prefill buckets: two oracle shapes, not four
        "serve_gpt": dataclasses.replace(GptConfig.tiny(), max_seq=64,
                                         n_layers=1),
        "serve_new_tokens": 4,
        "serve_bert": BertConfig.tiny(),
        "serve_bert_shape": (8, 16),
        "gpt_train": GptConfig(vocab_size=512, d_model=64, n_layers=1,
                               n_heads=4, d_ff=128, max_seq=128,
                               scan_blocks=True, remat=True),
        "gpt_batch": 2,
        "gpt_min_pallas_calls": 0,   # interpret mode leaves no custom call
        "resnet_step": resnet_step,
        "resnet_batch": (2, 32),
        "resnet_pallas_calls": 0,
        "hpo_trials": 1,
        "composite": CompositeConfig(vocab_size=64, d_model=32, n_heads=4,
                                     d_ff=64, n_layers=2, seq=16),
        "composite_batch": (2, 2),
        "composite_lr": 0.1,
    }


@pytest.fixture()
def rehearsal(monkeypatch, tmp_path):
    """chip_smoke with toy sizes, a CPU device standing in for the chip, and
    the compile cache placed (by the environment, so nothing is set in code)
    where the rest of the session will not read it."""
    monkeypatch.setattr(chip_smoke, "sizes", _toy_sizes)
    monkeypatch.setattr(env, "require_tpu", lambda: jax.devices()[0])
    monkeypatch.setenv(env.ENV_COMPILE_CACHE_DIR, str(tmp_path))


@pytest.mark.parametrize("argv,phases", [
    ([], ["device", "serve", "train_gpt", "train_resnet", "hpo"]),
    (["--chips", "4"], ["device", "multichip"]),
])
def test_rehearsal_runs_every_phase_and_ends_on_the_contract_line(
        rehearsal, capsys, argv, phases):
    assert chip_smoke.main(argv) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert [l["phase"] for l in lines if "phase" in l] == phases
    for line in lines[1:-1]:
        if "phase" in line:
            assert {"wall_s", "compile_s", "cache_load_s", "run_s",
                    "cache_hits", "cache_misses",
                    "peak_bytes_in_use"} <= set(line)
    last = lines[-1]
    assert list(last) == ["ok", "device"] and last["ok"] is True
    assert list(last["device"]) == ["platform", "kind", "count"]
    assert last["device"]["count"] == len(jax.devices())
    if not argv:
        assert "Backend" in lines[-2]["store_backend"]
    else:
        multichip = lines[-2]
        assert len(multichip["sharded"]["state_bytes_per_device"]) == 4
        assert min(multichip["sharded"]["state_bytes_per_device"]) > 0
        assert multichip["one_device"]["state_bytes_per_device"][1:] == [0] * 3
        assert any("fleet_replica_devices" in l for l in lines)


def test_a_failing_phase_ends_the_run_without_a_result(
        rehearsal, monkeypatch, capsys):
    def broken(cfg, seed):
        raise AssertionError("forced failure")

    monkeypatch.setattr(chip_smoke, "phase_serve", broken)
    with pytest.raises(AssertionError, match="forced failure"):
        chip_smoke.main([])
    out = capsys.readouterr().out
    assert '"ok"' not in out and '"train_gpt"' not in out


def test_no_tpu_stops_before_any_model_is_built(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "sizes", lambda: pytest.fail("built"))
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.main([])
    assert capsys.readouterr().out == ""


def test_compile_cache_dir_from_the_environment_is_not_set_in_code(
        monkeypatch, tmp_path):
    monkeypatch.setenv(env.ENV_COMPILE_CACHE_DIR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert env.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(env.ENV_COMPILE_CACHE_DIR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = env.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
