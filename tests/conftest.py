"""Test configuration: force JAX onto 8 virtual CPU devices.

Multi-chip hardware is unavailable in CI; all sharding/parallelism tests run
against a virtual 8-device CPU mesh (the reference's e2e harness likewise
tests distributed control flow against CPU-only CI clusters — SURVEY.md §4).
Must run before the first ``import jax`` anywhere in the test session.
"""

import json
import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from kubeflow_tpu.apiserver.store import Store  # noqa: E402
from kubeflow_tpu.apiserver.client import Client  # noqa: E402
from kubeflow_tpu.runtime.manager import Manager  # noqa: E402
from kubeflow_tpu.runtime.metrics import METRICS  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests (multi-device parity, long decode loops); "
        "tier-1 excludes them with -m 'not slow', the owning CI job runs "
        "them (multichip-e2e, disagg-serving-e2e)",
    )


#: A bench history in the committed files' own shape (BENCH_rNN.json:
#: per-bench rows in the stdout tail, the summary line parsed), eight rounds
#: shaped like the ones the gate was calibrated on: a flat ResNet plateau with
#: one dip inside the band (r04), a serving slide past it (r05), a recovery
#: that also brings the first SLI rows (r06), a training jump over the
#: absolute floors (r07), and a round whose new rows enter as baselines while
#: the accept rate clears its floor (r08).
_BENCH_ROUNDS = {
    1: dict(resnet=30.5, img_s=2510.0),
    2: dict(resnet=30.6, img_s=2520.0),
    3: dict(resnet=30.4, img_s=2500.0),
    4: dict(resnet=28.3, img_s=2320.0, gpt=42.4, tok_s=37600.0,
            decode=2605.0, bert=96.1, hpo=416.0),
    5: dict(resnet=30.6, img_s=2505.0, gpt=45.7, tok_s=37550.0,
            decode=2309.0, bert=105.1, hpo=370.0),
    6: dict(resnet=30.6, img_s=2508.0, gpt=45.7, tok_s=37590.0,
            decode=2932.0, bert=95.4, hpo=403.0,
            serving_ttft_p99_s=0.412, spec_accept_rate=0.144),
    7: dict(resnet=41.2, img_s=3373.0, gpt=52.4, tok_s=43070.0,
            decode=2947.0, bert=95.1, hpo=405.0,
            serving_ttft_p99_s=0.407, spec_accept_rate=0.144),
    8: dict(resnet=41.0, img_s=3360.0, gpt=52.6, tok_s=43190.0,
            decode=2954.0, bert=94.8, hpo=399.0,
            serving_ttft_p99_s=0.395, spec_accept_rate=0.574,
            decode_tok_s_heterogeneous=3012.0, kv_handoff_p99_s=0.018),
}


def _bench_round(resnet, img_s, gpt=None, tok_s=None, decode=None, bert=None,
                 hpo=None, **sli):
    rows = [{"metric": "resnet50_train_mfu_v5e_1chip", "value": resnet}]
    if gpt is not None:
        rows += [
            {"metric": "gpt2_medium_train_mfu_v5e_1chip", "value": gpt},
            {"metric": "serving_gpt_kv_decode_tokens_per_sec_b8",
             "value": decode},
            {"metric": "hpo_mnist_trials_per_hour", "value": hpo}]
    parsed = {**rows[0], "images_per_sec_per_chip": img_s,
              "gpt2_medium_mfu_pct": gpt, "gpt2_medium_tokens_per_sec": tok_s,
              "serving_decode_tokens_per_sec_b8": decode,
              "serving_bert_p50_ms_b8": bert, "hpo_trials_per_hour": hpo,
              **sli, "errors": None}
    return {"tail": "\n".join(json.dumps(r) for r in rows + [parsed]),
            "parsed": parsed}


@pytest.fixture(scope="session")
def bench_history(tmp_path_factory):
    """Directory holding a synthetic BENCH_r01..r08 + CONTROLPLANE_r01
    history for tools/bench_gate.py: the gate's behaviour is asserted on
    numbers the tests own, not on whichever records the repo root keeps."""
    root = tmp_path_factory.mktemp("bench_history")
    for n, metrics in _BENCH_ROUNDS.items():
        (root / f"BENCH_r{n:02d}.json").write_text(
            json.dumps(_bench_round(**metrics)))
    (root / "CONTROLPLANE_r01.json").write_text(json.dumps(
        {"tail": json.dumps({"metric": "scheduler_cycles_per_sec",
                             "value": 29000.0}), "parsed": None}))
    return root


@pytest.fixture()
def store():
    return Store()


@pytest.fixture()
def client(store):
    return Client(store)


@pytest.fixture()
def manager():
    mgr = Manager()
    yield mgr
    mgr.stop()


class _Region:
    """Stands in for ``profiling.annotate``'s region: keeps the stats."""

    def __init__(self, log, name, stats):
        self.log, self.name, self.stats = log, name, stats

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.log.append((self.name, self.stats))

    def set_metadata(self, **stats):
        self.stats.update(stats)


@pytest.fixture()
def engine_regions(monkeypatch):
    """The ``tpu.profiling.annotate`` regions the code under test closes,
    with no profiler open: a list of ``(name, stats)`` in closing order."""
    from kubeflow_tpu.tpu import profiling

    log = []
    monkeypatch.setattr(profiling, "annotate",
                        lambda name, **stats: _Region(log, name, stats))
    return log


@pytest.fixture(autouse=True)
def _reset_metrics():
    METRICS.reset()
    yield
