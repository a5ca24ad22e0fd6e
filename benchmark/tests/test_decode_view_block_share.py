"""``decode_view_block_share.serve`` on hand-made spans: toy sizes, no device."""

import json

import pytest

from benchmark import harness
from benchmark.metrics import _spans

NAME = "decode_view_block_share.serve"


def dispatch(start, dur=10.0, **stats):
    return _spans.Span("serving.engine.dispatch", float(start), float(dur), "engine", stats)


def obs(spans, kind="serve"):
    return {"kind": kind, "trace_window": (0.0, 1000.0), "serving_spans": spans}


def test_share_is_columns_read_over_columns_held():
    spans = [dispatch(10, rows=128, live=3, view_blocks=16, max_blocks=64),
             dispatch(200, rows=128, live=5, view_blocks=48, max_blocks=64),
             dispatch(400, rows=128, live=5, view_blocks=64, max_blocks=64),
             dispatch(995, rows=128, live=5, view_blocks=64, max_blocks=64),   # cut by the window
             _spans.Span("serving.engine.deliver", 500.0, 5.0, "engine",
                         {"kind": "chunk", "rows": 128, "tokens": 70})]
    read = harness.load_reader(NAME)
    assert read(obs(spans)) == pytest.approx(100.0 * (16 + 48 + 64) / (3 * 64))
    assert read(obs(spans, kind="train")) is None


def test_a_program_without_the_stats_reads_nothing():
    """The parent's dispatch spans carry ``rows`` and ``live`` alone, and a
    contiguous engine's carry no more: nothing to read, no error."""
    read = harness.load_reader(NAME)
    assert read(obs([dispatch(10, rows=128, live=3)])) is None
    assert read(obs([])) is None
    assert read({"kind": "serve"}) is None


def test_declared_with_the_serve_cell():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
                     "layer": "model step, serving", "moves": "serve_ms_per_token_p50",
                     "workloads": ["gpt2-medium.serve.chat"]}
    assert spec["per_layer"][-1] == entry          # appended, nothing before it moved
