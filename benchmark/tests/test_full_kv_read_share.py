"""``full_kv_read_share.serve`` on hand-made dispatch regions: toy sizes, no device."""

import pytest

from benchmark import harness
from benchmark.metrics import _spans
from benchmark.runners import mimo_serve

from . import toy_mimo

NAME = "full_kv_read_share.serve"
SIZES = mimo_serve.sizes_of(toy_mimo.CONFIG)


def dispatch(start, **stats):
    stats = {"rows": 8 * 4, "live": 3, "max_blocks": 32, "full_blocks": 30,
             "window_blocks": 12, "window_blocks_unreleased": 40, **stats}
    return _spans.Span("serving.engine.dispatch", float(start), 10.0, "engine", stats)


def obs(spans, **over):
    return {"kind": "serve", "sizes": SIZES, "decode_chunk": 4, "kv_block_t": 4,
            "trace_window": (0.0, 1000.0), "serving_spans": spans, **over}


def test_pages_fetched_over_every_slot_s_whole_row():
    """8 slots (32 rows of 4 steps), 32 columns a row: the kernel's
    dispatches count the pages their live rows hold."""
    spans = [dispatch(10, view_blocks=8, full_blocks_read=9),
             dispatch(200, view_blocks=32, full_blocks_read=41),
             dispatch(995, view_blocks=32, full_blocks_read=50)]     # cut by the window
    read = harness.load_reader(NAME)
    assert read(obs(spans)) == pytest.approx(100.0 * (9 + 41) / (2 * 8 * 32))


def test_a_program_that_gathers_the_view_counts_every_slot_s_view():
    """The parent's dispatches carry no ``full_blocks_read``: its gather
    reads ``view_blocks`` columns of every slot, live or not."""
    spans = [dispatch(10, view_blocks=8), dispatch(200, view_blocks=32)]
    read = harness.load_reader(NAME)
    assert read(obs(spans)) == pytest.approx(100.0 * (8 * 8 + 8 * 32) / (2 * 8 * 32))
    mixed = spans + [dispatch(400, view_blocks=32, full_blocks_read=16)]
    assert read(obs(mixed)) == pytest.approx(100.0 * (64 + 256 + 16) / (3 * 256))


def test_nothing_to_read_returns_none():
    read = harness.load_reader(NAME)
    assert read(obs([])) is None
    assert read({"kind": "train"}) is None
    assert read({"kind": "serve", "sizes": {"n_layer": 2}, "decode_chunk": 16}) is None
    # a one-kind engine's dispatches (no window kind) are not this metric's
    plain = _spans.Span("serving.engine.dispatch", 10.0, 10.0, "engine",
                        {"rows": 128, "live": 3, "view_blocks": 16, "max_blocks": 64})
    assert read(obs([plain])) is None
    bare = {k: v for k, v in obs([dispatch(10, view_blocks=8)]).items()
            if k not in ("trace_window", "serving_spans")}
    assert read(bare) is None
