"""``remat_forward_share.train4`` (ISSUE 31): declared in ``BENCHMARK.json``
for the four-chip training cell, and its reader gives what
``remat_forward_share.train``'s gives on the same observation: the share of
the chips' busy time under JAX's ``rematted_computation`` marker, None where
nothing ran under it (the parent's program, which recomputes nothing).

Hand-made events, the way ``tests/test_engine_tracing.py`` holds the
one-chip reader: the arithmetic is checked, never a time."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness, trace_reduce as tr  # noqa: E402

NAME = "remat_forward_share.train4"
CELL = "gpt2-large.train4.fsdp2-tp2"
STEP = "jit(step)/jit(main)/"
BACKWARD = STEP + "transpose(jvp(shmap_body))/while/body/"


def op(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%c"


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def observation(remat: bool, chips: int = 4):
    """One step on every chip: a forward matmul, the backward's recomputed
    softmax (150 ns) and GELU (50 ns) where the block is under
    ``jax.checkpoint``, a gradient matmul, a collective and the optimizer."""
    under = "checkpoint/rematted_computation/" if remat else ""
    scopes = {
        op("qkv.1"): STEP + "jvp(shmap_body)/while/body/attn/dot_general",
        op("softmax.2"): BACKWARD + under + "attn/exp",
        op("gelu.2"): BACKWARD + under + "mlp/tanh",
        op("dw1.1"): BACKWARD + "mlp/dot_general",
        op("sgd.1"): STEP + "optimizer/sub",
    }
    per_chip = [ev("%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b", 0, 800),
                ev(op("qkv.1"), 0, 300), ev(op("softmax.2"), 300, 150),
                ev(op("gelu.2"), 450, 50), ev(op("dw1.1"), 500, 200),
                ev("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p)", 700, 100),
                ev(op("sgd.1"), 800, 100)]
    ops = {f"/device:TPU:{i}": per_chip for i in range(chips)}
    return {"kind": "train", "chips": chips, "trace": tr.Trace(ops, {}, {}, []),
            "trace_window": (0.0, 1000.0), "busy_by_device": {d: 900e-9 for d in ops},
            "op_scopes": scopes}


def no_trace():
    obs = observation(remat=True)
    del obs["trace"]
    return obs


def serving():
    return dict(observation(remat=True), kind="serve")


def unnamed():
    """An executable cached before the scopes existed: no path on any operation."""
    return dict(observation(remat=True), op_scopes={})


CASES = {
    "four chips": (lambda: observation(remat=True), 100.0 * 4 * 200 / (4 * 900)),
    "one chip": (lambda: observation(remat=True, chips=1), 100.0 * 200 / 900),
    "the parent recomputes nothing": (lambda: observation(remat=False), None),
    "untraced run": (no_trace, None),
    "serve cell": (serving, None),
    "no scope paths": (unnamed, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reader_reads_what_the_one_chip_reader_reads(case):
    make, want = CASES[case]
    mine = harness.load_reader(NAME)(make())
    held = harness.load_reader("remat_forward_share.train")(make())
    assert mine == held
    assert mine is None if want is None else mine == pytest.approx(want)


def test_declared_for_the_four_chip_cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    assert entries[0] == {
        "name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "training loop", "moves": "train_tokens_per_s", "workloads": [CELL]}
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    moved = next(m for m in spec["end_to_end"] if m["name"] == entries[0]["moves"])
    assert cell["chips"] == 4 and CELL in moved["workloads"]
    assert (ROOT / "benchmark" / "metrics" / f"{NAME}.py").exists()
    # its layer is one the benchmark already names, letter for letter
    assert entries[0]["layer"] in {m["layer"] for m in spec["per_layer"] if m["name"] != NAME}


def test_the_cell_is_handed_the_metric():
    cell = harness.load_cell(CELL, seed=1, seconds=1.0, trace=True)
    assert NAME in [m["name"] for m in harness.metrics_for(cell, "per_layer")]
    other = harness.load_cell("gpt2-medium.train.b8x1024", seed=1, seconds=1.0, trace=True)
    assert NAME not in [m["name"] for m in harness.metrics_for(other, "per_layer")]
