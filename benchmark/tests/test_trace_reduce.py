"""The trace -> metrics reduction on hand-made events and on the small
recorded trace beside this file (``small_train.xplane.pb``: three steps of
a toy GptLM train step on a TPU v5e, recorded by PR 24)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

HERE = Path(__file__).resolve().parent
HLO = ('%attention.4 = (bf16[2,2,256,128]{3,2,1,0}, f32[2,2,256,1]{3,2,1,0}) custom-call('
       'bf16[2,2,256,128]{3,2,1,0} %a, bf16[2,2,256,128]{3,2,1,0} %b, bf16[2,2,256,128]{3,2,1,0} %c), '
       'custom_call_target="tpu_custom_call"')


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def test_names():
    assert tr.op_kind(HLO) == "custom-call" and tr.short_name(HLO) == "attention.4 custom-call"
    assert tr.is_pallas_call(HLO) and tr.operand_count(HLO) == 3
    assert tr.is_container("%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b")
    assert tr.is_collective("%all-gather-start.3 = (f32[2]) all-gather-start(f32[1] %x)")
    assert tr.is_collective("%ar = f32[2] all-reduce(f32[2] %x), to_apply=%add")
    assert not tr.is_collective("%fusion.1 = f32[2] fusion(f32[2] %x), kind=kLoop")


def test_intervals():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.total(tr.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert tr.clip([(0, 10)], (2, 4)) == [(2, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]


def hand_trace():
    fusion = "%fusion.1 = f32[2] fusion(f32[2] %x), kind=kLoop"
    gather = "%all-gather.1 = f32[4] all-gather(f32[2] %x), dimensions={0}"
    while_ = "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
    ops = {"/device:TPU:0": [ev(while_, 100, 500), ev(fusion, 100, 200), ev(gather, 300, 100),
                             ev(fusion, 400, 200), ev(fusion, 800, 100)]}
    asyncs = {"/device:TPU:0": [ev("%all-gather-start.2 = (f32[2]) all-gather-start(f32[1] %x)",
                                   550, 300)]}
    modules = {"/device:TPU:0": [ev("jit_step(123)", 100, 500), ev("jit_step(123)", 800, 100),
                                 ev("jit_other(9)", 0, 50)]}
    host = [ev("bench.window", 0, 1000), ev("bench.dispatch", 50, 100),
            ev("bench.block_until_ready", 600, 300)]
    return tr.Trace(ops, asyncs, modules, host)


def test_reductions_on_hand_made_events():
    t = hand_trace()
    w = tr.window_of(t, "bench.window")
    assert w == (0.0, 1000.0)
    assert tr.busy_by_device(t, w) == {"/device:TPU:0": pytest.approx(600e-9)}
    top = dict(tr.op_seconds(t, w))
    assert top["fusion.1 fusion"] == pytest.approx(500e-9) and "while.1 while" not in top
    assert len(tr.module_events(t, w, "step")) == 2
    # the sync gather 300-400 ran alone; the async one 550-850 was covered
    # by compute on 550-600 and 800-850
    assert tr.exposed_collective_seconds(t, w)["/device:TPU:0"] == pytest.approx(300e-9)
    gaps = dict(tr.idle_gaps(t, w))
    # idle: 0-100 (midpoint 50 -> dispatch... the shortest covering span),
    # 600-800 and 900-1000 (block_until_ready covers 700; 950 only the window)
    assert gaps["bench.block_until_ready"] == pytest.approx(200e-9)
    assert sum(gaps.values()) == pytest.approx(400e-9)


def test_recorded_trace_reads_as_it_did_when_it_was_recorded():
    path = HERE / "small_train.xplane.pb"
    t = tr.load(str(path))
    w = tr.window_of(t, "bench.window")
    busy = tr.busy_by_device(t, w)
    assert list(busy) == ["/device:TPU:0"]
    share = busy["/device:TPU:0"] / ((w[1] - w[0]) / 1e9)
    assert 0.0 < share <= 1.0
    assert len(tr.module_events(t, w, "train_step")) >= 2
    pallas = tr.kernel_events(t, w, tr.is_pallas_call)
    counts = {n: sum(1 for e in pallas if tr.operand_count(e.name) == n) for n in (3, 6)}
    # per step and layer: forward + remat's forward (3 operands), dq + dkv (6)
    assert counts[3] == counts[6] > 0
    assert tr.op_seconds(t, w)[0][1] > 0
    assert tr.exposed_collective_seconds(t, w) == {"/device:TPU:0": 0.0}
