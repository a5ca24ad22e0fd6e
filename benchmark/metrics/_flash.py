"""Shared by the two flash readers: find the kernel's events in the trace.

The three ``pl.pallas_call``s of ``ops/flash_attention.py`` carry no
``name=`` yet, so the trace prints them as ``%attention.<n> = ...
custom-call(...), custom_call_target="tpu_custom_call"`` under the flax
scope's name. Until the tracing issue names them, they are told apart by
their operands: the forward takes q, k, v (3); the two backward kernels
take q, k, v, do, lse, delta (6). A kernel whose name says ``flash`` and
``fwd`` / ``bwd`` / ``dq`` / ``dkv`` is taken by name instead."""

from benchmark import flops, trace_reduce as tr
from benchmark.peaks import peaks_for


def events(obs, which):
    def pred(name):
        if not tr.is_pallas_call(name):
            return False
        head = name.split(" = ", 1)[0].lower()
        if "flash" in head:
            back = any(t in head for t in ("bwd", "dq", "dkv"))
            return back if which == "bwd" else ("fwd" in head and not back)
        return tr.operand_count(name) == (3 if which == "fwd" else 6)

    return tr.kernel_events(obs["trace"], obs["trace_window"], pred)


def share(obs, which):
    if obs["kind"] != "train" or "trace" not in obs:
        return None
    evs = events(obs, which)
    if not evs:
        return None
    s = obs["sizes"]
    rows = obs["batch_shape"][0]
    heads, head_dim = s["n_head"], s["n_embd"] // s["n_head"]
    if which == "fwd":
        cost, calls = flops.flash_fwd_cost(rows, heads, obs["seq"], head_dim), len(evs)
    else:   # dq and dkv kernels together make one backward
        cost, calls = flops.flash_bwd_cost(rows, heads, obs["seq"], head_dim), len(evs) / 2.0
    ideal = flops.roofline_seconds(cost, peaks_for(obs["device_kind"])) * calls
    return 100.0 * ideal / (sum(e.dur_ns for e in evs) / 1e9)
