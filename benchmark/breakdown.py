"""Where the time of a traced run went, from the trace it left behind.

    python3 benchmark/breakdown.py <workload> [--program step]

Reads ``.bench_trace/<workload>/`` (written by the last ``--trace 1`` run of
that cell in this checkout) and prints one JSON object: the device's idle
seconds by the innermost ``serving.engine.*`` span of the engine thread,
the engine spans' own totals, and the device's seconds by scope (of the
executions of ``--program`` where given, else of the whole window) with the
largest operations that fall under none of the scopes. ``PERF.md`` section 5
is written from it. It measures nothing and the benchmark's result line
does not depend on it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

SERVE_SCOPES = ("kv_gather", "kv_write", "attn_scores", "lm_head", "sample", "mlp", "query", "key",
                "value", "out_proj", "ln_attn", "ln_mlp")
TRAIN_SCOPES = ("optimizer", "rematted_computation", "loss", "attention", "mlp", "attn", "embed",
                "unembed", "embedding")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workload")
    parser.add_argument("--program", default=None)
    args = parser.parse_args(argv)

    from benchmark import harness, trace_reduce as tr
    from benchmark.metrics import _scopes, _spans

    path = tr.find_xplane(str(harness.ROOT / ".bench_trace" / args.workload))
    trace = tr.load(path)
    window = tr.window_of(trace, harness.TRACE_SPAN)
    spans = sorted(_spans.spans_in_file(path)[0], key=lambda s: s.start_ns)
    obs = {"trace": trace, "trace_window": window,
           "op_scopes": _scopes.scopes_in_file(path)}
    busy = tr.busy_by_device(trace, window)
    out = {"xplane": path, "window_s": (window[1] - window[0]) / 1e9, "busy_s": busy,
           "scoped_ops": len(obs["op_scopes"]),
           "ambiguous_ops": [tr.short_name(n) for n in _scopes.ambiguous_in_file(path)]}
    if spans:
        totals = {}
        for s in spans:
            if s.start_ns >= window[0] and s.end_ns <= window[1]:
                n, t = totals.get(s.name, (0, 0.0))
                totals[s.name] = (n + 1, t + s.dur_ns / 1e9)
        out["engine_spans"] = {k: {"count": n, "seconds": t} for k, (n, t) in sorted(totals.items())}
        out["idle_by_engine_span"] = _spans.idle_by_span(trace, window, spans)
    runs = tr.module_events(trace, window, args.program) if args.program else None
    if runs is not None:
        out["program_runs"] = len(runs)
        out["program_s"] = sum(m.dur_ns for m in runs) / 1e9
    by_scope, rest = _scopes.seconds_by_scope(obs, SERVE_SCOPES if spans else TRAIN_SCOPES,
                                              within=runs)
    out["seconds_by_scope"] = by_scope
    out["ops_s"] = sum(_scopes.op_seconds(obs, runs).values())
    out["unattributed_s"] = sum(s for _, s in rest)
    out["unscoped_s"] = _scopes.seconds_where(obs, _scopes.names_no_part, within=runs)
    out["unattributed_top"] = rest[:12]
    kinds = {}      # "fusion [scope path]" over all operations of that kind there
    for label, seconds in rest:
        name, _, path = label.partition(" [")
        key = f"{name.split(' ')[-1]} [{path}"
        n, t = kinds.get(key, (0, 0.0))
        kinds[key] = (n + 1, t + seconds)
    out["unattributed_by_kind"] = [[k, n, t] for k, (n, t) in
                                   sorted(kinds.items(), key=lambda kv: -kv[1][1])[:12]]
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
