"""Serving benchmarks on the chip (VERDICT r2 #4 / round-1 ask #7).

Two rows, mirroring the reference's serving e2e shape
(testing/test_tf_serving.py:108-133 — HTTP predict against a served model):

1. **BERT-base MLM predict over real HTTP**: the model is hosted by
   ModelServer (kubeflow_tpu/serving/server.py) on a local port and driven
   through the same ``/v1/models/<name>:predict`` path users hit. Batch
   buckets 1/8/32; per-request wall latency p50/p99 + throughput. The
   response carries argmax token ids (serving-shaped output, not the
   15 MB/row logits tensor).

2. **GPT KV-cache decode**: prefill a 128-token prompt, then scanned
   single-token steps with the static-shape KV cache
   (models/gpt.py:generate) — steady-state decode tokens/s at batch 1/8.

Run via ``BENCH_MODEL=serving python bench.py`` or directly. Prints a table
plus one JSON line per row.
"""

from __future__ import annotations

import json
import os
import statistics
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

SEQ = 128


def bench_bert_http(batches=(1, 8, 32), requests_per_batch: int = 40) -> List[Dict[str, Any]]:
    import urllib.request

    from kubeflow_tpu.models.bert import BertConfig, BertForMaskedLM
    from kubeflow_tpu.serving.server import ModelServer, ServedModel

    cfg = BertConfig()  # base: 12 layers, hidden 768
    model = BertForMaskedLM(cfg)
    rng = jax.random.PRNGKey(0)
    sample = jax.random.randint(rng, (1, SEQ), 0, cfg.vocab_size)
    params = model.init(rng, sample)["params"]

    def apply_fn(p, ids):
        logits = model.apply({"params": p}, ids)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)  # serving-shaped output

    server = ModelServer()
    server.add(ServedModel(name="bert-base", apply_fn=apply_fn, params=params,
                           input_dtype=jnp.int32))
    httpd = server.app.serve(0)
    url = f"http://127.0.0.1:{httpd.port}/v1/models/bert-base:predict"

    rows = []
    try:
        rng_np = np.random.default_rng(0)
        for batch in batches:
            payload = json.dumps({
                "instances": rng_np.integers(0, cfg.vocab_size, (batch, SEQ)).tolist()
            }).encode()

            def request() -> float:
                t0 = time.perf_counter()
                req = urllib.request.Request(url, payload,
                                             {"content-type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    body = json.loads(resp.read())
                assert len(body["predictions"]) == batch
                return time.perf_counter() - t0

            request()  # warm: compiles this bucket
            lat = sorted(request() for _ in range(requests_per_batch))
            p50 = statistics.median(lat)
            # With 40 samples, index 37 is a real p95; a "p99" here would
            # just be the max (one hiccup), so report p95 + max.
            p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95) - 1)]
            rows.append({
                "batch": batch,
                "p50_ms": round(p50 * 1e3, 1),
                "p95_ms": round(p95 * 1e3, 1),
                "max_ms": round(lat[-1] * 1e3, 1),
                "qps": round(1.0 / p50, 2),
                "sequences_per_sec": round(batch / p50, 1),
            })
    finally:
        httpd.close()
    return rows


def bench_gpt_decode(batches=(1, 8), prompt_len: int = 128,
                     new_tokens: int = 256) -> List[Dict[str, Any]]:
    from kubeflow_tpu.models.gpt import GptConfig, GptLM, generate

    cfg = GptConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                    max_seq=prompt_len + new_tokens, vocab_size=32000)
    rng = jax.random.PRNGKey(0)
    model = GptLM(cfg)
    sample = jax.random.randint(rng, (1, prompt_len), 0, cfg.vocab_size)
    params = model.init(rng, sample)["params"]

    rows = []
    for batch in batches:
        prompt = jax.random.randint(rng, (batch, prompt_len), 0, cfg.vocab_size)
        out = generate(cfg, params, prompt, max_new_tokens=new_tokens)
        np.asarray(out)  # compile + warm, host fetch barrier
        t0 = time.perf_counter()
        out = generate(cfg, params, prompt, max_new_tokens=new_tokens)
        np.asarray(out)
        dt = time.perf_counter() - t0
        rows.append({
            "batch": batch,
            "wall_s": round(dt, 3),
            "decode_tokens_per_sec": round(batch * new_tokens / dt, 1),
            "ms_per_token": round(dt / new_tokens * 1e3, 2),
        })
    return rows


def bench_continuous(slots: int = 8, n_requests: int = 16,
                     prompt_len: int = 128, chunk: int = 16,
                     pipeline: int = 3) -> Dict[str, Any]:
    """Mixed-budget decode workload: continuous batching vs the static
    batch path on the SAME requests (VERDICT r3 #8).

    Budgets cycle [32, 64, 128, 224]: the static path groups ``slots``
    requests per batch and every member pays the group MAX (lockstep
    decode); the continuous engine retires each sequence at ITS budget and
    admits the next from the queue.

    ISSUE-12 knobs (docs/PERFORMANCE.md): ``BENCH_PAGED`` (default 1)
    runs the engine on the paged KV arena, ``BENCH_KV_BLOCKS`` sizes the
    arena (0 = full capacity), ``BENCH_PREFILL_CHUNK`` sets the
    chunked-prefill budget (engine default when unset, 0 disables).
    ``BENCH_SPEC`` (default 1) adds a second timed pass on a speculative
    engine — reporting ``spec_accept_rate`` and ``spec_tokens_per_sec``
    next to the plain numbers, ``BENCH_SPEC_K`` tokens per round.

    ISSUE-18 knobs: ``BENCH_KV_DTYPE`` (default bf16) runs every engine
    on the int8 arena when set to ``int8``. ``BENCH_DRAFT`` (default
    ``distill``) picks the speculative draft: ``distill`` trains a small
    draft from the target with training/distill.py (``BENCH_DISTILL_STEPS``
    KL steps, outside the timed window; on a trained target this is what
    lifts the accept rate past the gate floor), ``self`` keeps the r06
    truncated-layer self-draft (the target's own first ``n_layers // 4``
    blocks with tied embeddings — no second checkpoint, but on-policy
    agreement with the full stack's argmax is poor)."""
    from kubeflow_tpu.models.gpt import GptConfig, GptLM, generate
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    budgets = [(32, 64, 128, 224)[i % 4] for i in range(n_requests)]
    cfg = GptConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                    max_seq=prompt_len + max(budgets), vocab_size=32000)
    rng = jax.random.PRNGKey(0)
    model = GptLM(cfg)
    params = model.init(rng, jax.random.randint(rng, (1, prompt_len), 0,
                                                cfg.vocab_size))["params"]
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(i),
                                             (prompt_len,), 0, cfg.vocab_size))
               for i in range(n_requests)]
    total_tokens = sum(budgets)

    # -- static path: batches of `slots`, lockstep to the group max --------
    # warm: compile the per-budget generate programs outside the window.
    # NOTE this path is an OFFLINE ORACLE: it assumes all requests are known
    # upfront and groupable — online it would either wait to fill groups
    # (latency) or run part-empty ones (throughput).
    for b in sorted(set(budgets)):
        np.asarray(generate(cfg, params,
                            np.stack([prompts[0]] * min(slots, n_requests)),
                            max_new_tokens=b))
    t0 = time.perf_counter()
    static_done_at = [0.0] * n_requests
    for lo in range(0, n_requests, slots):
        group = list(range(lo, min(lo + slots, n_requests)))
        group_max = max(budgets[i] for i in group)
        batch = np.stack([prompts[i] for i in group])
        out = generate(cfg, params, batch, max_new_tokens=group_max)
        np.asarray(out)  # host fetch barrier
        for i in group:  # every member waits for the group max (lockstep)
            static_done_at[i] = time.perf_counter() - t0
    static_s = time.perf_counter() - t0

    # -- continuous path: same requests through the slot engine ------------
    paged = os.environ.get("BENCH_PAGED", "1") == "1"
    kv_blocks = int(os.environ.get("BENCH_KV_BLOCKS", "0") or 0) or None
    pc_env = os.environ.get("BENCH_PREFILL_CHUNK", "")
    prefill_chunk = int(pc_env) if pc_env else None
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "bf16")
    eng = ContinuousBatcher(cfg, params, slots=slots, chunk=chunk,
                            pipeline=pipeline, paged=paged,
                            kv_blocks=kv_blocks, prefill_chunk=prefill_chunk,
                            kv_dtype=kv_dtype)
    try:
        # warm the engine's programs (per-group-size prefill, adopt, and
        # the chunked step) the same way the static path's generate()
        # programs are warmed above — compiles must not sit inside the
        # timed window
        eng.prewarm(prompt_len)
        t0 = time.perf_counter()
        futs = [eng.submit(prompts[i], budgets[i]) for i in range(n_requests)]
        for f in futs:
            f.result(timeout=1800)
        continuous_s = time.perf_counter() - t0
        cont_lat = [f.done_at - t0 for f in futs]
    finally:
        eng.close()

    # SLO quantiles out of the engine's histograms (registry bucket
    # interpolation — the same numbers a /metrics scrape would show).
    # prewarm() runs uninstrumented, so only the timed requests count.
    # Queried BEFORE the speculative pass below adds its own observations.
    from kubeflow_tpu.runtime.metrics import METRICS

    def _q(name: str, q: float) -> float:
        v = METRICS.quantile(name, q)  # None = no observations (not 0.0)
        return round(v, 4) if v is not None else 0.0

    ttft_p50, ttft_p99 = _q("serving_ttft_seconds", 0.5), _q("serving_ttft_seconds", 0.99)
    queue_wait_p99 = _q("serving_queue_wait_seconds", 0.99)

    # -- speculative pass: distilled draft (default) or self-draft ---------
    spec: Dict[str, Any] = {}
    if os.environ.get("BENCH_SPEC", "1") == "1":
        spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
        draft_mode = os.environ.get("BENCH_DRAFT", "distill")
        if draft_mode == "distill":
            from kubeflow_tpu.training.distill import distill_draft

            # trained OUTSIDE the timed window; the distilled draft is the
            # bench default because the truncated-layer self-draft's accept
            # rate (~0.14 in r06/r07) throws away most speculative compute
            draft_cfg, draft_params = distill_draft(
                cfg, params,
                steps=int(os.environ.get("BENCH_DISTILL_STEPS", "300")),
                seed=0)
            draft_layers = draft_cfg.n_layers
        else:
            draft_layers = max(1, cfg.n_layers // 4)
            draft_cfg = GptConfig(d_model=cfg.d_model, n_layers=draft_layers,
                                  n_heads=cfg.n_heads, d_ff=cfg.d_ff,
                                  max_seq=cfg.max_seq,
                                  vocab_size=cfg.vocab_size)
            draft_params = {k: v for k, v in params.items()
                            if not k.startswith("block_")}
            for i in range(draft_layers):
                draft_params[f"block_{i}"] = params[f"block_{i}"]
        drafted0 = METRICS.counter("serving_spec_tokens_drafted_total").value
        accepted0 = METRICS.counter("serving_spec_tokens_accepted_total").value
        seng = ContinuousBatcher(cfg, params, slots=slots, chunk=chunk,
                                 pipeline=pipeline, paged=paged,
                                 kv_blocks=kv_blocks,
                                 prefill_chunk=prefill_chunk,
                                 kv_dtype=kv_dtype,
                                 spec_draft=(draft_cfg, draft_params),
                                 spec_k=spec_k)
        try:
            seng.prewarm(prompt_len)
            t0 = time.perf_counter()
            futs = [seng.submit(prompts[i], budgets[i])
                    for i in range(n_requests)]
            for f in futs:
                f.result(timeout=1800)
            spec_s = time.perf_counter() - t0
        finally:
            seng.close()
        drafted = METRICS.counter("serving_spec_tokens_drafted_total").value - drafted0
        accepted = METRICS.counter("serving_spec_tokens_accepted_total").value - accepted0
        spec = {
            "spec_k": spec_k,
            "spec_draft": draft_mode,
            "spec_draft_layers": draft_layers,
            "spec_wall_s": round(spec_s, 2),
            "spec_tokens_per_sec": round(total_tokens / spec_s, 1),
            "spec_accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
        }

    return {
        "ttft_p50": ttft_p50,
        "ttft_p99": ttft_p99,
        "queue_wait_p99": queue_wait_p99,
        "paged": paged,
        "kv_blocks": kv_blocks or "full",
        "kv_dtype": kv_dtype,
        "prefill_chunk": eng.prefill_chunk,
        **spec,
        "slots": slots, "requests": n_requests, "budgets": "32/64/128/224",
        "useful_tokens": total_tokens,
        "static_wall_s": round(static_s, 2),
        "static_tokens_per_sec": round(total_tokens / static_s, 1),
        "static_mean_latency_s": round(sum(static_done_at) / n_requests, 2),
        "continuous_wall_s": round(continuous_s, 2),
        "continuous_tokens_per_sec": round(total_tokens / continuous_s, 1),
        "continuous_mean_latency_s": round(sum(cont_lat) / n_requests, 2),
        "speedup": round(static_s / continuous_s, 3),
    }


def bench_disagg(slots: int = 8, n_requests: int = 24,
                 chunk: int = 16, pipeline: int = 3) -> Dict[str, Any]:
    """Heterogeneous-mix serving pass (ISSUE 18): two models multiplexed
    over a disaggregated fleet — a prefill pool and a decode pool per
    model — under the workload that punishes homogeneous replicas most:
    chatty short-prompt decode interleaved with long-prefill requests.

    The fleet runs ``kv_dtype`` from ``BENCH_KV_DTYPE`` (int8 doubles KV
    slots per HBM byte, the r08 default for this pass), routes on the
    per-request ``model`` id, and ships every prefill over the KV wire —
    so the reported aggregate decode tokens/s pays for routing, handoff
    serialization, and import, not just raw decode steps. Headline rows:
    ``decode_tok_s_heterogeneous`` (gate: strictly above the homogeneous
    r06 b8 decode row) and ``kv_handoff_p99_s`` (wire serialization +
    fetch tail). Disable with ``BENCH_DISAGG=0``."""
    from kubeflow_tpu.models.gpt import GptConfig, GptLM
    from kubeflow_tpu.runtime.metrics import METRICS
    from kubeflow_tpu.serving.fleet import EngineFleet

    prompt_short, prompt_long, budget = 64, 384, 128
    cfg = GptConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                    max_seq=prompt_long + budget, vocab_size=32000)
    rng = jax.random.PRNGKey(0)
    model = GptLM(cfg)
    sample = jax.random.randint(rng, (1, prompt_short), 0, cfg.vocab_size)
    params = {
        "alpha": model.init(jax.random.PRNGKey(0), sample)["params"],
        "beta": model.init(jax.random.PRNGKey(1), sample)["params"],
    }
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "int8")
    fleet = EngineFleet(
        cfg, params["alpha"], max_replicas=4,
        pools={"prefill": 1, "decode": 2},
        models={mid: (cfg, p) for mid, p in params.items()},
        model_slo={"alpha": "interactive", "beta": "batch"},
        slots=slots, chunk=chunk, pipeline=pipeline, name="bench-disagg",
        engine_kwargs={"kv_dtype": kv_dtype,
                       "prefill_chunk": prompt_short})
    # the mix: 2/3 chatty decode, 1/3 long prefill, models alternating
    reqs = []
    for i in range(n_requests):
        plen = prompt_long if i % 3 == 2 else prompt_short
        reqs.append(("alpha" if i % 2 == 0 else "beta",
                     np.asarray(jax.random.randint(
                         jax.random.PRNGKey(100 + i), (plen,), 0,
                         cfg.vocab_size))))
    try:
        # warm both pools' programs for both prompt shapes, per model
        for mid in params:
            for plen in (prompt_short, prompt_long):
                warm = np.asarray(jax.random.randint(
                    jax.random.PRNGKey(plen), (plen,), 0, cfg.vocab_size))
                fleet.submit(warm, 2, model=mid).result(timeout=1800)
        t0 = time.perf_counter()
        futs = [fleet.submit(p, budget, model=mid) for mid, p in reqs]
        for f in futs:
            f.result(timeout=1800)
        wall = time.perf_counter() - t0
        ttfts = sorted(f.first_token_at - f.submit_at for f in futs)
    finally:
        fleet.close()
    handoff_p99 = METRICS.quantile("serving_kv_handoff_seconds", 0.99)
    return {
        "models": 2,
        "pools": {"prefill": 1, "decode": 2},
        "kv_dtype": kv_dtype,
        "requests": n_requests,
        "prompt_mix": f"{prompt_short}/{prompt_long}",
        "budget": budget,
        "wall_s": round(wall, 2),
        "decode_tok_s_heterogeneous": round(n_requests * budget / wall, 1),
        "ttft_p99_s": round(ttfts[min(len(ttfts) - 1,
                                      int(len(ttfts) * 0.99))], 4),
        "kv_handoff_p99_s": (round(handoff_p99, 4)
                             if handoff_p99 is not None else 0.0),
    }


def main() -> int:
    from kubeflow_tpu.tpu.env import enable_compile_cache, require_tpu

    require_tpu()
    enable_compile_cache()
    bert = bench_bert_http()
    print(f"{'BERT-base predict (HTTP)':28s} {'p50':>8s} {'p95':>8s} {'max':>8s} {'seq/s':>8s}")
    for r in bert:
        print(f"  batch {r['batch']:<4d}                 {r['p50_ms']:7.1f}ms {r['p95_ms']:7.1f}ms {r['max_ms']:7.1f}ms {r['sequences_per_sec']:8.1f}")
    gpt = bench_gpt_decode()
    print(f"{'GPT-medium KV-cache decode':28s} {'tok/s':>8s} {'ms/tok':>8s}")
    for r in gpt:
        print(f"  batch {r['batch']:<4d}                 {r['decode_tokens_per_sec']:8.1f} {r['ms_per_token']:7.2f}")
    print(json.dumps({"metric": "bert_base_predict_http", "rows": bert, "unit": "ms/qps"}))
    print(json.dumps({"metric": "gpt_medium_kv_decode", "rows": gpt, "unit": "tokens_per_sec"}))
    cont = bench_continuous()
    print(f"{'Continuous vs static batching':28s} {cont['continuous_tokens_per_sec']:8.1f}"
          f" vs {cont['static_tokens_per_sec']:8.1f} tok/s ({cont['speedup']}x)")
    print(json.dumps({"metric": "gpt_continuous_batching", **cont,
                      "unit": "tokens_per_sec"}))
    if os.environ.get("BENCH_DISAGG", "1") == "1":
        dis = bench_disagg()
        print(f"{'Disagg heterogeneous mix':28s} "
              f"{dis['decode_tok_s_heterogeneous']:8.1f} tok/s "
              f"(handoff p99 {dis['kv_handoff_p99_s']}s)")
        print(json.dumps({"metric": "decode_tok_s_heterogeneous",
                          "value": dis["decode_tok_s_heterogeneous"],
                          "unit": "tokens_per_sec", **dis}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
