"""Per-slot KV-write strategies and the dispatch cost model of the decode
loop (VERDICT r4 #2), measured on the chip.

Every number is synced by a real host fetch (``np.asarray`` of a small
output), and per-op costs come from CHAINED dispatches divided by the chain
length. What it prices, and what serving/continuous.py's pipelined engine is
built around: the fixed cost of a dispatch+fetch round trip, the marginal
decode compute per token, and how far overlapping chunks hides the former
behind the latter before a deep dispatch queue degrades.

Strategies compared for the per-row cache write itself: where-select over
the whole cache, scatter ``.at[arange, cur].set``, vmapped
dynamic_update_slice, and the Pallas row-update kernel (ops/kv_cache.py).
The engine-level A/B (KUBEFLOW_TPU_KV_KERNEL=0 vs 1 on
e2e/serving_bench.py:bench_continuous) is the decision-grade comparison.

Run: ``python -m e2e.kv_update_probe``.
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

S, T, H, D = 8, 352, 16, 64
CHUNK = 16


def _sync(x) -> None:
    """Order-forcing host fetch: np.asarray of a tiny dependent slice."""
    leaf = jax.tree.leaves(x)[0]
    np.asarray(leaf[(0,) * (leaf.ndim - 1)][:1])


def _chained(fn, cache, new, cur, *, block: int = 8, blocks: int = 6) -> float:
    """Median per-op ms over ``blocks`` chained blocks of ``block`` donated
    dispatches, each block closed by a sync fetch. Chaining amortizes the
    ~115 ms RTT; the block bound keeps the queue shallow (deep queues
    degrade on this backend)."""
    out = fn(cache, new, cur)
    _sync(out)
    times = []
    for _ in range(blocks):
        t0 = time.perf_counter()
        for _ in range(block):
            out = fn(out, new, cur)
        _sync(out)
        times.append((time.perf_counter() - t0) / block)
    return float(np.median(times) * 1e3)


def isolated() -> dict:
    rng = np.random.default_rng(0)
    cache_np = rng.normal(size=(S, T, H, D)).astype(np.float32)
    new = jnp.asarray(rng.normal(size=(S, H, D)), jnp.bfloat16)
    cur = jnp.asarray(rng.integers(0, T, S), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def select(cache, new, cur):
        at = jnp.arange(T)[None, :, None, None] == cur[:, None, None, None]
        return jnp.where(at, new[:, None], cache)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(cache, new, cur):
        return cache.at[jnp.arange(S), cur].set(new)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def vmapped_dus(cache, new, cur):
        return jax.vmap(lambda row, n, c: jax.lax.dynamic_update_slice(
            row, n[None], (c, 0, 0)))(cache, new, cur)

    from kubeflow_tpu.ops.kv_cache import kv_row_update

    @functools.partial(jax.jit, donate_argnums=(0,))
    def pallas_row(cache, new, cur):
        return kv_row_update(cache, new, cur)

    out = {}
    for name, fn in [("where_select", select), ("scatter_at", scatter),
                     ("vmapped_dus", vmapped_dus), ("pallas_row", pallas_row)]:
        cache0 = jnp.asarray(cache_np, jnp.bfloat16)  # fresh: prior donated
        out[name + "_ms"] = round(_chained(fn, cache0, new, cur), 3)
    return out


def in_model() -> dict:
    """Engine-shaped measurement: chained chunk dispatches at pipeline
    depth 3 with per-chunk token fetches — exactly the production access
    pattern — for the shared-cursor model, the per-slot select path, and
    the per-slot Pallas-kernel path."""
    from kubeflow_tpu.models.gpt import GptConfig, GptLM

    cfg = GptConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                    max_seq=T, vocab_size=32000)
    rng = jax.random.PRNGKey(0)
    params = GptLM(cfg).init(rng, jax.random.randint(rng, (1, 128), 0,
                                                     cfg.vocab_size))["params"]

    def fresh_cache(per_slot: bool):
        kv = (S, cfg.max_seq, cfg.n_heads, cfg.head_dim)

        def extra():
            # a FRESH array per block: splicing one shared array object
            # into every block makes 24 duplicate leaves in a donated
            # pytree — double-donation, which this backend surfaces as an
            # InvalidArgument at the next fetch (found the hard way)
            return ({"cursors": jnp.full((S,), 128, jnp.int32)} if per_slot
                    else {"cursor": jnp.full((), 128, jnp.int32)})

        return {f"block_{i}": {"attention": {
            "k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **extra()}}
            for i in range(cfg.n_layers)}

    def build_chunk_step(per_slot: bool):
        model = GptLM(cfg, decode=True, per_slot=per_slot)

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def step(params, cache, tok):
            def one(carry, _):
                cache, tok = carry
                logits, upd = model.apply(
                    {"params": params, "cache": cache}, tok[:, None],
                    mutable=["cache"])
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (upd["cache"], nxt), nxt
            (cache, tok), toks = jax.lax.scan(one, (cache, tok), None,
                                              length=CHUNK)
            return cache, tok, jnp.moveaxis(toks, 0, 1)

        return step

    out = {}
    rows = [("shared_cursor", False, None),
            ("per_slot_select", True, "0"),
            ("per_slot_kernel", True, "1")]
    depth, n_chunks = 3, 14
    for name, per_slot, knob in rows:
        if knob is not None:
            os.environ["KUBEFLOW_TPU_KV_KERNEL"] = knob
        step = build_chunk_step(per_slot)
        cache = fresh_cache(per_slot)
        tok = jnp.zeros((S,), jnp.int32)
        cache, tok, toks = step(params, cache, tok)
        np.asarray(toks)  # warm/compile
        t0 = time.perf_counter()
        inflight = []
        for _ in range(n_chunks):
            cache, tok, toks = step(params, cache, tok)
            try:
                toks.copy_to_host_async()
            except Exception:
                pass
            inflight.append(toks)
            if len(inflight) >= depth:
                np.asarray(inflight.pop(0))
        for t in inflight:
            np.asarray(t)
        dt = (time.perf_counter() - t0) / n_chunks
        out[name + "_ms_per_chunk"] = round(dt * 1e3, 1)
        out[name + "_ms_per_token"] = round(dt / CHUNK * 1e3, 3)
    os.environ.pop("KUBEFLOW_TPU_KV_KERNEL", None)
    return out


def main() -> int:
    from kubeflow_tpu.tpu.env import enable_compile_cache

    enable_compile_cache()
    iso = isolated()
    print("isolated [8,352,16,64] bf16 single-row write (chained, synced):")
    for k, v in iso.items():
        print(f"  {k:20s} {v:8.3f} ms")
    model = in_model()
    print("in-model GPT-medium 24L chunk=16 depth-3 pipeline:")
    for k, v in model.items():
        print(f"  {k:32s} {v:8.3f}")
    print(json.dumps({"metric": "kv_update_probe", **iso, **model}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
