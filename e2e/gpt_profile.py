"""GPT-2-medium train-step decomposition (the stage_profile analog for the
transformer flagship).

Times each phase of the b8 x L1024 training step AS TRAINED (bf16 compute,
AdamW, Pallas flash attention), isolated into its own scanned tower with
the standard anti-hoist carry and host-fetch barrier:

  block      one transformer block fwd+bwd (x24 = the model body)
  embed_head embedding + final LN + tied LM head + CE loss fwd+bwd
  optimizer  AdamW update alone over the full param set

The full-step reference point is the bench itself (`BENCH_MODEL=gpt
python bench.py`). NOTE the towers are bounds, not addends: 24 x block has
measured ABOVE the full step — XLA schedules the full graph better than any
isolated piece (BASELINE.md, older findings).

Run:  python -m e2e.gpt_profile [--batch 8] [--seq 1024]
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import optax

# one copy of the honest timing harness
from e2e.ceiling import _timed as _scan_time


def profile(batch: int = 8, seq: int = 1024, steps: int = 20) -> List[Dict[str, Any]]:
    from kubeflow_tpu.models.gpt import GptBlock, GptConfig, GptLM, causal_lm_loss

    cfg = GptConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                    max_seq=seq, vocab_size=32000)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)
    rows: List[Dict[str, Any]] = []

    # -- one transformer block fwd+bwd --------------------------------------
    block = GptBlock(cfg)
    x0 = jax.random.normal(rng, (batch, seq, cfg.d_model), jnp.bfloat16) * 0.1
    positions = jnp.arange(seq)
    bparams = block.init(rng, x0, positions)["params"]

    def block_loss(p, x):
        return jnp.sum(jnp.abs(block.apply({"params": p}, x, positions)
                               .astype(jnp.float32))) * 1e-6

    @jax.jit
    def run_block(p, x):
        def body(c, _):
            xx = x + c * jnp.bfloat16(1e-30)
            loss, grads = jax.value_and_grad(block_loss)(p, xx)
            gsum = sum(jnp.sum(g.astype(jnp.float32))
                       for g in jax.tree_util.tree_leaves(grads))
            return c + jnp.bfloat16(loss * 1e-6 + gsum * 1e-30), ()
        c, _ = jax.lax.scan(body, jnp.bfloat16(0), None, length=steps)
        return c

    dt = _scan_time(run_block, (bparams, x0), steps)
    # per-block fwd FLOPs: 4 attn projections + 2 mlp matmuls + attention
    proj = 4 * 2.0 * batch * seq * cfg.d_model * cfg.d_model
    mlp = 2 * 2.0 * batch * seq * cfg.d_model * cfg.d_ff
    attn = 2 * 2.0 * batch * cfg.n_heads * seq * seq * cfg.head_dim / 2  # causal
    fl = 3.0 * (proj + mlp + attn)
    rows.append({"phase": "block (x1)", "ms": dt * 1e3, "tflops": fl / dt / 1e12,
                 "x24_ms": dt * 24 * 1e3})

    # -- embedding + LM head + loss fwd+bwd ---------------------------------
    import flax.linen as nn

    class EmbedHead(nn.Module):
        @nn.compact
        def __call__(self, ids):
            embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                             param_dtype=jnp.float32, name="embedding")
            x = embed(ids)  # stand-in body output
            x = nn.LayerNorm(dtype=jnp.float32, param_dtype=jnp.float32)(x)
            return x.astype(jnp.float32) @ embed.embedding.T.astype(jnp.float32)

    eh = EmbedHead()
    ehp = eh.init(rng, ids)["params"]

    def eh_loss(p, ids):
        return causal_lm_loss(eh.apply({"params": p}, ids), ids)

    @jax.jit
    def run_eh(p, ids):
        def body(c, _):
            # anti-hoist: roll the ids by the carry so the body is NOT
            # loop-invariant (a fixed (p, ids) body gets hoisted out of the
            # scan and the probe times one execution across all steps)
            ids2 = jnp.roll(ids, jnp.int32(c) + 1, axis=1)
            loss, grads = jax.value_and_grad(eh_loss)(p, ids2)
            gsum = sum(jnp.sum(g.astype(jnp.float32))
                       for g in jax.tree_util.tree_leaves(grads))
            # *1e-30, never *0 — an algebraic zero would DCE the grads
            return c + 1.0 + (loss + gsum) * 1e-30, ()
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=steps)
        return c

    dt = _scan_time(run_eh, (ehp, ids), steps)
    head = 2.0 * batch * seq * cfg.d_model * cfg.vocab_size
    rows.append({"phase": "embed+head+loss", "ms": dt * 1e3,
                 "tflops": 3.0 * head / dt / 1e12})

    # -- optimizer alone ------------------------------------------------------
    model = GptLM(cfg)
    params = model.init(rng, ids)["params"]
    opt = optax.adamw(3e-4, weight_decay=0.01)
    ostate = opt.init(params)
    fake_grads = jax.tree_util.tree_map(lambda p: (p * 1e-3).astype(p.dtype), params)

    @jax.jit
    def run_opt(params, ostate, grads):
        def body(carry, _):
            p, s = carry
            updates, s = opt.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return (p, s), ()
        (p, s), _ = jax.lax.scan(body, (params, ostate), None, length=steps)
        return sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree_util.tree_leaves(p))

    dt = _scan_time(run_opt, (params, ostate, fake_grads), steps)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    rows.append({"phase": "adamw update", "ms": dt * 1e3,
                 "gb_moved": round(n_params * 4 * 7 / 1e9, 2)})

    return rows


def main(argv=None) -> int:
    from kubeflow_tpu.tpu.env import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args(argv)
    rows = profile(args.batch, args.seq, args.steps)
    total = 0.0
    for r in rows:
        extra = f"  (x24 = {r['x24_ms']:.1f} ms)" if "x24_ms" in r else ""
        rate = f"{r['tflops']:6.1f} TF/s" if "tflops" in r else f"{r.get('gb_moved', '?')} GB/step"
        print(f"{r['phase']:18s} {r['ms']:8.2f} ms  {rate}{extra}", flush=True)
        total += r.get("x24_ms", r["ms"])
    print(f"{'sum (24 blocks + head + opt)':18s} {total:8.2f} ms")
    print(json.dumps({"metric": "gpt_step_profile", "batch": args.batch,
                      "seq": args.seq, "rows": rows, "sum_ms": round(total, 2)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
