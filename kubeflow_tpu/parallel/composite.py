"""Composed 4D parallelism: one GPT train step over dp x fsdp x tp x pp.

VERDICT r3 #6: the per-axis dryrun phases proved each parallelism axis as an
island; this module composes them in ONE program on ONE mesh — the way a
real large-model job runs (Megatron/GSPMD-style):

- ``pipe``  — transformer layers split into pipeline stage chunks
  (parallel/pipeline.py: shard_map + ppermute microbatch streaming; GPipe
  or, with ``virtual_stages>1``, the interleaved schedule),
- ``model`` — Megatron tensor parallelism INSIDE each stage, written as
  manual SPMD: column-split QKV/W1 (no comm), row-split WO/W2 followed by
  one ``psum`` over the ``model`` axis per sublayer,
- ``fsdp``  — ZeRO-3: weight shards live split over ``fsdp``; gathers run
  in one of three modes (``gather_mode``):
    * ``"eager"``     — gather each weight right before use, once per layer
      per microbatch (the baseline; autodiff transposes each gather into a
      per-microbatch gradient ``reduce_scatter``),
    * ``"overlap"``   — the per-stage layer loop is a ``lax.scan`` with a
      double-buffered carry that prefetches layer i+1's ``all_gather``
      while layer i computes, hiding gather latency behind the matmuls,
    * ``"amortized"`` — all chunk weights gather ONCE per train step via
      the pipeline's ``stage_prepare`` hook; the gathered tree is a scan
      constant, so cotangents accumulate across microbatches and each
      weight sees ONE transposed reduce-scatter per step (no_sync-style,
      ~M x less fsdp traffic at peak-memory cost of the gathered chunk).
- ``data``/``fsdp`` — the microbatch dim of the input stream is sharded
  over both batch axes (mesh.BATCH_AXES); gradient all-reduce over them is
  placed by autodiff through the shard_map.

Embedding/unembedding run OUTSIDE the pipeline under ordinary GSPMD jit
(vocab sharded over ``model``), so the program also exercises the
shard_map <-> GSPMD boundary in both directions.

All gather modes and both schedules are numerically equivalent (same math,
different comm placement); tests/test_multichip.py asserts the parities.

The reference has no in-tree parallelism at all (SURVEY.md §2.10); this is
the in-workload half of the TPU-native build. Checkpoint/resume across a
DIFFERENT mesh factorization is exercised in ``__graft_entry__``
(dryrun phase 5) via training/checkpoint.py's template-sharded restore.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..tpu import profiling
from .mesh import AXIS_FSDP, AXIS_MODEL, AXIS_PIPE, BATCH_AXES
from .pipeline import deinterleave_stage_params, interleave_stage_params, pipeline_apply

# the sharded weights are made before ``make_train_step``: their programs count
profiling.watch_compiles()

GATHER_MODES = ("eager", "overlap", "amortized")


@dataclass(frozen=True)
class CompositeConfig:
    vocab_size: int = 256
    d_model: int = 32
    n_heads: int = 4
    d_ff: int = 64
    n_layers: int = 4  # must divide by mesh pipe size * virtual_stages
    seq: int = 16


def _param_specs(cfg: CompositeConfig) -> Dict[str, Any]:
    """Stage-stacked weight specs. Stage dim on ``pipe``; Megatron column/
    row splits on ``model``; the remaining large dim sharded over ``fsdp``
    (ZeRO-3), gathered at use inside the stage body."""
    return {
        "ln1_scale": P(AXIS_PIPE, None, None),
        "ln2_scale": P(AXIS_PIPE, None, None),
        # [S*V, L, d, 3, d]: the qkv role dim is explicit and UNsharded — a
        # flat [d, 3d] column-shard would hand device 0 "all of q plus half
        # of k" and silently change the math between factorizations.
        "wqkv": P(AXIS_PIPE, None, AXIS_FSDP, None, AXIS_MODEL),
        "wo": P(AXIS_PIPE, None, AXIS_MODEL, AXIS_FSDP),    # [S*V, L, d/tp, d]
        "w1": P(AXIS_PIPE, None, AXIS_FSDP, AXIS_MODEL),    # [S*V, L, d, ff/tp]
        "w2": P(AXIS_PIPE, None, AXIS_MODEL, AXIS_FSDP),    # [S*V, L, ff/tp, d]
    }


def init_params(
    rng: jax.Array, cfg: CompositeConfig, mesh: Mesh, *, virtual_stages: int = 1
) -> Dict[str, Any]:
    """Global (sharded) param pytree: embed + stacked per-chunk blocks.

    Weights are drawn in canonical per-layer shape [n_layers, ...] and then
    reshaped into pp*V chunks, so the logical model is IDENTICAL across
    every (pp, virtual_stages) factorization — the parity tests and
    cross-factorization checkpoint resume depend on that. For V > 1 the
    chunk rows are permuted to the device-major round-robin layout
    :func:`kubeflow_tpu.parallel.pipeline.pipeline_apply` expects.
    """
    pp = mesh.shape[AXIS_PIPE]
    chunks = pp * virtual_stages
    if cfg.n_layers % chunks:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by "
            f"pipe={pp} * virtual_stages={virtual_stages}"
        )
    lpc = cfg.n_layers // chunks  # layers per stage chunk
    d, ff, nl = cfg.d_model, cfg.d_ff, cfg.n_layers
    ks = jax.random.split(rng, 5)
    scale = d ** -0.5

    def chunked(w):
        return w.reshape((chunks, lpc) + w.shape[1:])

    stages = {
        "ln1_scale": jnp.ones((chunks, lpc, d), jnp.float32),
        "ln2_scale": jnp.ones((chunks, lpc, d), jnp.float32),
        "wqkv": chunked(jax.random.normal(ks[0], (nl, d, 3, d), jnp.float32) * scale),
        "wo": chunked(jax.random.normal(ks[1], (nl, d, d), jnp.float32) * scale),
        "w1": chunked(jax.random.normal(ks[2], (nl, d, ff), jnp.float32) * scale),
        "w2": chunked(jax.random.normal(ks[3], (nl, ff, d), jnp.float32) * (ff ** -0.5)),
    }
    if virtual_stages > 1:
        stages = interleave_stage_params(stages, pp, virtual_stages)
    specs = _param_specs(cfg)
    stages = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k, v in stages.items()
    }
    embed = jax.device_put(
        jax.random.normal(ks[4], (cfg.vocab_size, d), jnp.float32) * scale,
        NamedSharding(mesh, P(AXIS_MODEL, None)),
    )
    return {"embed": embed, "stages": stages}


def canonical_params(
    params: Dict[str, Any], mesh: Mesh, *, virtual_stages: int = 1
) -> Dict[str, Any]:
    """Sharded stage tree -> canonical per-layer host arrays.

    Inverse of :func:`init_params`'s chunk+interleave: un-permutes the V>1
    round-robin layout and flattens [chunks, lpc, ...] back to
    [n_layers, ...]. The result is factorization-independent — the elastic
    checkpoint format (docs/ELASTICITY.md): a (pp=4, V=1) job saves here
    and a (pp=2, V=2) restart rebuilds its own chunking from it via
    :func:`params_from_canonical`.
    """
    pp = mesh.shape[AXIS_PIPE]
    stages = {
        k: np.asarray(jax.device_get(v)) for k, v in params["stages"].items()
    }
    if virtual_stages > 1:
        stages = jax.tree_util.tree_map(
            np.asarray, deinterleave_stage_params(stages, pp, virtual_stages)
        )
    stages = {k: v.reshape((-1,) + v.shape[2:]) for k, v in stages.items()}
    return {"embed": np.asarray(jax.device_get(params["embed"])), "stages": stages}


def params_from_canonical(
    canon: Dict[str, Any], cfg: CompositeConfig, mesh: Mesh, *, virtual_stages: int = 1
) -> Dict[str, Any]:
    """Canonical per-layer arrays -> the sharded stage tree for THIS mesh.

    Mirrors :func:`init_params`'s chunk/interleave/device_put exactly, so
    ``params_from_canonical(canonical_params(p, m1, V=a), cfg, m2, V=b)``
    is the same logical model on a different (pp, V) factorization.
    """
    pp = mesh.shape[AXIS_PIPE]
    chunks = pp * virtual_stages
    if cfg.n_layers % chunks:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by "
            f"pipe={pp} * virtual_stages={virtual_stages}"
        )
    lpc = cfg.n_layers // chunks
    stages = {}
    for k, v in canon["stages"].items():
        arr = jnp.asarray(v)
        stages[k] = arr.reshape((chunks, lpc) + arr.shape[1:])
    if virtual_stages > 1:
        stages = interleave_stage_params(stages, pp, virtual_stages)
    specs = _param_specs(cfg)
    stages = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k, v in stages.items()
    }
    embed = jax.device_put(
        jnp.asarray(canon["embed"]), NamedSharding(mesh, P(AXIS_MODEL, None))
    )
    return {"embed": embed, "stages": stages}


def param_shardings(cfg: CompositeConfig, mesh: Mesh) -> Dict[str, Any]:
    """NamedSharding tree matching :func:`init_params` — the checkpoint
    restore template for THIS mesh (cross-factorization resume)."""
    specs = _param_specs(cfg)
    return {
        "embed": NamedSharding(mesh, P(AXIS_MODEL, None)),
        "stages": {k: NamedSharding(mesh, s) for k, s in specs.items()},
    }


def _gather_layer(wqkv_l, wo_l, w1_l, w2_l):
    """all_gather one layer's fsdp weight shards to full (tp-local) size.

    Autodiff transposes each tiled gather into a gradient reduce_scatter —
    the ZeRO-3 contract."""
    return (
        lax.all_gather(wqkv_l, AXIS_FSDP, axis=0, tiled=True),  # [d, 3, d/tp]
        lax.all_gather(wo_l, AXIS_FSDP, axis=1, tiled=True),    # [d/tp, d]
        lax.all_gather(w1_l, AXIS_FSDP, axis=0, tiled=True),    # [d, ff/tp]
        lax.all_gather(w2_l, AXIS_FSDP, axis=1, tiled=True),    # [ff/tp, d]
    )


#: What the backward pass keeps of a block besides its arguments (``h`` and
#: the gathered weights: ``jax.checkpoint`` keeps what comes in): the matmul
#: outputs and both ``psum`` results. Scores, mask, softmax, the GELU's and
#: LayerNorm's pieces are recomputed from these in the backward, so no
#: [heads, seq, seq] buffer is stacked over the layers, and no collective
#: runs twice.
SAVED_IN_BLOCK = ("qkv", "ctx", "attn_out", "pre", "mlp_out")


def _remat(block):
    """``block`` under :data:`SAVED_IN_BLOCK`'s policy. ``_stage_fn`` hands
    it a fresh callable every trace: ``jax.checkpoint`` keeps a traced
    function by identity, and a step built after this module's ``lax`` was
    swapped (the benchmark's left-out-exchange fault) must trace anew."""
    return jax.checkpoint(
        block, policy=jax.checkpoint_policies.save_only_these_names(*SAVED_IN_BLOCK))


def _block(cfg: CompositeConfig, h, ln1, ln2, wqkv, wo, w1, w2):
    """One transformer block, weights fully gathered over fsdp (still
    tp-local): Megatron column/row splits with one psum per sublayer."""

    def ln(x, scale):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale

    # attention: column-split QKV -> local heads; causal; row-split WO
    with jax.named_scope("attn"):
        x = ln(h, ln1)
        qkv = checkpoint_name(
            jnp.einsum("bsd,drh->bsrh", x, wqkv), "qkv")  # [mb, s, 3, d/tp]
        dl = qkv.shape[-1]                               # d/tp local width
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        hd = cfg.d_model // cfg.n_heads
        nh = dl // hd                                    # local heads
        mb, s, _ = q.shape
        q = q.reshape(mb, s, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(mb, s, nh, hd).transpose(0, 2, 1, 3)
        v = v.reshape(mb, s, nh, hd).transpose(0, 2, 1, 3)
        scores = (q @ k.transpose(0, 1, 3, 2)) * (hd ** -0.5)
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -1e30)
        attn = jax.nn.softmax(scores, axis=-1) @ v       # [mb, nh, s, hd]
        attn = checkpoint_name(attn.transpose(0, 2, 1, 3).reshape(mb, s, dl), "ctx")
        # row-split output proj: partial sums reduced over the model axis
        h = h + checkpoint_name(lax.psum(attn @ wo, AXIS_MODEL), "attn_out")
    # mlp: column-split W1 (no comm), row-split W2 (+psum)
    with jax.named_scope("mlp"):
        x = ln(h, ln2)
        pre = checkpoint_name(x @ w1, "pre")
        h = h + checkpoint_name(lax.psum(jax.nn.gelu(pre) @ w2, AXIS_MODEL), "mlp_out")
    return h


def _stage_fn(
    cfg: CompositeConfig,
    p: Dict[str, jax.Array],
    h: jax.Array,
    *,
    gather_mode: str = "eager",
) -> jax.Array:
    """One pipeline stage chunk = lpc transformer blocks, manual SPMD.

    ``p`` leaves are LOCAL shards [lpc, ...] (chunk dim already selected by
    the pipeline body); ``h`` is the local microbatch [mb_local, seq, d].
    ``gather_mode`` picks where the fsdp all_gathers run: per-layer at use
    ("eager"), prefetched one layer ahead in a double-buffered scan carry
    ("overlap"), or not at all because the caller pre-gathered via
    ``stage_prepare`` ("pregathered", the amortized path).
    """
    lns = (p["ln1_scale"], p["ln2_scale"])
    ws = (p["wqkv"], p["wo"], p["w1"], p["w2"])
    run_block = _remat(functools.partial(_block, cfg))

    if gather_mode == "overlap":
        lpc = p["ln1_scale"].shape[0]

        def gather_at(i):
            return _gather_layer(
                *(lax.dynamic_index_in_dim(w, i, keepdims=False) for w in ws)
            )

        def body(carry, i):
            h, g = carry
            # Issue layer i+1's gathers BEFORE touching layer i's weights:
            # the collectives have no data dependence on the block compute,
            # so the compiler can run them concurrently (async collectives
            # on TPU), hiding gather latency behind the matmuls. The final
            # iteration prefetches a clamped duplicate that is discarded.
            g_next = gather_at(jnp.minimum(i + 1, lpc - 1))
            ln1, ln2 = (
                lax.dynamic_index_in_dim(s, i, keepdims=False) for s in lns
            )
            h = run_block(h, ln1, ln2, *g)
            return (h, g_next), None

        (h, _), _ = lax.scan(body, (h, gather_at(0)), jnp.arange(lpc))
        return h

    def block(h, layer):
        ln1, ln2, wqkv_l, wo_l, w1_l, w2_l = layer
        if gather_mode == "pregathered":
            wqkv, wo, w1, w2 = wqkv_l, wo_l, w1_l, w2_l
        else:  # eager: gather the weight shard right before use (ZeRO-3)
            wqkv, wo, w1, w2 = _gather_layer(wqkv_l, wo_l, w1_l, w2_l)
        return run_block(h, ln1, ln2, wqkv, wo, w1, w2), None

    h, _ = lax.scan(block, h, lns + ws)
    return h


def _stage_prepare_fn(p: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Amortized-mode hook: gather ALL chunk weights once per train step.

    Runs inside the pipeline's shard_map before the time scan, on local
    leaves [V, lpc, ...] — the fsdp-sharded axes sit one dim further right
    than in the per-layer gathers. The prepared tree is a scan constant:
    each weight's gradient reduce-scatter runs once per step instead of
    once per microbatch."""
    return {
        "ln1_scale": p["ln1_scale"],
        "ln2_scale": p["ln2_scale"],
        "wqkv": lax.all_gather(p["wqkv"], AXIS_FSDP, axis=2, tiled=True),
        "wo": lax.all_gather(p["wo"], AXIS_FSDP, axis=3, tiled=True),
        "w1": lax.all_gather(p["w1"], AXIS_FSDP, axis=2, tiled=True),
        "w2": lax.all_gather(p["w2"], AXIS_FSDP, axis=3, tiled=True),
    }


def make_train_step(
    cfg: CompositeConfig,
    mesh: Mesh,
    lr: float = 0.1,
    *,
    virtual_stages: int = 1,
    gather_mode: str = "eager",
    mask_bubbles: bool = True,
):
    """jit-able (params, ids[M, mb, seq]) -> (params, loss): one SGD step of
    next-token CE under the full dp x fsdp x tp x pp composition.

    ``virtual_stages``/``gather_mode``/``mask_bubbles`` pick the schedule
    and comm placement (see module docstring); every combination computes
    the same math. ``params`` must come from :func:`init_params` with the
    same ``virtual_stages``.
    """
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"gather_mode must be one of {GATHER_MODES}, got {gather_mode!r}")
    batch_spec = P(None, BATCH_AXES, None)  # [M, mb, seq]
    h_spec = P(None, BATCH_AXES, None, None)  # [M, mb, seq, d]
    specs = _param_specs(cfg)
    inner_mode = "pregathered" if gather_mode == "amortized" else gather_mode
    stage_prepare = _stage_prepare_fn if gather_mode == "amortized" else None

    def loss_fn(params, ids):
        # GSPMD region: embedding lookup, vocab sharded over `model`
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], ids, axis=0)  # [M, mb, s, d]
        h = pipeline_apply(
            lambda p, hh: _stage_fn(cfg, p, hh, gather_mode=inner_mode),
            params["stages"],
            h,
            mesh,
            param_specs={k: specs[k] for k in params["stages"]},
            x_spec=h_spec,
            out_spec=h_spec,
            virtual_stages=virtual_stages,
            mask_bubbles=mask_bubbles,
            stage_prepare=stage_prepare,
        )
        with jax.named_scope("unembed"):
            logits = h @ params["embed"].T  # [M, mb, s, vocab]
            targets = jnp.roll(ids, -1, axis=-1)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(logp, targets[..., None], axis=-1))

    def step(params, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        with jax.named_scope("optimizer"):
            params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads)
        return params, loss

    in_sharding = (param_shardings(cfg, mesh), NamedSharding(mesh, batch_spec))
    return jax.jit(step, in_shardings=in_sharding,
                   out_shardings=(in_sharding[0], NamedSharding(mesh, P())))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(None, BATCH_AXES, None))
