"""GPT-style decoder-only causal LM — the long-context flagship.

The platform's transformer training family (BASELINE's BERT covers the
serving/MLM path; this covers autoregressive training at long sequence
lengths). TPU-first choices:

- attention runs the Pallas flash kernel (ops/flash_attention) by default —
  fused, O(L) memory, causal masking inside the kernel; the attention fn is
  injectable so ring attention (parallel/ring_attention) drops in for
  sequence parallelism over the ``seq`` mesh axis,
- rotary position embeddings (no learned position table to shard),
- pre-LN blocks, bf16 activations / f32 params + norms,
- parameter names follow kubeflow_tpu.parallel.sharding's logical-axis
  conventions (query/key/value → heads, up_proj/down_proj → mlp,
  embedding → vocab/embed), so dp/fsdp/tp placement is a rules swap,
- optional MoE FFN (parallel/moe) for expert parallelism,
- optional per-block remat (``jax.checkpoint`` under ``SAVED_IN_BLOCK``'s
  policy): the backward keeps a block's input, its matmul outputs and the
  flash kernel's residuals, and recomputes only the elementwise pieces.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from kubeflow_tpu.ops.flash_attention import SAVED_RESIDUALS, flash_attention

#: What ``cfg.remat`` keeps of a block for its backward, besides the block's
#: input: every value whose recomputation would cost a matmul or the flash
#: kernel. The three projections before RoPE, the kernel's output and
#: log-sum-exp, the output projection's result (the second LayerNorm reads
#: the block's input plus it) and the up-projection's result before the
#: GELU. Both LayerNorms, RoPE, the GELU, casts and residual sums are
#: recomputed. The down-projection's result feeds only the residual sum,
#: whose backward reads nothing, so it is not kept. An MoE FFN names nothing
#: and is recomputed whole.
SAVED_IN_BLOCK = ("query", "key", "value", *SAVED_RESIDUALS, "attn_out", "mlp_pre")


def _remat(block, **kwargs):
    """``block`` (a module class) under :data:`SAVED_IN_BLOCK`'s policy, for
    both of ``GptLM``'s layouts."""
    return nn.remat(
        block, policy=jax.checkpoint_policies.save_only_these_names(*SAVED_IN_BLOCK),
        **kwargs)


def _kept(cfg: "GptConfig", x: jax.Array, name: str) -> jax.Array:
    """``x`` under ``name`` where ``cfg.remat`` asks for :func:`_remat`'s
    policy, ``x`` itself elsewhere. Outside a ``jax.checkpoint`` a name
    computes nothing, but it is an equation, and a module's second distinct
    one renumbers its other private functions as it lowers (``_where_116``
    becomes ``_where_117``): another text and compile-cache key for the same
    instructions. So the serving programs of this block hold no name."""
    return checkpoint_name(x, name) if cfg.remat else x


@dataclass(frozen=True)
class GptConfig:
    vocab_size: int = 32000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # MoE: num_experts=0 = dense FFN; >0 replaces the MLP every block.
    num_experts: int = 0
    moe_k: int = 2
    # scan_blocks: stack the transformer blocks as ONE ``nn.scan`` over
    # layer-stacked params instead of n_layers unrolled calls — compile
    # time and program size stop growing with depth (the 24-layer bench
    # config traces one block). Param tree changes from ``block_{i}/...``
    # to ``blocks/...`` with a leading layer axis; ``stack_block_params``
    # converts. Training/forward only — the decode path keeps the unrolled
    # layout its per-layer cache naming depends on.
    scan_blocks: bool = False

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @classmethod
    def tiny(cls) -> "GptConfig":
        return cls(vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq=128)

    @classmethod
    def small(cls) -> "GptConfig":
        return cls(d_model=768, n_layers=12, n_heads=12, d_ff=3072)  # ~GPT-2 124M

    @classmethod
    def base(cls) -> "GptConfig":
        return cls(d_model=1024, n_layers=24, n_heads=16, d_ff=4096)  # ~GPT-2 medium


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [b, L, heads, head_dim]; positions: [L] (shared
    across the batch) or [b, L] (per-row — continuous batching, where each
    slot sits at its own sequence position)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., L, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if positions.ndim == 1:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:  # [b, L, half] -> broadcast over heads
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    return flash_attention(q, k, v, causal=True)


class GptAttention(nn.Module):
    cfg: GptConfig
    attention_fn: Callable = causal_flash_attention
    decode: bool = False
    per_slot: bool = False  # per-row cache cursors (continuous batching)
    # paged: per-slot decode against a shared block arena + per-call block
    # tables instead of a contiguous [b, max_seq] cache (ISSUE 12). The
    # cache collection holds "k_arena"/"v_arena" [kv_blocks, kv_block_t,
    # h, d] (last row = trash block) and "cursors" [b]; the caller passes
    # the [b, max_blocks] table each apply.
    paged: bool = False
    kv_blocks: int = 0
    kv_block_t: int = 16
    # kv_dtype: arena storage precision (ISSUE 18). "bf16" stores cfg.dtype
    # directly (bit-parity ground truth); "int8" stores symmetric
    # per-(row, head) quantized values with an f32 scale arena alongside
    # ("k_scale"/"v_scale" [kv_blocks, kv_block_t, h, 1]) — 2x KV positions
    # per HBM byte, dequantized to f32 at the attention read.
    kv_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype {self.kv_dtype!r}: expected bf16|int8")
        if self.kv_dtype == "int8" and self.decode and not self.paged:
            raise ValueError("int8 KV cache requires the paged arena layout")
        dense = functools.partial(
            nn.DenseGeneral,
            features=(cfg.n_heads, cfg.head_dim),
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=jnp.float32,
            use_bias=False,
        )
        if self.decode:
            if self.paged:
                if not self.per_slot:
                    raise ValueError("paged KV decode requires per_slot=True")
                return self._paged_decode_attention(x, dense, block_tables)
            return self._decode_attention(x, dense)

        def proj(name):
            return _kept(cfg, dense(name=name)(x), name)

        q = rope(proj("query"), positions, cfg.rope_theta)
        k = rope(proj("key"), positions, cfg.rope_theta)
        v = proj("value")
        ctx = self.attention_fn(q, k, v)  # [b, L, heads, head_dim]
        return _kept(cfg, self._out_proj(ctx), "attn_out")

    def _out_proj(self, ctx: jax.Array) -> jax.Array:
        return nn.DenseGeneral(
            features=self.cfg.d_model,
            axis=(-2, -1),
            dtype=self.cfg.dtype,
            param_dtype=jnp.float32,
            use_bias=False,
            name="out_proj",
        )(ctx)

    def _decode_attention(self, x: jax.Array, dense) -> jax.Array:
        """Incremental attention against a KV cache (prefill: L>1 from
        position 0; decode steps: L==1 appended at the cache cursor).
        Static shapes throughout — the cache is [b, max_seq, h, d] and the
        validity mask makes unwritten slots invisible.

        ``per_slot=True`` keeps a cursor PER ROW (``cursors`` [b]) so every
        batch slot sits at its own sequence position — the cache layout
        continuous batching needs (serving/continuous.py): sequences join
        and leave the running batch without touching other rows.
        """
        cfg = self.cfg
        b, seg_len = x.shape[0], x.shape[1]
        cache_k = self.variable(
            "cache", "k", jnp.zeros, (b, cfg.max_seq, cfg.n_heads, cfg.head_dim), cfg.dtype
        )
        cache_v = self.variable(
            "cache", "v", jnp.zeros, (b, cfg.max_seq, cfg.n_heads, cfg.head_dim), cfg.dtype
        )
        if self.per_slot:
            cursors = self.variable("cache", "cursors", lambda: jnp.zeros((b,), jnp.int32))
            start = cursors.value                                   # [b]
            seg_positions = start[:, None] + jnp.arange(seg_len)    # [b, L]
            q = rope(dense(name="query")(x), seg_positions, cfg.rope_theta)
            k = rope(dense(name="key")(x), seg_positions, cfg.rope_theta)
            v = dense(name="value")(x)
            with jax.named_scope("kv_write"):
                if seg_len == 1:
                    # broadcast-select instead of vmapped
                    # dynamic_update_slice: the vmap form lowers to a
                    # scatter (measured ~3x slower per decode step); a
                    # where over the cache fuses into one elementwise pass
                    at = (jnp.arange(cfg.max_seq)[None, :, None, None]
                          == start[:, None, None, None])        # [b,max,1,1]
                    keys = jnp.where(at, k, cache_k.value)
                    values = jnp.where(at, v, cache_v.value)
                else:
                    upd = jax.vmap(
                        lambda cache_row, seg, s: jax.lax.dynamic_update_slice(
                            cache_row, seg, (s, 0, 0))
                    )
                    keys = upd(cache_k.value, k, start)
                    values = upd(cache_v.value, v, start)
            mask = (jnp.arange(cfg.max_seq)[None, None, None, :]
                    <= seg_positions[:, None, :, None])             # [b,1,L,max]
        else:
            cursor = self.variable("cache", "cursor", lambda: jnp.zeros((), jnp.int32))
            start = cursor.value
            seg_positions = start + jnp.arange(seg_len)
            q = rope(dense(name="query")(x), seg_positions, cfg.rope_theta)
            k = rope(dense(name="key")(x), seg_positions, cfg.rope_theta)
            v = dense(name="value")(x)
            with jax.named_scope("kv_write"):
                keys = jax.lax.dynamic_update_slice(cache_k.value, k, (0, start, 0, 0))
                values = jax.lax.dynamic_update_slice(cache_v.value, v, (0, start, 0, 0))
            mask = (jnp.arange(cfg.max_seq)[None, None, None, :]
                    <= seg_positions[None, None, :, None])
        # flax init runs the forward once for shapes/params — the cache must
        # not advance then, or the first real prefill starts mid-cache.
        if not self.is_initializing():
            cache_k.value = keys
            cache_v.value = values
            if self.per_slot:
                cursors.value = start + seg_len
            else:
                cursor.value = start + seg_len

        return self._out_proj(self._masked_attention(q, keys, values, mask))

    def _masked_attention(self, q: jax.Array, keys: jax.Array,
                          values: jax.Array, mask: jax.Array) -> jax.Array:
        """Scores, mask, softmax and context in float32 over the whole
        view it is given ([b, max_seq] from the contiguous cache, the
        table's columns from the paged arena), for both decode paths;
        returns the context in the compute type. The float32 conversion of
        the view belongs to ``kv_gather`` and the rest to ``attn_scores``.
        Each operation is traced where it always was, so the names move no
        instruction."""
        f32 = jnp.float32
        scale = self.cfg.head_dim**-0.5
        with jax.named_scope("attn_scores"):
            qf = q.astype(f32)
        with jax.named_scope("kv_gather"):
            kf = keys.astype(f32)
        with jax.named_scope("attn_scores"):
            scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
        with jax.named_scope("kv_gather"):
            vf = values.astype(f32)
        with jax.named_scope("attn_scores"):
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
        return ctx.astype(self.cfg.dtype)

    def _paged_decode_attention(self, x: jax.Array, dense,
                                block_tables: jax.Array) -> jax.Array:
        """Per-slot decode against the shared block arena (ISSUE 12).

        Same math as the per-slot branch of :meth:`_decode_attention`, with
        the [b, max_seq] cache replaced by an indirect view: the write goes
        through the block table (``ops.kv_cache.kv_block_update``, one XLA
        scatter a token), and the read gathers ``arena[tables]`` back
        into a [b, max_blocks*block_t, h, d] view. When ``block_t`` divides
        ``max_seq`` (the engine enforces it) that view has exactly the
        contiguous cache's shape, so the masked softmax/einsum below is
        bit-identical to the contiguous path — the parity suite's contract.
        Rows whose table entries point at the trash block read garbage
        there, but only at positions the ``<= cursor`` mask already hides.

        The view is as wide as the table: a caller that passes only the
        first ``c`` columns (the engine does, ``c`` covering the longest
        granted row of the dispatch) gets gather, conversion, scores,
        softmax and context over ``c * block_t`` positions. Every live
        row's cursor lies inside its granted blocks, so the positions left
        out are ones the mask zeroes anyway; a row that writes beyond the
        columns passed (dead, or past its budget) writes to trash.
        """
        cfg = self.cfg
        b, seg_len = x.shape[0], x.shape[1]
        quant = self.kv_dtype == "int8"
        arena_shape = (max(self.kv_blocks, 1), self.kv_block_t,
                       cfg.n_heads, cfg.head_dim)
        arena_dtype = jnp.int8 if quant else cfg.dtype
        cache_k = self.variable("cache", "k_arena", jnp.zeros, arena_shape, arena_dtype)
        cache_v = self.variable("cache", "v_arena", jnp.zeros, arena_shape, arena_dtype)
        if quant:
            scale_shape = arena_shape[:3] + (1,)
            scale_k = self.variable("cache", "k_scale", jnp.zeros, scale_shape, jnp.float32)
            scale_v = self.variable("cache", "v_scale", jnp.zeros, scale_shape, jnp.float32)
        cursors = self.variable("cache", "cursors", lambda: jnp.zeros((b,), jnp.int32))
        if block_tables is None:
            raise ValueError("paged decode needs block_tables=[b, max_blocks]")
        start = cursors.value                                   # [b]
        seg_positions = start[:, None] + jnp.arange(seg_len)    # [b, L]
        q = rope(dense(name="query")(x), seg_positions, cfg.rope_theta)
        k = rope(dense(name="key")(x), seg_positions, cfg.rope_theta)
        v = dense(name="value")(x)
        from ..ops.kv_cache import kv_block_update, quantize_kv

        with jax.named_scope("kv_write"):
            if quant:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                keys_arena = kv_block_update(
                    cache_k.value, kq, start, block_tables, max_seq=cfg.max_seq)
                vals_arena = kv_block_update(
                    cache_v.value, vq, start, block_tables, max_seq=cfg.max_seq)
                k_scales = kv_block_update(
                    scale_k.value, ks, start, block_tables, max_seq=cfg.max_seq)
                v_scales = kv_block_update(
                    scale_v.value, vs, start, block_tables, max_seq=cfg.max_seq)
            else:
                keys_arena = kv_block_update(
                    cache_k.value, k, start, block_tables, max_seq=cfg.max_seq)
                vals_arena = kv_block_update(
                    cache_v.value, v, start, block_tables, max_seq=cfg.max_seq)
        if not self.is_initializing():
            cache_k.value = keys_arena
            cache_v.value = vals_arena
            if quant:
                scale_k.value = k_scales
                scale_v.value = v_scales
            cursors.value = start + seg_len

        bt = arena_shape[1]
        mb = block_tables.shape[1]
        view = (b, mb * bt, cfg.n_heads, cfg.head_dim)
        with jax.named_scope("kv_gather"):
            # every slot's view out of the arena, as wide as the table
            if quant:
                # load-dequantized read: gather values + scales through
                # the same table, dequantize to f32 (the einsums are f32
                # regardless)
                sview = (b, mb * bt, cfg.n_heads, 1)
                keys = (keys_arena[block_tables].reshape(view).astype(jnp.float32)
                        * k_scales[block_tables].reshape(sview))
                values = (vals_arena[block_tables].reshape(view).astype(jnp.float32)
                          * v_scales[block_tables].reshape(sview))
            else:
                keys = keys_arena[block_tables].reshape(view)
                values = vals_arena[block_tables].reshape(view)
        mask = (jnp.arange(mb * bt)[None, None, None, :]
                <= seg_positions[:, None, :, None])             # [b,1,L,mb*bt]
        return self._out_proj(self._masked_attention(q, keys, values, mask))


class GptMlp(nn.Module):
    cfg: GptConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, param_dtype=jnp.float32,
                     use_bias=False, name="up_proj")(x)
        h = nn.gelu(_kept(cfg, h, "mlp_pre"))
        return nn.Dense(cfg.d_model, dtype=cfg.dtype, param_dtype=jnp.float32,
                        use_bias=False, name="down_proj")(h)


class GptBlock(nn.Module):
    cfg: GptConfig
    attention_fn: Callable = causal_flash_attention
    mesh: Optional[Any] = None
    decode: bool = False
    per_slot: bool = False
    paged: bool = False
    kv_blocks: int = 0
    kv_block_t: int = 16
    kv_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        ln = functools.partial(nn.LayerNorm, dtype=jnp.float32, param_dtype=jnp.float32)
        x = x + GptAttention(cfg, self.attention_fn, self.decode, self.per_slot,
                             self.paged, self.kv_blocks, self.kv_block_t,
                             self.kv_dtype, name="attention")(
            ln(name="ln_attn")(x).astype(cfg.dtype), positions, block_tables
        )
        normed = ln(name="ln_mlp")(x).astype(cfg.dtype)
        if cfg.num_experts > 0:
            from kubeflow_tpu.parallel.moe import MoEMlp

            ffn = MoEMlp(
                num_experts=cfg.num_experts,
                d_ff=cfg.d_ff,
                k=cfg.moe_k,
                mesh=self.mesh,
                dtype=cfg.dtype,
                name="moe",
            )(normed)
        else:
            ffn = GptMlp(cfg, name="mlp")(normed)
        return x + ffn

    def scan_body(self, x: jax.Array, positions: jax.Array):
        """(carry, ys) form of ``__call__`` for ``nn.scan`` (cfg.scan_blocks)."""
        return self(x, positions), None


class GptLM(nn.Module):
    """Decoder-only LM. input_ids [b, L] -> logits [b, L, vocab] (f32).

    The output projection ties to the input embedding (standard GPT-2
    weight tying — halves the largest parameter and its gradient traffic).
    """

    cfg: GptConfig
    attention_fn: Callable = causal_flash_attention
    mesh: Optional[Any] = None
    decode: bool = False
    per_slot: bool = False
    paged: bool = False
    kv_blocks: int = 0
    kv_block_t: int = 16
    kv_dtype: str = "bf16"

    @nn.compact
    def __call__(self, input_ids: jax.Array, *,
                 block_tables: Optional[jax.Array] = None,
                 return_hidden: bool = False) -> jax.Array:
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=jnp.float32,
            name="embedding",
        )
        x = embed(input_ids)
        positions = jnp.arange(input_ids.shape[1])  # decode path derives its own
        if cfg.scan_blocks and not self.decode:
            # One traced block, n_layers iterations: params stack on a
            # leading layer axis under ``blocks/``; remat wraps the body, so
            # the backward scan reads ``SAVED_IN_BLOCK`` stacked over the
            # layers and runs no forward matmul or kernel again.
            body = GptBlock
            if cfg.remat:
                body = _remat(body, prevent_cse=False, methods=["scan_body"])
            stack = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=cfg.n_layers,
                methods=["scan_body"],
            )
            x, _ = stack(cfg, self.attention_fn, self.mesh,
                         name="blocks").scan_body(x, positions)
        else:
            if cfg.scan_blocks and self.decode:
                raise ValueError(
                    "scan_blocks is a training/forward layout; the decode path "
                    "needs per-layer cache naming — unstack the params "
                    "(inverse of stack_block_params) and decode with "
                    "scan_blocks=False"
                )
            block = GptBlock
            if cfg.remat:
                block = _remat(GptBlock, static_argnums=())
            for i in range(cfg.n_layers):
                x = block(cfg, self.attention_fn, self.mesh, self.decode,
                          self.per_slot, self.paged, self.kv_blocks,
                          self.kv_block_t, self.kv_dtype,
                          name=f"block_{i}")(x, positions, block_tables)
        x = nn.LayerNorm(dtype=jnp.float32, param_dtype=jnp.float32, name="ln_final")(x)
        if return_hidden:
            # final hidden states for a fused loss (blockwise_causal_lm_loss)
            # — the [b, L, vocab] logits never materialize
            return x.astype(jnp.float32)
        # tied LM head in f32 (embed.attend would compute in the module's
        # bf16 dtype; the final softmax wants full precision)
        with jax.named_scope("lm_head"):
            logits = x.astype(jnp.float32) @ embed.embedding.T.astype(jnp.float32)
        return logits


def causal_lm_loss(logits: jax.Array, input_ids: jax.Array) -> jax.Array:
    """Next-token cross entropy; position t predicts token t+1."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    targets = input_ids[:, 1:]
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def blockwise_causal_lm_loss(
    hidden: jax.Array,
    embedding: jax.Array,
    input_ids: jax.Array,
    block_size: int = 4096,
) -> jax.Array:
    """Fused next-token cross entropy over a tied LM head that never
    materializes the ``[b, L, vocab]`` f32 logits.

    Same math as ``causal_lm_loss(hidden @ embedding.T, ids)``:
    ``loss = mean(logsumexp(x·W^T) - x·W[target])``, with the logsumexp
    accumulated ONLINE over vocab chunks (running max + rescaled sum — the
    ``causal_flash_attention`` trick applied to the vocab axis). Peak
    residency is one ``[tokens, block_size]`` chunk instead of the full
    ``[b, L, vocab]`` f32 logits (1 GiB at the bench's b8/L1024/V32000,
    ~3x that through log_softmax), which is what caps the benchable batch.
    The scan body is ``jax.checkpoint``ed so backward recomputes each
    chunk's logits instead of saving them.

    ``hidden``: [b, L, d] final hidden states (``GptLM(...)(ids,
    return_hidden=True)``); ``embedding``: the [vocab, d] tied embedding
    (``params["embedding"]["embedding"]``) — gradients flow to both.
    """
    b, seq_len, d = hidden.shape
    vocab = embedding.shape[0]
    x = hidden[:, :-1].reshape(b * (seq_len - 1), d).astype(jnp.float32)
    targets = input_ids[:, 1:].reshape(-1)

    n_blocks = -(-vocab // block_size)
    padded = n_blocks * block_size
    w = embedding.astype(jnp.float32)
    if padded != vocab:
        w = jnp.pad(w, ((0, padded - vocab), (0, 0)))
    w = w.reshape(n_blocks, block_size, d)
    valid = (jnp.arange(padded) < vocab).reshape(n_blocks, block_size)

    def body(carry, wv):
        wb, valid_b = wv
        m, s = carry
        logits = jax.lax.dot_general(
            x, wb, (((1,), (1,)), ((), ())))          # [tokens, block_size]
        logits = jnp.where(valid_b[None, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        return (m_new, s), None

    init = (
        jnp.full((x.shape[0],), -1e30, jnp.float32),
        jnp.zeros((x.shape[0],), jnp.float32),
    )
    (m, s), _ = jax.lax.scan(jax.checkpoint(body), init, (w, valid))
    lse = m + jnp.log(s)
    # target logit via a [tokens, d] gather — never the full logits row
    target_logit = jnp.sum(x * embedding[targets].astype(jnp.float32), axis=-1)
    return jnp.mean(lse - target_logit)


def stack_block_params(params: Any, n_layers: int) -> Any:
    """Convert an unrolled-layout param tree (``block_0..block_{n-1}``) to
    the ``scan_blocks=True`` layout (``blocks`` with a leading layer axis).
    Lets loop-trained checkpoints load into the scanned model (the decode
    path keeps the unrolled layout, so serving checkpoints stay as-is)."""
    layers = [params[f"block_{i}"] for i in range(n_layers)]
    out = {k: v for k, v in params.items() if not k.startswith("block_")}
    out["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return out


@functools.lru_cache(maxsize=64)
def _generate_fn(cfg: GptConfig, max_new_tokens: int, temperature: float):
    """One compiled decode program per (config, token budget, temperature);
    prompt shape differences re-specialize inside the same jit cache."""
    model = GptLM(cfg, decode=True)

    def sample(logits: jax.Array, key: jax.Array) -> jax.Array:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)

    @jax.jit
    def run(params, cache, prompt_ids, rng):
        logits, updated = model.apply(
            {"params": params, "cache": cache}, prompt_ids, mutable=["cache"]
        )
        rng, key = jax.random.split(rng)
        tok = sample(logits[:, -1], key)

        def step(carry, _):
            cache, tok, rng = carry
            logits, updated = model.apply(
                {"params": params, "cache": cache}, tok[:, None], mutable=["cache"]
            )
            rng, key = jax.random.split(rng)
            nxt = sample(logits[:, -1], key)
            return (updated["cache"], nxt, rng), tok

        (cache, last, rng), toks = jax.lax.scan(
            step, (updated["cache"], tok, rng), None, length=max_new_tokens - 1
        )
        generated = jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
        return jnp.concatenate([prompt_ids.astype(jnp.int32), generated], axis=1)

    return model, run


def generate(
    cfg: GptConfig,
    params: Any,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    temperature: float = 0.0,
) -> jax.Array:
    """Autoregressive decoding with a KV cache: one prefill forward over the
    prompt, then `lax.scan` single-token steps — static shapes throughout
    (the TPU decoding recipe), with the compiled program cached across calls
    per (config, max_new_tokens, temperature, prompt shape).
    ``temperature=0`` is greedy; otherwise samples.

    Returns [batch, prompt_len + max_new_tokens] token ids (int32).
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    total = prompt_ids.shape[1] + max_new_tokens
    if total > cfg.max_seq:
        raise ValueError(f"prompt+new = {total} exceeds max_seq {cfg.max_seq}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    _, run = _generate_fn(cfg, max_new_tokens, float(temperature))
    return run(params, _fresh_cache(cfg, prompt_ids.shape[0]), prompt_ids, rng)


def _fresh_cache(cfg: GptConfig, batch: int) -> Any:
    """Zeroed KV cache in the exact structure GptLM(decode=True) owns —
    closed-form from the config, no tracing on the request path. (Module
    naming drift would break `generate` outright, which the decode tests
    catch.)"""
    kv_shape = (batch, cfg.max_seq, cfg.n_heads, cfg.head_dim)
    return {
        f"block_{i}": {
            "attention": {
                "k": jnp.zeros(kv_shape, cfg.dtype),
                "v": jnp.zeros(kv_shape, cfg.dtype),
                "cursor": jnp.zeros((), jnp.int32),
            }
        }
        for i in range(cfg.n_layers)
    }
