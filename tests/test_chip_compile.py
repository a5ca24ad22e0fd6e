"""The main path's Pallas kernels, compiled at real widths by the TPU's own
compiler for a described (not attached) v5e — guide ``on-chip-measurement``
§2.3. Interpret mode passes kernels the chip refuses: a strided slice Mosaic
cannot lower, a block that overflows VMEM. Nothing runs here, so a pass says
"lowers and fits", never "correct" or "fast"."""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.ops.chunk_attention import chunk_attention
from kubeflow_tpu.ops.flash_attention import flash_attention
from kubeflow_tpu.ops.fused_bottleneck import fused_bottleneck, fused_transition
from kubeflow_tpu.ops.grouped_matmul import grouped_matmul, grouped_swiglu
from kubeflow_tpu.ops.head_choice import head_choice
from kubeflow_tpu.ops.paged_attention import paged_decode_attention
from kubeflow_tpu.ops.ssm import ssm_decode_update

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2. The persistent compile cache is off around the
    module: an entry written without a chip cannot be read back and only
    warns."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Sharding on one described v5e device."""
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _grouped_kernels(text):
    """The expert layers' grouped products in a compiled program: the gated
    pair and the down product, a Mosaic kernel each."""
    return sum('custom_call_target="tpu_custom_call"' in line
               and ("grouped_swiglu" in line or "grouped_matmul" in line)
               for line in text.splitlines())


def _bottleneck_shapes(n, hw, cin, cmid, cout, proj):
    shapes = [((n, hw, hw, cin), BF16),
              ((cin, cmid), F32), ((cmid,), F32), ((cmid,), F32),
              ((3, 3, cmid, cmid), F32), ((cmid,), F32), ((cmid,), F32),
              ((cmid, cout), F32), ((cout,), F32), ((cout,), F32)]
    if proj:
        shapes += [((cin, cout), F32), ((cout,), F32), ((cout,), F32)]
    return shapes


def test_flash_attention_fwd_bwd_gpt_train_shape(chip):
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(F32).sum()

    qkv = [((8, 1024, 16, 64), BF16)] * 3   # the GPT row: b8 L1024 h16 d64
    assert _compile(chip, jax.grad(loss, argnums=(0, 1, 2)), *qkv) == 3


def test_flash_attention_fwd_bwd_gpt_default_length(chip):
    """GPT's default length of 2,048: a grid of 2 x 2 blocks of 1,024, whose
    steps the kernels classify by program id (the diagonal's two walked in
    sub-tiles, the one below it whole, the one above skipped)."""
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(F32).sum()

    qkv = [((8, 2048, 16, 64), BF16)] * 3
    assert _compile(chip, jax.grad(loss, argnums=(0, 1, 2)), *qkv) == 3


def test_fused_bottleneck_identity_stage4(chip):
    # hw7, 2048 -> 512 -> 2048: the widest weights (the VMEM-binding stage)
    shapes = _bottleneck_shapes(256, 7, 2048, 512, 2048, proj=False)
    assert _compile(
        chip, lambda *a: fused_bottleneck(*a, interpret=False), *shapes) == 1


@pytest.mark.parametrize("hw,cin,cmid,cout,stride", [
    (56, 64, 64, 256, 1),       # stage-1 head: channel-expanding, stride 1
    (56, 256, 128, 512, 2),     # stage-2 head
    (14, 1024, 512, 2048, 2),   # stage-4 head: the VMEM-binding one
])
def test_fused_transition_resnet50_heads(chip, hw, cin, cmid, cout, stride):
    shapes = _bottleneck_shapes(256, hw, cin, cmid, cout, proj=True)
    assert _compile(
        chip,
        lambda *a: fused_transition(*a, stride=stride, interpret=False),
        *shapes) == 1


# A MiMo-V2 prefill chunk of 1,024 positions at the published widths (qk
# 192, v 128, 64 query heads): a full layer's 4 KV heads x 16 query heads as
# rows against an 8,192-position view, and a window layer's 8 x 8 against a
# ring of 160 positions and the chunk itself, with its sinks.
@pytest.mark.parametrize("kv,group,keys,window", [(4, 16, 8192, None), (8, 8, 160 + 1024, 128)],
                         ids=["full", "window"])
def test_chunk_attention_mimo_prefill_shape(chip, kv, group, keys, window):
    rows = 1024 * group

    def attend(q, k, v, q_pos, k_pos, sink):
        return chunk_attention(q, k, v, q_pos, k_pos, scale=192 ** -0.5, window=window,
                               sink=sink if window else None, interpret=False)

    shapes = [((kv, rows, 192), BF16), ((kv, keys, 192), BF16), ((kv, keys, 128), BF16),
              ((rows,), I32), ((keys,), I32), ((kv, rows), F32)]
    assert _compile(chip, attend, *shapes) == 1


# The MiMo cell's decode step: 32 slots, 64 query heads handed over heads
# apart (4 KV heads x 192), the full kind's arenas of 16,384 blocks of 16
# and the trash block, the block table at each of the four view widths. A
# pass says the kernel lowers and its buffers fit VMEM, its table SMEM.
MIMO_ARENAS = [((16385, 16, 4 * 192), BF16), ((16385, 16, 4 * 128), BF16)]


@pytest.mark.parametrize("columns", [128, 256, 384, 512])
def test_paged_decode_attention_mimo_decode_shape(chip, columns):
    def attend(q, keys, vals, table, lengths):
        return paged_decode_attention(q, keys, vals, table, lengths, scale=192 ** -0.5,
                                      kv_heads=4, interpret=False)

    shapes = [((32, 64, 4 * 192), BF16)] + MIMO_ARENAS + [((32, columns), I32), ((32,), I32)]
    assert _compile(chip, attend, *shapes) == 1


def test_mimo_decode_program_copies_no_arena(chip, monkeypatch):
    """The whole scanned ``step`` program of the cell (7 layers at the
    published widths, 16 steps a dispatch, the widest view): both
    full-attention layers hold the kernel, and nothing between the token's
    scatter and the kernel moves an arena into another layout (an
    arena-shaped ``copy`` cost 2.2 ms a step once: PERF.md section 6, PR 28)."""
    import re

    from kubeflow_tpu.models import mimo
    from kubeflow_tpu.ops import grouped_matmul as grouped_module, paged_attention
    from kubeflow_tpu.serving.family import MimoFamily

    # the backend here is the CPU, where the kernels would run interpreted
    monkeypatch.setattr(paged_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(grouped_module, "_interpret_default", lambda: False)
    cfg = mimo.MimoConfig()
    family = MimoFamily(cfg, slots=32, kv_blocks=16384, kv_block_t=16)
    rings = family.rings(16)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    params = described(jax.eval_shape(lambda: mimo.init_params(cfg, jax.random.PRNGKey(0))))
    cache = described(jax.eval_shape(family.fresh_cache))
    rows = [((32,), I32), ((32,), F32), ((32, 2), jnp.uint32), ((32, 512), I32),
            ((32, rings.cols), I32), ((32,), jnp.bool_)]
    text = family.build_step(16).lower(
        params, cache, *[jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                         for shape, dtype in rows]).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "paged_decode_attention" in line]
    assert len(kernels) == 2
    assert _grouped_kernels(text) == 2 * sum(cfg.moe_layers) and "ragged-dot" not in text
    arena = r"bf16\[\d+,16,(768|512|1536|1024)\]"
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= {arena}\S* copy(-start)?\(", line)]
    assert not copies, copies



# The EvaByte cell's shapes (ISSUE 32): 24 slots, 32 heads of 128 with a KV
# head each, arenas of 16-row blocks 4,096 wide; the local kind's 24 rings of
# 129 blocks, the summary kind's 1,536 blocks, each with its trash block.
EVA_LOCAL, EVA_SUMMARY = 24 * 129 + 1, 1536 + 1


@pytest.mark.parametrize("blocks,columns", [(EVA_LOCAL, 128), (EVA_SUMMARY, 32),
                                            (EVA_SUMMARY, 128)],
                         ids=["local", "summary_narrowest", "summary_widest"])
def test_paged_decode_attention_eva_decode_shape(chip, blocks, columns):
    """The decode kernel as the EvaByte step calls it, once a kind: queries
    heads apart (32 x 4,096), the whole-product value branch, the softmax's
    maximum and sum as two more outputs, 16 pages a group."""
    from kubeflow_tpu.models.evabyte import PAGES_PER_GROUP

    def attend(q, keys, vals, table, lengths):
        return paged_decode_attention(q, keys, vals, table, lengths, scale=128 ** -0.5,
                                      kv_heads=32, pages=PAGES_PER_GROUP, stats=True,
                                      interpret=False)

    arena = ((blocks, 16, 32 * 128), BF16)
    shapes = [((24, 32, 32 * 128), BF16), arena, arena, ((24, columns), I32), ((24,), I32)]
    assert _compile(chip, attend, *shapes) == 1


@pytest.mark.parametrize("chunk,keys", [(2048, 2048 + 2048), (512, 2048 + 2048 + 512)],
                         ids=["a_whole_window", "a_quarter"])
def test_chunk_attention_eva_prefill_shape(chip, chunk, keys):
    """The prefill kernel at the EvaByte cell's shapes: 32 heads of 128, one
    query head a KV head, a chunk's queries against the summaries' widest
    view (128 blocks of 16 rows), the window's earlier chunks where the
    chunk is not a whole window, and the chunk itself."""
    def attend(q, k, v, q_pos, k_pos):
        return chunk_attention(q, k, v, q_pos, k_pos, scale=128 ** -0.5, interpret=False)

    shapes = [((32, chunk, 128), BF16), ((32, keys, 128), BF16), ((32, keys, 128), BF16),
              ((chunk,), I32), ((keys,), I32)]
    assert _compile(chip, attend, *shapes) == 1


def test_eva_decode_program_copies_no_arena(chip, monkeypatch):
    """The whole scanned ``step`` program of the cell (8 layers at the
    published widths, 24 slots, 16 steps a dispatch, the widest view): every
    layer holds the kernel twice (a kind each), and nothing between the
    token's scatter, the summary's scatter and the kernels moves an arena
    into another layout (PERF.md section 6, PR 28); its temporaries are
    megabytes beside 13 GB of weights and arenas."""
    import re

    from kubeflow_tpu.models import evabyte
    from kubeflow_tpu.ops import paged_attention
    from kubeflow_tpu.serving.family import EvaFamily

    monkeypatch.setattr(paged_attention, "_interpret_default", lambda: False)
    cfg = evabyte.EvaConfig()
    family = EvaFamily(cfg, slots=24, kv_blocks=1536, kv_block_t=16)
    rings = family.rings(16)
    assert rings.cols == 129 and family.local_blocks + 1 == EVA_LOCAL

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    params = described(jax.eval_shape(lambda: evabyte.init_params(cfg, jax.random.PRNGKey(0))))
    cache = described(jax.eval_shape(family.fresh_cache))
    rows = [((24,), I32), ((24,), F32), ((24, 2), jnp.uint32), ((24, 128), I32),
            ((24, rings.cols), I32), ((24,), jnp.bool_)]
    compiled = family.build_step(16).lower(
        params, cache, *[jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                         for shape, dtype in rows]).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "paged_decode_attention" in line]
    assert len(kernels) == 2 * cfg.n_layers
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= bf16\[\d+,16,4096\]\S* copy(-start)?\(", line)]
    assert not copies, copies
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64e6 and 12.5e9 < memory.argument_size_in_bytes < 13.5e9


@pytest.mark.parametrize("columns", [64, 128, 192])
def test_paged_decode_attention_sdar_block_shape(chip, columns):
    """The SDAR cell's block attention: 64 slots, each 4 positions x 32
    query heads = 128 query rows laid apart over 4 KV heads of 128, pages of
    16 rows of 512, a view of ``columns`` pages (quarters of a row of 256;
    the mix's longest row, 2,304 positions, reaches the third)."""
    def attend(q, k, v, table, lengths):
        return paged_decode_attention(q, k, v, table, lengths, scale=128 ** -0.5,
                                      kv_heads=4, interpret=False)

    shapes = [((64, 128, 512), BF16), ((9217, 16, 512), BF16), ((9217, 16, 512), BF16),
              ((64, columns), I32), ((64,), I32)]
    assert _compile(chip, attend, *shapes) == 1


def test_sdar_programs_fit_the_chip_and_copy_no_arena(chip, monkeypatch):
    """The cell's two device programs at the published widths (6 layers, all
    128 experts, the whole vocabulary, 64 slots, 9,216 pages): the dispatch
    of 16 block passes holds the kernel once a layer and moves no arena into
    another layout; at temperature 0 its head is ONE ``head_choice`` kernel
    and the logits of 256 rows over 151,936 ids are never written: no array
    ``[64, 4, 151936]`` anywhere, and no ``reshape`` or ``copy`` that ends in
    151,936 columns (the branch a sampling slot takes reduces its logits as
    rows too); the chunk program of 2,048 rows holds ``chunk_attention``
    once a layer and no head; weights and arenas are 10.5 GB, the
    temporaries half a gigabyte (the sampling branch's logits, written only
    where a slot samples, and the sorted expert rows), under the chip's 16."""
    import re

    from kubeflow_tpu.models import sdar
    from kubeflow_tpu.ops import (chunk_attention as chunk_module,
                                  grouped_matmul as grouped_module,
                                  head_choice as head_module, paged_attention)
    from kubeflow_tpu.serving.family import SdarFamily

    monkeypatch.setattr(paged_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(chunk_module, "_interpret_default", lambda: False)
    monkeypatch.setattr(grouped_module, "_interpret_default", lambda: False)
    monkeypatch.setattr(head_module, "_interpret_default", lambda: False)
    cfg = sdar.SdarConfig()
    family = SdarFamily(cfg, slots=64, kv_blocks=9216, kv_block_t=16)
    assert family.cursor_moves(16) == 32 and family.kv_ahead == 4

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    one = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    params = described(jax.eval_shape(lambda: sdar.init_params(cfg, jax.random.PRNGKey(0))))
    cache = described(jax.eval_shape(family.fresh_cache))
    step = family.build_step(16).lower(
        params, cache, one((64,), I32), one((64,), F32), one((64, 2), jnp.uint32),
        one((64, 192), I32)).compile()
    text = step.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "paged_decode_attention" in line]
    assert len(kernels) == cfg.n_layers
    assert _grouped_kernels(text) == 2 * cfg.n_layers and "ragged-dot" not in text
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= bf16\[\d+,16,512\]\S* copy(-start)?\(", line)]
    assert not copies, copies
    assert sum('custom_call_target="tpu_custom_call"' in line and "head_choice" in line
               for line in text.splitlines()) == 1
    assert "[64,4,151936]" not in text
    # stricter than it must be: the sampling branch moves no logits either
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= \w+\[[\d,]*151936\]\S* (reshape|copy(-start)?)\(", line)]
    assert not moved, moved
    memory = step.memory_analysis()
    assert 10.3e9 < memory.argument_size_in_bytes < 10.8e9
    assert memory.temp_size_in_bytes < 0.7e9
    chunk = family.build_chunk_prefill().lower(
        params, cache, one((2048,), I32), one((), I32), one((), I32), one((), F32),
        one((2,), jnp.uint32), one((128,), I32), one((128,), I32)).compile()
    text = chunk.as_text()
    assert sum('custom_call_target="tpu_custom_call"' in line and "chunk_attention" in line
               for line in text.splitlines()) == cfg.n_layers
    # no head reads the last layer's output, so its experts are not computed
    assert _grouped_kernels(text) == 2 * (cfg.n_layers - 1) and "ragged-dot" not in text
    assert chunk.memory_analysis().temp_size_in_bytes < 0.7e9


def test_head_choice_sdar_pass_shape(chip):
    """The SDAR cell's head at temperature 0: 64 slots x 4 positions of
    hidden 2,048 over 151,936 ids (148 tiles of 1,024 columns and one of
    384, masked), three numbers a row out."""
    shapes = [((256, 2048), BF16), ((2048, 151936), BF16)]
    assert _compile(chip, lambda x, head: head_choice(x, head, interpret=False), *shapes) == 1


@pytest.mark.parametrize("rows,groups,d,f", [
    (2048, 128, 2048, 768), (16384, 128, 2048, 768),
    (256, 16, 4096, 2048), (16384, 16, 4096, 2048)],
    ids=["sdar_dispatch", "sdar_chunk", "mimo_step", "mimo_chunk"])
def test_grouped_products_held_experts_shapes(chip, rows, groups, d, f):
    """The four shapes ``held_experts_ffn`` hands the kernel: SDAR's 128
    experts of 2,048 x 768 (matrices whole, 3.1 MB a block) and MiMo's 16
    held of 4,096 x 2,048 (column blocks of 4.2 MB), a dispatch's and a
    prefill chunk's assignment rows."""
    def ffn(x, w_gate, w_up, w_down, sizes):
        mid = grouped_swiglu(x, w_gate, w_up, sizes, interpret=False)
        return grouped_matmul(mid, w_down, sizes, interpret=False)

    shapes = [((rows, d), BF16), ((groups, d, f), BF16), ((groups, d, f), BF16),
              ((groups, f, d), BF16), ((groups,), I32)]
    assert _compile(chip, ffn, *shapes) == 2


def test_composite_step_stacks_no_scores_over_the_layers(topo):
    """``composite.make_train_step`` at the sizes of the cell
    ``gpt2-large.train4.fsdp2-tp2`` (36 layers of 1,280, two sequences of
    1,024, fsdp 2 x model 2) on the four described chips: the backward
    recomputes the attention probabilities, so no buffer of 36 layers of
    [heads, 1024, 1024] is left, and the program's temporaries are under
    4 GB a chip (9.30 GB before PR 31, 2.90 with it)."""
    import re

    from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh

    cfg = composite.CompositeConfig(vocab_size=50304, d_model=1280, n_heads=20, d_ff=5120,
                                    n_layers=36, seq=1024)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, model=2), devices=topo.devices)
    made = jax.eval_shape(lambda key: composite.init_params(key, cfg, mesh), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a, sharding: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        made, composite.param_shardings(cfg, mesh))
    ids = jax.ShapeDtypeStruct((1, 2, cfg.seq), I32, sharding=composite.batch_sharding(mesh))
    compiled = composite.make_train_step(cfg, mesh, lr=1e-4).lower(params, ids).compile()
    stacked = set(re.findall(r"\w+\[(?:\d+,)*36,(?:\d+,)*1024,1024\]", compiled.as_text()))
    assert not stacked, stacked
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_gpt_train_step_runs_the_forward_kernel_once(chip, monkeypatch):
    """``bench.gpt_train_step`` at the sizes of the cell
    ``gpt2-medium.train.b8x1024`` (24 layers of 1,024, 8 x 1,024 tokens,
    AdamW, ``bench.GPT_TRAIN_KNOBS``): remat keeps the flash kernel's output
    and log-sum-exp (``models/gpt.SAVED_IN_BLOCK``), so the compiled step
    holds ONE forward kernel beside the two backward ones (two before
    PR 34, the second under ``rematted_computation``), and it fits the
    chip: 4.24 GB of arguments and 9.65 GB of temporaries of 15.75 GiB
    (3.99 GB of temporaries before)."""
    import importlib
    import re

    import optax

    import bench
    from kubeflow_tpu.models.gpt import GptConfig

    # the backend here is the CPU and the kernels would lower interpreted
    flash = importlib.import_module("kubeflow_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_interpret_default", lambda: False)
    cfg = GptConfig(vocab_size=50257, d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                    max_seq=1024, **bench.GPT_TRAIN_KNOBS)     # as runners/gpt_train.py builds it
    opt = optax.adamw(3e-4, weight_decay=0.01)
    model, train_step = bench.gpt_train_step(cfg, opt)
    ids = jax.ShapeDtypeStruct((8, cfg.max_seq), I32, sharding=chip)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(ids.shape, I32))["params"])
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        described(params), described(jax.eval_shape(opt.init, params)), ids).compile()
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?(flash_\w+)/pallas_call',
                         compiled.as_text())
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75 * 2**30


# The Sarvam cell's shapes (ISSUE 39): 64 heads, a latent row of 640 a
# position (512 latent + 64 rotary key + 64 zeros), 16 slots, pages of 16.
@pytest.mark.parametrize("columns", [512, 2048], ids=["narrowest", "widest"])
def test_latent_decode_attention_sarvam_shape(chip, columns):
    """The absorbed decode kernel as the step calls it: 16 slots of 64
    heads' 640-wide queries over the arena's 32,769 pages, 32 pages a
    group (655 KB a buffer)."""
    from kubeflow_tpu.ops.latent_attention import latent_decode_attention

    def attend(q, arena, table, lengths):
        return latent_decode_attention(q, arena, table, lengths, scale=0.1352, latent=512,
                                       interpret=False)

    assert _compile(chip, attend, ((16, 64, 640), BF16), ((32769, 16, 640), BF16),
                    ((16, columns), I32), ((16,), I32)) == 1


@pytest.mark.parametrize("keys", [8192, 32768], ids=["narrowest_view", "widest_view"])
def test_latent_prefill_attention_sarvam_shape(chip, keys):
    """The expanded prefill kernel as the chunk program calls it: 64 heads
    of a chunk of 4,096 queries, each ``[q_nope | q_rope | 0]`` (128 + 64 +
    64), against a view of ``keys`` rows, expanded ``BLOCK_K`` rows at a
    time through each head's 512 x 128 W_uk and W_uv."""
    from kubeflow_tpu.ops.latent_attention import latent_prefill_attention

    def attend(q, view, wuk, wuv, start):
        return latent_prefill_attention(q, view, wuk, wuv, start, scale=0.1352,
                                        interpret=False)

    assert _compile(chip, attend, ((64, 4096, 256), BF16), ((keys, 640), BF16),
                    ((64, 512, 128), BF16), ((64, 512, 128), BF16), ((), I32)) == 1


def test_sarvam_programs_fit_the_chip_and_copy_no_arena(chip, monkeypatch):
    """The cell's two device programs at the published widths (5 layers,
    32 of 128 experts held, 65,536 ids, 16 slots, 32,768 pages): the decode
    dispatch of 16 steps holds the absorbed kernel once a layer and moves
    no latent arena into another layout; the chunk program of 4,096 rows
    holds the expanded kernel once a layer; weights and arenas are 12.43 GB,
    the step's temporaries under 0.7 GB and the chunk's under 1.4 GB, under
    the chip's 16."""
    import re

    from kubeflow_tpu.models import sarvam
    from kubeflow_tpu.ops import grouped_matmul as grouped_module, latent_attention
    from kubeflow_tpu.serving.family import SarvamFamily

    monkeypatch.setattr(latent_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(grouped_module, "_interpret_default", lambda: False)
    cfg = sarvam.SarvamConfig()
    family = SarvamFamily(cfg, slots=16, kv_blocks=32768, kv_block_t=16)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    one = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    params = described(jax.eval_shape(lambda: sarvam.init_params(cfg, jax.random.PRNGKey(0))))
    cache = described(jax.eval_shape(family.fresh_cache))
    step = family.build_step(16).lower(
        params, cache, one((16,), I32), one((16,), F32), one((16, 2), jnp.uint32),
        one((16, 2048), I32)).compile()
    text = step.as_text()
    kernel = lambda name: sum('custom_call_target="tpu_custom_call"' in line and name in line
                              for line in text.splitlines())
    assert kernel("latent_decode_attention") == cfg.n_layers
    assert _grouped_kernels(text) == 2 * sum(cfg.moe_layers) and "ragged-dot" not in text
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= bf16\[32769,16,640\]\S* copy(-start)?\(", line)]
    assert not copies, copies
    memory = step.memory_analysis()
    assert 12.3e9 < memory.argument_size_in_bytes < 12.6e9
    assert memory.temp_size_in_bytes < 0.7e9
    chunk = family.build_chunk_prefill().lower(
        params, cache, one((4096,), I32), one((), I32), one((), I32), one((), F32),
        one((2,), jnp.uint32), one((2048,), I32), one((256,), I32)).compile()
    text = chunk.as_text()
    assert kernel("latent_prefill_attention") == cfg.n_layers
    assert chunk.memory_analysis().temp_size_in_bytes < 1.4e9


def test_ssm_decode_update_nemotron_shape(chip):
    """The Mamba layers' decode update as the step calls it: 64 slots of 8
    groups of 16 heads, each a float32 state of 64 x 128 (4.2 MB a slot),
    the state left in HBM and its live rows walked by DMA; ``decay``, ``u``,
    ``B``, ``C`` and ``y`` whole in VMEM (about 4.8 MB with their tiles'
    padding)."""
    def update(state, decay, u, B, C, live):
        return ssm_decode_update(state, decay, u, B, C, live, interpret=False)

    assert _compile(chip, update, ((64, 8, 16, 64, 128), F32), ((64, 128), F32),
                    ((64, 128, 64), F32), ((64, 8, 128), F32), ((64, 8, 128), F32),
                    ((64,), jnp.bool_)) == 1


def test_nemotron_programs_fit_the_chip_and_copy_no_state(chip, monkeypatch):
    """The cell's two device programs at the published widths (one period
    of 11 layers: 5 Mamba, 5 latent expert layers with 128 of 512 experts
    held, 1 attention layer; 32,768 ids; 64 slots; 65,536 pages): the decode
    dispatch of 16 steps holds the state update once a Mamba layer, the
    paged attention once and the two grouped products once an expert layer,
    and moves no Mamba state into another buffer (the update aliases it);
    the chunk program of 2,048 rows holds ``chunk_attention`` once; weights,
    states and arenas are 11.8 GB and each program's temporaries under 1.5
    GB, under the chip's 16."""
    import re

    from kubeflow_tpu.models import nemotron
    from kubeflow_tpu.ops import chunk_attention as chunk_module
    from kubeflow_tpu.ops import grouped_matmul as grouped_module, paged_attention, ssm
    from kubeflow_tpu.serving.family import NemotronFamily

    for module in (chunk_module, grouped_module, paged_attention, ssm):
        monkeypatch.setattr(module, "_interpret_default", lambda: False)
    cfg = nemotron.NemotronConfig()
    family = NemotronFamily(cfg, slots=64, kv_blocks=65536, kv_block_t=16)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    one = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    params = described(jax.eval_shape(lambda: nemotron.init_params(cfg, jax.random.PRNGKey(0))))
    cache = described(jax.eval_shape(family.fresh_cache))
    step = family.build_step(16).lower(
        params, cache, one((64,), I32), one((64,), F32), one((64, 2), jnp.uint32),
        one((64, 1024), I32), one((64,), jnp.bool_)).compile()
    text = step.as_text()
    # a kernel's own line: its name left of the '=' (a consumer's line
    # names it among its operands)
    kernel = lambda name: sum('custom_call_target="tpu_custom_call"' in line
                              and name in line.split("=")[0] for line in text.splitlines())
    mamba, experts = cfg.pattern.count("M"), cfg.pattern.count("E")
    assert kernel("ssm_decode_update") == mamba
    assert kernel("paged_decode_attention") == cfg.pattern.count("*")
    assert kernel("grouped_relu2") == kernel("grouped_matmul") == experts
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= f32\[64,8,16,64,128\]\S* copy(-start)?\(", line)]
    assert not copies, copies
    memory = step.memory_analysis()
    assert 11.6e9 < memory.argument_size_in_bytes < 12.0e9
    assert memory.temp_size_in_bytes < 1.5e9
    chunk = family.build_chunk_prefill().lower(
        params, cache, one((2048,), I32), one((), I32), one((), I32), one((), F32),
        one((2,), jnp.uint32), one((1024,), I32), one((128,), I32), one((), I32)).compile()
    text = chunk.as_text()
    assert kernel("chunk_attention") == cfg.pattern.count("*")
    assert chunk.memory_analysis().temp_size_in_bytes < 1.5e9
