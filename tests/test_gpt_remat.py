"""What ``GptConfig.remat`` keeps of a block (``models/gpt.py``'s
``SAVED_IN_BLOCK`` / ``_remat``, ``ops/flash_attention.py``'s
``SAVED_RESIDUALS``): the loss and gradients of the plain block, each
block's forward matmuls and flash kernel run once, every kept name read by
the backward, and ``remat=False`` programs that lower as they did. All
on jaxprs and lowered text at toy size with the kernels interpreted: what
the chip's compiler makes of it is the benchmark's training cell's to say."""

import collections
import dataclasses
import functools
import importlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import gpt
from kubeflow_tpu.models.gpt import GptConfig, GptLM, causal_lm_loss

# the module: ``kubeflow_tpu.ops`` re-exports the function under its name
flash = importlib.import_module("kubeflow_tpu.ops.flash_attention")

# every width its own number: seq 16, d 32, head 8, ff 96
CFG = GptConfig(vocab_size=64, d_model=32, n_layers=3, n_heads=4, d_ff=96, max_seq=32,
                dtype=jnp.float32)
IDS = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size)
LAYOUTS = {"scanned": True, "unrolled": False}
FORWARD_KERNEL, BACKWARD_KERNELS = "flash_fwd", ("flash_bwd_dq", "flash_bwd_dkv")


def _model(scan_blocks: bool, remat: bool) -> GptLM:
    return GptLM(dataclasses.replace(CFG, scan_blocks=scan_blocks, remat=remat))


@functools.lru_cache(maxsize=None)
def _params(scan_blocks: bool):
    return _model(scan_blocks, False).init(jax.random.PRNGKey(0), IDS)["params"]


def _loss(model: GptLM):
    return lambda p: causal_lm_loss(model.apply({"params": p}, IDS), IDS)


def _walk(jaxpr):
    """Every equation, those of nested jaxprs too; a kernel's own body is
    the kernel's and is not entered."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def _work(scan) -> collections.Counter:
    """The matmuls and kernels of one layer scan's body, kernels by name."""
    found = collections.Counter()
    for eqn in _walk(scan.params["jaxpr"].jaxpr):
        if eqn.primitive.name == "dot_general":
            found["dot_general"] += 1
        elif eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
    return found


@functools.lru_cache(maxsize=None)
def _layer_scans(remat: bool, saved=None):
    """(forward, backward) layer scans of ``jax.grad(loss)`` for the scanned
    model, with ``saved`` in ``SAVED_IN_BLOCK``'s place if given."""
    model = _model(True, remat)
    with mock.patch.object(gpt, "SAVED_IN_BLOCK", gpt.SAVED_IN_BLOCK if saved is None else saved):
        jaxpr = jax.make_jaxpr(jax.grad(_loss(model)))(_params(True)).jaxpr
    scans = [e for e in _walk(jaxpr)
             if e.primitive.name == "scan" and e.params["length"] == CFG.n_layers]
    (forward,) = [e for e in scans if not e.params["reverse"]]
    (backward,) = [e for e in scans if e.params["reverse"]]
    return forward, backward


@pytest.mark.parametrize("layout", LAYOUTS)
def test_loss_and_every_gradient_are_the_plain_blocks(layout):
    scan_blocks = LAYOUTS[layout]
    params = _params(scan_blocks)
    loss, grads = jax.value_and_grad(_loss(_model(scan_blocks, True)))(params)
    loss_plain, grads_plain = jax.value_and_grad(_loss(_model(scan_blocks, False)))(params)
    assert float(loss) == float(loss_plain)      # the forward pass is the same program
    for (path, g), g_plain in zip(jax.tree_util.tree_leaves_with_path(grads),
                                  jax.tree_util.tree_leaves(grads_plain)):
        g, g_plain = np.asarray(g), np.asarray(g_plain)
        assert np.abs(g_plain).max() > 0, path
        np.testing.assert_allclose(
            g, g_plain, rtol=1e-5, atol=1e-5 * np.abs(g_plain).max(), err_msg=str(path))


def test_the_forward_kernel_and_matmuls_run_once_a_block():
    """The flash forward ``pallas_call`` lies in the forward scan alone, and
    the backward scan, recomputation included (``remat2``'s body lies inside
    it), holds the matmuls and kernels of the plain block's backward."""
    forward, backward = _layer_scans(remat=True)
    plain_forward, plain_backward = _layer_scans(remat=False)
    assert any(e.primitive.name == "remat2" for e in _walk(backward.params["jaxpr"].jaxpr))
    assert not any(e.primitive.name == "remat2"
                   for e in _walk(plain_backward.params["jaxpr"].jaxpr))
    assert _work(forward) == _work(plain_forward)
    assert _work(forward)[FORWARD_KERNEL] == 1
    assert _work(forward)["dot_general"] == 6       # q, k, v, out, up, down
    assert _work(backward) == _work(plain_backward)
    assert FORWARD_KERNEL not in _work(backward)
    assert all(_work(backward)[name] == 1 for name in BACKWARD_KERNELS)
    assert _work(backward)["dot_general"] == 12     # a weight's and an input's gradient each


def test_what_is_kept_is_what_is_documented():
    assert flash.SAVED_RESIDUALS == ("flash_out", "flash_lse")
    assert gpt.SAVED_IN_BLOCK == (
        "query", "key", "value", "flash_out", "flash_lse", "attn_out", "mlp_pre")
    # stacked over the layers for the backward: the names and the block's input
    forward, _ = _layer_scans(remat=True)
    stacked = [v for v in forward.outvars if v.aval.shape[:1] == (CFG.n_layers,)]
    assert len(stacked) == len(gpt.SAVED_IN_BLOCK) + 1, [v.aval for v in stacked]


@pytest.mark.parametrize("dropped", gpt.SAVED_IN_BLOCK)
def test_every_kept_name_is_read_by_the_backward(dropped):
    """Without any one of them the backward runs a forward matmul or the
    forward kernel again."""
    kept = tuple(n for n in gpt.SAVED_IN_BLOCK if n != dropped)
    _, backward = _layer_scans(remat=True, saved=kept)
    _, whole = _layer_scans(remat=True)
    again = _work(backward) - _work(whole)
    assert again, dropped
    assert set(again) <= {"dot_general", FORWARD_KERNEL}, again
    assert (FORWARD_KERNEL in again) == (dropped in flash.SAVED_RESIDUALS), again


def _inert():
    """Both modules' ``checkpoint_name`` replaced by the identity."""
    plain = lambda x, name: x  # noqa: E731
    return (mock.patch.object(gpt, "checkpoint_name", plain),
            mock.patch.object(flash, "checkpoint_name", plain))


def _decode_program(**kwargs):
    model = GptLM(CFG, decode=True, **kwargs)
    tables = ({"block_tables": jnp.zeros((IDS.shape[0], CFG.max_seq // 16), jnp.int32)}
              if kwargs.get("paged") else {})
    variables = model.init(jax.random.PRNGKey(0), IDS, **tables)

    def serve(v, ids, temperatures, tables):
        """Shaped as the engine's prefill: private functions (``_where``) of
        several shapes after the model, whose numbers a name would move."""
        logits, updated = model.apply(v, ids, mutable=["cache"], **tables)
        first = jnp.argmax(logits[:, -1], axis=-1)
        return updated, jnp.where(temperatures > 0, first + 1, first), jnp.where(ids > 0, ids, 1)

    return jax.jit(serve), (variables, IDS, jnp.zeros((IDS.shape[0],)), tables)


def _train_program(scan_blocks):
    return (jax.jit(jax.value_and_grad(_loss(_model(scan_blocks, remat=False)))),
            (_params(scan_blocks),))


PROGRAMS = {
    "decode": lambda: _decode_program(),
    "decode, a cursor a slot": lambda: _decode_program(per_slot=True),
    "decode, paged": lambda: _decode_program(per_slot=True, paged=True, kv_blocks=5),
    "training without remat, scanned": lambda: _train_program(True),
    "training without remat, unrolled": lambda: _train_program(False),
}


def _renumbered(text: str) -> str:
    """``text`` with its private functions' numbers (``@_where_74``) counted
    anew in order of appearance. The lowering numbers them from one counter
    a module, which every clash of two private symbols advances, and a
    second distinct ``name`` equation clashes with the first (both are
    ``@name`` while they lower): the number is no part of what is computed,
    but it is part of the text and of the compile cache's key."""
    seen = {}
    return re.sub(r"@([A-Za-z_][\w.]*?)_(\d+)\b",
                  lambda m: f"@{m[1]}_{seen.setdefault(m[0], len(seen))}", text)


@pytest.mark.parametrize("program", PROGRAMS)
def test_without_remat_the_programs_are_as_they_were(program):
    """``remat=False`` lowers to the StableHLO text it lowers to with every
    ``checkpoint_name`` taken out. The decode programs hold no ``name``
    equation at all (``gpt._kept``), so they are equal to the byte, numbers
    of private functions included; a gradient holds the flash kernel's two,
    which compute nothing and move those numbers."""
    fn, args = PROGRAMS[program]()
    named = fn.lower(*args).as_text()
    names = [e.params["name"] for e in _walk(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.primitive.name == "name"]
    a, b = _inert()
    with a, b:
        bare = fn.lower(*args).as_text()
    assert "stablehlo" in named
    if program.startswith("decode"):
        assert not names
        assert named == bare
    else:
        assert set(names) == set(flash.SAVED_RESIDUALS)
        assert _renumbered(named) == _renumbered(bare)
