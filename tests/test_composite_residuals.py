"""What the composite step's backward pass keeps (``composite._remat``,
``SAVED_IN_BLOCK``): the same loss and gradients as the block without
``jax.checkpoint``, no score-shaped residual stacked over the layers, at
most two of the MLP's hidden width, and no collective run a second time.
All on the step's jaxpr and on the 8 virtual CPU devices: nothing here says
what the chip's compiler makes of it (``tests/test_chip_compile.py`` does)."""

import collections
import contextlib
import functools
from unittest import mock

import jax
import numpy as np
import pytest

from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh
from kubeflow_tpu.parallel.composite import (
    GATHER_MODES,
    CompositeConfig,
    batch_sharding,
    init_params,
    make_train_step,
)

# every width its own number: seq 24, d 32, d/tp 16, ff/tp 48, head 8
CFG = CompositeConfig(vocab_size=64, d_model=32, n_heads=4, d_ff=96, n_layers=4, seq=24)
TP, LPC = 2, 2          # model axis; layers a stage chunk (4 layers over pipe 2)
HIDDEN = (CFG.seq, CFG.d_ff // TP)      # a sequence of the MLP's hidden rows on one chip
COLLECTIVES = ("psum", "all_gather", "reduce_scatter")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshConfig(data=1, fsdp=2, model=TP, pipe=2))


@pytest.fixture(scope="module")
def args(mesh):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 4, CFG.seq), 0, CFG.vocab_size)
    return (init_params(jax.random.PRNGKey(0), CFG, mesh),
            jax.device_put(ids, batch_sharding(mesh)))


def _plain():
    """The same block, called without ``jax.checkpoint``: what the parent ran."""
    return mock.patch.object(composite, "_remat", lambda block: block)


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


@pytest.fixture(scope="module")
def layer_scans(mesh, args):
    """(mode, plain) -> (forward, backward) layer scans of the step's jaxpr:
    ``lax.scan``s as long as a stage chunk has layers. The time scan over
    microbatches (3 steps here) holds them and is another length. Each step
    is traced once for the module."""
    @functools.lru_cache(maxsize=None)
    def scans(mode, plain=False):
        with _plain() if plain else contextlib.nullcontext():
            jaxpr = jax.make_jaxpr(make_train_step(CFG, mesh, gather_mode=mode))(*args).jaxpr
        found = [e for e in _walk(jaxpr)
                 if e.primitive.name == "scan" and e.params["length"] == LPC]
        return ([e for e in found if not e.params["reverse"]],
                [e for e in found if e.params["reverse"]])

    return scans


def _stacked(scans):
    """Shapes a layer (the leading, stacked dim dropped) of everything the
    forward layer scans hand on: the carry out and the saved residuals."""
    return [tuple(v.aval.shape[1:]) for e in scans for v in e.outvars
            if v.aval.shape[:1] == (LPC,)]


def _collectives(scans):
    return collections.Counter(
        e.primitive.name for scan in scans for e in _walk(scan.params["jaxpr"].jaxpr)
        if e.primitive.name in COLLECTIVES)


@pytest.mark.parametrize("mode", GATHER_MODES)
def test_loss_and_gradients_are_the_plain_blocks(mesh, args, mode):
    """lr 1: the new parameters are p - g, so the gradient is read back from
    the state, the way the benchmark's cell reads it."""
    params, ids = args
    new, loss = make_train_step(CFG, mesh, lr=1.0, gather_mode=mode)(params, ids)
    with _plain():
        new_plain, loss_plain = make_train_step(CFG, mesh, lr=1.0, gather_mode=mode)(params, ids)
    assert float(loss) == float(loss_plain)       # the forward pass is the same program
    grads, grads_plain = (
        jax.tree_util.tree_map(lambda p, q: np.asarray(p) - np.asarray(q), params, tree)
        for tree in (new, new_plain))
    for (path, g), g_plain in zip(jax.tree_util.tree_leaves_with_path(grads),
                                  jax.tree_util.tree_leaves(grads_plain)):
        assert np.abs(g_plain).max() > 0, path
        np.testing.assert_allclose(
            g, g_plain, rtol=1e-5, atol=1e-5 * np.abs(g_plain).max(), err_msg=str(path))


@pytest.mark.parametrize("mode", GATHER_MODES)
def test_no_scores_and_two_hidden_widths_a_layer_are_kept(layer_scans, mode):
    forward, _ = layer_scans(mode)
    kept = _stacked(forward)
    assert forward and kept
    scores = [s for s in kept if s[-2:] == (CFG.seq, CFG.seq)]
    hidden = [s for s in kept if s[-2:] == HIDDEN]
    assert not scores, scores
    assert 1 <= len(hidden) <= 2 * len(forward), kept     # `pre`, and nothing else as wide
    # the walk sees what it claims to: the plain block stacks both
    plain = _stacked(layer_scans(mode, plain=True)[0])
    assert [s for s in plain if s[-2:] == (CFG.seq, CFG.seq)]
    assert len([s for s in plain if s[-2:] == HIDDEN]) > 2 * len(forward)


@pytest.mark.parametrize("mode", GATHER_MODES)
def test_no_collective_runs_twice(layer_scans, mode):
    """Two Megatron psums a layer forward and two backward, recomputation
    included (``remat2``'s body lies inside the backward scan), and as
    many gathers and reduce-scatters as the plain block's step."""
    forward, backward = layer_scans(mode)
    mine = _collectives(forward), _collectives(backward)
    assert any(e.primitive.name == "remat2"
               for scan in backward for e in _walk(scan.params["jaxpr"].jaxpr))
    plain_forward, plain_backward = layer_scans(mode, plain=True)
    assert (len(forward), len(backward)) == (len(plain_forward), len(plain_backward))
    assert mine == (_collectives(plain_forward), _collectives(plain_backward))
    assert mine[0]["psum"] == 2 * len(forward) and mine[1]["psum"] == 2 * len(backward)
