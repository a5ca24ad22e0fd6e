"""The plain float32 references against the program at toy size on the
CPU (float32 program, flash kernel in interpret mode): same weights from
the benchmark's maker, same batch, agreement to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import composite as ref_c
from benchmark.reference import gpt as ref_g

SIZES = {"vocab_size": 512, "n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128}


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("scan_blocks", [True, False])
def test_weights_fit_gptlm_tree(scan_blocks):
    from kubeflow_tpu.models.gpt import GptConfig, GptLM

    cfg = GptConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
                    max_seq=128, scan_blocks=scan_blocks)
    want = jax.eval_shape(lambda: GptLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    got = weights.gpt_tree(weights.gpt_canonical(2**31 + 7, SIZES), scan_blocks)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    for w, g in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        assert w.shape == g.shape and w.dtype == g.dtype
    if scan_blocks:
        back = weights.gpt_canonical_from_tree(got)
        assert sorted(back) == sorted(weights.gpt_canonical(2**31 + 7, SIZES))


def test_gpt_reference_matches_gptlm_forward_loss_and_gradients():
    from kubeflow_tpu.models.gpt import GptConfig, GptLM, blockwise_causal_lm_loss

    canon = weights.gpt_canonical(2**31 + 7, SIZES)
    cfg = GptConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
                    max_seq=128, dtype=jnp.float32, scan_blocks=True, remat=True)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 128), 0, 512)
    tree = weights.gpt_tree(canon, True)
    logits = GptLM(cfg).apply({"params": tree}, ids)
    want = ref_g.logits_at(canon, ref_g.hidden(canon, ids))
    assert float(jnp.abs(logits - want).max()) < 1e-5 * float(want.std()) * 100

    def loss_fn(p):
        hid = GptLM(cfg).apply({"params": p}, ids, return_hidden=True)
        return blockwise_causal_lm_loss(hid, p["embedding"]["embedding"], ids)

    loss, grad = jax.value_and_grad(loss_fn)(tree)
    ref_loss, ref_grad = ref_g.loss_and_grad(canon, ids, rows_per_block=2)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    got = weights.gpt_canonical_from_tree(grad)
    for k in ref_grad:
        assert rel(got[k], ref_grad[k]) < 1e-4, k


def test_adamw_reference_matches_optax():
    import optax

    canon = {"w": jnp.arange(6.0).reshape(2, 3) / 7 - 0.3}
    grads = [{"w": jnp.sin(jnp.arange(6.0).reshape(2, 3) + i)} for i in range(3)]
    hp = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                      weight_decay=hp["weight_decay"])
    p, s = canon, opt.init(canon)
    q, t = canon, ref_g.adamw_init(canon)
    for g in grads:
        u, s = opt.update(g, s, p)
        p = optax.apply_updates(p, u)
        q, t = ref_g.adamw_step(q, t, g, **hp)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(q["w"]), rtol=1e-6, atol=1e-9)


def test_composite_reference_matches_the_sharded_step():
    from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh
    from kubeflow_tpu.parallel.composite import CompositeConfig

    sizes = {"vocab_size_run": 256, "n_embd": 32, "n_layer": 4, "n_inner": 64}
    ccfg = CompositeConfig(vocab_size=256, d_model=32, n_heads=4, d_ff=64, n_layers=4, seq=16)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, model=2), devices=jax.devices()[:4])
    canon = weights.composite_canonical(5, sizes)
    tree = jax.device_put(weights.composite_tree(canon), composite.param_shardings(ccfg, mesh))
    want = composite.init_params(jax.random.PRNGKey(0), ccfg, mesh)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(tree)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 16), 0, 250)
    new, loss = composite.make_train_step(ccfg, mesh, lr=1e-2)(
        tree, jax.device_put(ids, composite.batch_sharding(mesh)))
    ref_loss, grad = ref_c.loss_and_grad(canon, ids[0], 4)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    ref_new = ref_c.sgd_step(canon, grad, 1e-2)
    got = weights.composite_canonical_from_tree(new)
    for k in ref_new:
        assert float(jnp.abs(got[k] - ref_new[k]).max()) < 1e-6, k
    # accumulating row by row is the same mathematics
    _, grad_rows = ref_c.loss_and_grad(canon, ids[0], 4, rows_per_block=1)
    for k in grad:
        assert rel(grad_rows[k], grad[k]) < 1e-5, k


def test_sharded_weights_equal_one_device_weights():
    from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh
    from kubeflow_tpu.parallel.composite import CompositeConfig

    sizes = {"vocab_size_run": 256, "n_embd": 32, "n_layer": 4, "n_inner": 64}
    ccfg = CompositeConfig(vocab_size=256, d_model=32, n_heads=4, d_ff=64, n_layers=4, seq=16)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, model=2), devices=jax.devices()[:4])
    sharded = weights.composite_canonical(
        2**31 + 9, sizes,
        out_shardings={k: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
                       for k in ("embed", "wqkv", "wo", "w1", "w2", "ln1_scale", "ln2_scale")})
    single = weights.composite_canonical(2**31 + 9, sizes)
    for k in single:
        assert (np.asarray(sharded[k]) == np.asarray(single[k])).all(), k


def test_fp8_cast_is_coarser_than_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    fp8 = float(jnp.abs(ref_g.fp8_cast(x) - x).mean())
    bf16 = float(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).mean())
    assert 6 * bf16 < fp8 < 40 * bf16
