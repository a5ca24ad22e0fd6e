"""Training-step decomposition: where the ResNet step's time actually goes.

VERDICT r2 #3 asked for a per-op/profile table behind the MFU number. This
backend exposes no per-op trace, so the decomposition is measured the way
the bench measures everything else — each variant scanned inside one
executable with a host-fetch barrier — and each line isolates one
subsystem:

  fwd_eval      forward only, BN in inference mode (no stats writes)
  fwd_train     forward with BN batch stats (adds the normalization pass)
  fwd_bwd       + backward (the conv-transpose/grad convs dominate)
  full_step     + SGD-momentum update (optimizer HBM pass over 25.6M params)

The deltas between lines attribute time: (fwd_train - fwd_eval) ≈ BN stats
cost, (fwd_bwd - 2×fwd) ≈ backward inefficiency beyond the 2× analytic
FLOPs, (full - fwd_bwd) ≈ optimizer + param-cast overhead. Combined with
e2e/ceiling.py's kernel rates this bounds the achievable MFU for this
model family on this chip (the 3x3 convs at ResNet's 64-128 channel widths
sustain 61-93 TF/s of the 197 peak — a 128-wide MXU is half-idle below 128
input channels, so the conv mix itself caps ResNet-50 well under the
theoretical 100%).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _scan_time(fn, args, steps: int) -> float:
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: float(jnp.sum(x.astype(jnp.float32))), out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: float(jnp.sum(x.astype(jnp.float32))), out)
    return (time.perf_counter() - t0) / steps


def profile(batch: int = 256, steps: int = 30) -> Dict[str, Any]:
    from kubeflow_tpu.models import ResNet50
    from kubeflow_tpu.training import ClassifierTask
    from kubeflow_tpu.training.classifier import cross_entropy_loss, sgd_momentum

    model = ResNet50(num_classes=1000)
    task = ClassifierTask(model=model, optimizer=sgd_momentum(lr=0.1, total_steps=1000))
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(rng, (batch, 224, 224, 3), jnp.float32)
    labels = jax.random.randint(rng, (batch,), 0, 1000)
    state = task.init(rng, images)
    step = task.make_train_step()

    # Every body perturbs its input by the loop carry (×1e-30, numerically
    # invisible) — without this XLA hoists the whole loop-invariant model
    # call out of the scan and the probe times ONE forward plus adds
    # (measured 4 ms/step for a 2.1 TFLOP forward = impossible 500 TF/s).
    @jax.jit
    def fwd_eval(params, batch_stats, images):
        def body(c, _):
            x = images + c * jnp.float32(1e-30)
            logits = model.apply({"params": params, "batch_stats": batch_stats},
                                 x, train=False)
            return c + jnp.sum(logits.astype(jnp.float32)), ()
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=steps)
        return c

    @jax.jit
    def fwd_train(params, batch_stats, images):
        def body(c, _):
            x = images + c * jnp.float32(1e-30)
            logits, mut = model.apply({"params": params, "batch_stats": batch_stats},
                                      x, train=True, mutable=["batch_stats"])
            extra = sum(jnp.sum(v.astype(jnp.float32))
                        for v in jax.tree_util.tree_leaves(mut))
            return c + jnp.sum(logits.astype(jnp.float32)) + extra, ()
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=steps)
        return c

    @jax.jit
    def fwd_bwd(params, batch_stats, images, labels):
        def body(c, _):
            x = images + c * jnp.float32(1e-30)
            def loss_fn(p):
                logits, _ = model.apply({"params": p, "batch_stats": batch_stats},
                                        x, train=True, mutable=["batch_stats"])
                return cross_entropy_loss(logits, labels)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            gsum = sum(jnp.sum(g.astype(jnp.float32))
                       for g in jax.tree_util.tree_leaves(grads))
            return c + loss + gsum, ()
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=steps)
        return c

    @jax.jit
    def full(state, images, labels):
        def body(s, _):
            s2, m = step(s, images, labels)
            return s2, m["loss"]
        final, losses = jax.lax.scan(body, state, None, length=steps)
        checksum = sum(jnp.sum(p.astype(jnp.float32))
                       for p in jax.tree_util.tree_leaves(final.params))
        return losses[-1], checksum

    rows = {}
    rows["fwd_eval"] = _scan_time(fwd_eval, (state.params, state.batch_stats, images), steps)
    rows["fwd_train"] = _scan_time(fwd_train, (state.params, state.batch_stats, images), steps)
    rows["fwd_bwd"] = _scan_time(fwd_bwd, (state.params, state.batch_stats, images, labels), steps)
    rows["full_step"] = _scan_time(full, (state, images, labels), steps)
    return {"batch": batch, "seconds": rows}


def main() -> None:
    from kubeflow_tpu.tpu.env import enable_compile_cache

    enable_compile_cache()
    out = profile(batch=int(os.environ.get("PROFILE_BATCH", "256")))
    rows = out["seconds"]
    full = rows["full_step"]
    print(f"{'phase':12s} {'ms/step':>9s} {'of full':>8s}")
    for name, dt in rows.items():
        print(f"{name:12s} {dt * 1e3:8.1f}  {100 * dt / full:7.1f}%")
    bn = rows["fwd_train"] - rows["fwd_eval"]
    bwd = rows["fwd_bwd"] - rows["fwd_train"]
    opt = rows["full_step"] - rows["fwd_bwd"]
    print(f"{'Δ bn_stats':12s} {bn * 1e3:8.1f}  {100 * bn / full:7.1f}%")
    print(f"{'Δ backward':12s} {bwd * 1e3:8.1f}  {100 * bwd / full:7.1f}%")
    print(f"{'Δ optimizer':12s} {opt * 1e3:8.1f}  {100 * opt / full:7.1f}%")
    print(json.dumps({"metric": "resnet50_step_decomposition", **out}))


if __name__ == "__main__":
    main()
