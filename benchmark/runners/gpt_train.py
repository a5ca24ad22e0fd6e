"""Runner ``gpt_train``: ``bench.gpt_train_step`` on one chip.

The program's own entry (``bench.gpt_train_step`` with
``bench.GPT_TRAIN_KNOBS``, its default) is called, not copied, jitted with
params and optimizer state donated. Set-up builds ONE compiled step with
its state, drives it from the seed through its first three steps (whose
readings ``correct`` compares) and hands that same object to the window.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from .. import correct, harness, traffic, trainloop, weights
from ..reference import gpt as ref

LEAVES = tuple(sorted(
    ("embedding", "wq", "wk", "wv", "wo", "w_up", "w_down", "ln_attn_scale",
     "ln_attn_bias", "ln_mlp_scale", "ln_mlp_bias", "ln_final_scale", "ln_final_bias")))


def leaf_norms(canon_like: Dict[str, Any]) -> Dict[str, Any]:
    """Per leaf, the norm of each layer's slice (stacked leaves: over all
    axes but the first; the rest: over everything)."""
    import jax.numpy as jnp

    def norm(name, x):
        x = x.astype(jnp.float32)
        stacked = name not in ("embedding", "ln_final_scale", "ln_final_bias")
        axes = tuple(range(1, x.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes))

    return {k: norm(k, canon_like[k]) for k in LEAVES}


def sizes_of(config: Dict[str, Any]) -> Dict[str, int]:
    return {k: int(config[k]) for k in
            ("vocab_size", "n_embd", "n_layer", "n_head", "n_inner", "n_positions")}


def build_reference(sizes: Dict[str, int], opt: Dict[str, float], cast=None):
    """The plain reference through the first steps as ONE jitted function of
    (key, ids [steps, rows, seq]): its own weights from the key, then per
    step loss, gradient (row by row) and a plain AdamW update. Returns
    losses, the first gradient's leaf norms, the params' change's leaf
    norms. ``cast`` = the lower-precision control."""
    import jax

    dims = dict(vocab=sizes["vocab_size"], d=sizes["n_embd"], layers=sizes["n_layer"],
                heads=sizes["n_head"], ff=sizes["n_inner"])

    @jax.jit
    def go(key, ids):
        canon0 = weights._gpt_canonical(key, **dims)

        def one(carry, batch):
            canon, state = carry
            loss, grad = ref.loss_and_grad(canon, batch, 1, cast)
            canon, state = ref.adamw_step(canon, state, grad, **opt)
            return (canon, state), (loss, leaf_norms(grad))

        (canon, _), (losses, grads) = jax.lax.scan(
            one, (canon0, ref.adamw_init(canon0)), ids)
        change = leaf_norms({k: canon[k] - canon0[k] for k in canon0})
        return losses, {k: v[0] for k, v in grads.items()}, change

    return go


def reference_readings(seed: int, sizes: Dict[str, int], batches: np.ndarray,
                       opt: Dict[str, float], cast=None, rows=None) -> Dict[str, Any]:
    """``rows`` = keep only these rows of every batch (the half-batch
    fault, planted in the reference put in the program's place)."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(batches if rows is None else batches[:, rows])
    losses, grad, change = build_reference(sizes, opt, cast)(weights.seed_key(seed), ids)
    return {"losses": np.asarray(losses), "grad": jax.device_get(grad),
            "change": jax.device_get(change)}


class Program:
    """The compiled step with its state: what set-up builds and the window
    drives."""

    def __init__(self, cell: harness.Cell, device: Any):
        import jax
        import optax

        import bench
        from kubeflow_tpu.models.gpt import GptConfig

        self.sizes = sizes_of(cell.config)
        self.opt = dict(cell.deploy["optimizer"])
        self.mix, self.seed, self.device = cell.mix, cell.seed, device
        s = self.sizes
        gcfg = GptConfig(vocab_size=s["vocab_size"], d_model=s["n_embd"],
                         n_layers=s["n_layer"], n_heads=s["n_head"], d_ff=s["n_inner"],
                         max_seq=s["n_positions"], **bench.GPT_TRAIN_KNOBS)
        if tuple(self.mix["shape"])[1] > gcfg.max_seq:
            raise SystemExit("the mix's sequences exceed the model's positions")
        o = self.opt
        opt = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"])
        _, train_step = bench.gpt_train_step(gcfg, opt)
        self.scan = gcfg.scan_blocks
        canon = weights.gpt_canonical(self.seed, s)
        self.params = jax.jit(lambda c: weights.gpt_tree(c, self.scan))(canon)
        del canon
        self.opt_state = jax.jit(opt.init)(self.params)
        ids = jax.ShapeDtypeStruct(tuple(self.mix["shape"]), np.int32)
        self.step = jax.jit(train_step, donate_argnums=(0, 1)).lower(
            self.params, self.opt_state, ids).compile()
        self.temp_bytes = int(self.step.memory_analysis().temp_size_in_bytes)

    def feed(self, i: int):
        import jax

        return jax.device_put(
            traffic.batch(self.mix, self.sizes["vocab_size"], self.seed, i), self.device)

    def __call__(self, batch):
        self.params, self.opt_state, loss = self.step(self.params, self.opt_state, batch)
        return loss

    def canonical(self, tree):
        if not self.scan:
            raise SystemExit("gpt_train reads the scanned layout only")
        return weights.gpt_canonical_from_tree(tree)

    def first_gradient_norms(self):
        """From the optimizer's state after ONE step: mu = (1 - b1) g."""
        import jax

        mu = next(s.mu for s in jax.tree_util.tree_leaves(
            self.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu"))
        b1 = self.opt["b1"]
        norms = jax.jit(lambda m: leaf_norms(self.canonical(m)))(mu)
        return {k: np.asarray(v) / (1.0 - b1) for k, v in jax.device_get(norms).items()}

    def change_norms(self):
        """Norms of params minus the initial weights, remade from the seed."""
        import jax

        s = self.sizes
        dims = dict(vocab=s["vocab_size"], d=s["n_embd"], layers=s["n_layer"],
                    heads=s["n_head"], ff=s["n_inner"])

        @jax.jit
        def go(params, key):
            now, then = self.canonical(params), weights._gpt_canonical(key, **dims)
            return leaf_norms({k: now[k] - then[k] for k in then})

        return jax.device_get(go(self.params, weights.seed_key(self.seed)))

    def first_steps(self) -> Dict[str, Any]:
        """Steps 0..2 through the window's own call and feed."""
        losses: List[float] = []
        grad = None
        for i in range(trainloop.FIRST_STEPS):
            losses.append(float(self(self.feed(i))))
            if i == 0:
                grad = self.first_gradient_norms()
        return {"losses": np.asarray(losses), "grad": grad,
                "change": self.change_norms()}

    def free(self) -> None:
        self.params = self.opt_state = self.step = None
        gc.collect()


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    program = Program(cell, devices[0])
    harness.note("weights made, step compiled")
    readings = program.first_steps()
    harness.note("first steps driven and read")
    profiler = harness.Profiler(cell) if cell.trace else None
    setup_s = time.perf_counter() - t0
    window = trainloop.run_window(program, program.feed, trainloop.FIRST_STEPS,
                                  cell.seconds, profiler)
    peak = harness.allocator_peak(devices) + program.temp_bytes
    sizes, opt, temp = program.sizes, program.opt, program.temp_bytes
    program.free()
    reference = reference_readings(
        cell.seed, sizes, trainloop.first_batches(cell, sizes["vocab_size"]), opt)
    return trainloop.outcome(
        cell, devices, sizes=sizes, vocab_run=sizes["vocab_size"],
        program_name="train_step", window=window, readings=readings,
        reference=reference, setup_s=setup_s, peak=peak, temp_bytes=temp,
        profiler=profiler)


def limit_readings(cell: harness.Cell, devices: List[Any], seeds: List[int],
                   control_seeds: int):
    """For ``benchmark/limits.py``: per seed the program's numbers against
    the reference (the lower reading), and for the first ``control_seeds``
    the control's (the reference at fp8) and the half-batch fault's (the
    reference over the first half of every batch's rows)."""
    import dataclasses

    every = {k: float("inf") for k in ("loss_gap", "grad_norm_gap", "change_norm_gap")}
    for n, seed in enumerate(seeds):
        one = dataclasses.replace(cell, seed=seed)
        program = Program(one, devices[0])
        prog = program.first_steps()
        sizes, opt = program.sizes, program.opt
        program.free()
        batches = trainloop.first_batches(one, sizes["vocab_size"])
        reference = reference_readings(seed, sizes, batches, opt)

        def numbers(who, got):
            row = {name: value for name, value, _ in
                   correct.train_checks(got, reference, every)}
            return {"seed": seed, "who": who, **row,
                    "losses": [float(x) for x in got["losses"]]}

        yield numbers("program", prog)
        if n < control_seeds:
            yield numbers("control_fp8", reference_readings(
                seed, sizes, batches, opt, cast=ref.fp8_cast))
            half = slice(0, batches.shape[1] // 2)
            yield numbers("fault_half_batch", reference_readings(
                seed, sizes, batches, opt, rows=half))
