"""Runner ``composite_train``: ``composite.make_train_step`` on a mesh over
the cell's chips (ZeRO-3 gathers over ``fsdp``, Megatron psums over
``model``, gradient reduce-scatter, GSPMD embed / unembed).

The program's entry is called at its defaults. Set-up builds ONE compiled
step with its sharded state, drives it from the seed through its first
three steps and hands the same object to the window. The plain reference
runs afterwards on ONE device, a row at a time.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from .. import correct, harness, traffic, trainloop, weights
from ..reference import composite as ref
from ..reference import gpt as ref_gpt

LEAVES = ("embed", "ln1_scale", "ln2_scale", "w1", "w2", "wo", "wqkv")


def leaf_norms(canon_like: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    def norm(name, x):
        axes = None if name == "embed" else tuple(range(1, x.ndim))
        return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2, axis=axes))

    return {k: norm(k, canon_like[k]) for k in LEAVES}


def sizes_of(cell: harness.Cell) -> Dict[str, int]:
    s = {k: int(cell.config[k]) for k in
         ("vocab_size", "n_embd", "n_layer", "n_head", "n_inner", "n_positions")}
    s["vocab_size_run"] = int(cell.deploy.get("vocab_size_run", s["vocab_size"]))
    return s


def _dims(s: Dict[str, int]) -> Dict[str, int]:
    return dict(vocab=s["vocab_size_run"], d=s["n_embd"], layers=s["n_layer"], ff=s["n_inner"])


def build_reference(sizes: Dict[str, int], lr: float, cast=None):
    """(key, ids [steps, rows, seq]) -> losses, first-gradient leaf norms,
    params'-change leaf norms, on one device. The gradient is read the way
    the program's is: from the state after the step, (p0 - p1) / lr."""
    import jax

    heads = sizes["n_head"]

    @jax.jit
    def go(key, key_again, ids):
        def one(canon, rows):
            loss, grad = ref.loss_and_grad(canon, rows, heads, cast)
            new = ref.sgd_step(canon, grad, lr)
            moved = leaf_norms({k: canon[k] - new[k] for k in canon})
            return new, (loss, {k: v / lr for k, v in moved.items()})

        canon, (losses, grads) = jax.lax.scan(
            one, weights._composite_canonical(key, **_dims(sizes)), ids)
        # the initial weights are remade, not kept: a third copy of 774M
        # float32 parameters does not fit one chip beside the step (the key
        # comes in twice so that the compiler cannot merge the two makes)
        canon0 = weights._composite_canonical(key_again, **_dims(sizes))
        change = leaf_norms({k: canon[k] - canon0[k] for k in canon0})
        return losses, {k: v[0] for k, v in grads.items()}, change

    return go


def reference_readings(seed: int, sizes: Dict[str, int], batches: np.ndarray,
                       lr: float, device: Any, cast=None, rows=None) -> Dict[str, Any]:
    import jax

    ids = batches.reshape((batches.shape[0], -1, batches.shape[-1]))
    if rows is not None:
        ids = ids[:, rows]
    with jax.default_device(device):
        key = jax.device_put(weights.seed_key(seed), device)
        losses, grad, change = build_reference(sizes, lr, cast)(
            key, key + 0, jax.device_put(ids, device))
    return {"losses": np.asarray(losses), "grad": jax.device_get(grad),
            "change": jax.device_get(change)}


class Program:
    def __init__(self, cell: harness.Cell, devices: List[Any]):
        import jax

        from kubeflow_tpu.parallel import MeshConfig, composite, make_mesh
        from kubeflow_tpu.parallel.composite import CompositeConfig

        self.sizes = s = sizes_of(cell)
        self.lr = float(cell.deploy["lr"])
        self.mix, self.seed = cell.mix, cell.seed
        self.ccfg = CompositeConfig(vocab_size=s["vocab_size_run"], d_model=s["n_embd"],
                                    n_heads=s["n_head"], d_ff=s["n_inner"],
                                    n_layers=s["n_layer"], seq=int(self.mix["shape"][-1]))
        self.mesh = make_mesh(MeshConfig(**cell.deploy["mesh"]), devices=devices)
        self.shardings = composite.param_shardings(self.ccfg, self.mesh)
        self.batch_sharding = composite.batch_sharding(self.mesh)
        self.make = jax.jit(
            lambda key: weights.composite_tree(weights._composite_canonical(key, **_dims(s))),
            out_shardings=self.shardings)
        self.params = self.make(weights.seed_key(self.seed))
        ids = jax.ShapeDtypeStruct(tuple(self.mix["shape"]), np.int32,
                                   sharding=self.batch_sharding)
        self.step = composite.make_train_step(self.ccfg, self.mesh, lr=self.lr).lower(
            self.params, ids).compile()
        self.temp_bytes = int(self.step.memory_analysis().temp_size_in_bytes)
        text = self.step.as_text()
        self.collectives = sum(text.count(f" {op}(") + text.count(f" {op}-start(")
                               for op in ("all-gather", "all-reduce", "reduce-scatter"))

    def feed(self, i: int):
        import jax

        return jax.device_put(
            traffic.batch(self.mix, self.sizes["vocab_size"], self.seed, i),
            self.batch_sharding)

    def __call__(self, batch):
        self.params, loss = self.step(self.params, batch)
        return loss

    def moved_norms(self, before, scale: float = 1.0):
        import jax

        @jax.jit
        def go(now, then):
            a = weights.composite_canonical_from_tree(now)
            b = weights.composite_canonical_from_tree(then)
            return leaf_norms({k: b[k] - a[k] for k in a})

        return {k: np.asarray(v) / scale
                for k, v in jax.device_get(go(self.params, before)).items()}

    def first_steps(self) -> Dict[str, Any]:
        start = self.params           # the step does not donate: p0 stays
        losses, grad = [], None
        for i in range(trainloop.FIRST_STEPS):
            losses.append(float(self(self.feed(i))))
            if i == 0:
                grad = self.moved_norms(start, self.lr)
        return {"losses": np.asarray(losses), "grad": grad,
                "change": self.moved_norms(start)}

    def free(self) -> None:
        self.params = self.step = self.make = None
        gc.collect()


def run(cell: harness.Cell, devices: List[Any], t0: float) -> harness.Outcome:
    program = Program(cell, devices)
    harness.note("sharded weights made, step compiled")
    readings = program.first_steps()
    harness.note("first steps driven and read")
    profiler = harness.Profiler(cell) if cell.trace else None
    setup_s = time.perf_counter() - t0
    window = trainloop.run_window(program, program.feed, trainloop.FIRST_STEPS,
                                  cell.seconds, profiler)
    peak = harness.allocator_peak(devices) + program.temp_bytes
    sizes, lr, temp, collectives = (program.sizes, program.lr, program.temp_bytes,
                                    program.collectives)
    program.free()
    reference = reference_readings(
        cell.seed, sizes, trainloop.first_batches(cell, sizes["vocab_size"]), lr, devices[0])
    return trainloop.outcome(
        cell, devices, sizes=sizes, vocab_run=sizes["vocab_size_run"],
        program_name="step", window=window, readings=readings, reference=reference,
        setup_s=setup_s, peak=peak, temp_bytes=temp, profiler=profiler,
        collectives_in_program=collectives)


def limit_readings(cell: harness.Cell, devices: List[Any], seeds: List[int],
                   control_seeds: int):
    """For ``benchmark/limits.py``: per seed the program's numbers against
    the reference, and for the first seeds the control's (the reference at
    fp8), the half-batch fault's (the reference over the first row of the
    two) and the left-out exchange's (the program itself with every
    ``psum`` over the model axis turned into the identity)."""
    import dataclasses

    import jax

    from kubeflow_tpu.parallel import composite

    every = {k: float("inf") for k in ("loss_gap", "grad_norm_gap", "change_norm_gap")}

    class NoPsum:
        def __getattr__(self, name):
            return (lambda x, axis: x) if name == "psum" else getattr(jax.lax, name)

    for n, seed in enumerate(seeds):
        one = dataclasses.replace(cell, seed=seed)
        program = Program(one, devices)
        prog = program.first_steps()
        sizes, lr = program.sizes, program.lr
        program.free()
        broken = None
        if n < control_seeds:
            real, composite.lax = composite.lax, NoPsum()
            try:
                program = Program(one, devices)
                broken = program.first_steps()
                program.free()
            finally:
                composite.lax = real
        batches = trainloop.first_batches(one, sizes["vocab_size"])
        reference = reference_readings(seed, sizes, batches, lr, devices[0])

        def numbers(who, got):
            row = {name: value for name, value, _ in
                   correct.train_checks(got, reference, every)}
            return {"seed": seed, "who": who, **row,
                    "losses": [float(x) for x in got["losses"]]}

        yield numbers("program", prog)
        if n < control_seeds:
            yield numbers("fault_no_exchange", broken)
            yield numbers("control_fp8", reference_readings(
                seed, sizes, batches, lr, devices[0], cast=ref_gpt.fp8_cast))
            yield numbers("fault_half_batch", reference_readings(
                seed, sizes, batches, lr, devices[0], rows=slice(0, 1)))
