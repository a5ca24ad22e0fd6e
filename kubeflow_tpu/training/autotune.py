"""Bench-in-the-loop autotuner: the attribution plane closing its own loop.

PR 8 built the pricing side (``price_callable``: AOT cost analysis +
roofline verdicts, no allocation) and the measuring side (``StepClock``).
This module wires them into a two-stage sweep over kernel/knob configs:

1. **prune** — every candidate is priced with ``price_callable`` (an AOT
   compile of its train step from ``ShapeDtypeStruct``s); only the ``keep``
   best roofline estimates survive. Pricing a config costs one compile,
   never a training step, so the sweep can afford a wide grid.
2. **measure** — survivors run a handful of real steps under a
   ``StepClock``; the measured step time picks the winner. Rooflines rank,
   clocks decide.

The sweep is generic over knob dicts: the ResNet bench sweeps the fused
kernel set and batch bucket, the GPT bench sweeps ``remat``/``scan_blocks``
(and the FSDP ``gather_mode`` when the mesh has more than one device).
``bench.py`` records ``AutotuneResult.to_row()`` in its bench rows
(``autotune`` field), so a BENCH round documents the config that produced
it — reproducibility is the point.

``python -m kubeflow_tpu.training.autotune --quick`` runs the sweep on
toy shapes (CPU interpret-mode friendly); the ``autotune-smoke`` presubmit
keeps that path from rotting.
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

#: knob dict -> roofline seconds (may raise; errors are recorded, not fatal)
PriceFn = Callable[[Dict[str, Any]], float]
#: knob dict -> measured seconds per step (may raise)
MeasureFn = Callable[[Dict[str, Any]], float]


@dataclass
class TunedCandidate:
    """One swept config: knobs + what the two stages said about it."""

    knobs: Dict[str, Any]
    est_seconds: Optional[float] = None       # stage-1 roofline price
    measured_seconds: Optional[float] = None  # stage-2 StepClock pick
    pruned: bool = False                      # dropped after pricing
    error: Optional[str] = None               # a stage raised; excluded

    def to_dict(self) -> Dict[str, Any]:
        return {
            "knobs": self.knobs,
            "est_seconds": self.est_seconds,
            "measured_seconds": self.measured_seconds,
            "pruned": self.pruned,
            "error": self.error,
        }


@dataclass
class AutotuneResult:
    """The sweep's verdict + full audit table."""

    family: str                       # "resnet" | "gpt" | ...
    chosen: Dict[str, Any]
    candidates: List[TunedCandidate] = field(default_factory=list)
    quick: bool = False

    def to_row(self) -> Dict[str, Any]:
        """Compact form for a bench row's ``autotune`` field."""
        measured = [c for c in self.candidates if c.measured_seconds is not None]
        return {
            "family": self.family,
            "chosen": self.chosen,
            "swept": len(self.candidates),
            "pruned": sum(1 for c in self.candidates if c.pruned),
            "measured": len(measured),
            "errors": sum(1 for c in self.candidates if c.error),
            "quick": self.quick,
        }

    def to_dict(self) -> Dict[str, Any]:
        d = self.to_row()
        d["candidates"] = [c.to_dict() for c in self.candidates]
        return d

    def render(self) -> str:
        lines = [f"# autotune[{self.family}] chosen: {self.chosen}"]
        for c in self.candidates:
            est = f"{c.est_seconds * 1e3:.3f}ms" if c.est_seconds is not None else "-"
            meas = (f"{c.measured_seconds * 1e3:.3f}ms"
                    if c.measured_seconds is not None else "-")
            tag = "PRUNED" if c.pruned else ("ERROR " + c.error if c.error else "")
            lines.append(f"  {c.knobs}  est={est}  measured={meas}  {tag}")
        return "\n".join(lines)


def sweep(
    family: str,
    candidates: List[Dict[str, Any]],
    *,
    measure: MeasureFn,
    price: Optional[PriceFn] = None,
    keep: int = 2,
    quick: bool = False,
    log: Optional[Callable[[str], None]] = None,
) -> AutotuneResult:
    """Run the two-stage sweep. With a ``price`` fn, only the ``keep``
    cheapest roofline estimates are measured; without one, every candidate
    is. The winner is the smallest measured step; if every measurement
    fails, the best (un-errored) estimate; if even pricing failed
    everywhere, the first candidate (the caller's default ordering)."""
    if not candidates:
        raise ValueError("sweep needs at least one candidate")
    say = log or (lambda s: None)
    table = [TunedCandidate(knobs=dict(k)) for k in candidates]

    if price is not None:
        for c in table:
            try:
                c.est_seconds = float(price(c.knobs))
            except Exception as exc:
                # pricing is advisory, never fatal — an unpriceable
                # candidate (e.g. collectives, invisible to single-program
                # cost analysis) is still MEASURED, just never pruned-by-
                # price and never eligible for the price fallback
                c.error = f"price: {exc}"
        priced = sorted((c for c in table if c.est_seconds is not None),
                        key=lambda c: c.est_seconds)
        for c in priced[max(1, keep):]:
            c.pruned = True
        say(f"autotune[{family}]: priced {len(priced)}/{len(table)}, "
            f"measuring {sum(1 for c in table if not c.pruned)}")

    for c in table:
        if c.pruned:
            continue
        try:
            start = time.perf_counter()
            c.measured_seconds = float(measure(c.knobs))
            say(f"autotune[{family}]: {c.knobs} -> "
                f"{c.measured_seconds * 1e3:.3f} ms/step "
                f"(swept in {time.perf_counter() - start:.1f}s)")
        except Exception as exc:
            c.error = (f"{c.error}; measure: {exc}" if c.error
                       else f"measure: {exc}")
            say(f"autotune[{family}]: {c.knobs} failed: {exc}")

    measured = [c for c in table if c.measured_seconds is not None]
    if measured:
        chosen = min(measured, key=lambda c: c.measured_seconds).knobs
    else:
        # no measurement survived anywhere (e.g. no hardware): the best
        # roofline estimate decides; with no estimates either, the first
        # candidate (the caller's default ordering) wins
        priced_ok = [c for c in table if c.est_seconds is not None]
        chosen = (min(priced_ok, key=lambda c: c.est_seconds).knobs
                  if priced_ok else table[0].knobs)
    return AutotuneResult(family=family, chosen=chosen, candidates=table,
                          quick=quick)


def measure_steps(compiled: Callable[[], Any], steps: int = 3) -> float:
    """Median wall-clock of ``steps`` calls to a zero-arg thunk that runs
    one step and blocks until the result is ready (StepClock's compute
    phase, without needing the full loop scaffolding)."""
    from kubeflow_tpu.tpu.profiling import StepClock

    clock = StepClock()
    for _ in range(steps):
        with clock.phase("compute"):
            compiled()
        clock.end_step()
    times = sorted(s.get("compute", 0.0) for s in clock.steps)
    return times[len(times) // 2]


# -- quick mode: toy shapes, CPU interpret-mode friendly ----------------------


def resnet_quick_candidates() -> List[Dict[str, Any]]:
    return [{"fused_blocks": False}, {"fused_blocks": True}]


def autotune_resnet_quick(steps: int = 2) -> AutotuneResult:
    """The ResNet sweep at toy shape: fused kernel set on/off, priced via
    the unfused reference (XLA credits no FLOPs in a Pallas call — same
    ground rule as bench.py's MFU numerator), measured with real grad
    steps on whatever backend is present."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.resnet import BottleneckBlock, ResNet
    from kubeflow_tpu.training.attribution import price_callable

    batch, image = 4, 32
    x = jnp.zeros((batch, image, image, 3), jnp.float32)

    def build(fused: bool):
        return ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                      num_classes=10, num_filters=8, fused_blocks=fused)

    ref = build(False)
    variables = ref.init(jax.random.PRNGKey(0), x, train=False)

    def price(knobs: Dict[str, Any]) -> float:
        struct_v = jax.eval_shape(lambda: variables)
        struct_x = jax.ShapeDtypeStruct(x.shape, x.dtype)
        cost = price_callable(
            lambda v, a: ref.apply(v, a, train=False), struct_v, struct_x,
            name="resnet_quick", kind="model")
        # the fused path saves the inter-op HBM round trips; credit the
        # roofline with the traffic the kernel keeps in VMEM
        return cost.est_seconds * (0.7 if knobs["fused_blocks"] else 1.0)

    def measure(knobs: Dict[str, Any]) -> float:
        model = build(knobs["fused_blocks"])

        def loss_fn(params, batch_stats):
            out = model.apply(
                {"params": params, "batch_stats": batch_stats}, x,
                train=False)
            return jnp.mean(out ** 2)

        grad = jax.jit(jax.grad(loss_fn))
        g = grad(variables["params"], variables["batch_stats"])  # compile
        jax.block_until_ready(g)
        return measure_steps(
            lambda: jax.block_until_ready(
                grad(variables["params"], variables["batch_stats"])),
            steps=steps)

    return sweep("resnet", resnet_quick_candidates(), measure=measure,
                 price=price, keep=2, quick=True)


def gpt_quick_candidates(n_devices: int = 1) -> List[Dict[str, Any]]:
    grid = [
        {"remat": False, "scan_blocks": True},
        {"remat": True, "scan_blocks": True},
        {"remat": False, "scan_blocks": False},
    ]
    if n_devices > 1:
        grid = [dict(g, gather_mode=m) for g in grid
                for m in ("overlap", "eager")]
    return grid


def autotune_gpt_quick(steps: int = 2) -> AutotuneResult:
    """The GPT sweep at toy shape: remat x scan_blocks (x fsdp gather mode
    when the mesh has >1 device), priced by AOT cost of the candidate's own
    train step (remat's recompute shows up in its FLOPs), measured with
    real steps."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.models.gpt import GptConfig, GptLM
    from kubeflow_tpu.training.attribution import price_callable

    n_dev = len(jax.devices())
    batch, seq = 2, 32
    ids = jnp.zeros((batch, seq), jnp.int32)

    def build(knobs: Dict[str, Any]):
        cfg = GptConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                        max_seq=seq, vocab_size=64,
                        remat=bool(knobs.get("remat")),
                        scan_blocks=bool(knobs.get("scan_blocks")))
        model = GptLM(cfg)
        params = model.init(jax.random.PRNGKey(0), ids)
        tx = optax.sgd(1e-2)

        def loss_fn(p):
            logits = model.apply(p, ids)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            tgt = jnp.roll(ids, -1, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

        def step(p, opt):
            loss, g = jax.value_and_grad(loss_fn)(p)
            updates, opt = tx.update(g, opt, p)
            return optax.apply_updates(p, updates), opt, loss

        return jax.jit(step), params, tx.init(params)

    def build_fsdp(knobs: Dict[str, Any]):
        from kubeflow_tpu.training.fsdp import (
            FsdpConfig, fsdp_batch_sharding, fsdp_mesh, init_fsdp_params,
            make_fsdp_train_step)

        cfg = FsdpConfig(d_model=32, n_layers=2, n_heads=2, d_ff=64,
                         seq=seq, vocab_size=64)
        mesh = fsdp_mesh()
        params = init_fsdp_params(jax.random.PRNGKey(0), cfg, mesh)
        fids = jax.device_put(
            jnp.zeros((max(batch, n_dev), seq), jnp.int32),
            fsdp_batch_sharding(mesh))
        step = make_fsdp_train_step(cfg, mesh,
                                    gather_mode=knobs["gather_mode"])
        return step, params, fids

    def price(knobs: Dict[str, Any]) -> float:
        if "gather_mode" in knobs:
            # collectives are invisible to single-program cost analysis;
            # rank gather modes by measurement only
            raise ValueError("gather_mode is measured, not priced")
        step, params, opt = build(knobs)
        sp = jax.eval_shape(lambda: params)
        so = jax.eval_shape(lambda: opt)
        return price_callable(
            lambda p, o: step(p, o)[2], sp, so,
            name="gpt_quick", kind="model", train_factor=1.0).est_seconds

    def measure(knobs: Dict[str, Any]) -> float:
        if "gather_mode" in knobs:
            step, params, fids = build_fsdp(knobs)
            out = step(params, fids)
            jax.block_until_ready(out)
            return measure_steps(
                lambda: jax.block_until_ready(step(params, fids)),
                steps=steps)
        step, params, opt = build(knobs)
        out = step(params, opt)
        jax.block_until_ready(out)
        return measure_steps(
            lambda: jax.block_until_ready(step(params, opt)), steps=steps)

    # with gather_mode in the grid pricing is per-candidate impossible for
    # the fsdp rows; sweep() records those as price errors and still
    # measures them (pruning only ever drops priced candidates)
    cands = gpt_quick_candidates(n_dev)
    return sweep("gpt", cands, measure=measure,
                 price=None if n_dev > 1 else price,
                 keep=2, quick=True)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--quick", action="store_true",
                        help="toy shapes; the autotune-smoke presubmit path")
    parser.add_argument("--family", choices=("resnet", "gpt", "all"),
                        default="all")
    parser.add_argument("--steps", type=int, default=2)
    args = parser.parse_args(argv)
    if not args.quick:
        parser.error("only --quick is wired for standalone runs; the full "
                     "sweep runs inside bench.py (BENCH_AUTOTUNE=1)")
    from kubeflow_tpu.tpu.env import enable_compile_cache

    enable_compile_cache()
    out: Dict[str, Any] = {}
    if args.family in ("resnet", "all"):
        out["resnet"] = autotune_resnet_quick(steps=args.steps).to_dict()
    if args.family in ("gpt", "all"):
        out["gpt"] = autotune_gpt_quick(steps=args.steps).to_dict()
    print(json.dumps(out, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
