"""Headline benchmark: ResNet-50 training MFU on one TPU chip.

The reference publishes no benchmark numbers (BASELINE.md); the driver's
north-star is ResNet-50 at >=60% MFU on v5e. This bench runs the flagship
training step (fwd+bwd+SGD in one jit, bf16, synthetic data — measuring the
compute path, not input pipeline) and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` = measured MFU / 0.60 target (>=1.0 beats the north-star).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from kubeflow_tpu.tpu import profiling

TARGET_MFU = 0.60
# whoever imports a train step of this file has its compiles counted from here
profiling.watch_compiles()


def _batch_candidates() -> list:
    # 256 first: it measured marginally better than 512 (batch 512 spills
    # more activations), and the first batch that fits is the headline.
    try:
        override = os.environ.get("BENCH_BATCH")
        return [int(override)] if override else [256, 512, 128, 64, 32]
    except ValueError:
        return [256, 512, 128, 64, 32]


def _timed_steps() -> int:
    # 50 steps in one scan: long enough that the fixed per-dispatch cost is
    # a small share of the window.
    try:
        return int(os.environ.get("BENCH_STEPS", "50"))
    except ValueError:
        return 50


def _repeats() -> int:
    # Repeat the timed window and take the MEDIAN; the reported spread
    # makes a transient stall visible instead of becoming the headline.
    try:
        return max(1, int(os.environ.get("BENCH_REPEATS", "3")))
    except ValueError:
        return 3


def _timed_windows(fn, repeats: int):
    """Run ``fn()`` (one fetched-checksum window) ``repeats`` times; return
    (median_seconds, [per-window seconds])."""
    import statistics

    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
        # every window's fetched results must be finite — the median time
        # may come from any of them, so none may be a corrupted run
        fn.check()
    return statistics.median(times), times


def _step_breakdown(clock, timed_steps: int) -> dict:
    """StepClock summary → the per-step dict bench rows carry. One clock
    "step" is one timed WINDOW (timed_steps scan iterations), so window
    phases are normalized back to per-step seconds; compile stays a
    one-time total."""
    s = clock.summary()
    return {
        "compile_s": round(s.get("compile_s", 0.0), 3),
        "data_wait_s_per_step": round(s.get("data_wait", 0.0) / timed_steps, 6),
        "device_compute_s_per_step": round(s.get("compute", 0.0) / timed_steps, 6),
        "fetch_s_per_step": round(s.get("fetch", 0.0) / timed_steps, 6),
        "host_other_s_per_step": round(s.get("other", 0.0) / timed_steps, 6),
    }


def _attribution_row(make_costs, clock, timed_steps: int, generation: str):
    """Per-module attribution for a bench row (BENCH_ATTRIBUTION=0 skips).
    ``make_costs`` prices the model walk — compile-time only, nothing
    executes — and the report decomposes the clock's measured window into
    data-wait / fused-compute / un-fused-compute / other step fractions.
    Guarded: attribution failing must never fail the bench."""
    if os.environ.get("BENCH_ATTRIBUTION", "1") != "1":
        return None
    try:
        from kubeflow_tpu.training.attribution import attribution_report

        report = attribution_report(make_costs(), clock=clock,
                                    steps_per_record=timed_steps,
                                    generation=generation)
        return report.to_dict(top_n=5)
    except Exception as e:
        return {"error": str(e)[:160]}


def _sweep_or_fail(family: str, candidates, **kwargs):
    """``autotune.sweep`` with no survivor's-win: the sweep records a
    candidate's exception and lets the others decide, which would turn a
    kernel the compiler refuses into a slower row and exit 0. In the bench
    every candidate is a program the row may be timed on, so any error
    fails the row."""
    from kubeflow_tpu.training.autotune import sweep

    result = sweep(family, candidates,
                   log=lambda s: print(s, file=sys.stderr), **kwargs)
    failed = [c for c in result.candidates if c.error]
    if failed:
        raise RuntimeError(
            f"autotune[{family}]: " + "; ".join(
                f"{c.knobs}: {c.error}" for c in failed))
    return result


def resnet_train_step(fused: bool, stem: str = "s2d"):
    """The ResNet-50 row's program: ``(task, jitted train step)``."""
    from kubeflow_tpu.models import ResNet50
    from kubeflow_tpu.training import ClassifierTask
    from kubeflow_tpu.training.classifier import sgd_momentum

    model = ResNet50(num_classes=1000, stem=stem, fused_blocks=fused)
    task = ClassifierTask(
        model=model, optimizer=sgd_momentum(lr=0.1, total_steps=1000))
    return task, task.make_train_step()


def _bench(batch: int, gen: str):
    from kubeflow_tpu.training import mfu
    from kubeflow_tpu.training.flops import compiled_with_cost
    from kubeflow_tpu.runtime.tracing import TRACER
    from kubeflow_tpu.tpu.profiling import StepClock

    # s2d stem: opt-in on the model (param-tree compat) but the bench always
    # wants the fast path.
    stem = os.environ.get("BENCH_STEM", "s2d")
    timed_steps = _timed_steps()
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(rng, (batch, 224, 224, 3), jnp.float32)
    labels = jax.random.randint(rng, (batch,), 0, 1000)

    def make_step(fused: bool):
        return resnet_train_step(fused, stem)

    # Both paths declare the SAME variable tree (resnet._ConvKernel /
    # _FoldedNorm), so one init serves fused and unfused executables.
    task0, _ = make_step(False)
    state = task0.init(rng, images)

    # All timed steps run inside ONE executable (lax.scan): a single
    # dispatch covers the whole window, so per-dispatch latency and
    # async-dispatch artifacts cannot distort the measurement. The fetched
    # outputs depend on the LAST step's update (param checksum) and loss,
    # so no step can be dead-code-eliminated. Images/labels are ARGUMENTS:
    # a closure-captured batch becomes a constant of the program.
    def make_window(fused: bool, steps: int):
        _, step = make_step(fused)

        @jax.jit
        def run_steps(state, images, labels):
            def body(s, _):
                s2, metrics = step(s, images, labels)
                return s2, metrics["loss"]
            final, losses = jax.lax.scan(body, state, None, length=steps)
            checksum = sum(jnp.sum(p.astype(jnp.float32))
                           for p in jax.tree_util.tree_leaves(final.params))
            return losses[-1], checksum

        return run_steps

    # BENCH_FUSED: 1 = Pallas fused bottlenecks, 0 = XLA composite,
    # auto (default) = measured head-to-head via the autotune sweep, keep
    # the winner: the acceptance bar is "never slower than the composite",
    # so the bench measures instead of assuming.
    # BENCH_AUTOTUNE=0 skips the measurement and pins the fused path.
    fused_mode = os.environ.get("BENCH_FUSED", "auto")
    autotune_on = os.environ.get("BENCH_AUTOTUNE", "1") != "0"
    calibration = None
    autotune_row = None
    if fused_mode in ("0", "1"):
        use_fused = fused_mode == "1"
        autotune_row = {"family": "resnet",
                        "chosen": {"fused_blocks": use_fused},
                        "pinned": f"BENCH_FUSED={fused_mode}"}
    elif not autotune_on:
        use_fused = True
    else:
        calib_steps = max(4, min(10, timed_steps))

        def _measure(knobs):
            run = make_window(knobs["fused_blocks"], calib_steps)
            loss, cs = run(state, images, labels)  # compile + warmup
            _ = (float(loss), float(cs))
            t0 = time.perf_counter()
            loss, cs = run(state, images, labels)
            _ = (float(loss), float(cs))
            return (time.perf_counter() - t0) / calib_steps

        result = _sweep_or_fail(
            "resnet",
            [{"fused_blocks": False}, {"fused_blocks": True}],
            measure=_measure)
        use_fused = bool(result.chosen["fused_blocks"])
        autotune_row = result.to_row()
        # legacy row shape, kept for cross-round history comparisons
        calibration = {
            "fused" if c.knobs["fused_blocks"] else "unfused":
                round(c.measured_seconds, 6)
            for c in result.candidates}

    clock = StepClock(tracer=TRACER)
    run_steps = make_window(use_fused, timed_steps)

    # Per-step FLOPs always from the UNFUSED step: XLA credits ZERO flops
    # inside a Pallas custom call (same blindness as flash attention), so
    # probing the fused executable would drop most of the conv work from
    # the numerator and fake an MFU collapse. Same model math either way.
    # (And never the whole window: cost analysis counts a while-loop body
    # once, not × trip count.) compiled_with_cost times this compile; the
    # window compile below is also charged to the clock so compile_s never
    # pollutes a timed window.
    _, step_ref = make_step(False)
    with clock.compile():
        _, flops, _ = compiled_with_cost(step_ref, state, images, labels)
    if not flops:
        raise RuntimeError("XLA cost analysis reported no FLOPs for the "
                           "ResNet-50 reference step")

    # AOT-compile the window under the compile clock, then one warmup
    # execution OUTSIDE it, forced to completion by the host fetch.
    with clock.compile():
        run_steps, _, _ = compiled_with_cost(run_steps, state, images, labels)
    loss, checksum = run_steps(state, images, labels)
    _ = (float(loss), float(checksum))
    clock.mark()  # warmup execution is untimed — keep it out of "other"

    import math

    results = {}

    def window():
        with clock.compute():
            loss, checksum = run_steps(state, images, labels)
            jax.block_until_ready((loss, checksum))
        with clock.fetch():
            # host fetch = real barrier; finiteness checked outside the timer
            results["loss"], results["checksum"] = float(loss), float(checksum)
        clock.end_step()

    def check():
        if not all(math.isfinite(v) for v in results.values()):
            raise RuntimeError(f"non-finite bench result: {results}")

    window.check = check
    total, window_times = _timed_windows(window, _repeats())
    dt = total / timed_steps

    # HBM telemetry from the window executable's memory_analysis (the loop
    # reuses temps, so the window's resident bytes ARE the step's peak);
    # published as the training_step_peak_hbm_bytes gauge and the bench row.
    from kubeflow_tpu.training.attribution import record_step_peak_hbm
    from kubeflow_tpu.training.flops import memory_stats

    mem = memory_stats(run_steps)
    record_step_peak_hbm(mem)

    def _resnet_costs():
        from kubeflow_tpu.training.attribution import attribute_resnet

        return attribute_resnet(batch=batch, image=224, stem=stem,
                                fused_blocks=use_fused, generation=gen)

    attribution = _attribution_row(_resnet_costs, clock, timed_steps, gen)
    return {
        "images_per_sec_per_chip": batch / dt,
        "step_seconds": dt,
        "mfu": mfu(flops, dt, num_chips=1, generation=gen),
        "window_mfus": [round(mfu(flops, t / timed_steps, 1, gen) * 100, 2)
                        for t in window_times],
        "generation": gen,
        "batch": batch,
        "flops_per_step": flops,
        "fused_blocks": use_fused,
        "fused_calibration": calibration,
        "autotune": autotune_row,
        "step_breakdown": _step_breakdown(clock, timed_steps),
        "peak_hbm_bytes": (mem or {}).get("peak_hbm_bytes"),
        "memory": mem,
        "attribution": attribution,
    }


#: The GPT row's knobs at 24L x 1024, b8 x 1024 on one 16 GB chip. Under the
#: installed compiler the scanned, un-rematerialized window needs 22.5 GiB of
#: the 15.75 GiB there is (compile rehearsal for a described v5e, PR 21), so
#: remat is on and that candidate is not in the sweep. Remat keeps a block's
#: input, its matmul outputs and the flash kernel's residuals
#: (``models/gpt.SAVED_IN_BLOCK``) and recomputes the elementwise pieces:
#: 9.65 GB of temporaries beside 4.24 GB of arguments (the same rehearsal,
#: PR 34), and no block's forward runs twice.
GPT_TRAIN_KNOBS = {"scan_blocks": True, "remat": True}
GPT_SWEEP = [GPT_TRAIN_KNOBS, {"scan_blocks": False, "remat": False}]


def gpt_train_config(seq: int = 1024, **knobs):
    """GPT-2-medium-class config of the training row (``knobs`` override
    ``GPT_TRAIN_KNOBS``)."""
    from kubeflow_tpu.models.gpt import GptConfig

    return GptConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                     max_seq=seq, vocab_size=32000,
                     **{**GPT_TRAIN_KNOBS, **knobs})


def gpt_train_step(cfg, opt, fused_loss: bool = True):
    """The GPT row's program: ``(model, train_step)`` with
    ``train_step(params, opt_state, ids) -> (params, opt_state, loss)``.
    The blockwise loss never materializes the [b, L, vocab] f32 logits
    (1 GiB at b8/L1024)."""
    import optax

    from kubeflow_tpu.models.gpt import (
        GptLM, blockwise_causal_lm_loss, causal_lm_loss)

    model = GptLM(cfg)

    def loss_fn(p, ids):
        if fused_loss:
            hidden = model.apply({"params": p}, ids, return_hidden=True)
            with jax.named_scope("loss"):
                return blockwise_causal_lm_loss(
                    hidden, p["embedding"]["embedding"], ids)
        return causal_lm_loss(model.apply({"params": p}, ids), ids)

    def train_step(params, opt_state, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

    return model, train_step


def _bench_gpt(batch: int, seq: int, gen: str):
    """GPT-2-medium-class causal LM train step (AdamW, bf16 compute, Pallas
    flash attention). The matmul-dominated counterpart to the ResNet row:
    it shows the MFU the framework reaches when the model shape suits the
    128x128 MXU — ResNet's 64-wide convs cannot."""
    import optax as _optax

    from kubeflow_tpu.models.gpt import GptLM, causal_lm_loss
    from kubeflow_tpu.training import mfu
    from kubeflow_tpu.training.flops import compiled_with_cost
    from kubeflow_tpu.runtime.tracing import TRACER
    from kubeflow_tpu.tpu.profiling import StepClock

    # BENCH_GPT_SCAN / BENCH_REMAT pin a knob, BENCH_FUSED_LOSS=0 compares
    # the plain loss. With BENCH_AUTOTUNE on (default), unpinned knobs are
    # swept over GPT_SWEEP by training.autotune: priced first (AOT compile,
    # no steps), survivors measured with short windows, the winner drives
    # the run.
    scan_env = os.environ.get("BENCH_GPT_SCAN")
    remat_env = os.environ.get("BENCH_REMAT")
    fused_loss = os.environ.get("BENCH_FUSED_LOSS", "1") == "1"
    autotune_on = os.environ.get("BENCH_AUTOTUNE", "1") != "0"
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (batch, seq), 0, 32000)
    opt = _optax.adamw(3e-4, weight_decay=0.01)
    timed_steps = _timed_steps()

    def make_cfg(scan_blocks, remat):
        return gpt_train_config(seq, scan_blocks=scan_blocks, remat=remat)

    def build(cfg):
        model, train_step = gpt_train_step(cfg, opt, fused_loss)

        def make_run(n):
            def run_steps(params, opt_state, ids):
                def body(carry, _):
                    p, s = carry
                    p, s, loss = train_step(p, s, ids)
                    return (p, s), loss
                (p, s), losses = jax.lax.scan(
                    body, (params, opt_state), None, length=n)
                checksum = sum(jnp.sum(x.astype(jnp.float32))
                               for x in jax.tree_util.tree_leaves(p))
                return losses[-1], checksum
            return run_steps

        return model, train_step, make_run

    pinned = {k: env == "1" for k, env in
              (("scan_blocks", scan_env), ("remat", remat_env))
              if env is not None}
    default_knobs = {**GPT_TRAIN_KNOBS, **pinned}
    candidates = [c for c in GPT_SWEEP
                  if all(c[k] == v for k, v in pinned.items())]
    autotune_row = None
    if autotune_on and len(candidates) > 1:
        from kubeflow_tpu.training.attribution import price_callable

        calib_steps = max(2, min(4, timed_steps))

        def _price(knobs):
            model_c, step_c, _ = build(make_cfg(**knobs))
            p_s = jax.eval_shape(model_c.init, rng, ids)["params"]
            o_s = jax.eval_shape(opt.init, p_s)
            return price_callable(
                step_c, p_s, o_s, ids, name="gpt_bench", kind="model",
                generation=gen, train_factor=1.0).est_seconds

        def _measure(knobs):
            model_c, _, make_run_c = build(make_cfg(**knobs))
            p = model_c.init(rng, ids)["params"]
            o = opt.init(p)
            run = jax.jit(make_run_c(calib_steps))
            out = run(p, o, ids)  # compile + warmup
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = run(p, o, ids)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / calib_steps

        result = _sweep_or_fail(
            "gpt", candidates, measure=_measure, price=_price, keep=2)
        chosen = dict(default_knobs)
        chosen.update(result.chosen)
        autotune_row = result.to_row()
    else:
        chosen = default_knobs
        autotune_row = {"family": "gpt", "chosen": dict(chosen),
                        "pinned": "env"}

    scan_blocks = bool(chosen["scan_blocks"])
    cfg = make_cfg(scan_blocks, bool(chosen["remat"]))
    model, train_step, make_run = build(cfg)
    params = model.init(rng, ids)["params"]
    opt_state = opt.init(params)
    run_steps = jax.jit(make_run(timed_steps))

    clock = StepClock(tracer=TRACER)
    # FLOPs numerator from the REFERENCE path (unrolled blocks, plain
    # loss): XLA cost analysis counts a while-loop body ONCE, so probing
    # the scanned / vocab-chunked executables would undercount the blocks
    # 24x and the LM head ~8x — the fast paths would fake an MFU drop.
    # Lowering from eval_shape structs keeps the probe allocation-free.
    import dataclasses as _dc

    # remat=False too: rematerialized flops are recompute, not model
    # work — counting them would inflate the numerator of a remat config.
    ref_model = GptLM(_dc.replace(cfg, scan_blocks=False, remat=False))

    def ref_step(params, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda p: causal_lm_loss(ref_model.apply({"params": p}, ids), ids)
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return _optax.apply_updates(params, updates), opt_state, loss

    ref_params = jax.eval_shape(ref_model.init, rng, ids)["params"]
    ref_opt_state = jax.eval_shape(opt.init, ref_params)
    with clock.compile():
        _, flops, _ = compiled_with_cost(
            jax.jit(ref_step), ref_params, ref_opt_state, ids)
    if not flops:
        raise RuntimeError("XLA cost analysis reported no FLOPs for the "
                           "GPT reference step")
    # XLA cost analysis counts ZERO flops inside the Pallas flash-attention
    # custom call (verified: identical totals for b8xL1024 and b4xL2048,
    # whose attention flops differ 2x) — add the causal attention work the
    # kernel actually executes, or attention-heavy configs are
    # under-credited. Convention matches the rest of the numerator
    # (2 flops/MAC): one causal dot = 2*L^2*d/2 flops per (b, head); fwd
    # has 2 dots (QK^T, PV), bwd 5 (recomputed s, dp, dq, dk, dv) = 3.5x.
    causal_dot = 2.0 * batch * cfg.n_heads * seq * seq * cfg.head_dim / 2
    flops += 3.5 * (2 * causal_dot) * cfg.n_layers

    with clock.compile():
        run_steps, _, _ = compiled_with_cost(run_steps, params, opt_state, ids)
    loss, checksum = run_steps(params, opt_state, ids)
    _ = (float(loss), float(checksum))
    clock.mark()  # warmup execution is untimed — keep it out of "other"
    import math

    results = {}

    def window():
        with clock.compute():
            loss, checksum = run_steps(params, opt_state, ids)
            jax.block_until_ready((loss, checksum))
        with clock.fetch():
            results["loss"], results["checksum"] = float(loss), float(checksum)
        clock.end_step()

    def check():
        if not all(math.isfinite(v) for v in results.values()):
            raise RuntimeError(f"non-finite gpt bench: {results}")

    window.check = check
    total, window_times = _timed_windows(window, _repeats())
    dt = total / timed_steps
    from kubeflow_tpu.training.attribution import record_step_peak_hbm
    from kubeflow_tpu.training.flops import memory_stats

    mem = memory_stats(run_steps)
    record_step_peak_hbm(mem)

    def _gpt_costs():
        from kubeflow_tpu.training.attribution import attribute_gpt

        return attribute_gpt(cfg, batch=batch, seq=seq,
                             fused_loss=fused_loss, generation=gen)

    attribution = _attribution_row(_gpt_costs, clock, timed_steps, gen)
    return {
        "tokens_per_sec_per_chip": batch * seq / dt,
        "step_seconds": dt,
        "mfu": mfu(flops, dt, num_chips=1, generation=gen),
        "window_mfus": [round(mfu(flops, t / timed_steps, 1, gen) * 100, 2)
                        for t in window_times],
        "generation": gen,
        "batch": batch,
        "seq": seq,
        "scan_blocks": scan_blocks,
        "remat": cfg.remat,
        "fused_loss": fused_loss,
        "autotune": autotune_row,
        "step_breakdown": _step_breakdown(clock, timed_steps),
        "peak_hbm_bytes": (mem or {}).get("peak_hbm_bytes"),
        "memory": mem,
        "attribution": attribution,
    }


def _multichip_mesh_sizes(n_devices: int) -> dict:
    """Default dp x fsdp x tp x pp factorization for ``n_devices``: peel
    off pipe, model, fsdp as factors of 2 (innermost axes smallest), data
    absorbs the rest. Overridable per axis via BENCH_MC_{PP,TP,FSDP}."""
    def _env(name, default):
        try:
            return int(os.environ.get(name) or default)
        except ValueError:
            return default

    rest = n_devices
    pp = _env("BENCH_MC_PP", 2 if rest % 2 == 0 else 1)
    rest //= pp
    tp = _env("BENCH_MC_TP", 2 if rest % 2 == 0 else 1)
    rest //= tp
    fs = _env("BENCH_MC_FSDP", 2 if rest % 2 == 0 else 1)
    return {"pipe": pp, "model": tp, "fsdp": fs, "data": n_devices // (pp * tp * fs)}


def _bench_multichip(gen: str):
    """Composed 4D (dp x fsdp x tp x pp) GPT train-step throughput across
    ALL local devices — the multi-chip half of the bench story. Emits
    tokens/sec/chip, weak-scaling efficiency vs a 1-chip run of the same
    per-chip token load, the schedule's bubble fraction, and the analytic
    per-axis comm bytes (parallel/comm.py), all surfaced through
    StepClock/MetricsRegistry."""
    from kubeflow_tpu.parallel import composite as composite_mod
    from kubeflow_tpu.parallel.comm import composite_comm_bytes, composite_step_flops
    from kubeflow_tpu.parallel.mesh import MeshConfig, make_mesh
    from kubeflow_tpu.parallel.pipeline import schedule_stats
    from kubeflow_tpu.runtime.metrics import METRICS
    from kubeflow_tpu.runtime.tracing import TRACER
    from kubeflow_tpu.tpu.profiling import StepClock

    devices = jax.devices()
    n_dev = len(devices)
    sizes = _multichip_mesh_sizes(n_dev)
    d_model = int(os.environ.get("BENCH_MC_DMODEL", "128"))
    cfg = composite_mod.CompositeConfig(
        vocab_size=int(os.environ.get("BENCH_MC_VOCAB", "512")),
        d_model=d_model,
        n_heads=int(os.environ.get("BENCH_MC_HEADS", "4")),
        d_ff=int(os.environ.get("BENCH_MC_FF", str(4 * d_model))),
        n_layers=int(os.environ.get("BENCH_MC_LAYERS", "8")),
        seq=int(os.environ.get("BENCH_MC_SEQ", "128")),
    )
    num_micro = int(os.environ.get("BENCH_MC_MICRO", "8"))
    mb = int(os.environ.get("BENCH_MC_MB", "8"))  # global microbatch size
    virtual_stages = int(os.environ.get("BENCH_PP_VIRTUAL", "2"))
    gather_mode = os.environ.get("BENCH_GATHER_MODE", "overlap")
    timed_steps = int(os.environ.get("BENCH_MC_STEPS", "5"))
    if cfg.n_layers % (sizes["pipe"] * virtual_stages):
        virtual_stages = 1  # odd factorization: fall back to GPipe

    mesh = make_mesh(MeshConfig(**sizes))
    rng = jax.random.PRNGKey(0)
    ids = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (num_micro, mb, cfg.seq),
                           0, cfg.vocab_size),
        composite_mod.batch_sharding(mesh))

    clock = StepClock(metrics=METRICS.namespace("multichip"), tracer=TRACER)

    def timed_run(use_mesh, use_v, use_gather, use_ids, label, use_clock):
        """Compile + warm one train step on ``use_mesh``, then time
        ``timed_steps`` chained steps per window (param updates chain, so
        no step is dead code; windows restart from the same init). Each run
        gets its OWN clock: the 1-chip reference must not pollute the
        multichip row's step_breakdown."""
        params0 = composite_mod.init_params(rng, cfg, use_mesh,
                                            virtual_stages=use_v)
        with use_clock.compile():
            step = composite_mod.make_train_step(
                cfg, use_mesh, virtual_stages=use_v, gather_mode=use_gather)
            p, loss = step(params0, use_ids)  # first call compiles
            jax.block_until_ready(loss)
        from kubeflow_tpu.training.flops import memory_stats

        # jit cache is warm; this only re-runs the (cached) AOT path
        mem = memory_stats(step.lower(params0, use_ids).compile())
        use_clock.mark()
        results = {}

        def window():
            with use_clock.compute():
                p, loss = params0, None
                for _ in range(timed_steps):
                    p, loss = step(p, use_ids)
                jax.block_until_ready(loss)
            with use_clock.fetch():
                results["loss"] = float(loss)
            use_clock.end_step()

        def check():
            import math
            if not math.isfinite(results.get("loss", float("nan"))):
                raise RuntimeError(f"non-finite {label} bench loss: {results}")

        window.check = check
        total, _times = _timed_windows(window, _repeats())
        return total / timed_steps, results["loss"], mem

    dt, loss, mem = timed_run(mesh, virtual_stages, gather_mode, ids,
                              "multichip", clock)
    tokens_per_step = num_micro * mb * cfg.seq
    tok_per_chip = tokens_per_step / dt / n_dev

    # Weak-scaling reference: ONE device, same per-chip token load
    # (mb/n_dev), full model, no pipeline — what this chip would do alone.
    scaling_efficiency = tok_1chip = None
    mb1 = max(1, mb // n_dev)
    if os.environ.get("BENCH_MC_1CHIP", "1") == "1":
        mesh1 = make_mesh(MeshConfig(), devices=[devices[0]])
        ids1 = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1),
                               (num_micro, mb1, cfg.seq), 0, cfg.vocab_size),
            composite_mod.batch_sharding(mesh1))
        clock_ref = StepClock(metrics=METRICS.namespace("multichip_ref"),
                              tracer=TRACER, span_name="bench.1chip_ref")
        dt1, _, _ = timed_run(mesh1, 1, "eager", ids1, "1chip", clock_ref)
        tok_1chip = num_micro * mb1 * cfg.seq / dt1
        scaling_efficiency = tok_per_chip / tok_1chip

    stats = schedule_stats(num_micro, sizes["pipe"], virtual_stages)
    stats_gpipe = schedule_stats(num_micro, sizes["pipe"], 1)
    comm = composite_comm_bytes(cfg, mesh, num_micro, mb,
                                virtual_stages=virtual_stages,
                                gather_mode=gather_mode)
    clock.note("tokens_per_sec_per_chip", tok_per_chip)
    clock.note("bubble_fraction", stats["bubble_fraction"])
    if scaling_efficiency is not None:
        clock.note("scaling_efficiency", scaling_efficiency)
    for axis, b in comm.items():
        clock.note(f"comm_bytes_{axis}", b)

    flops = composite_step_flops(cfg, tokens_per_step)
    from kubeflow_tpu.training.attribution import record_step_peak_hbm

    record_step_peak_hbm(mem, metrics=METRICS.namespace("multichip"))
    # fractions-only attribution: no per-module walk for the composite
    # (pipeline stages aren't flax blocks), but the step decomposition
    # still rides along so the row explains its own wall clock
    attribution = _attribution_row(lambda: [], clock, timed_steps, gen)
    return {
        "tokens_per_sec_per_chip": tok_per_chip,
        "tokens_per_sec_1chip": tok_1chip,
        "scaling_efficiency": scaling_efficiency,
        "n_devices": n_dev,
        "mesh": sizes,
        "virtual_stages": virtual_stages,
        "gather_mode": gather_mode,
        "num_micro": num_micro,
        "microbatch": mb,
        "microbatch_1chip": mb1,
        "seq": cfg.seq,
        "n_layers": cfg.n_layers,
        "d_model": cfg.d_model,
        "bubble_fraction": stats["bubble_fraction"],
        "bubble_fraction_gpipe": stats_gpipe["bubble_fraction"],
        "comm_bytes_per_step": {k: round(v) for k, v in comm.items()},
        "flops_per_step": flops,
        "step_seconds": dt,
        "loss": loss,
        "step_breakdown": _step_breakdown(clock, timed_steps),
        "peak_hbm_bytes": (mem or {}).get("peak_hbm_bytes"),
        "memory": mem,
        "attribution": attribution,
    }


def _run_multichip(platform: str, gen: str) -> dict:
    try:
        r = _bench_multichip(gen)
        return _emit({
            "metric": f"multichip_composite_tokens_per_sec_per_chip_{r['n_devices']}dev",
            "value": round(r["tokens_per_sec_per_chip"], 1),
            "unit": "tokens_per_sec_per_chip",
            "vs_baseline": None,  # reference publishes no multichip numbers
            "scaling_efficiency": (round(r["scaling_efficiency"], 4)
                                   if r["scaling_efficiency"] is not None else None),
            "tokens_per_sec_1chip": (round(r["tokens_per_sec_1chip"], 1)
                                     if r["tokens_per_sec_1chip"] is not None else None),
            "n_devices": r["n_devices"],
            "mesh": r["mesh"],
            "virtual_stages": r["virtual_stages"],
            "gather_mode": r["gather_mode"],
            "num_micro": r["num_micro"],
            "microbatch": r["microbatch"],
            "bubble_fraction": round(r["bubble_fraction"], 4),
            "bubble_fraction_gpipe": round(r["bubble_fraction_gpipe"], 4),
            "comm_bytes_per_step": r["comm_bytes_per_step"],
            "loss": round(r["loss"], 4),
            "step_breakdown": r["step_breakdown"],
            "peak_hbm_bytes": r.get("peak_hbm_bytes"),
            "attribution": r.get("attribution"),
            "platform": platform,
        })
    except Exception as e:
        return _error_row("multichip_composite_tokens_per_sec_per_chip",
                          "tokens_per_sec_per_chip", e)


def _emit(row: dict) -> dict:
    print(json.dumps(row), flush=True)
    return row


def _error_row(metric: str, unit: str, error: BaseException) -> dict:
    """A bench that failed: traceback to stderr, a row whose ``error``
    fails the exit code. The suite goes on to the next bench."""
    traceback.print_exception(error)
    return _emit({"metric": metric, "value": 0.0, "unit": unit,
                  "vs_baseline": 0.0, "error": str(error)[:200]})


def _run_resnet(platform: str, gen: str) -> dict:
    candidates = _batch_candidates()
    for batch in candidates:
        try:
            r = _bench(batch, gen)
            return _emit({
                "metric": f"resnet50_train_mfu_{r['generation']}_1chip",
                "value": round(r["mfu"] * 100, 2),
                "unit": "percent_mfu",
                "vs_baseline": round(r["mfu"] / TARGET_MFU, 4),
                "images_per_sec_per_chip": round(r["images_per_sec_per_chip"], 1),
                "batch": r["batch"],
                "window_mfus": r.get("window_mfus"),
                "fused_blocks": r.get("fused_blocks"),
                "fused_calibration": r.get("fused_calibration"),
                "autotune": r.get("autotune"),
                "step_breakdown": r.get("step_breakdown"),
                "peak_hbm_bytes": r.get("peak_hbm_bytes"),
                "attribution": r.get("attribution"),
                "platform": platform,
            })
        except Exception as e:
            # out of device memory at this batch -> try the next smaller;
            # anything else (a compile error, a refused kernel) is the
            # finding, not a reason to time a different program
            if ("RESOURCE_EXHAUSTED" not in str(e)
                    or batch == candidates[-1]):
                return _error_row("resnet50_train_mfu", "percent_mfu", e)
            print(f"resnet batch {batch}: RESOURCE_EXHAUSTED, trying smaller",
                  file=sys.stderr)


def _run_gpt(platform: str, gen: str,
             allow_legacy_batch: bool = False) -> dict:
    # BENCH_GPT_BATCH disambiguates from the resnet BENCH_BATCH in suite
    # mode; BENCH_MODEL=gpt keeps honoring BENCH_BATCH (the round-3 knob).
    legacy = os.environ.get("BENCH_BATCH") if allow_legacy_batch else None
    batch = int(os.environ.get("BENCH_GPT_BATCH") or legacy or "8")
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    try:
        r = _bench_gpt(batch, seq, gen)
        return _emit({
            "metric": f"gpt2_medium_train_mfu_{r['generation']}_1chip",
            "value": round(r["mfu"] * 100, 2),
            "unit": "percent_mfu",
            "vs_baseline": round(r["mfu"] / TARGET_MFU, 4),
            "tokens_per_sec_per_chip": round(r["tokens_per_sec_per_chip"], 1),
            "batch": r["batch"], "seq": r["seq"],
            "window_mfus": r.get("window_mfus"),
            "scan_blocks": r.get("scan_blocks"),
            "remat": r.get("remat"),
            "fused_loss": r.get("fused_loss"),
            "autotune": r.get("autotune"),
            "step_breakdown": r.get("step_breakdown"),
            "peak_hbm_bytes": r.get("peak_hbm_bytes"),
            "attribution": r.get("attribution"),
            "platform": platform,
        })
    except Exception as e:
        return _error_row("gpt2_medium_train_mfu", "percent_mfu", e)


def _run_serving(platform: str, gen: str) -> dict:
    """Serving rows condensed for the summary: BERT HTTP p50 at batch 8 and
    KV-decode tokens/s at batch 8 (full sweep on the per-metric line)."""
    try:
        from e2e.serving_bench import (bench_bert_http, bench_continuous,
                                       bench_disagg, bench_gpt_decode)

        bert = bench_bert_http()
        decode = bench_gpt_decode()
        cont = (bench_continuous()
                if os.environ.get("BENCH_CONTINUOUS", "1") == "1" else None)
        disagg = (bench_disagg()
                  if os.environ.get("BENCH_DISAGG", "1") == "1" else None)
        b8 = next((r for r in bert if r["batch"] == 8), bert[-1])
        d8 = next((r for r in decode if r["batch"] == 8), decode[-1])
        return _emit({
            "metric": "serving_gpt_kv_decode_tokens_per_sec_b8",
            "value": d8["decode_tokens_per_sec"],
            "unit": "tokens_per_sec",
            "vs_baseline": None,  # reference publishes no serving numbers (BASELINE.md)
            "bert_http_p50_ms_b8": b8["p50_ms"],
            "bert_http_rows": bert,
            "decode_rows": decode,
            "continuous_batching": cont,
            # SLO quantiles from the engine run's histograms (registry
            # bucket interpolation — the serving row's latency headline)
            "ttft_p50": cont.get("ttft_p50") if cont else None,
            "ttft_p99": cont.get("ttft_p99") if cont else None,
            "queue_wait_p99": cont.get("queue_wait_p99") if cont else None,
            # paged/chunked/speculative knob readout (ISSUE 12): the spec
            # accept rate rides into the summary line so the bench gate can
            # track it round over round
            "spec_accept_rate": cont.get("spec_accept_rate") if cont else None,
            # disaggregated heterogeneous-mix pass (ISSUE 18): aggregate
            # decode tok/s across two multiplexed models with prefill/decode
            # pools and the quantized KV handoff in the serving path
            "disagg": disagg,
            "decode_tok_s_heterogeneous": (
                disagg.get("decode_tok_s_heterogeneous") if disagg else None),
            "kv_handoff_p99_s": (
                disagg.get("kv_handoff_p99_s") if disagg else None),
            "platform": platform,
        })
    except Exception as e:
        return _error_row("serving_gpt_kv_decode_tokens_per_sec_b8",
                          "tokens_per_sec", e)


def _run_hpo(platform: str, gen: str) -> dict:
    """Real-objective HPO study throughput (BASELINE Katib row: trials/hour)."""
    try:
        from e2e.studyjob_driver import run_studyjob_e2e

        max_trials = int(os.environ.get("BENCH_HPO_TRIALS", "16"))
        early = os.environ.get("BENCH_HPO_EARLYSTOP", "1") == "1"
        status = run_studyjob_e2e(
            "mnist", max_trials=max_trials, parallel=4, timeout=900.0,
            early_stopping=early)
        return _emit({
            "metric": "hpo_mnist_trials_per_hour",
            "value": status["trialsPerHour"],
            "unit": "trials_per_hour",
            "vs_baseline": None,  # reference publishes no Katib throughput (BASELINE.md)
            "trials": max_trials,
            "trials_succeeded": status.get("trialsSucceeded"),
            "trials_pruned": status.get("trialsPruned", 0),
            "elapsed_seconds": status["elapsedSeconds"],
            "best_accuracy": (status.get("currentOptimalTrial") or {})
                .get("observation", {}).get("accuracy"),
            "platform": platform,
        })
    except Exception as e:
        return _error_row("hpo_mnist_trials_per_hour", "trials_per_hour", e)


def main() -> int:
    """Default: run EVERY flagship bench, one JSON line each, then a final
    summary line holding all of them (VERDICT r3 #2: the driver keeps the
    last line — it must carry the build's actual best numbers, not just the
    ResNet row). ``BENCH_MODEL=resnet|gpt|serving|hpo|multichip`` runs one
    bench only; the multichip row joins the suite when >1 device is up.
    Runs on the chip only: no TPU, or one the peaks table does not know,
    stops here."""
    from kubeflow_tpu.tpu.env import enable_compile_cache, require_tpu
    from kubeflow_tpu.training.flops import detect_generation

    platform = require_tpu().platform
    gen = detect_generation()
    enable_compile_cache()
    mode = os.environ.get("BENCH_MODEL", "all")
    if mode == "serving":
        from e2e.serving_bench import main as serving_main

        return serving_main()
    if mode == "gpt":
        r = _run_gpt(platform, gen, allow_legacy_batch=True)
        return 0 if not r.get("error") else 1
    if mode == "hpo":
        r = _run_hpo(platform, gen)
        return 0 if not r.get("error") else 1
    if mode == "resnet":
        r = _run_resnet(platform, gen)
        return 0 if not r.get("error") else 1
    if mode == "multichip":
        r = _run_multichip(platform, gen)
        return 0 if not r.get("error") else 1

    skip = set(filter(None, os.environ.get("BENCH_SKIP", "").split(",")))
    benches = [("resnet", _run_resnet), ("gpt", _run_gpt),
               ("serving", _run_serving), ("hpo", _run_hpo)]
    if len(jax.devices()) > 1:  # multichip row only means something on >1 chip
        benches.append(("multichip", _run_multichip))
    rows = {}
    for name, fn in benches:
        if name in skip:
            continue
        rows[name] = fn(platform, gen)

    resnet = rows.get("resnet", {})
    gpt = rows.get("gpt", {})
    summary = {
        # Headline stays the ResNet north-star (comparable across rounds);
        # the other flagship numbers ride along on the same driver-parsed line.
        "metric": resnet.get("metric", "resnet50_train_mfu"),
        "value": resnet.get("value", 0.0),
        "unit": "percent_mfu",
        "vs_baseline": resnet.get("vs_baseline", 0.0),
        "images_per_sec_per_chip": resnet.get("images_per_sec_per_chip"),
        "gpt2_medium_mfu_pct": gpt.get("value"),
        "gpt2_medium_tokens_per_sec": gpt.get("tokens_per_sec_per_chip"),
        "serving_decode_tokens_per_sec_b8": rows.get("serving", {}).get("value"),
        "serving_bert_p50_ms_b8": rows.get("serving", {}).get("bert_http_p50_ms_b8"),
        "serving_ttft_p99_s": rows.get("serving", {}).get("ttft_p99"),
        "spec_accept_rate": rows.get("serving", {}).get("spec_accept_rate"),
        "decode_tok_s_heterogeneous": rows.get("serving", {}).get(
            "decode_tok_s_heterogeneous"),
        "kv_handoff_p99_s": rows.get("serving", {}).get("kv_handoff_p99_s"),
        "hpo_trials_per_hour": rows.get("hpo", {}).get("value"),
        "multichip_tokens_per_sec_per_chip": rows.get("multichip", {}).get("value"),
        "multichip_scaling_efficiency": rows.get("multichip", {}).get("scaling_efficiency"),
        "platform": platform,
        "errors": {k: v["error"] for k, v in rows.items() if v.get("error")} or None,
    }
    _emit(summary)
    return 0 if not summary["errors"] else 1


if __name__ == "__main__":
    sys.exit(main())
