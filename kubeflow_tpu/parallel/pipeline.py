"""Pipeline parallelism: stage-partitioned layers, microbatch streaming.

Two schedules, one implementation, expressed the TPU way: every pipeline
stage is the *same* SPMD program under ``shard_map`` over the ``pipe`` mesh
axis; stage weights live stacked with the stage dimension sharded over that
axis, and activations hop stage->stage+1 once per step via ``lax.ppermute``
(one ICI hop). Autodiff through the forward schedule yields the
reverse-order backward schedule automatically — ``ppermute`` differentiates
into the inverse permutation — so there is no hand-written backward
pipeline.

**GPipe** (``virtual_stages=1``): with M microbatches and S stages the loop
runs M+S-1 steps; bubble fraction (S-1)/(M+S-1) shrinks as M grows.

**Interleaved / virtual stages** (``virtual_stages=V>1``): each device owns
V round-robin chunks of the layer stack (global chunk g = v*S + d lives on
device d = g % S, so params stack to [S*V, ...] in device-major round-robin
order — see :func:`interleave_stage_params`). Each microbatch circulates
the ring V times; a circular buffer on stage 0 holds last-stage outputs
until their re-entry slot. The loop runs V*M + S - 1 steps of 1/V the
per-step work, cutting the bubble fraction to (S-1)/(V*M+S-1) — the
Megatron-LM interleaved schedule, at the cost of V-1 extra ring traversals
of activation traffic.

Both schedules need M >= S (the circular-buffer slot math is conflict-free
iff microbatches outnumber stages; M == S works, M < S raises). Bubble
steps re-read wrapped microbatches whose output is discarded;
``mask_bubbles=True`` (default) wraps the stage body in ``lax.cond`` so
those steps skip the FLOPs entirely — validity depends only on (t, pipe
coordinate), so collectives inside the stage over *other* mesh axes stay
uniform within their groups.

Per-device parameter memory is 1/S of the stacked stack, the usual reason
to pick ``pipe`` over pure fsdp when layers are deep and ICI hops are
cheap. The reference control plane has no in-tree parallelism
(SURVEY.md §2.10); this is part of the in-workload half of the TPU-native
build.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_PIPE


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack S (or S*V) per-stage pytrees into one pytree with a leading
    stage dim, in natural order (row g holds chunk g).

    The result is what :func:`pipeline_apply` consumes; shard its leading
    dim over the ``pipe`` mesh axis (``stage_param_spec``). For
    ``virtual_stages > 1`` permute to device-major round-robin order first
    with :func:`interleave_stage_params`.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def stage_param_spec(leaf: jax.Array) -> P:
    """PartitionSpec for stacked stage params: stage dim over ``pipe``."""
    return P(AXIS_PIPE, *([None] * (leaf.ndim - 1)))


def _round_robin_perm(n_stages: int, virtual_stages: int) -> List[int]:
    """Row d*V+v of the interleaved layout holds natural chunk v*S+d."""
    return [v * n_stages + d for d in range(n_stages) for v in range(virtual_stages)]


def interleave_stage_params(stage_params: Any, n_stages: int, virtual_stages: int) -> Any:
    """Natural chunk order [S*V, ...] -> device-major round-robin layout.

    After this permutation, sharding the leading dim over ``pipe`` hands
    device d exactly its V chunks {d, S+d, 2S+d, ...} as local rows
    [0..V), which is what the interleaved schedule indexes by repeat r.
    Identity when ``virtual_stages == 1``.
    """
    perm = jnp.array(_round_robin_perm(n_stages, virtual_stages))
    return jax.tree_util.tree_map(lambda p: jnp.take(p, perm, axis=0), stage_params)


def deinterleave_stage_params(stage_params: Any, n_stages: int, virtual_stages: int) -> Any:
    """Inverse of :func:`interleave_stage_params` (back to natural order)."""
    perm = _round_robin_perm(n_stages, virtual_stages)
    inv = [0] * len(perm)
    for row, g in enumerate(perm):
        inv[g] = row
    inv_arr = jnp.array(inv)
    return jax.tree_util.tree_map(lambda p: jnp.take(p, inv_arr, axis=0), stage_params)


def schedule_stats(
    num_micro: int, n_stages: int, virtual_stages: int = 1
) -> Dict[str, float]:
    """Analytic schedule shape: step counts and bubble fraction.

    Each step does 1/virtual_stages of a GPipe step's work, so
    ``bubble_fraction`` (share of a device's step-time spent idle) is
    (S-1)/(V*M+S-1) and strictly drops as V grows; ``bubble_steps`` is the
    per-device idle step count S-1 in the schedule's own step units.
    """
    total = virtual_stages * num_micro + n_stages - 1
    bubble = n_stages - 1
    return {
        "total_steps": total,
        "compute_steps": virtual_stages * num_micro,
        "bubble_steps": bubble,
        "bubble_fraction": bubble / total,
    }


def _local_pipeline(
    params: Any,
    x: jax.Array,
    *,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis_name: str,
    n_stages: int,
    virtual_stages: int,
    mask_bubbles: bool,
    stage_prepare: Optional[Callable[[Any], Any]],
) -> jax.Array:
    """Per-device body. params: stage-local (leading dim V, round-robin
    chunks); x: [M, mb, ...]. One unified loop covers both schedules; the
    GPipe path is the V==1 specialization (static chunk 0, no circular
    buffer) so it stays bit-for-bit what it was before virtual stages."""
    stage = lax.axis_index(axis_name)
    is_first = stage == 0
    is_last = stage == n_stages - 1
    if stage_prepare is not None:
        # Runs ONCE per train step, outside the time scan: prepared leaves
        # are scan constants, so their cotangents accumulate across all
        # V*M compute steps and transpose into ONE reduce_scatter per
        # weight instead of one per microbatch (no_sync-style).
        params = stage_prepare(params)
    V = virtual_stages
    num_micro = x.shape[0]
    total_steps = V * num_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    if V == 1:
        chunk0 = jax.tree_util.tree_map(lambda p: p[0], params)

        def select_chunk(r):
            return chunk0
    else:

        def select_chunk(r):
            rr = jnp.clip(r, 0, V - 1)
            return jax.tree_util.tree_map(
                lambda p: lax.dynamic_index_in_dim(p, rr, keepdims=False), params
            )

    probe = jax.eval_shape(stage_fn, select_chunk(0), x[0])
    zeros_y = jnp.zeros(probe.shape, probe.dtype)

    def step(carry, t):
        recv, circ, out = carry
        # Device d's schedule position: repeat r of microbatch m, valid for
        # V*M of the total_steps. The ring hop means device d+1 at step t+1
        # sees the same (r, m) its upstream neighbor computed at step t.
        u = t - stage
        r = u // num_micro
        m = jnp.mod(u, num_micro)
        valid = jnp.logical_and(u >= 0, u < V * num_micro)
        if V > 1:
            # Bank what the last stage sent us: microbatch (t - S) mod M
            # finished its previous ring pass exactly in time to re-enter
            # stage 0 here (store-then-read keeps M == S hazard-free).
            circ = lax.dynamic_update_index_in_dim(
                circ, recv, jnp.mod(t - n_stages, num_micro), axis=0
            )
            circ_m = lax.dynamic_index_in_dim(circ, m, keepdims=False)
        x_m = lax.dynamic_index_in_dim(x, m, keepdims=False)
        if V > 1:
            first_in = jnp.where(r <= 0, x_m, circ_m)
        else:
            first_in = x_m
        inp = jnp.where(is_first, first_in, recv)
        p_t = select_chunk(r)
        if mask_bubbles:
            # Bubble steps would burn real FLOPs on discarded output; skip
            # them. `valid` is uniform across any collective group inside
            # stage_fn (those span non-pipe axes), so collectives stay
            # consistent; valid computations only ever consume
            # valid-produced values, so results are unchanged bit-for-bit.
            y = lax.cond(valid, lambda: stage_fn(p_t, inp), lambda: zeros_y)
        else:
            y = stage_fn(p_t, inp)
        # Last stage on the final repeat banks microbatch m's output.
        bank = jnp.logical_and(jnp.logical_and(is_last, valid), r == V - 1)
        cur = lax.dynamic_index_in_dim(out, m, keepdims=False)
        out = lax.dynamic_update_index_in_dim(out, jnp.where(bank, y, cur), m, axis=0)
        recv = lax.ppermute(y, axis_name, fwd_perm)
        return (recv, circ, out), None

    out0 = jnp.zeros(x.shape[:1] + probe.shape, probe.dtype)
    recv0 = jnp.zeros(probe.shape, probe.dtype)
    # The circular re-entry buffer only exists for V > 1; a scalar stands in
    # for it on the GPipe path so the carry structure stays uniform.
    circ0 = jnp.zeros(x.shape[:1] + probe.shape, probe.dtype) if V > 1 else jnp.zeros(())
    (_, _, out), _ = lax.scan(step, (recv0, circ0, out0), jnp.arange(total_steps))
    # Results live on the last stage only; psum broadcasts them (every other
    # stage contributes zeros) so the caller sees a replicated [M, mb, ...].
    return lax.psum(jnp.where(is_last, out, jnp.zeros_like(out)), axis_name)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = AXIS_PIPE,
    param_specs: Any = None,
    x_spec: P = P(),
    out_spec: P = P(),
    virtual_stages: int = 1,
    mask_bubbles: bool = True,
    stage_prepare: Optional[Callable[[Any], Any]] = None,
) -> jax.Array:
    """Run x through S*V pipelined stage chunks of ``stage_fn`` over ``mesh``.

    - ``stage_fn(params_chunk, h) -> h'`` — one stage chunk; output
      shape/dtype must equal input (homogeneous inter-stage activations,
      the GPipe contract).
    - ``stage_params`` — pytree with leading stage dim S*V, sharded over
      ``axis_name``. For ``virtual_stages > 1`` the rows must be in
      device-major round-robin order (:func:`interleave_stage_params`) so
      each device's local rows [0..V) are its chunks {d, S+d, ...}.
    - ``x`` — [num_microbatches, microbatch, ...] input stream, replicated
      over ``axis_name`` (batch axes may shard its microbatch dim).
    - ``virtual_stages=V`` — interleaved schedule: V*M+S-1 steps of 1/V the
      work, bubble fraction (S-1)/(V*M+S-1). ``virtual_stages=1`` is GPipe
      and reproduces it exactly.
    - ``mask_bubbles`` — skip the stage body on bubble steps via
      ``lax.cond`` (numerically identical either way; saves the FLOPs).
    - ``stage_prepare(local_params) -> local_params`` — optional hook run
      once per call inside the shard_map, before the time scan, on the
      local [V, ...]-leading param tree. Use it to ``all_gather`` fsdp
      weight shards once per step instead of once per microbatch: the
      prepared tree is a scan constant, so the gathers' transposed
      reduce-scatters also run once, amortized across microbatches.

    Composition with the other mesh axes (parallel/composite.py): pass
    ``param_specs`` to also shard weight dims over ``fsdp``/``model`` (the
    stage dim must stay on ``axis_name``), ``x_spec``/``out_spec`` to shard
    the microbatch dim over the batch axes; ``stage_fn`` then runs manual
    SPMD — it sees LOCAL shards and uses collectives (all_gather over fsdp,
    psum over model) itself, exactly like a Megatron block.

    Returns [num_microbatches, microbatch, ...] outputs, replicated over the
    pipe axis. Differentiable end-to-end.
    """
    n_stages = mesh.shape[axis_name]
    if virtual_stages < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {virtual_stages}")
    if n_stages > x.shape[0]:
        raise ValueError(
            f"need at least as many microbatches as stages: "
            f"{x.shape[0]} microbatches < {n_stages} stages"
        )
    want = n_stages * virtual_stages
    for path, leaf in jax.tree_util.tree_flatten_with_path(stage_params)[0]:
        if leaf.shape[:1] != (want,):
            raise ValueError(
                f"stage_params leading dim must be n_stages*virtual_stages="
                f"{n_stages}*{virtual_stages}={want}; leaf "
                f"{jax.tree_util.keystr(path)} has shape {leaf.shape}"
            )
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(stage_param_spec, stage_params)
    fn = jax.shard_map(
        functools.partial(
            _local_pipeline,
            stage_fn=stage_fn,
            axis_name=axis_name,
            n_stages=n_stages,
            virtual_stages=virtual_stages,
            mask_bubbles=mask_bubbles,
            stage_prepare=stage_prepare,
        ),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(stage_params, x)
