"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

Switch/top-k routing in the TPU-native style: dense dispatch/combine
einsums with *static* capacity (no dynamic shapes under jit — XLA tiles
them straight onto the MXU), expert FFN weights stacked [E, ...] and
sharded over the ``expert`` axis, expert inputs sharding-constrained to the
same axis so XLA inserts the all-to-all between data and expert layouts.
Load-balance auxiliary loss follows the Switch Transformer formulation.

The reference has no MoE/parallelism code at all (SURVEY.md §2.10); this
module is part of the in-workload compute path of the TPU-native build.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.ops.grouped_matmul import grouped_matmul, grouped_swiglu
from kubeflow_tpu.parallel.mesh import AXIS_EXPERT, BATCH_AXES


def _constrain(x: jax.Array, mesh: Optional[Mesh], spec: P) -> jax.Array:
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def top_k_routing(
    router_logits: jax.Array, num_experts: int, capacity: int, k: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Position-based top-k token->expert assignment with static capacity.

    router_logits: [tokens, E]. Returns (dispatch [tokens, E, C] one-hot,
    combine [tokens, E, C] gate-weighted, aux_loss scalar). Tokens beyond an
    expert's capacity are dropped (their combine weights are zero), the
    standard Switch behavior; earlier positions win, matching the
    sequential-priority formulation.
    """
    tokens = router_logits.shape[0]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [tokens, k]
    # expert_mask[t, j, e] — token t's j-th choice is expert e.
    expert_mask = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32)

    # Position of each token in its chosen expert's queue, counting all
    # higher-priority (choice-major, then position) assignments.
    flat_mask = expert_mask.transpose(1, 0, 2).reshape(k * tokens, num_experts)
    pos_in_expert = jnp.cumsum(flat_mask, axis=0) - flat_mask  # [k*tokens, E]
    pos = (pos_in_expert * flat_mask).sum(-1).reshape(k, tokens).T.astype(jnp.int32)  # [tokens, k]
    keep = (pos < capacity) & (gate_vals > 0)

    # aux loss: mean fraction of tokens routed to e * mean router prob for e
    # (computed over first choices, Switch eq. 4), scaled by E.
    first_choice = expert_mask[:, 0, :]
    density = first_choice.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux_loss = num_experts * jnp.sum(density * density_proxy)

    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity, dtype=jnp.float32)
    # dispatch[t, e, c] = token t occupies slot c of expert e.
    dispatch = jnp.einsum("tke,tkc->tec", expert_mask, pos_oh)
    combine = jnp.einsum("tke,tkc,tk->tec", expert_mask, pos_oh, gate_vals.astype(jnp.float32))
    return dispatch, combine, aux_loss


class MoEMlp(nn.Module):
    """Expert-parallel FFN block: route -> all-to-all -> expert MLP -> return.

    Drop-in for a dense transformer MLP ([..., d_model] -> [..., d_model]).
    Stacked expert kernels are named ``experts_wi``/``experts_wo`` so the
    sharding heuristic (parallel/sharding.py ``expert`` rule) places their
    leading dim on the ``expert`` mesh axis. Pass ``mesh`` to add activation
    sharding constraints; aux loss is sown under ``("losses", "moe_aux")``.
    """

    num_experts: int
    d_ff: int
    k: int = 2
    capacity_factor: float = 1.25
    mesh: Optional[Mesh] = None
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig_shape = x.shape
        d_model = x.shape[-1]
        x2 = x.reshape(-1, d_model)
        tokens = x2.shape[0]
        capacity = max(1, int(self.capacity_factor * self.k * tokens / self.num_experts))

        router = self.param(
            "router", nn.initializers.lecun_normal(), (d_model, self.num_experts), jnp.float32
        )
        logits = x2.astype(jnp.float32) @ router
        dispatch, combine, aux = top_k_routing(logits, self.num_experts, capacity, self.k)
        self.sow("losses", "moe_aux", aux)

        wi = self.param(
            "experts_wi",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (self.num_experts, d_model, self.d_ff),
            jnp.float32,
        )
        wo = self.param(
            "experts_wo",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (self.num_experts, self.d_ff, d_model),
            jnp.float32,
        )

        # [tokens, d] -> [E, C, d]: XLA lowers this resharding to all-to-all
        # when tokens are batch-sharded and expert tensors expert-sharded.
        expert_in = jnp.einsum("td,tec->ecd", x2.astype(self.dtype), dispatch.astype(self.dtype))
        expert_in = _constrain(expert_in, self.mesh, P(AXIS_EXPERT, None, None))
        h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(self.dtype))
        h = nn.gelu(h)
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(self.dtype))
        out = _constrain(out, self.mesh, P(AXIS_EXPERT, None, None))
        y = jnp.einsum("ecd,tec->td", out, combine.astype(self.dtype))
        y = _constrain(y.reshape(orig_shape), self.mesh, P(BATCH_AXES, *([None] * (len(orig_shape) - 1))))
        return y.astype(x.dtype)


# -- dropless expert layer that is told which experts it holds ----------------
#
# The serving path's expert layer (models/mimo.py). Unlike ``MoEMlp`` above it
# has no capacity and drops nothing: the router scores ALL experts, the
# assignments that land on the experts held here are sorted by expert and go
# through grouped matrix products, and the layer returns the held experts'
# part of the result. What the experts held elsewhere would add is left out
# (an exchange across chips would bring it; on one chip there is none, and
# nothing stands in for it).

def sigmoid_top_k(h: jax.Array, router: jax.Array, bias: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Router in float32 over every expert: ``s = sigmoid(h @ router)``, the
    ``k`` experts with the largest ``s + bias`` (the bias enters the choice
    only), weights ``s`` normalised over the k chosen. h: [T, d]. Returns
    (expert ids [T, k] int32, weights [T, k] float32)."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(h.astype(f32), router.astype(f32),
                                    precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias.astype(f32), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def softmax_top_k(h: jax.Array, router: jax.Array, k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Router in float32 over every expert: ``r = softmax(h @ router)``, the
    ``k`` experts with the largest ``r``, weights ``r`` normalised over the k
    chosen (``norm_topk_prob``). h: [T, d]. Returns (expert ids [T, k]
    int32, weights [T, k] float32)."""
    f32 = jnp.float32
    probs = jax.nn.softmax(jnp.dot(h.astype(f32), router.astype(f32),
                                   precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def held_experts_ffn(h: jax.Array, idx: jax.Array, weights: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     first_held: int = 0, live: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a SwiGLU expert layer, dropless.

    h [T, d]; idx / weights [T, k] from the router over all experts; the
    experts ``first_held .. first_held + n_held - 1`` live here, stacked in
    ``w_gate`` / ``w_up`` [n_held, d, f] and ``w_down`` [n_held, f, d].
    ``live`` [T] bool leaves rows out (padding, dead slots). Returns
    (``sum over held chosen experts of weight * expert(h)`` [T, d] in h's
    type, stats int32 [3] = assignments on held experts, the busiest held
    expert's, held experts touched).

    Every assignment has a row of its own in a [T * k] buffer sorted by
    expert (assignments elsewhere sort past the last group), so no skew
    drops a token; the grouped products (``ops/grouped_matmul.py``) read
    each touched expert's matrices once, multiply each group by its expert
    and never visit the rows past the groups.
    """
    T, k = idx.shape
    n_held = w_gate.shape[0]
    local = idx.reshape(-1) - first_held
    held = (local >= 0) & (local < n_held)
    if live is not None:
        held = held & jnp.repeat(live, k)
    with jax.named_scope("moe_dispatch"):
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)                    # [T*k]
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                        axis=0, dtype=jnp.int32)                 # [n_held]
        rows = h[order // k]                                     # [T*k, d]
        in_group = jnp.arange(T * k) < jnp.sum(sizes)
    with jax.named_scope("moe_experts"):
        mid = grouped_swiglu(rows, w_gate, w_up, sizes)          # in h's type
        out = grouped_matmul(mid, w_down, sizes,
                             preferred_element_type=jnp.float32)
    with jax.named_scope("moe_combine"):
        out = jnp.where(in_group[:, None], out, 0.0)
        back = jnp.argsort(order)                                # inverse
        per_choice = out[back].reshape(T, k, -1)
        w = jnp.where(held.reshape(T, k), weights, 0.0)
        y = jnp.einsum("tkd,tk->td", per_choice, w.astype(jnp.float32))
    stats = jnp.stack([jnp.sum(sizes), jnp.max(sizes),
                       jnp.sum(sizes > 0, dtype=jnp.int32)]).astype(jnp.int32)
    return y.astype(h.dtype), stats
