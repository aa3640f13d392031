"""Mixture-of-experts layer with expert parallelism (SURVEY.md §2.3 "Expert
parallel (EP/MoE)" — absent from the reference, a first-class TPU-build
equivalent here).

TPU-first design — the GShard/Switch dispatch formulation, not a torch-style
gather/scatter loop:

* routing uses a **static expert capacity** ``C`` so every shape is known at
  trace time (XLA requirement); over-capacity tokens are dropped (their
  residual path still carries them);
* dispatch/combine are dense one-hot einsums — they lower to MXU matmuls and
  give GSPMD a clean pattern to turn into ``all_to_all`` over the ``expert``
  mesh axis;
* expert weights are stacked on a leading ``expert`` axis with logical names
  ``("expert", "embed", "ffn")`` so :data:`tony_tpu.parallel.RULES` shards
  each expert's FFN over the EP axis (and its hidden dim over TP);
* the Switch load-balancing auxiliary loss is sown into a ``losses``
  collection; :func:`tony_tpu.train.make_train_step` adds any sown losses to
  the objective.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def router_assignment(gates: jax.Array, top_k: int, capacity: int):
    """Top-k expert assignment with per-expert capacity.

    Args:
      gates: [G, S, E] f32 router probabilities (softmax over E).
      top_k: experts per token.
      capacity: max tokens an expert accepts per group (static).

    Returns:
      dispatch: [G, S, E, C] one-hot f32 — token s of group g occupies
        capacity slot c of expert e.
      combine: [G, S, E, C] f32 — dispatch weighted by the (renormalized)
        router probability.
      aux: scalar Switch load-balancing loss (un-scaled).
    """
    g, s, e = gates.shape
    if top_k > e:
        raise ValueError(f"top_k={top_k} exceeds n_experts={e}")
    remaining = gates
    dispatch = jnp.zeros((g, s, e, capacity), gates.dtype)
    combine = jnp.zeros((g, s, e, capacity), gates.dtype)
    for _ in range(top_k):  # static, tiny (k ≤ 2 in practice)
        choice = jnp.argmax(remaining, axis=-1)                # [G, S]
        onehot = jax.nn.one_hot(choice, e, dtype=gates.dtype)  # [G, S, E]
        # Position of this token within its chosen expert's queue, counting
        # earlier tokens (in sequence order) AND slots taken in earlier
        # top-k rounds.
        taken = dispatch.sum(axis=(1, 3))                      # [G, E]
        pos = (jnp.cumsum(onehot, axis=1) - onehot             # [G, S, E]
               + taken[:, None, :])
        pos = (pos * onehot).sum(axis=-1).astype(jnp.int32)    # [G, S]
        fits = (pos < capacity).astype(gates.dtype)            # [G, S]
        slot = jax.nn.one_hot(pos, capacity, dtype=gates.dtype)  # [G, S, C]
        hot = (onehot * fits[..., None])[..., None] * slot[:, :, None, :]
        dispatch = dispatch + hot
        gate = (gates * onehot).sum(-1)                        # [G, S]
        combine = combine + gate[..., None, None] * hot
        # Exclude chosen experts with -inf, not by multiplying to zero: if
        # a token's remaining probabilities all underflowed to 0, argmax
        # would tie-break to expert 0 and could re-select an already-chosen
        # expert (double-booking its capacity). -inf can never win argmax
        # while any un-chosen expert remains.
        remaining = jnp.where(onehot > 0, -jnp.inf, remaining)
    # Renormalize combine weights over the k selected experts so the output
    # is a convex mixture (dropped tokens keep weight 0 → pure residual).
    total = combine.sum(axis=(2, 3), keepdims=True)
    combine = jnp.where(total > 0, combine / jnp.maximum(total, 1e-9), 0.0)
    # Switch aux loss: E · Σ_e fraction_routed(e) · mean_prob(e), averaged
    # over groups — minimized (=1) when routing is perfectly balanced; the
    # mean-prob factor is what gradients flow through.
    first = jax.nn.one_hot(jnp.argmax(gates, -1), e, dtype=gates.dtype)
    frac = first.mean(axis=1)        # [G, E] fraction of tokens → expert
    prob = gates.mean(axis=1)        # [G, E] mean router probability
    aux = e * (frac * prob).sum(axis=-1).mean()
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Expert-parallel SwiGLU FFN: drop-in for the dense MLP block.

    Input [B, T, D]; groups = batch rows (already sharded over the DP axes),
    experts sharded over the ``expert`` mesh axis — the dispatch einsum is
    where GSPMD inserts the EP ``all_to_all``.

    ``explicit_a2a=True`` (with ``mesh=``) routes dispatch/FFN/combine
    through the collective scheduler instead
    (:func:`tony_tpu.parallel.sched.moe_dispatch_ffn_combine`): the EP
    ``all_to_all`` is issued explicitly per capacity chunk
    (``a2a_chunks``) inside the layer so chunk *c+1*'s a2a rides under
    chunk *c*'s expert FFN compute, rather than whatever one-shot
    schedule GSPMD picks for the einsum. Same math (per-chunk combine-sum
    reassociation aside); owns only the expert axis, so it needs
    ``tp=sp=pp=1`` and must not run inside another manual region (the
    accum engine's) — the einsum path stays the default and the GSPMD
    numerics pin.
    """
    dim: int
    ffn_hidden: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_coef: float = 0.01
    dtype: object = jnp.bfloat16
    explicit_a2a: bool = False
    mesh: Any = None
    a2a_chunks: int = 2

    @nn.compact
    @jax.named_scope("moe")   # the device scope, whatever the module's name
    def __call__(self, x):
        b, t, d = x.shape
        e, f = self.n_experts, self.ffn_hidden
        capacity = max(1, int(self.capacity_factor * t * self.top_k / e))

        wr = self.param("w_router", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "expert_dim")),
            (d, e), jnp.float32)
        # Router in f32: softmax over few logits, numerics matter more
        # than MXU throughput here.
        gates = jax.nn.softmax(x.astype(jnp.float32) @ wr, axis=-1)
        dispatch, combine, aux = router_assignment(
            gates, self.top_k, capacity)
        self.sow("losses", "moe_aux", self.aux_coef * aux,
                 reduce_fn=lambda a, c: a + c,
                 init_fn=lambda: jnp.float32(0.0))

        stacked = lambda name, shape, logical: self.param(
            name, nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), logical), shape, jnp.float32)
        w_gate = stacked("w_gate", (e, d, f), ("expert", "embed", "ffn"))
        w_up = stacked("w_up", (e, d, f), ("expert", "embed", "ffn"))
        w_down = stacked("w_down", (e, f, d), ("expert", "ffn", "embed"))

        if self.explicit_a2a:
            if self.mesh is None:
                raise ValueError(
                    "MoEMLP(explicit_a2a=True) needs mesh=: the scheduler "
                    "issues the a2a over the mesh's expert axis itself")
            from tony_tpu.parallel import sched  # lazy: models stay light
            y = sched.moe_dispatch_ffn_combine(
                x, dispatch, combine, (w_gate, w_up, w_down), self.mesh,
                chunks=self.a2a_chunks, dtype=self.dtype)
            return nn.with_logical_constraint(
                y, ("batch", "act_seq", "act_embed"))

        # Dispatch: [B,S,E,C] × [B,S,D] → [E,B,C,D] (the EP all_to_all).
        xin = jnp.einsum("gsec,gsd->egcd", dispatch.astype(self.dtype),
                         x, precision=jax.lax.Precision.DEFAULT)
        xin = nn.with_logical_constraint(
            xin, ("expert", "batch", None, "act_embed"))
        h = nn.silu(jnp.einsum("egcd,edf->egcf", xin,
                               w_gate.astype(self.dtype)))
        h = h * jnp.einsum("egcd,edf->egcf", xin, w_up.astype(self.dtype))
        out = jnp.einsum("egcf,efd->egcd", h, w_down.astype(self.dtype))
        out = nn.with_logical_constraint(
            out, ("expert", "batch", None, "act_embed"))
        # Combine back to token order: [B,S,E,C] × [E,B,C,D] → [B,S,D].
        y = jnp.einsum("gsec,egcd->gsd", combine.astype(self.dtype), out)
        return nn.with_logical_constraint(
            y, ("batch", "act_seq", "act_embed"))
