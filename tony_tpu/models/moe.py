"""Mixture-of-experts layers (SURVEY.md §2.3 "Expert parallel (EP/MoE)" —
absent from the reference, a first-class TPU-build equivalent here). Two
routing paths, one module each:

:class:`MoEMLP` — **static capacity** (GShard/Switch), for a few experts
spread over an ``expert`` mesh axis (``mixtral-8x7b``, ``llama-moe-tiny``):

* every expert takes at most ``C`` tokens a group so every shape is known
  at trace time; over-capacity tokens are **dropped** (their residual path
  still carries them);
* dispatch/combine are dense one-hot ``[G, S, E, C]`` einsums — MXU
  matmuls, and the pattern GSPMD turns into ``all_to_all`` over the
  ``expert`` axis;
* expert weights are stacked on a leading ``expert`` axis with logical names
  ``("expert", "embed", "ffn")`` so :data:`tony_tpu.parallel.RULES` shards
  each expert's FFN over the EP axis (and its hidden dim over TP);
* the Switch load-balancing auxiliary loss is sown into a ``losses``
  collection; :func:`tony_tpu.train.make_train_step` adds any sown losses to
  the objective.

:class:`DroplessMoE` — **no token dropped**, for many fine-grained experts
of which this chip holds a contiguous range (``keye-vl-2.0-30b-a3b``: 16 of
128): the layer routes over all ``n_experts``, is told which it holds
(``experts_held``, ``expert_offset``) and returns the part of the result
its own experts give. The one-hot form is ``tokens x experts x capacity``
and cannot be the path at 128 experts: rows are **sorted by expert**, the
experts run as one grouped matmul over ragged row groups
(:func:`tony_tpu.ops.gmm.grouped_matmul`: on the TPU Pallas kernels that
walk the groups and cost the rows that are there; XLA's own
``jax.lax.ragged_dot`` costs the whole row buffer on the v5e, by an amount
that follows the routing — PERF.md §5), and the result is put back in
token order.
On one chip the layer runs without its exchange; nothing stands in for the
absent chips.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu import profiler
from tony_tpu.ops.gmm import grouped_matmul


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


def route_top_k(x: jax.Array, w_router: jax.Array, top_k: int):
    """``(experts [N, k] int32, gates [N, k] float32)`` of the rows
    ``x [N, D]``: softmax over every expert in float32 (the products too:
    an expert that flips on rounding changes a token's whole output),
    the ``top_k`` largest, renormalised over those."""
    logits = jnp.dot(x.astype(jnp.float32), w_router,
                     precision=jax.lax.Precision.HIGHEST)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    gates = gates / gates.sum(axis=-1, keepdims=True)
    return experts.astype(jnp.int32), gates


def _int8_image(x: jax.Array, axis: int) -> jax.Array:
    """``x`` with every vector along ``axis`` (a matmul's contracted
    dimension) put on its symmetric int8 grid (``ops.quant``'s scale and
    rounding) and given back in ``x``'s dtype; the gradient passes
    straight through. What an int8 matmul with a float32 rescale
    multiplies, for a grouped matmul that has no int8 kernel."""
    from tony_tpu.ops import quant

    scale = quant.scale_of(jnp.max(jnp.abs(x.astype(jnp.float32)),
                                   axis=axis, keepdims=True))
    image = quant.dequantize(quant.quantize(x, scale), scale, x.dtype)
    return x + jax.lax.stop_gradient(image - x)


# Tokens a dropless layer routes at a time (fewer where a call has fewer).
# A chunk's buffers hold ``CHUNK * top_k`` rows of the model width, the
# worst case of all of them held here: at 8 experts a token and width 2048
# that is 32 MiB a buffer, which the keye-vl-2.0-30b-a3b step has room for
# beside its state; the one value that has run on the chip (PERF.md §5).
CHUNK = 1024


class DroplessMoE(nn.Module):
    """Dropless top-k SwiGLU experts over the contiguous range
    ``[expert_offset, expert_offset + experts_held)`` of ``n_experts``.

    Input ``[B, T, D]``; the output is ``sum_{e in top_k(t), e held}
    gate[t, e] * FFN_e(x_t)`` — with every expert held, the whole layer.
    Tokens are taken :data:`CHUNK` at a time: a chunk's sorted rows are
    sized for the worst case (every one of its ``CHUNK * top_k`` routed
    rows held here), the grouped matmul computes only the rows that are,
    and a chunk is recomputed in the backward instead of kept.

    Device scopes ``moe`` > ``moe_route``, ``moe_dispatch``,
    ``moe_experts``, ``moe_combine``. With the ``stats`` collection
    mutable (a train step's is), ``moe_rows_held``,
    ``moe_rows_max_expert`` and ``moe_groups_fed`` (the (chunk, held
    expert) pairs that got a row) of the call are sown (what a dropless
    layer has to carry: nothing bounds them but the routing)."""
    dim: int
    ffn_hidden: int
    n_experts: int
    top_k: int = 8
    experts_held: int = 0           # 0: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16
    # The int8 lane (``TransformerConfig.quant``'s "mlp"): the operands of
    # the three grouped matmuls take their int8 image first.
    quant: bool = False

    @nn.compact
    @jax.named_scope("moe")
    def __call__(self, x):
        b, t, d = x.shape
        e, f, k = self.n_experts, self.ffn_hidden, self.top_k
        held = self.experts_held or e
        if not 0 <= self.expert_offset <= e - held:
            raise ValueError(f"experts [{self.expert_offset}, "
                             f"{self.expert_offset + held}) of {e}")
        profiler.count_once("moe:experts_total", e)
        profiler.count_once("moe:experts_held", held)
        profiler.count_once("moe:top_k", k)
        wr = self.param("w_router", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "expert_dim")),
            (d, e), jnp.float32)
        stacked = lambda name, shape, logical: self.param(
            name, nn.with_logical_partitioning(
                nn.initializers.lecun_normal(batch_axis=(0,)), logical),
            shape, jnp.float32).astype(self.dtype)
        w_gate = stacked("w_gate", (held, d, f), ("expert", "embed", "ffn"))
        w_up = stacked("w_up", (held, d, f), ("expert", "embed", "ffn"))
        w_down = stacked("w_down", (held, f, d), ("expert", "ffn", "embed"))

        if self.quant:
            w_gate, w_up, w_down = (_int8_image(w, 1)
                                    for w in (w_gate, w_up, w_down))
        rows_in = (lambda a: _int8_image(a, 1)) if self.quant else (
            lambda a: a)

        n = b * t
        chunk = min(CHUNK, n)
        if n % chunk:
            raise ValueError(f"{n} tokens are not whole chunks of {chunk}")
        rows = x.reshape(n // chunk, chunk, d)

        @jax.checkpoint
        def one_chunk(xc):
            with jax.named_scope("moe_route"):
                experts, gates = route_top_k(xc, wr, k)
                local = experts - self.expert_offset
                mine = (local >= 0) & (local < held)
                # Rows of experts held elsewhere sort behind every group.
                local = jnp.where(mine, local, held).reshape(-1)
            with jax.named_scope("moe_dispatch"):
                order = jnp.argsort(local, stable=True)
                sizes = jnp.bincount(local, length=held + 1)[:held].astype(
                    jnp.int32)
                # Past the last group a grouped kernel leaves what it found
                # (forward and backward): those rows are NAMED zero on the
                # way in and on the way out, never multiplied by it.
                live = (jnp.arange(chunk * k) < sizes.sum())[:, None]
                xs = jnp.where(live, jnp.take(xc, order // k, axis=0), 0)
            with jax.named_scope("moe_experts"):
                xs = rows_in(xs)
                h = nn.silu(grouped_matmul(xs, w_gate, sizes)) \
                    * grouped_matmul(xs, w_up, sizes)
                out = grouped_matmul(rows_in(h), w_down, sizes)
            with jax.named_scope("moe_combine"):
                out = jnp.where(live, out, 0)
                back = jnp.zeros_like(order).at[order].set(
                    jnp.arange(chunk * k, dtype=order.dtype),
                    unique_indices=True)
                out = jnp.take(out, back, axis=0, unique_indices=True)
                y = jnp.einsum("tkd,tk->td", out.reshape(chunk, k, d),
                               jnp.where(mine, gates, 0.0).astype(out.dtype),
                               preferred_element_type=jnp.float32)
            return y.astype(x.dtype), sizes

        y, sizes = jax.lax.map(one_chunk, rows)
        if self.is_mutable_collection("stats"):
            per_expert = sizes.sum(axis=0)
            self.sow("stats", "moe_rows_held", per_expert.sum())
            self.sow("stats", "moe_rows_max_expert", per_expert.max())
            self.sow("stats", "moe_groups_fed", (sizes > 0).sum())
        return y.reshape(b, t, d)
