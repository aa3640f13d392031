"""The plain reference of the ``keye-vl-2.0-30b-a3b`` configuration: the
language model's forward pass, its two losses, gradients and AdamW in
straightforward ``jax.numpy``. float32 throughout, matmuls at ``highest``
precision, an explicit ``top_k``, a dense softmax over the selected set, a
Python loop over the held experts; no kernels, no sorted dispatch, no
cache. It imports nothing of the program.

A layer (pre-norm residual for both halves, ``x = RMSNorm(h)``):

* attention: ``q = x Wq`` (heads x head_dim), ``k = x Wk``, ``v = x Wv``
  (kv_heads), RMSNorm over each q and k head, rotary over every pair;
* indexer: ``qI[t, j] = xs_t WqI_j``, ``kI[s] = xs_s WkI`` (rotary on both),
  ``w[t] = xs_t Ww / sqrt(J E)`` with ``xs = stop_gradient(x)``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``;
  ``S_t`` = the ``topk`` keys of largest ``I[t, .]`` (``jax.lax.top_k``:
  ties to the lower position; every key while ``t < topk``);
* ``o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, g(h)] /
  sqrt(head_dim)) v[s, g(h)]``, then ``Wo``;
* experts: ``r = softmax(x Wr)`` over all of them, the ``top_k`` largest,
  gates renormalised over those; the layer returns ``sum_{e in top_k(t), e
  held} gate[t, e] (silu(x Wg_e) * (x Wu_e)) Wd_e`` for the contiguous
  range of experts it is given. No token is dropped, no expert is shared;
* ``L_I = mean_t KL(p_t || softmax_{S_t} I[t, .])``, ``p_t`` the attention
  probabilities summed over the heads on ``S_t``, L1-normalised, detached.

``L = L_LM + sum_layers L_I``; ``L_LM`` is the next-token cross entropy
over the vocabulary slice.

DEPARTURES (memory only, no arithmetic changed): attention, selection and
``L_I`` are taken ``Q_ROWS`` query rows at a time, the head's loss
``LOSS_ROWS`` rows at a time, and each of those, each expert's FFN and each
layer is rematerialised in the backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.reference import HIGHEST, matmul, rms_norm, rope

Q_ROWS = 128
LOSS_ROWS = 2048
LAYER_LEAVES = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "wq", "wk",
                "wv", "wo", "index_wq", "index_wk", "index_w", "w_router",
                "w_gate", "w_up", "w_down")


def selection(scores, pos_q, topk):
    """bool [rows, T]: the ``topk`` keys of largest score at or before each
    query's position (all of them where there are no more)."""
    t = scores.shape[1]
    valid = jnp.arange(t)[None, :] <= pos_q[:, None]
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(topk, t))
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return chosen & valid


def attention_rows(rows, q, qi, wi, k, v, ki, cfg):
    """The attention output and the summed KL of the query rows ``rows``
    (positions, [r]): ``q [r, h, d]``, ``qi [r, J, E]``, ``wi [r, J]``
    against every key."""
    h, kvh, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    z = jnp.einsum("rje,ke->rjk", qi, ki, precision=HIGHEST)
    index = jnp.einsum("rjk,rj->rk", jax.nn.relu(z), wi, precision=HIGHEST)
    keep = selection(jax.lax.stop_gradient(index), rows, cfg["index_topk"])
    kk, vv = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    s = jnp.einsum("rhd,khd->hrk", q, kk, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(hd))
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hrk,khd->rhd", p, vv, precision=HIGHEST)
    target = jax.lax.stop_gradient(p.sum(axis=0))
    target = target / target.sum(axis=-1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(target > 0, target * (
        jnp.log(jnp.where(target > 0, target, 1.0)) - logq), 0.0))
    return out.reshape(-1, h * hd), kl


def attention(x, lw, cfg):
    """(attention output [T, hidden], L_I of the layer)."""
    t = x.shape[0]
    h, kvh, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    j, e = cfg["index_heads"], cfg["index_dim"]
    pos, theta = jnp.arange(t), cfg["rope_theta"]
    q = rms_norm(matmul(x, lw["wq"]).reshape(t, h, hd), lw["q_norm"],
                 cfg["eps"])
    k = rms_norm(matmul(x, lw["wk"]).reshape(t, kvh, hd), lw["k_norm"],
                 cfg["eps"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    v = matmul(x, lw["wv"]).reshape(t, kvh, hd)
    xs = jax.lax.stop_gradient(x)
    qi = rope(matmul(xs, lw["index_wq"]).reshape(t, j, e), pos, theta)
    ki = rope(matmul(xs, lw["index_wk"]).reshape(t, 1, e), pos, theta)[:, 0]
    wi = matmul(xs, lw["index_w"]) / jnp.sqrt(jnp.float32(j * e))
    r = min(Q_ROWS, t)
    assert t % r == 0, (t, r)
    block = jax.checkpoint(lambda a: attention_rows(*a, k, v, ki, cfg))
    out, kl = jax.lax.map(block, (
        pos.reshape(-1, r), q.reshape(-1, r, h, hd), qi.reshape(-1, r, j, e),
        wi.reshape(-1, r, j)))
    return matmul(out.reshape(t, h * hd), lw["wo"]), kl.sum() / t


def experts(x, lw, cfg, held=None, offset=None):
    """The part of the expert layer that the experts ``[offset, offset +
    held)`` give (the configuration's own range by default)."""
    held = cfg["experts_held"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    r = jax.nn.softmax(matmul(x, lw["w_router"]), axis=-1)
    gates, chosen = jax.lax.top_k(r, cfg["top_k"])
    gates = gates / gates.sum(axis=-1, keepdims=True)
    @jax.checkpoint                              # DEPARTURE (memory only)
    def gated(x, gate, w_gate, w_up, w_down):
        return gate[:, None] * reference.swiglu(x, w_gate, w_up, w_down)

    y = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), axis=-1)
        y = y + gated(x, gate, lw["w_gate"][e], lw["w_up"][e],
                      lw["w_down"][e])
    return y


def layer(x, lw, cfg):
    a, kl = attention(rms_norm(x, lw["attn_norm"], cfg["eps"]), lw, cfg)
    x = x + a
    return x + experts(rms_norm(x, lw["mlp_norm"], cfg["eps"]), lw, cfg), kl


def hidden(w, tokens, cfg):
    """One sequence ``tokens`` [T] -> (the final norm's output, sum of the
    layers' L_I). DEPARTURE: ids index a slice of the published table."""
    x = w["embed"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def step(carry, lw):
        x, kl = carry
        x, kl_i = layer(x, lw, cfg)
        return (x, kl + kl_i), None

    # The layers one after the other over the stacked leaves (a scan: its
    # backward writes each layer's gradient into the stacked leaf in
    # place, where a Python loop over w[n][i] holds a padded copy a layer).
    (x, kl), _ = jax.lax.scan(step, (x, jnp.float32(0.0)),
                              {n: w[n] for n in LAYER_LEAVES})
    return rms_norm(x, w["final_norm"], cfg["eps"]), kl


def row_losses(w, tokens, cfg):
    """(L_LM, L_I) of one sequence."""
    h, kl = hidden(w, tokens, cfg)
    h, labels = h[:-1], tokens[1:]
    r = h.shape[0]
    rows = min(LOSS_ROWS, r)
    pad = (-r) % rows
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1])
    lp = jnp.pad(labels, (0, pad)).reshape(-1, rows)
    wt = jnp.pad(jnp.ones((r,), jnp.float32), (0, pad)).reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        hb, lb, wb = args
        logp = jax.nn.log_softmax(matmul(hb, w["lm_head"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0]
                        * wb)
    return jnp.sum(jax.lax.map(block, (hp, lp, wt))) / r, kl


def losses(w, batch, cfg):
    """(L_LM, L_I), each the mean over ``batch`` [b, T], row by row."""
    one = jax.checkpoint(lambda row: row_losses(w, row, cfg))
    lm, kl = jax.lax.map(one, batch)
    return jnp.mean(lm), jnp.mean(kl)


def loss(w, batch, cfg):
    return sum(losses(w, batch, cfg))


def train_steps(w, batches, cfg, lr):
    """``reference.train_steps`` with this module's objective: the first
    ``len(batches)`` AdamW steps from float32 weights ``w`` (consumed) ->
    the (L_LM, L_I) pairs, the per-leaf norms of the first gradient, the
    weights after the last step."""
    def total(w, b):
        lm, kl = losses(w, b, cfg)
        return lm + kl, (lm, kl)

    grad = jax.jit(jax.value_and_grad(total, has_aux=True))
    update = jax.jit(lambda w, gs: reference.adamw(w, gs, lr),
                     donate_argnums=0)
    pairs, grads, gnorms = [], [], None
    for b in batches:
        (_, pair), g = grad(w, b)
        if gnorms is None:
            gnorms = jax.jit(reference.leaf_norms)(g)
        grads.append(g)
        w = update(w, grads)
        pairs.append(pair)
    return pairs, gnorms, w
