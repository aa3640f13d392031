"""The plain reference of the ``kimi-linear-48b-a3b`` configuration (Kimi
Linear, arXiv:2510.26692): the forward pass, its loss, gradients and AdamW
in straightforward ``jax.numpy``. float32 throughout, matmuls at
``highest`` precision; the linear attention as the token-by-token
recurrence, the latent attention as a masked softmax, the router and the
experts dense over the held range; no kernels, no chunked solve, no sorted
dispatch, no cache. It imports nothing of the program.

A layer, for ``h [T, D]`` (pre-norm residual halves, RMSNorm eps from the
configuration): ``h += mixer(RMSNorm(h)); h += ffn(RMSNorm(h))``.

* **KDA** (``x`` the normed input, ``H`` heads of ``d``): ``q, k, v =
  silu(conv(x W_{q,k,v}))`` — a depthwise causal convolution of ``K`` taps
  (tap ``K-1`` multiplies the current step, steps before 0 are zeros); ``q,
  k <- q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6)`` a head, ``q <- q
  d^-1/2``; ``g_t = -exp(A_log_h) softplus(W_f2 (W_f1 x_t) + dt_bias)``
  [H, d]; ``beta_t = sigmoid(x_t W_b)`` [H]; the recurrence, a head::

      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                                              S_-1 = 0

  ``y = W_o (RMSNorm_d(o_t) * sigmoid(W_g2 (W_g1 x_t) + b_g))``.
* **MLA**: ``q_h = x W_q`` (nope + rope wide); ``[c, k_s] = x W_kva``;
  ``[k_h, v_h] = RMSNorm(c) W_kvb``; key of head ``h`` = ``[k_h, k_s]``
  (``k_s`` shared by the heads, no rotation anywhere); causal softmax of
  ``q_h . key_h / sqrt(nope + rope)``; ``W_o`` over the values.
* **dense**: ``W_down(silu(W_gate y) * W_up y)``.
* **experts**: ``s = sigmoid(y W_r)``; chosen = the ``top_k`` largest of
  ``s + b`` (ties to the lower index; ``b`` enters nothing else and gets no
  gradient); ``gate_e = scale * s_e / sum of the chosen s``; ``sum over
  chosen e in the held range of gate_e FFN_e(y) + FFN_shared(y)``.

Final RMSNorm; ``logits = x W_head`` (untied); ``L = L_LM``, the next-token
cross entropy over the slice.

DEPARTURES (memory only, no arithmetic changed): the recurrence is scanned
in blocks of ``SCAN_ROWS`` steps, each rematerialised in the backward pass
(one block's states are held at a time); attention is taken ``Q_ROWS``
query rows at a time, the feed-forwards ``ROWS`` positions at a time, the
head's loss ``LOSS_ROWS`` rows at a time, and each of those, each expert's
FFN and each layer is rematerialised. The experts held elsewhere add
nothing, as the configuration's file says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.reference import HIGHEST, matmul, rms_norm

SCAN_ROWS = 256
Q_ROWS = 128
ROWS = 4096
LOSS_ROWS = 1024


def conv(a, taps):
    """Depthwise causal convolution: ``a [T, E]``, ``taps [K, E]``; tap
    ``K-1`` multiplies the current step."""
    out, at = jnp.zeros_like(a), a
    for j in range(taps.shape[0]):               # j steps back
        out = out + taps[taps.shape[0] - 1 - j] * at
        at = jnp.concatenate([jnp.zeros_like(at[:1]), at[:-1]], axis=0)
    return out


def recurrence(q, k, v, g, beta):
    """``q, k, g [T, H, d]``, ``v [T, H, dv]``, ``beta [T, H]`` -> ``o
    [T, H, dv]``: the delta rule with a per-channel decay, step by step."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision=HIGHEST))
        s = s + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    @jax.checkpoint                              # DEPARTURE (memory only)
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    t, h, d = q.shape
    n = min(SCAN_ROWS, t)
    assert t % n == 0, (t, n)
    xs = tuple(a.reshape(t // n, n, *a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((h, d, v.shape[2]), jnp.float32), xs)
    return o.reshape(t, h, v.shape[2])


def kda(x, lw, cfg):
    """The KDA mixer of the module docstring. DEPARTURE (memory only):
    each of q, k, v, the decay and the output gate is rematerialised in
    the backward pass, so one ``[T, H d]`` chain is held at a time."""
    t = x.shape[0]
    h, d = cfg["kda_heads"], cfg["kda_head_dim"]
    heads = lambda a: a.reshape(t, h, d)
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def mixed(x, w, taps, scale):
        a = heads(jax.nn.silu(conv(matmul(x, w), taps)))
        return a if scale is None else unit(a) * scale

    @jax.checkpoint
    def decay(x, lw):
        g = -jnp.exp(lw["a_log"])[:, None] * heads(jax.nn.softplus(
            matmul(matmul(x, lw["wf1"]), lw["wf2"]) + lw["dt_bias"]))
        return g, jax.nn.sigmoid(matmul(x, lw["wb"]))

    @jax.checkpoint
    def gated(x, o, lw):
        gate = jax.nn.sigmoid(
            matmul(matmul(x, lw["wg1"]), lw["wg2"]) + lw["bg2"])
        o = rms_norm(o, lw["o_norm"], cfg["eps"]).reshape(t, h * d)
        return matmul(o * gate, lw["wo"])

    q, k, v = (mixed(x, lw["w" + n], lw["conv_" + n], scale)
               for n, scale in (("q", d ** -0.5), ("k", 1.0), ("v", None)))
    g, beta = decay(x, lw)
    return gated(x, recurrence(q, k, v, g, beta), lw)


def mla_rows(rows, q, key, v, cfg):
    """The attention output of the query rows at positions ``rows`` [r],
    ``q [r, H, nope + rope]``, against every key."""
    s = jnp.einsum("rhd,khd->hrk", q, key, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    seen = jnp.arange(key.shape[0])[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hrk,khd->rhd", p, v,
                      precision=HIGHEST).reshape(rows.shape[0], -1)


def mla(x, lw, cfg):
    t = x.shape[0]
    h, r, dn, dv = cfg["mla_heads"], cfg["kv_rank"], cfg["nope"], cfg["v_dim"]

    @jax.checkpoint
    def qkv(x, lw):
        q = matmul(x, lw["wq"]).reshape(t, h, dn + cfg["rope"])
        kva = matmul(x, lw["wkv_a"])
        kv = matmul(rms_norm(kva[:, :r], lw["kv_norm"], cfg["eps"]),
                    lw["wkv_b"]).reshape(t, h, dn + dv)
        shared = jnp.broadcast_to(kva[:, None, r:], (t, h, cfg["rope"]))
        return q, jnp.concatenate([kv[..., :dn], shared], -1), kv[..., dn:]

    q, key, v = qkv(x, lw)
    n = min(Q_ROWS, t)
    assert t % n == 0, (t, n)
    block = jax.checkpoint(lambda a: mla_rows(*a, key, v, cfg))
    out = jax.lax.map(block, (jnp.arange(t).reshape(-1, n),
                              q.reshape(-1, n, *q.shape[1:])))
    return matmul(out.reshape(t, -1), lw["wo"])


def route(y, lw, cfg):
    """(chosen [T, k] int, gates [T, k]): the sigmoid router."""
    s = jax.nn.sigmoid(matmul(y, lw["w_router"]))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(lw["router_bias"]),
                              cfg["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, cfg["route_scale"] * picked / picked.sum(-1, keepdims=True)


def experts(y, lw, cfg, held=None, offset=None, shared=True):
    """The part of the expert layer that the experts ``[offset, offset +
    held)`` give (the configuration's own range by default) and, with
    ``shared``, the shared expert."""
    held = cfg["experts_held"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    chosen, gates = route(y, lw, cfg)

    @jax.checkpoint                              # DEPARTURE (memory only)
    def gated(y, gate, w_gate, w_up, w_down):
        return gate[:, None] * reference.swiglu(y, w_gate, w_up, w_down)

    out = jnp.zeros_like(y)
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == offset + e, gates, 0.0), axis=-1)
        out = out + gated(y, gate, lw["w_gate"][e], lw["w_up"][e],
                          lw["w_down"][e])
    if shared and cfg["shared"]:
        out = out + reference.swiglu(y, lw["shared_gate"], lw["shared_up"],
                                     lw["shared_down"])
    return out


def ffn_half(x, lw, ffn, cfg):
    """x + its feed-forward, ``ROWS`` positions at a time: nothing here
    looks at another position."""
    def rows(xb):
        y = rms_norm(xb, lw["norm2"], cfg["eps"])
        if ffn == "dense":
            return xb + reference.swiglu(y, lw["w_gate"], lw["w_up"],
                                         lw["w_down"])
        return xb + experts(y, lw, cfg)

    t = x.shape[0]
    n = min(ROWS, t)
    assert t % n == 0, (t, n)
    return jax.lax.map(jax.checkpoint(rows),
                       x.reshape(-1, n, x.shape[1])).reshape(x.shape)


def layer(x, lw, kind, ffn, cfg):
    mixer = kda if kind == "kda" else mla
    x = x + mixer(rms_norm(x, lw["norm1"], cfg["eps"]), lw, cfg)
    return ffn_half(x, lw, ffn, cfg)


def layer_leaves(w, i):
    return {n[len(f"L{i}."):]: a for n, a in w.items()
            if n.startswith(f"L{i}.")}


def hidden(w, tokens, cfg):
    """One sequence ``tokens`` [T] -> the final norm's output. DEPARTURE:
    ids index a slice of the published table."""
    x = w["embed"].astype(jnp.float32)[tokens]
    for i, (kind, ffn) in enumerate(zip(cfg["kinds"], cfg["ffns"])):
        x = jax.checkpoint(
            lambda x, lw, kind=kind, ffn=ffn: layer(x, lw, kind, ffn, cfg))(
                x, layer_leaves(w, i))
    return rms_norm(x, w["final_norm"], cfg["eps"])


def row_loss(w, tokens, cfg):
    """L_LM of one sequence, the logits through the untied head."""
    h, labels = hidden(w, tokens, cfg)[:-1], tokens[1:]
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
    return jnp.sum(jax.lax.map(block, (hp, lp, wt))) / r


def rows_held(w, batch, cfg):
    """[expert layers] int: the (token, choice) pairs of ``batch`` [b, T]
    whose expert is one of the held range, layer by layer (what a dropless
    layer's ``stats`` count at the same weights)."""
    lo, hi = cfg["expert_offset"], cfg["expert_offset"] + cfg["experts_held"]

    def one(tokens):
        x, counts = w["embed"].astype(jnp.float32)[tokens], []
        for i, (kind, ffn) in enumerate(zip(cfg["kinds"], cfg["ffns"])):
            lw = layer_leaves(w, i)
            if ffn == "experts":
                mixer = kda if kind == "kda" else mla
                x1 = x + mixer(rms_norm(x, lw["norm1"], cfg["eps"]), lw, cfg)
                chosen, _ = route(rms_norm(x1, lw["norm2"], cfg["eps"]),
                                  lw, cfg)
                counts.append(jnp.sum((chosen >= lo) & (chosen < hi)))
            x = layer(x, lw, kind, ffn, cfg)
        return jnp.stack(counts)
    return jnp.sum(jax.lax.map(one, batch), axis=0)


def loss(w, batch, cfg):
    """Mean next-token cross entropy over ``batch`` [b, T], row by row
    (DEPARTURE, memory only: of several rows each is rematerialised)."""
    one = lambda row: row_loss(w, row, cfg)
    if batch.shape[0] > 1:
        one = jax.checkpoint(one)
    return jnp.mean(jax.lax.map(one, batch))


def train_steps(w, batches, cfg, lr):
    """``reference.train_steps`` with this module's objective: the first
    ``len(batches)`` AdamW steps from float32 weights ``w`` (consumed) ->
    the losses, the per-leaf norms of the first gradient, the weights
    after the last step. DEPARTURE (memory only): between two steps the
    gradients so far wait on the host — at 1 x 32768 the next gradient's
    program leaves the chip no room for them beside the weights."""
    grad = jax.jit(jax.value_and_grad(lambda w, b: loss(w, b, cfg)))
    update = jax.jit(lambda w, gs: reference.adamw(w, gs, lr),
                     donate_argnums=0)
    losses, grads, gnorms = [], [], None
    for b in batches:
        l, g = grad(w, b)
        if gnorms is None:
            gnorms = jax.jit(reference.leaf_norms)(g)
        w = update(w, grads + [g])
        grads.append(jax.device_get(g))
        del g                       # the next gradient needs its room
        losses.append(float(l))
    return losses, gnorms, w
