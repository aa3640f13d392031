"""The plain reference of the ``olmo-hybrid-7b`` configuration (Gated
DeltaNet, arXiv:2412.06464, beside full attention, the Olmo family's
reordered norm): the forward pass, its loss, gradients and AdamW in
straightforward ``jax.numpy``. float32 throughout, matmuls at ``highest``
precision; the linear attention as the token-by-token recurrence, the full
attention as a masked softmax; no kernels, no chunked solve, no cache. It
imports nothing of the program.

A layer, for ``h [T, D]``: ``h += RMSNorm(mixer(h)); h += RMSNorm(FFN(h))``
(the norm after the sublayer, inside the residual branch), ``FFN(u) =
(silu(u W_gate) * u W_up) W_down``.

* **gdn** (``H`` held heads of ``dk`` key and ``dv`` value channels):
  ``q, k, v = silu(conv(x W_{q,k,v}))`` — a depthwise causal convolution of
  ``K`` taps (tap ``K-1`` multiplies the current step, steps before 0 are
  zeros); ``q, k <- q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6)`` a head,
  ``q <- q dk^-1/2``; ``g_t = -exp(A_log_h) softplus(x_t w_a,h +
  dt_bias_h)``, ONE scalar a head; ``beta_t = 2 sigmoid(x_t w_b,h)`` (1 x
  without ``neg_eigval``); the recurrence, a head::

      S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                              S in R^{dk x dv}, S_-1 = 0

  ``y = W_o [RMSNorm_dv(o_h) * silu((x W_z)_h)]_h``.
* **attn**: ``q, k, v = x W_{q,k,v}`` over the held heads of ``head_dim``;
  RMSNorm over q's and k's whole width, the mean square over the columns
  held (``groups`` = 1; the share test takes it a half at a time, ``groups``
  = 2: the one scalar a token two chips would exchange to norm over the
  published width); no rotation; causal softmax of ``q . k /
  sqrt(head_dim)``; ``W_o``.

Final RMSNorm; ``logits = x W_head`` (untied); ``L = L_LM``, the next-token
cross entropy over the slice.

DEPARTURES (memory only, no arithmetic changed): the recurrence is scanned
in blocks of ``SCAN_ROWS`` steps, each rematerialised in the backward pass;
attention is taken ``Q_ROWS`` query rows at a time, the feed-forward
``ROWS`` positions at a time, the head's loss ``LOSS_ROWS`` rows at a time,
and each of those, each chain of a mixer and each layer is rematerialised.
The heads held elsewhere add nothing, as the configuration's file says.
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
    """``q, k [T, H, dk]``, ``v [T, H, dv]``, ``g, beta [T, H]`` -> ``o
    [T, H, dv]``: the delta rule under one decay a head, step by step."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision=HIGHEST))
        s = s + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    @jax.checkpoint                              # DEPARTURE (memory only)
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    t, h, dk = q.shape
    n = min(SCAN_ROWS, t)
    assert t % n == 0, (t, n)
    xs = tuple(a.reshape(t // n, n, *a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[2]), jnp.float32), xs)
    return o.reshape(t, h, v.shape[2])


def gdn(x, lw, cfg):
    """The Gated DeltaNet mixer of the module docstring over the heads
    ``lw`` holds. DEPARTURE (memory only): each of q, k, v, the decay and
    the gated output is rematerialised in the backward pass."""
    t = x.shape[0]
    h = lw["a_log"].shape[0]
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def mixed(x, w, taps, scale):
        a = jax.nn.silu(conv(matmul(x, w), taps)).reshape(t, h, -1)
        return a if scale is None else unit(a) * scale

    @jax.checkpoint
    def decay(x, lw):
        g = -jnp.exp(lw["a_log"]) * jax.nn.softplus(
            matmul(x, lw["wa"]) + lw["dt_bias"])
        return g, (2.0 if cfg["neg_eigval"] else 1.0) * jax.nn.sigmoid(
            matmul(x, lw["wb"]))

    @jax.checkpoint
    def gated(x, o, lw):
        o = rms_norm(o, lw["o_norm"], cfg["eps"]).reshape(t, -1)
        return matmul(o * jax.nn.silu(matmul(x, lw["wz"])), lw["wo"])

    q, k, v = (mixed(x, lw["w" + n], lw["conv_" + n], scale)
               for n, scale in (("q", cfg["dk"] ** -0.5), ("k", 1.0),
                                ("v", None)))
    g, beta = decay(x, lw)
    return gated(x, recurrence(q, k, v, g, beta), lw)


def attn_rows(rows, q, k, v):
    """The attention output of the query rows at positions ``rows`` [r],
    ``q [r, H, d]``, against every key."""
    s = jnp.einsum("rhd,khd->hrk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    seen = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hrk,khd->rhd", p, v,
                      precision=HIGHEST).reshape(rows.shape[0], -1)


def attn(x, lw, cfg, groups: int = 1):
    """The attention mixer of the module docstring over the heads ``lw``
    holds; the q/k-norm's mean square over each of ``groups`` equal parts
    of the width."""
    t, hd = x.shape[0], cfg["head_dim"]

    def normed(a, scale):
        parts = a.reshape(t, groups, -1)
        return (parts * jax.lax.rsqrt(jnp.mean(
            parts * parts, -1, keepdims=True) + cfg["eps"])).reshape(
                a.shape) * scale

    @jax.checkpoint
    def qkv(x, lw):
        return tuple(a.reshape(t, -1, hd) for a in (
            normed(matmul(x, lw["wq"]), lw["q_norm"]),
            normed(matmul(x, lw["wk"]), lw["k_norm"]),
            matmul(x, lw["wv"])))

    q, k, v = qkv(x, lw)
    n = min(Q_ROWS, t)
    assert t % n == 0, (t, n)
    block = jax.checkpoint(lambda a: attn_rows(*a, k, v))
    out = jax.lax.map(block, (jnp.arange(t).reshape(-1, n),
                              q.reshape(-1, n, *q.shape[1:])))
    return matmul(out.reshape(t, -1), lw["wo"])


def ffn_half(x, lw, cfg):
    """x + RMSNorm(FFN(x)), ``ROWS`` positions at a time: nothing here
    looks at another position."""
    def rows(xb):
        return xb + rms_norm(reference.swiglu(
            xb, lw["w_gate"], lw["w_up"], lw["w_down"]), lw["norm2"],
            cfg["eps"])

    t = x.shape[0]
    n = min(ROWS, t)
    assert t % n == 0, (t, n)
    return jax.lax.map(jax.checkpoint(rows),
                       x.reshape(-1, n, x.shape[1])).reshape(x.shape)


def layer(x, lw, kind, cfg):
    mixer = gdn if kind == "gdn" else attn
    x = x + rms_norm(mixer(x, lw, cfg), lw["norm1"], cfg["eps"])
    return ffn_half(x, lw, cfg)


def layer_leaves(w, i):
    return {n[len(f"L{i}."):]: a for n, a in w.items()
            if n.startswith(f"L{i}.")}


def hidden(w, tokens, cfg):
    """One sequence ``tokens`` [T] -> the final norm's output. DEPARTURE:
    ids index a slice of the published table."""
    x = w["embed"].astype(jnp.float32)[tokens]
    for i, kind in enumerate(cfg["kinds"]):
        x = jax.checkpoint(
            lambda x, lw, kind=kind: layer(x, lw, kind, cfg))(
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
    gradients so far wait on the host — at 766 M parameters the next
    gradient's program leaves the chip no room for them beside the
    weights."""
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
