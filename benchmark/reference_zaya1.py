"""The plain reference of the ``zaya1-8b`` configuration (ZAYA1-8B; Zyphra,
"Compressed Convolutional Attention", arXiv:2510.04476; ZAYA1 technical
report, arXiv:2511.17127): the forward pass, its loss, gradients and AdamW
in straightforward ``jax.numpy``. float32 throughout, matmuls at
``highest`` precision, the convolutions as shifted sums, a dense softmax, a
Python loop over the held experts; no kernels, no sorted dispatch, no
cache. It imports nothing of the program.

A layer, for ``h [T, D]`` (``H`` query heads over ``G`` key/value heads of
``d``, ``g(h) = h // (H / G)``; a convolution's tap ``K-1`` multiplies the
current step, steps before 0 are zeros):

* ``x = RMSNorm(h)``, ``x'_t = x_{t-1}``, ``x'_0 = 0``;
* ``q~ = x Wq`` [T, H, d]; ``k~ = x Wk`` [T, G, d]; ``v = [x Wv_1 | x'
  Wv_2]`` [T, G, d]: the first half of the value heads from the token, the
  second half from the token before it (``Wv = [Wv_1 | Wv_2]``);
* ``u = [q~ | k~]``; ``c1_t = a0 * u_t + a1 * u_{t-1} + b1`` (depthwise:
  ``a0 = conv0_w[K-1]``, ``a1 = conv0_w[K-2]``); ``c2_t[j] = A0[j] c1_t[j]
  + A1[j] c1_{t-1}[j] + b2[j]`` for each of the ``H + G`` heads ``j``
  (``A.[j]`` [d, d], ``c1[j]`` a row vector times it);
* ``q[h] = c2_q[h] + (q~[h] + k~[g(h)]) / 2``; ``k[g] = c2_k[g] + (k~[g]
  + mean_{h in g} q~[h]) / 2``;
* ``q[h] <- q[h] / sqrt(mean(q[h]^2) + eps)`` (length ``sqrt(d)``), ``k[g]``
  alike times ``tau_g``; rotary over the first ``rope_fraction * d`` dims of
  each head (adjacent pairs, frequencies over the rotated width); causal
  softmax of ``q k^T / sqrt(d)`` in the H-over-G grouping; ``Wo``;
  ``h <- h + attn``;
* ``y = RMSNorm(h)``; router: ``r_l = y Wd + bd + gamma_l * r_{l-1}`` (no
  such term in the first layer: ``r_0 = 0``), ``p = softmax(W3 gelu(W2
  gelu(W1 RMSNorm(r_l) + b1) + b2))`` (exact gelu), ``e = argmax p`` (ties
  to the lower index); ``h <- h + p_e FFN_e(y)`` where expert ``e`` is one
  of the contiguous range the layer is given, nothing otherwise. No token
  is dropped, no expert is shared, the gate is ``p_e`` itself.

Final RMSNorm; ``logits = x E^T`` over the table ``E`` the ids were
embedded with; ``L = L_LM``, the next-token cross entropy over the slice.

DEPARTURES (memory only, no arithmetic changed): attention is taken
``Q_ROWS`` query rows at a time, everything of a layer that works on one
position alone (the expert layer's norm, the router, the experts) ``ROWS``
positions at a time, the head's loss ``LOSS_ROWS`` rows at a time, and each
of those, ``qkv``, each expert's FFN and each layer is rematerialised in
the backward pass. Left out, as the configuration's file
lists: the depth-skipping expert, learned scales on the residual merge,
router balancing biases; the experts held elsewhere add nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.reference import HIGHEST, matmul, rms_norm, rope

Q_ROWS = 128
ROWS = 4096
LOSS_ROWS = 1024
LAYER_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "conv0_w",
                "conv0_b", "conv1_w", "conv1_b", "tau", "r_down", "r_bdown",
                "r_gamma", "r_norm", "r_w1", "r_b1", "r_w2", "r_b2", "r_w3",
                "w_gate", "w_up", "w_down")


def before(a):
    """``a [T, ...]`` one step later, zeros at step 0."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def unit(a, eps):
    """Each last-axis vector at length sqrt(its width)."""
    return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)


def rope_part(a, pos, theta, fraction):
    rot = int(a.shape[-1] * fraction)
    return jnp.concatenate([rope(a[..., :rot], pos, theta), a[..., rot:]], -1)


def qkv(x, lw, cfg):
    """(q [T, H, d], k [T, G, d], v [T, G, d]) ready for the softmax."""
    t = x.shape[0]
    h, g, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q_raw, k_raw = matmul(x, lw["wq"]), matmul(x, lw["wk"])
    now = (g - g // 2) * d
    v = jnp.concatenate([matmul(x, lw["wv"][:, :now]),
                         matmul(before(x), lw["wv"][:, now:])], -1)
    u = jnp.concatenate([q_raw, k_raw], -1)
    taps0, taps1 = lw["conv0_w"], lw["conv1_w"]
    c1, u_at = lw["conv0_b"], u
    for j in range(taps0.shape[0]):          # tap K-1-j: j steps back
        c1 = c1 + taps0[taps0.shape[0] - 1 - j] * u_at
        u_at = before(u_at)
    c2, c_at = lw["conv1_b"].reshape(h + g, d), c1.reshape(t, h + g, d)
    for j in range(taps1.shape[0]):
        c2 = c2 + jnp.einsum("tjd,jde->tje", c_at,
                             taps1[taps1.shape[0] - 1 - j], precision=HIGHEST)
        c_at = before(c_at)
    qt, kt = q_raw.reshape(t, h, d), k_raw.reshape(t, g, d)
    q = c2[:, :h] + (qt + jnp.repeat(kt, h // g, axis=1)) / 2
    k = c2[:, h:] + (kt + qt.reshape(t, g, h // g, d).mean(axis=2)) / 2
    q = unit(q, cfg["eps"])
    k = unit(k, cfg["eps"]) * lw["tau"][:, None]
    pos = jnp.arange(t)
    turn = lambda a: rope_part(a, pos, cfg["rope_theta"],
                               cfg["rope_fraction"])
    return turn(q), turn(k), v.reshape(t, g, d)


def attention_rows(rows, q, k, v, cfg):
    """The attention output of the query rows at positions ``rows`` [r],
    ``q [r, H, d]``, against every key."""
    h, g, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    kk, vv = (jnp.repeat(a, h // g, axis=1) for a in (k, v))
    s = jnp.einsum("rhd,khd->hrk", q, kk, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    seen = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hrk,khd->rhd", p, vv,
                      precision=HIGHEST).reshape(-1, h * d)


def attention(x, lw, cfg):
    t = x.shape[0]
    q, k, v = jax.checkpoint(lambda x, lw: qkv(x, lw, cfg))(x, lw)
    r = min(Q_ROWS, t)
    assert t % r == 0, (t, r)
    block = jax.checkpoint(lambda a: attention_rows(*a, k, v, cfg))
    out = jax.lax.map(block, (jnp.arange(t).reshape(-1, r),
                              q.reshape(-1, r, *q.shape[1:])))
    return matmul(out.reshape(t, -1), lw["wo"])


def router(y, r_prev, lw, cfg):
    """(p [T, experts], r [T, router_hidden])."""
    r = matmul(y, lw["r_down"]) + lw["r_bdown"] + lw["r_gamma"] * r_prev
    z = rms_norm(r, lw["r_norm"], cfg["eps"])
    z = jax.nn.gelu(matmul(z, lw["r_w1"]) + lw["r_b1"], approximate=False)
    z = jax.nn.gelu(matmul(z, lw["r_w2"]) + lw["r_b2"], approximate=False)
    return jax.nn.softmax(matmul(z, lw["r_w3"]), axis=-1), r


def experts(y, p, lw, cfg, held=None, offset=None):
    """The part of the expert layer that the experts ``[offset, offset +
    held)`` give (the configuration's own range by default): each token's
    most probable expert, gated by its probability."""
    held = cfg["experts_held"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    chosen = jnp.argmax(p, axis=-1)
    gate = jnp.take_along_axis(p, chosen[:, None], axis=-1)[:, 0]

    @jax.checkpoint                              # DEPARTURE (memory only)
    def gated(y, gate, w_gate, w_up, w_down):
        return gate[:, None] * reference.swiglu(y, w_gate, w_up, w_down)

    out = jnp.zeros_like(y)
    for e in range(held):
        out = out + gated(y, jnp.where(chosen == offset + e, gate, 0.0),
                          lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e])
    return out


def expert_half(x, r_prev, lw, cfg):
    """(x + its experts' part, the router state), ``ROWS`` positions at a
    time: nothing here looks at another position."""
    def rows(args):
        xb, rb = args
        y = rms_norm(xb, lw["mlp_norm"], cfg["eps"])
        p, r = router(y, rb, lw, cfg)
        return xb + experts(y, p, lw, cfg), r

    t = x.shape[0]
    n = min(ROWS, t)
    assert t % n == 0, (t, n)
    out, r = jax.lax.map(jax.checkpoint(rows), (
        x.reshape(-1, n, x.shape[1]), r_prev.reshape(-1, n, r_prev.shape[1])))
    return out.reshape(x.shape), r.reshape(r_prev.shape)


def layer(x, r_prev, lw, cfg):
    x = x + attention(rms_norm(x, lw["attn_norm"], cfg["eps"]), lw, cfg)
    return expert_half(x, r_prev, lw, cfg)


def hidden(w, tokens, cfg):
    """One sequence ``tokens`` [T] -> the final norm's output. DEPARTURE:
    ids index a slice of the published table."""
    x = w["embed"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def step(carry, lw):
        return layer(*carry, lw, cfg), None

    # The layers one after the other over the stacked leaves (a scan: its
    # backward writes each layer's gradient into the stacked leaf in
    # place). The router state before the first layer is zero: its
    # ``gamma * r`` term is absent there.
    r0 = jnp.zeros((tokens.shape[0], cfg["router_hidden"]), jnp.float32)
    (x, _), _ = jax.lax.scan(step, (x, r0), {n: w[n] for n in LAYER_LEAVES})
    return rms_norm(x, w["final_norm"], cfg["eps"])


def row_loss(w, tokens, cfg):
    """L_LM of one sequence, the logits against the tied table."""
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
        logp = jax.nn.log_softmax(matmul(hb, w["embed"].T), -1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0]
                        * wb)
    return jnp.sum(jax.lax.map(block, (hp, lp, wt))) / r


def rows_held(w, batch, cfg):
    """[layers] int: the tokens of ``batch`` [b, T] whose expert is one of
    the held range, layer by layer (what a dropless layer's ``stats``
    count at the same weights)."""
    lo, hi = cfg["expert_offset"], cfg["expert_offset"] + cfg["experts_held"]

    def one(tokens):
        def step(carry, lw):
            x, r_prev = carry
            x = x + attention(rms_norm(x, lw["attn_norm"], cfg["eps"]),
                              lw, cfg)
            y = rms_norm(x, lw["mlp_norm"], cfg["eps"])
            p, r = router(y, r_prev, lw, cfg)
            chosen = jnp.argmax(p, axis=-1)
            held = jnp.sum((chosen >= lo) & (chosen < hi))
            return (x + experts(y, p, lw, cfg), r), held

        x = w["embed"].astype(jnp.float32)[tokens]
        r0 = jnp.zeros((tokens.shape[0], cfg["router_hidden"]), jnp.float32)
        return jax.lax.scan(step, (x, r0),
                            {n: w[n] for n in LAYER_LEAVES})[1]
    return jnp.sum(jax.lax.map(one, batch), axis=0)


def loss(w, batch, cfg):
    """Mean next-token cross entropy over ``batch`` [b, T], row by row."""
    one = jax.checkpoint(lambda row: row_loss(w, row, cfg))
    return jnp.mean(jax.lax.map(one, batch))


def train_steps(w, batches, cfg, lr):
    """``reference.train_steps`` with this module's objective: the first
    ``len(batches)`` AdamW steps from float32 weights ``w`` (consumed) ->
    the losses, the per-leaf norms of the first gradient, the weights
    after the last step."""
    grad = jax.jit(jax.value_and_grad(lambda w, b: loss(w, b, cfg)))
    update = jax.jit(lambda w, gs: reference.adamw(w, gs, lr),
                     donate_argnums=0)
    losses, grads, gnorms = [], [], None
    for b in batches:
        l, g = grad(w, b)
        if gnorms is None:
            gnorms = jax.jit(reference.leaf_norms)(g)
        grads.append(g)
        w = update(w, grads)
        losses.append(l)
    return losses, gnorms, w
