"""The plain reference of the ``phi-4-mini-flash-reasoning`` configuration:
forward pass, next-token loss, gradients and AdamW in straightforward
``jax.numpy``. float32 throughout, matmuls at ``highest`` precision, the
scan as ``lax.scan`` over time, attention by explicit masked softmax. No
kernel, nothing of ``tony_tpu``; the optimizer and the norms are
``benchmark/reference.py``'s.

The model (SambaY decoder-hybrid-decoder, arXiv:2507.06607; published keys
hidden 2560, FFN 10240, 40 heads / 20 K/V heads of 64, window 512,
LayerNorm eps 1e-5, tied embedding, no positional encoding)
---------------------------------------------------------------------------
Layer i, pre-norm: ``x <- x + mixer_i(LN(x)); x <- x + MLP(LN(x))``, LN =
LayerNorm with scale and bias. ``MLP(u) = (up * silu(gate)) W2`` with
``[gate, up] = u W1`` (no bias). The mixer by kind (published order: even
i <= 16 ``mamba``; odd i <= 15 ``swa``; i = 17 ``full``; even i >= 18
``gmu``; odd i >= 19 ``cross``; here the configuration's ``layer_kinds``):

* ``mamba`` (Mamba-1; E = 2d, N = 16, conv 4, R = ceil(d/16)):
  ``[xs, z] = u W_in``; ``xc = silu(conv4_causal_depthwise(xs) + b_c)``;
  ``[r, B, C] = xc W_x``; ``D_t = softplus(r W_dt + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(D_t * A) * h_{t-1} + (D_t * xc_t) (x)
  B_t``, ``h_0 = 0``; ``y_t = h_t C_t + Dskip * xc_t``; the memory
  ``m = y`` (before the gate); ``out = (y * silu(z)) W_out``.
* ``gmu``: ``out = (m * silu(u W_g)) W_o``, m the latest ``mamba``'s, at
  the same token.
* ``swa`` / ``full``: ``[q, k, v] = u W_qkv + b``; heads (2p, 2p+1) are
  pair p (20 query pairs, 10 K/V pairs, two query pairs to one K/V pair);
  with ``v = [v_1, v_2]`` (128 wide)
  ``a = softmax(q_1 k_1^T / 8 + M) v - lam * softmax(q_2 k_2^T / 8 + M) v``,
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  ``lam0 = 0.8 - 0.6 exp(-0.3 i)``;
  ``a <- RMSNorm_128(a) * (1 - lam0)``; concatenate the pairs,
  ``out = a W_o + b_o``. M is the causal mask, for ``swa`` also -inf where
  ``t_q - t_k >= window``. ``full`` keeps its k, v.
* ``cross``: ``q = u W_q + b`` only; k, v are the ``full`` layer's; the
  same differential form, causal.
* Final LN, ``logits = h E^T`` with E the (sliced) embedding table;
  next-token cross entropy.

Departures from the published description, each marked ``DEPARTURE`` below:
the depth index i of ``lam0`` is the layer's index in the cut stack (the
published model has 32 layers, this one 6); the vocabulary is a slice;
memory-only liberties that change no arithmetic (time blocks of the scan,
pairs of the attention and row blocks of the loss are rematerialised).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.reference import HIGHEST, matmul

SCAN_BLOCK = 128      # time steps rematerialised together
LOSS_ROWS = 1024      # rows of the logits held at once


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def mlp(u, w1, w2):
    gate, up = jnp.split(matmul(u, w1), 2, axis=-1)    # gate first
    return matmul(up * jax.nn.silu(gate), w2)


def causal_conv(xs, w, b):
    """Depthwise over time: out[t] = sum_j w[j] * xs[t - (K-1) + j] + b."""
    k, t = w.shape[0], xs.shape[0]
    xp = jnp.pad(xs, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[j] for j in range(k)) + b


def selective_scan(xc, dt, a, bm, cm, d_skip):
    """``xc``, ``dt`` [T, E]; ``a`` [E, N]; ``bm``, ``cm`` [T, N]. The
    state is held as [N, E]."""
    t = xc.shape[0]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[None, :] * a.T) * h \
            + b_t[:, None] * (dt_t * x_t)[None, :]
        return h, jnp.sum(h * c_t[:, None], 0) + d_skip * x_t

    # DEPARTURE (memory only): time blocks are rematerialised in the
    # backward pass, so T states are never held at once.
    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    pad = (-t) % SCAN_BLOCK
    seqs = [jnp.pad(v, ((0, pad), (0, 0))).reshape(
        -1, SCAN_BLOCK, v.shape[1]) for v in (xc, dt, bm, cm)]
    _, y = jax.lax.scan(block, jnp.zeros(a.T.shape, jnp.float32),
                        tuple(seqs))
    return y.reshape(-1, xc.shape[1])[:t]


def mamba(u, lw, cfg):
    n, r = cfg["ssm_state"], cfg["dt_rank"]
    xs, z = jnp.split(matmul(u, lw["in_proj"]), 2, axis=-1)
    xc = jax.nn.silu(causal_conv(xs, lw["conv_w"], lw["conv_b"]))
    rbc = matmul(xc, lw["x_proj"])
    low, bm, cm = rbc[:, :r], rbc[:, r:r + n], rbc[:, r + n:]
    dt = jax.nn.softplus(matmul(low, lw["dt_w"]) + lw["dt_b"])
    y = selective_scan(xc, dt, -jnp.exp(lw["a_log"]), bm, cm, lw["d_skip"])
    return matmul(y * jax.nn.silu(z), lw["out_proj"]), y


def gmu(u, m, lw):
    return matmul(m * jax.nn.silu(matmul(u, lw["w_gate"])), lw["w_out"])


def diff_attention(q, k, v, lw, index, window, cfg):
    """``q`` [T, heads, hd]; ``k``, ``v`` [T, kv_heads, hd]."""
    t, h, hd = q.shape
    pairs, kv_pairs = h // 2, k.shape[1] // 2
    pos = jnp.arange(t)
    gap = pos[:, None] - pos[None, :]
    mask = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    # DEPARTURE: i is the index in the cut stack, not in the 32 layers.
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(lw["lq1"] * lw["lk1"])) \
        - jnp.exp(jnp.sum(lw["lq2"] * lw["lk2"])) + lam0

    def softmax_v(qh, kh, vv):
        s = jnp.matmul(qh, kh.T, precision=HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.matmul(p, vv, precision=HIGHEST)

    # DEPARTURE (memory only): one pair's two score maps at a time,
    # rematerialised in the backward pass.
    @jax.checkpoint
    def pair(args):
        q2, k2, v2 = args                     # [T, 2, hd] each
        vv = v2.reshape(t, 2 * hd)
        a = softmax_v(q2[:, 0], k2[:, 0], vv) \
            - lam * softmax_v(q2[:, 1], k2[:, 1], vv)
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)
                              + cfg["eps"]) * lw["subln"]
        return a * (1.0 - lam0)

    qp = jnp.moveaxis(q.reshape(t, pairs, 2, hd), 1, 0)
    share = pairs // kv_pairs                 # query pairs per K/V pair
    kp = jnp.repeat(jnp.moveaxis(k.reshape(t, kv_pairs, 2, hd), 1, 0),
                    share, axis=0)
    vp = jnp.repeat(jnp.moveaxis(v.reshape(t, kv_pairs, 2, hd), 1, 0),
                    share, axis=0)
    a = jax.lax.map(pair, (qp, kp, vp))       # [pairs, T, 2 hd]
    out = jnp.moveaxis(a, 0, 1).reshape(t, h * hd)
    return matmul(out, lw["wo"]) + lw["bo"]


def attention(u, lw, kind, index, cfg, kv=None):
    t = u.shape[0]
    h, kvh, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    if kind == "cross":
        q = matmul(u, lw["wq"]) + lw["bq"]
        k, v = kv
    else:
        qkv = matmul(u, lw["wqkv"]) \
            + jnp.concatenate([lw["bq"], lw["bk"], lw["bv"]])
        q, k, v = jnp.split(qkv, (h * hd, (h + kvh) * hd), axis=-1)
    out = diff_attention(
        q.reshape(t, h, hd), k.reshape(t, kvh, hd), v.reshape(t, kvh, hd),
        lw, index, cfg["window"] if kind == "swa" else None, cfg)
    return out, (k, v)


def layer(x, streams, lw, kind, index, cfg):
    """One layer on one sequence; returns x and the streams later layers
    read (``m``; ``kv``)."""
    u = layer_norm(x, lw["norm1.scale"], lw["norm1.bias"], cfg["eps"])
    if kind == "mamba":
        out, m = mamba(u, lw, cfg)
        streams = dict(streams, m=m)
    elif kind == "gmu":
        out = gmu(u, streams["m"], lw)
    else:
        out, kv = attention(u, lw, kind, index, cfg, streams.get("kv"))
        if kind == "full":
            streams = dict(streams, kv=kv)
    x = x + out
    u = layer_norm(x, lw["norm2.scale"], lw["norm2.bias"], cfg["eps"])
    return x + mlp(u, lw["w1"], lw["w2"]), streams


def layer_weights(w: dict, i: int) -> dict:
    prefix = f"L{i}."
    return {n[len(prefix):]: a for n, a in w.items() if n.startswith(prefix)}


def hidden(w, tokens, cfg):
    """One sequence ``tokens`` [T] -> the final LayerNorm's output [T, d].
    DEPARTURE: ids index a slice of the published table."""
    x = w["embed"].astype(jnp.float32)[tokens]
    streams: dict = {}
    for i, kind in enumerate(cfg["kinds"]):
        step = jax.checkpoint(
            lambda x, s, lw, kind=kind, i=i: layer(x, s, lw, kind, i, cfg))
        x, streams = step(x, streams, layer_weights(w, i))
    return layer_norm(x, w["final_norm.scale"], w["final_norm.bias"],
                      cfg["eps"])


def logits(w, tokens, cfg):
    return matmul(hidden(w, tokens, cfg), w["embed"].T)    # tied head


def row_loss(w, tokens, cfg):
    """Mean next-token cross entropy of one sequence. DEPARTURE (memory
    only): LOSS_ROWS rows of the logits at a time."""
    h = hidden(w, tokens, cfg)[:-1]
    labels = tokens[1:]
    r = h.shape[0]
    pad = (-r) % LOSS_ROWS
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, LOSS_ROWS, h.shape[1])
    lp = jnp.pad(labels, (0, pad)).reshape(-1, LOSS_ROWS)
    wt = jnp.pad(jnp.ones((r,), jnp.float32), (0, pad)).reshape(
        -1, LOSS_ROWS)

    @jax.checkpoint
    def block(args):
        hb, lb, wb = args
        logp = jax.nn.log_softmax(matmul(hb, w["embed"].T), -1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0]
                        * wb)
    return jnp.sum(jax.lax.map(block, (hp, lp, wt))) / r


def loss(w, batch, cfg):
    """Mean over ``batch`` [b, T], row by row."""
    one = jax.checkpoint(lambda row: row_loss(w, row, cfg))
    return jnp.mean(jax.lax.map(one, batch))


def train_steps(w, batches, cfg, lr):
    """``reference.train_steps`` with this module's loss: the first
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
