"""The plain reference: the dense SwiGLU decoder, its next-token loss,
gradients and AdamW, in straightforward
``jax.numpy``. float32 throughout, matmuls at ``highest`` precision (on a
TPU a float32 matmul is otherwise several bfloat16 passes short), no
kernels, no cache, no batching tricks. It imports nothing of the program.

The only liberties are memory ones that change no arithmetic: the loss is
taken row by row and each layer is rematerialised in the backward pass, so
that the float32 state of a 0.7 B-parameter model and its activations fit
one chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(x, w):
    """x [..., k] @ w [k, n] in float32 at ``highest``."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x [s, h, d]; rotates the pairs (x[2i], x[2i+1]) by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(x, lw, cfg):
    s = x.shape[0]
    h, kvh, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    pos = jnp.arange(s)
    q = rope(matmul(x, lw["wq"]).reshape(s, h, hd), pos, cfg["rope_theta"])
    k = rope(matmul(x, lw["wk"]).reshape(s, kvh, hd), pos, cfg["rope_theta"])
    v = matmul(x, lw["wv"]).reshape(s, kvh, hd)
    k, v = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                     precision=HIGHEST)
    return matmul(out.reshape(s, h * hd), lw["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return matmul(jax.nn.silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


LAYER_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
                "w_gate", "w_up", "w_down")


def layer(x, lw, cfg):
    x = x + attention(rms_norm(x, lw["attn_norm"], cfg["eps"]), lw, cfg)
    y = rms_norm(x, lw["mlp_norm"], cfg["eps"])
    return x + swiglu(y, lw["w_gate"], lw["w_up"], lw["w_down"])


def forward_row(w, tokens, cfg):
    """One sequence ``tokens`` [s] -> logits [s, vocab]."""
    x = w["embed"].astype(jnp.float32)[tokens]
    step = jax.checkpoint(lambda x, lw: layer(x, lw, cfg))
    for i in range(cfg["layers"]):
        x = step(x, {n: w[n][i] for n in LAYER_LEAVES})
    return matmul(rms_norm(x, w["final_norm"], cfg["eps"]), w["lm_head"])


def row_loss(w, tokens, cfg):
    logp = jax.nn.log_softmax(forward_row(w, tokens, cfg)[:-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


def loss(w, batch, cfg):
    """Mean next-token cross entropy over ``batch`` [b, s], row by row."""
    one = jax.checkpoint(lambda row: row_loss(w, row, cfg))
    return jnp.mean(jax.lax.map(one, batch))


def adamw(w, grads, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4):
    """Step ``len(grads)`` of decoupled-weight-decay Adam (Loshchilov &
    Hutter; decay on every leaf) from the gradients of every step so far:
    the moments are their geometric sums, m_t = (1-b1) sum b1^(t-i) g_i.
    Holding the few gradients the check follows is smaller than holding
    weights, both moments and a gradient at once."""
    t = len(grads)

    def leaf(w, *gs):
        m = sum((1 - b1) * b1 ** (t - i) * g for i, g in enumerate(gs, 1))
        v = sum((1 - b2) * b2 ** (t - i) * g * g for i, g in enumerate(gs, 1))
        return w - lr * ((m / (1 - b1 ** t))
                         / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                         + weight_decay * w)
    return jax.tree.map(leaf, w, *grads)


def train_steps(w, batches, cfg, lr):
    """Follow the first ``len(batches)`` AdamW steps from float32 weights
    ``w`` (consumed). Returns the per-step losses, the per-leaf norms of
    the first gradient, and the weights after the last step. Gradient and
    update are separate programs, so that the gradient's temporaries and
    the update's never share the chip."""
    grad = jax.jit(jax.value_and_grad(lambda w, b: loss(w, b, cfg)))
    update = jax.jit(lambda w, gs: adamw(w, gs, lr), donate_argnums=0)
    losses, grads, gnorms = [], [], None
    for b in batches:
        l, g = grad(w, b)
        if gnorms is None:
            gnorms = jax.jit(leaf_norms)(g)
        grads.append(g)
        w = update(w, grads)
        losses.append(l)
    return losses, gnorms, w


def leaf_norms(tree: dict) -> dict:
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for n, a in tree.items()}


def change_norms(w_new: dict, w_old: dict) -> dict:
    return leaf_norms({n: w_new[n].astype(jnp.float32)
                       - w_old[n].astype(jnp.float32) for n in w_new})
