"""The plain reference of the ``glm-4.7-flash`` configuration (GLM-4.7-Flash,
``glm4_moe_lite``: DeepSeek-V3's keys, arXiv:2412.19437; GLM-4.5's report,
arXiv:2508.06471): the forward pass, both losses, gradients and AdamW in
straightforward ``jax.numpy``. float32 throughout, matmuls at ``highest``
precision; the latent attention as a masked softmax over each head's
256-wide rotated query and key, the router and the experts dense over the
held range; no kernels, no sorted dispatch, no cache. It imports nothing
of the program.

A block, for ``h [T, D]`` (pre-norm residual halves, RMSNorm eps 1e-5):
``h += MLA(RMSNorm(h)); h += FFN(RMSNorm(h))``; final RMSNorm; untied head.

* **MLA** (hidden 2048, 20 heads, ``q_lora_rank`` 768, ``kv_lora_rank``
  512, ``qk_nope_head_dim`` 192, ``qk_rope_head_dim`` 64, ``v_head_dim``
  256, no bias): ``c_q = RMSNorm_768(x W_qa)``; ``[q_h^n, q_h^r] = c_q
  W_qb`` (768 -> 20 x (192 + 64)); ``[c, k^r] = x W_kva`` (2048 -> 512 +
  64); ``[k_h^n, v_h] = RMSNorm_512(c) W_kvb`` (512 -> 20 x (192 + 256));
  ``q_h = [q_h^n, R_t q_h^r]``, ``k_h = [k_h^n, R_t k^r]`` with ``k^r``
  one row shared by the heads, not normed, ``R_t`` the rotation of
  position t over the 64 columns at ``rope_theta`` 1e6
  (``partial_rotary_factor`` 1: all 64; ``rope_scaling`` null); causal
  ``softmax(q_h . k_h / sqrt(256)) v_h``; ``W_o`` (20 x 256 -> 2048).
  Expanded for training, not absorbed.
* **FFN.** Layer 0 (``first_k_dense_replace`` 1): SwiGLU of 10240. Every
  other block: ``s = sigmoid(x W_r)`` over 64 experts in float32; chosen
  = the 4 largest of ``s + b`` (``topk_method`` ``noaux_tc``, ``n_group``
  1, ``topk_group`` 1; ties to the lower index; ``b`` enters nothing else
  and gets no gradient); ``gate_e = 1.8 * s_e / sum of the chosen s``
  (``norm_topk_prob``, ``routed_scaling_factor``); ``y = sum over chosen e
  of gate_e FFN_e(x) + FFN_shared(x)``, SwiGLU of 1536 each.
* **MTP** (``num_nextn_predict_layers`` 1; DeepSeek-V3 section 2.2): with
  ``h_i`` the last block's output **before** the final norm and ``t_i``
  the tokens, ``h'_i = [RMSNorm(Emb(t_{i+1})); RMSNorm(h_i)] W_eh`` (4096
  -> 2048, the embedding's half first), one more block of the model's own
  kind (MLA + experts, its own weights, positions as the main stack's),
  ``logits'_i = Head(RMSNorm(block(h')_i))`` against ``t_{i+2}``. ``Emb``
  and ``Head`` are the main model's: one table, one head, two uses each.

``L = L_LM + 0.3 L_MTP``, each a mean over its own positions (T - 1 and
T - 2).

DEPARTURES from the published model, each listed under ``assumed`` in the
configuration's file with its reason: the rotation pairs NEIGHBOURING
columns (a fixed permutation of ``W_qb``'s and ``W_kva``'s columns under
seeded weights); the selection bias ``b`` is held constant (its balancing
update is a recipe the config does not state); the weight 0.3, the
sharing of table and head and the order of the two halves are the
reports', not keys of the config; the MTP block runs over all T rows, the
last embedding the row's FIRST token in place of the one it lacks (no
earlier row sees it, the last two rows carry no loss); ids index a slice
of the published table; the experts held elsewhere add nothing, as the
configuration's file says. DEPARTURES in memory only, no arithmetic
changed: attention is taken ``Q_ROWS`` query rows at a time, the
feed-forwards ``ROWS`` positions at a time, a head's loss ``LOSS_ROWS``
rows at a time, and each of those, each expert's FFN and each block is
rematerialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.reference import HIGHEST, matmul, rms_norm
# The sigmoid router with its selection bias, the held experts' part and
# the feed-forward half ``ROWS`` positions at a time: the same plain forms
# as the Kimi Linear configuration's (``route_sigmoid``'s semantics).
from benchmark.reference_kimilinear import experts, ffn_half, route  # noqa: F401

Q_ROWS = 128
LOSS_ROWS = 1024


def attend(rows, q, key, v):
    """The attention output of the query rows at positions ``rows`` [r],
    ``q [r, H, 256]``, against every key ``[T, H, 256]``."""
    s = jnp.einsum("rhd,khd->hrk", q, key, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    seen = jnp.arange(key.shape[0])[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hrk,khd->rhd", p, v,
                      precision=HIGHEST).reshape(rows.shape[0], -1)


def mla(x, lw, cfg):
    t = x.shape[0]
    h, r, dn, ds, dv = (cfg[n] for n in
                        ("heads", "kv_rank", "nope", "rope", "v_dim"))
    pos = jnp.arange(t)

    @jax.checkpoint
    def qkv(x, lw):
        c_q = rms_norm(matmul(x, lw["wq_a"]), lw["q_norm"], cfg["eps"])
        q = matmul(c_q, lw["wq_b"]).reshape(t, h, dn + ds)
        q = jnp.concatenate(
            [q[..., :dn], reference.rope(q[..., dn:], pos, cfg["theta"])], -1)
        kva = matmul(x, lw["wkv_a"])
        kv = matmul(rms_norm(kva[:, :r], lw["kv_norm"], cfg["eps"]),
                    lw["wkv_b"]).reshape(t, h, dn + dv)
        k_r = reference.rope(kva[:, None, r:], pos, cfg["theta"])
        key = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (t, h, ds))], -1)
        return q, key, kv[..., dn:]

    q, key, v = qkv(x, lw)
    n = min(Q_ROWS, t)
    assert t % n == 0, (t, n)
    block = jax.checkpoint(lambda a: attend(*a, key, v))
    out = jax.lax.map(block, (pos.reshape(-1, n),
                              q.reshape(-1, n, *q.shape[1:])))
    return matmul(out.reshape(t, -1), lw["wo"])


def attn_half(x, lw, cfg):
    return x + mla(rms_norm(x, lw["norm1"], cfg["eps"]), lw, cfg)


def block(x, lw, ffn, cfg):
    return ffn_half(attn_half(x, lw, cfg), lw, ffn, cfg)


def leaves(w, prefix):
    return {n[len(prefix) + 1:]: a for n, a in w.items()
            if n.startswith(prefix + ".")}


def mtp_input(w, x, tokens, cfg):
    """``h'``: the join of the next token's embedding and the stack's
    output ``x`` (before the final norm) through ``W_eh``."""
    lw = leaves(w, "M")
    ahead = w["embed"].astype(jnp.float32)[jnp.roll(tokens, -1)]
    return matmul(jnp.concatenate(
        [rms_norm(ahead, lw["e_norm"], cfg["eps"]),
         rms_norm(x, lw["h_norm"], cfg["eps"])], -1), lw["w_eh"])


def heads_read(w, x, tokens, cfg):
    """The stack's output ``x`` (before the final norm) -> what the head
    reads for each loss: the final norm's output and (None without the
    module) the multi-token-prediction module's."""
    second = None
    if cfg["mtp"]:
        lw = leaves(w, "M")
        second = jax.checkpoint(
            lambda x, lw: block(x, lw, cfg["ffns"][-1], cfg))(
                mtp_input(w, x, tokens, cfg), lw)
        second = rms_norm(second, lw["final_norm"], cfg["eps"])
    return rms_norm(x, w["final_norm"], cfg["eps"]), second


def stack(w, tokens, cfg):
    """One sequence ``tokens`` [T] -> the last block's output, before the
    final norm. DEPARTURE: ids index a slice of the published table."""
    x = w["embed"].astype(jnp.float32)[tokens]
    for i, ffn in enumerate(cfg["ffns"]):
        x = jax.checkpoint(lambda x, lw, ffn=ffn: block(x, lw, ffn, cfg))(
            x, leaves(w, f"L{i}"))
    return x


def logits(w, tokens, cfg):
    """``(logits, logits')`` of one sequence, through the one head."""
    first, second = heads_read(w, stack(w, tokens, cfg), tokens, cfg)
    return matmul(first, w["lm_head"]), \
        None if second is None else matmul(second, w["lm_head"])


def head_loss(w, h, labels):
    """Mean cross entropy of the rows ``h`` against ``labels`` through the
    untied head, ``LOSS_ROWS`` rows at a time."""
    r = h.shape[0]
    rows = min(LOSS_ROWS, r)
    pad = (-r) % rows
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1])
    lp = jnp.pad(labels, (0, pad)).reshape(-1, rows)
    wt = jnp.pad(jnp.ones((r,), jnp.float32), (0, pad)).reshape(-1, rows)

    @jax.checkpoint
    def part(args):
        hb, lb, wb = args
        logp = jax.nn.log_softmax(matmul(hb, w["lm_head"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0]
                        * wb)
    return jnp.sum(jax.lax.map(part, (hp, lp, wt))) / r


def top_losses(w, x, tokens, cfg):
    """``(L_LM, L_MTP)`` of one sequence from the stack's output ``x``:
    row i against token i + 1, the module's row i against token i + 2 (0
    without the module)."""
    first, second = heads_read(w, x, tokens, cfg)
    lm = head_loss(w, first[:-1], tokens[1:])
    if second is None:
        return lm, jnp.float32(0.0)
    return lm, head_loss(w, second[:-2], tokens[2:])


def row_losses(w, tokens, cfg):
    return top_losses(w, stack(w, tokens, cfg), tokens, cfg)


def rows_held(w, batch, cfg):
    """[expert layers] int: the (token, choice) pairs of ``batch`` [b, T]
    whose expert is one of the held range, layer by layer, the
    multi-token-prediction module's last (what a dropless layer's
    ``stats`` count at the same weights)."""
    lo, hi = cfg["expert_offset"], cfg["expert_offset"] + cfg["experts_held"]

    def count(x, lw):
        chosen, _ = route(rms_norm(attn_half(x, lw, cfg), lw["norm2"],
                                   cfg["eps"]), lw, cfg)
        return jnp.sum((chosen >= lo) & (chosen < hi))

    def one(tokens):
        x, counts = w["embed"].astype(jnp.float32)[tokens], []
        for i, ffn in enumerate(cfg["ffns"]):
            lw = leaves(w, f"L{i}")
            if ffn == "experts":
                counts.append(count(x, lw))
            x = block(x, lw, ffn, cfg)
        if cfg["mtp"] and cfg["ffns"][-1] == "experts":
            counts.append(count(mtp_input(w, x, tokens, cfg),
                                leaves(w, "M")))
        return jnp.stack(counts)
    return jnp.sum(jax.lax.map(one, batch), axis=0)


def losses(w, batch, cfg):
    """``(L_LM, L_MTP)``, each a mean over ``batch`` [b, T], row by row
    (DEPARTURE, memory only: of several rows each is rematerialised)."""
    one = lambda row: row_losses(w, row, cfg)
    if batch.shape[0] > 1:
        one = jax.checkpoint(one)
    lm, mtp = jax.lax.map(one, batch)
    return jnp.mean(lm), jnp.mean(mtp)


def loss(w, batch, cfg):
    """``(L, (L_LM, L_MTP))`` with ``L = L_LM + mtp_weight L_MTP``."""
    lm, mtp = losses(w, batch, cfg)
    return lm + cfg["mtp_weight"] * mtp, (lm, mtp)


_BLOCK = jax.jit(block, static_argnums=(2, 3))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block_back(x, lw, dy, ffn, cfg):
    return jax.vjp(lambda x, lw: block(x, lw, ffn, cfg), x, lw)[1](dy)


@functools.partial(jax.jit, static_argnums=3)
def _top(w_top, x, tokens, cfg):
    """``((L, (L_LM, L_MTP)), (dL/dw_top, dL/dx))`` of one sequence from
    the stack's output: the final norm, the head twice and the whole
    multi-token-prediction module."""
    def total(w_top, x):
        lm, mtp = top_losses(w_top, x, tokens, cfg)
        return lm + cfg["mtp_weight"] * mtp, (lm, mtp)
    return jax.value_and_grad(total, (0, 1), has_aux=True)(w_top, x)


def value_and_grad(w, batch, cfg):
    """``jax.value_and_grad(loss, has_aux=True)(w, batch, cfg)``, the chain
    rule walked by hand: a program a block forward, one for the top (final
    norm, both passes of the head, the module), a program a block
    backward, the table's two gradients added. DEPARTURE (memory only): as
    ONE program the 16k gradient holds 2.3 GiB a block at once (16.7 GiB
    for the chip's 15.75, described v5e: each block's key, value and query
    copies for its two attention loops), whatever is rematerialised;
    tests/test_glm47flash.py holds this to autodiff of :func:`loss`."""
    cfg = _Frozen(cfg)
    names = ("embed", "lm_head", "final_norm")
    w_top = {n: a for n, a in w.items() if n in names or n.startswith("M.")}
    grads, lms, mtps, scale = {}, [], [], 1.0 / batch.shape[0]

    def add(name, g):
        grads[name] = scale * g + grads[name] if name in grads else scale * g
    for tokens in batch:
        xs = [w["embed"].astype(jnp.float32)[tokens]]
        for i, ffn in enumerate(cfg["ffns"]):
            xs.append(_BLOCK(xs[-1], leaves(w, f"L{i}"), ffn, cfg))
        (_, (lm, mtp)), (g_top, dx) = _top(w_top, xs.pop(), tokens, cfg)
        lms.append(lm)
        mtps.append(mtp)
        for n, g in g_top.items():
            add(n, g)
        for i, ffn in reversed(list(enumerate(cfg["ffns"]))):
            dx, dlw = _block_back(xs.pop(), leaves(w, f"L{i}"), dx, ffn, cfg)
            for n, g in dlw.items():
                add(f"L{i}.{n}", g)
        grads["embed"] = grads["embed"].at[tokens].add(scale * dx)
    lm, mtp = jnp.mean(jnp.stack(lms)), jnp.mean(jnp.stack(mtps))
    return (lm + cfg["mtp_weight"] * mtp, (lm, mtp)), grads


class _Frozen(dict):
    """The configuration as a static argument of a jitted program."""
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def train_steps(w, batches, cfg, lr):
    """``reference.train_steps`` with this module's objective: the first
    ``len(batches)`` AdamW steps from float32 weights ``w`` (consumed) ->
    the ``(L_LM, L_MTP)`` of each step, the per-leaf norms of the first
    gradient, the weights after the last step. The gradient is
    :func:`value_and_grad`'s. DEPARTURE (memory only): between two steps
    the gradients so far wait on the host."""
    grad = lambda w, b: value_and_grad(w, b, cfg)
    update = jax.jit(lambda w, gs: reference.adamw(w, gs, lr),
                     donate_argnums=0)
    pairs, grads, gnorms = [], [], None
    for b in batches:
        (_, (lm, mtp)), g = grad(w, b)
        if gnorms is None:
            gnorms = jax.jit(reference.leaf_norms)(g)
        w = update(w, grads + [g])
        grads.append(jax.device_get(g))
        del g                       # the next gradient needs its room
        pairs.append((float(lm), float(mtp)))
    return pairs, gnorms, w
