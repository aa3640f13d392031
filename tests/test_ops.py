"""Pallas-kernel tests (interpret mode on CPU) against the pure-JAX
reference — the kernel-correctness tier of the compute plane."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import attention as att
from tony_tpu.ops import flash_attention, reference_attention


def rand_qkv(b=2, h=3, t=64, d=16, dtype=jnp.float32, tk=None):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    tk = t if tk is None else tk
    return (jax.random.normal(ks[0], (b, h, t, d), dtype),
            jax.random.normal(ks[1], (b, h, tk, d), dtype),
            jax.random.normal(ks[2], (b, h, tk, d), dtype))


# Explicit blocks (several to a side at these lengths) beside the rule's
# own choice (``None``: one block at these lengths; the multi-block rule
# paths have their own cases below).
BLOCKS = pytest.mark.parametrize("block", [16, None],
                                 ids=["blocks16", "rule"])


@BLOCKS
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal, block):
    q, k, v = rand_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_kernel_uneven_blocks():
    # Causal self-attention with T not divisible by ANY tile-legal block
    # (t=40 isn't a multiple of 16) takes the zero-pad kernel path —
    # still exact.
    q, k, v = rand_qkv(t=40)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_block_shrinks_to_dividing_size(causal):
    # T divisible by 16 but not by the requested block must shrink the
    # block (96 @ limit 64 → 48) and run the kernel unpadded — no
    # fallback warning even non-causal (the t=384-at-default-256 case).
    import warnings

    from tony_tpu.ops import attention as att

    assert att._fit_block(64, 96) == 48
    q, k, v = rand_qkv(t=96)
    att._warned.clear()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
    assert not caught
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _assert_kernel_matches_reference(q, k, v, causal, block=32):
    """Values AND grads through the kernel path, with NO fallback warning
    — the block-shape regression guard (the chip's compiler refused the
    first kernel's unaligned blocks, and ragged/odd shapes then silently
    materialized the T×T reference score matrix)."""
    import warnings

    from tony_tpu.ops import attention as att

    att._warned.clear()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = flash_attention(q, k, v, causal=causal, block_q=block,
                              block_k=block, interpret=True)
    assert not caught
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    w = jax.random.normal(jax.random.PRNGKey(17), q.shape)
    g_f = jax.jit(jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block,
        interpret=True) * w).sum(), (0, 1, 2)))(q, k, v)
    g_r = jax.jit(jax.grad(lambda q, k, v: (reference_attention(
        q, k, v, causal=causal) * w).sum(), (0, 1, 2)))(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


RULE_OR_32 = pytest.mark.parametrize("block", [32, None],
                                     ids=["blocks32", "rule"])


@RULE_OR_32
def test_flash_ragged_noncausal_pads_and_masks(block):
    # Non-causal ragged shapes used to fall back to the reference (end-
    # padded keys would soak up softmax mass); now the kernels mask the
    # padded keys via the static kv_len and stay on the kernel path.
    q, k, v = rand_qkv(t=48, tk=40)
    _assert_kernel_matches_reference(q, k, v, causal=False, block=block)


@RULE_OR_32
@pytest.mark.parametrize("causal", [True, False])
def test_flash_cross_lengths_run_kernel(causal, block):
    # Cross-attention lengths (t != tk, neither dividing the blocks).
    q, k, v = rand_qkv(b=1, h=2, t=40, d=16, tk=24)
    _assert_kernel_matches_reference(q, k, v, causal=causal, block=block)


@RULE_OR_32
@pytest.mark.parametrize("streamed", [False, True])
def test_flash_ragged_streamed_kernels(streamed, block):
    # The same mask through the streamed-KV kernel family.
    from tony_tpu.ops import attention as att

    old = att._RESIDENT_KV_BYTES
    att._RESIDENT_KV_BYTES = 0 if streamed else old
    try:
        q, k, v = rand_qkv(b=1, h=2, t=40, tk=24, d=16)
        _assert_kernel_matches_reference(q, k, v, causal=False, block=block)
    finally:
        att._RESIDENT_KV_BYTES = old


@pytest.mark.parametrize("causal,streamed", [(True, False), (True, True),
                                             (False, False)])
def test_rule_blocks_several_to_a_side(causal, streamed):
    """The rule's choice where it is more than one block a side, values
    and gradients: t = 1030 is ragged, pads to the next lane multiple
    (1152) and runs three blocks of 384 a side — resident, and streamed
    (where the rule's bound is 1024: two blocks of 576)."""
    from tony_tpu.ops import attention as att

    old = att._RESIDENT_KV_BYTES
    att._RESIDENT_KV_BYTES = 0 if streamed else old
    try:
        want = 576 if streamed else 384
        assert att._plan_dispatch(1030, 1030, None, None, causal, None, 16,
                                  4)[1].dkv == (want, want)
        q, k, v = rand_qkv(b=1, h=1, t=1030, d=16)
        _assert_kernel_matches_reference(q, k, v, causal=causal, block=None)
    finally:
        att._RESIDENT_KV_BYTES = old


@pytest.mark.parametrize("shape,want", [
    # (t, tk, causal, window, head size, item size) -> plan, (bq, bk), pad
    ((2048, 2048, True, None, 128, 2), ("kernel", (512, 512), None)),
    ((512, 512, True, None, 128, 2), ("kernel", (512, 512), None)),
    ((4096, 4096, True, None, 128, 2), ("kernel", (512, 512), None)),
    ((8192, 8192, True, None, 128, 2), ("kernel", (1024, 1024), None)),
    ((8192, 8192, True, 512, 128, 2), ("kernel", (512, 512), None)),
    ((8192, 8192, True, 100, 128, 2), ("kernel", (512, 512), None)),
    ((8192, 8192, True, 2000, 128, 2), ("kernel", (1024, 1024), None)),
    ((2048, 2048, True, 512, 128, 2), ("kernel", (512, 512), None)),
    ((8192, 8192, True, None, 128, 4), ("kernel", (512, 512), None)),
    ((8192, 8192, True, None, 256, 2), ("kernel", (512, 512), None)),
    ((16384, 16384, True, None, 64, 2), ("kernel", (1024, 1024), None)),
    ((384, 384, True, None, 128, 2), ("kernel", (384, 384), None)),
    ((640, 640, False, None, 128, 2), ("kernel", (320, 320), None)),
    ((2048, 512, False, None, 128, 2), ("kernel", (512, 512), None)),
    ((1000, 1000, True, None, 128, 2), ("pad", (512, 512), 1024)),
    ((1030, 1030, True, None, 128, 2), ("pad", (384, 384), 1152)),
    ((100, 100, True, None, 128, 2), ("pad", (112, 112), 112)),
    ((8, 8, True, None, 128, 2), ("pad", (16, 16), 16)),
    ((1000, 777, False, None, 128, 2),
     ("pad_masked", (512, 448), (1024, 896, 777))),
    ((40, 24, True, None, 16, 4), ("pad_masked", (48, 32), (48, 32, 24))),
])
def test_rule_picks_blocks_from_the_shape(shape, want):
    """``block_q=None``: the blocks follow from the call's shapes — 512 a
    side with K/V resident, 1024 streamed, never wider than a window (or
    512), halved for rows wider than 128 x bf16, fitted to the (padded)
    lengths — and all three kernels divide the lengths they run."""
    from tony_tpu.ops import attention as att

    t, tk, causal, window, d, itemsize = shape
    plan, blocks, extra = att._plan_dispatch(t, tk, None, None, causal,
                                             window, d, itemsize)
    assert (plan, blocks.fwd, extra) == want
    t_run, tk_run = (extra[:2] if isinstance(extra, tuple)
                     else (extra or t, extra or tk))
    for bq, bk in blocks:
        assert t_run % bq == 0 and tk_run % bk == 0
        assert bq % 16 == 0 and bk % 16 == 0
    facts = att.block_facts(t, tk, None, None, causal, window, d, itemsize)
    assert (facts["block_q.dkv"], facts["block_k.dkv"]) == blocks.dkv
    assert facts["kv_blocks_visited"] <= facts["kv_blocks_total"] == \
        (t_run // blocks.fwd[0]) * (tk_run // blocks.fwd[1])


def test_explicit_blocks_are_honoured_and_must_come_in_pairs():
    from tony_tpu.ops import attention as att

    assert att._plan_dispatch(2048, 2048, 256, 128, True) == (
        "kernel", att.Blocks(*[(256, 128)] * 3), None)
    assert att._plan_dispatch(40, 40, 16, 16, True)[1:] == (
        att.Blocks(*[(16, 16)] * 3), 48)
    with pytest.raises(ValueError, match="both or neither"):
        att._plan_dispatch(2048, 2048, 256, None, True)


@RULE_OR_32
@pytest.mark.parametrize("d", [20, 12])
def test_flash_odd_head_dim_runs_kernel(d, block):
    # head_dim off the 8-row sublane tile: zero-padded feature dim, still
    # the kernel path — values and grads exact, output dtype/shape kept.
    q, k, v = rand_qkv(b=1, h=2, t=32, d=d)
    _assert_kernel_matches_reference(q, k, v, causal=True, block=block)
    q, k, v = rand_qkv(b=1, h=2, t=40, tk=24, d=d)
    _assert_kernel_matches_reference(q, k, v, causal=False, block=block)


def test_flash_kernel_bf16():
    q, k, v = rand_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = reference_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@BLOCKS
@pytest.mark.parametrize("causal", [True, False])
def test_flash_grad_matches_reference_grad(causal, block):
    q, k, v = rand_qkv(b=1, h=2, t=32, d=8)
    # Non-uniform cotangent so dq/dk/dv all get exercised asymmetrically.
    w = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 32, 8))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=block,
                                block_k=block, interpret=True) * w).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) * w).sum()

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@BLOCKS
def test_flash_padded_grad_matches_reference(block):
    # Causal self-attention with T not divisible by the blocks takes the
    # zero-pad path (not the reference fallback); grads must stay exact
    # including the pad-slice boundary.
    q, k, v = rand_qkv(b=1, h=2, t=40, d=8)
    w = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 40, 8))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=block,
                                block_k=block, interpret=True) * w).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) * w).sum()

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_flash_sharded_matches_reference():
    # The shard_map wrapper (batch on dp, heads on tp) must agree with the
    # unsharded reference on an 8-device mesh.
    from tony_tpu.ops import flash_attention_sharded
    from tony_tpu.parallel import make_mesh

    mesh = make_mesh(dp=2, sp=2, tp=2)
    q, k, v = rand_qkv(b=4, h=8, t=32, d=8)
    out = jax.jit(
        lambda q, k, v: flash_attention_sharded(
            q, k, v, mesh, block_q=16, block_k=16, interpret=True))(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_cpu_dispatch_uses_reference():
    # On the CPU backend with no interpret flag, dispatch must not try to
    # compile a TPU kernel.
    q, k, v = rand_qkv(t=32)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_packed_matches_classic(causal):
    # The packed [B, T, H*D] entry must agree with the classic layout on
    # values AND grads (interpret mode; d=128 for lane alignment).
    b, h, t, d = 2, 2, 64, 128
    q, k, v = rand_qkv(b=b, h=h, t=t, d=d)
    from tony_tpu.ops import flash_attention_packed

    pack = lambda x: x.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    def loss_packed(q, k, v):
        return flash_attention_packed(
            pack(q), pack(k), pack(v), h, causal=causal, block_q=16,
            block_k=16, interpret=True).sum()

    def loss_classic(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=16, interpret=True).sum()

    np.testing.assert_allclose(float(loss_packed(q, k, v)),
                               float(loss_classic(q, k, v)), rtol=1e-4)
    gp = jax.jit(jax.grad(loss_packed, (0, 1, 2)))(q, k, v)
    gc = jax.jit(jax.grad(loss_classic, (0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_flash_packed_bad_head_dim_falls_back():
    # head_dim not lane-aligned: warn + unpacked fallback, still correct.
    import warnings

    from tony_tpu.ops import attention as att
    from tony_tpu.ops import flash_attention_packed

    b, h, t, d = 2, 3, 32, 16
    q, k, v = rand_qkv(b=b, h=h, t=t, d=d)
    pack = lambda x: x.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    att._warned.clear()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = flash_attention_packed(pack(q), pack(k), pack(v), h,
                                     block_q=16, block_k=16, interpret=True)
    assert any("head_dim" in str(w.message) for w in caught)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out.reshape(b, t, h, d).transpose(0, 2, 1, 3)),
        np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_streamed_kv_matches_reference(causal):
    """Long-context path: force the streamed-KV kernels (k-block grid axis
    + VMEM scratch accumulators) by shrinking the resident threshold, and
    check values AND grads against the reference."""
    from tony_tpu.ops import attention as att

    old = att._RESIDENT_KV_BYTES
    att._RESIDENT_KV_BYTES = 0   # every shape takes the streamed kernels
    try:
        q, k, v = rand_qkv(b=1, h=2, t=64, d=16)
        w = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 64, 16))

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=causal, block_q=16,
                                    block_k=16, interpret=True) * w).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=causal) * w).sum()

        np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                   float(loss_ref(q, k, v)), rtol=1e-4)
        g_f = jax.jit(jax.grad(loss_flash, (0, 1, 2)))(q, k, v)
        g_r = jax.jit(jax.grad(loss_ref, (0, 1, 2)))(q, k, v)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

        # Packed layout through the streamed kernels too.
        from tony_tpu.ops import flash_attention_packed
        b_, h_, t_, d_ = 1, 2, 32, 128
        q2, k2, v2 = rand_qkv(b=b_, h=h_, t=t_, d=d_)
        pack = lambda x: x.transpose(0, 2, 1, 3).reshape(b_, t_, h_ * d_)
        out_p = flash_attention_packed(pack(q2), pack(k2), pack(v2), h_,
                                       causal=causal, block_q=16,
                                       block_k=16, interpret=True)
        ref2 = reference_attention(q2, k2, v2, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out_p.reshape(b_, t_, h_, d_).transpose(0, 2, 1, 3)),
            np.asarray(ref2), atol=2e-5, rtol=2e-5)
    finally:
        att._RESIDENT_KV_BYTES = old


def rand_gqa(b=1, h=4, hkv=2, t=64, d=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    return (jax.random.normal(ks[0], (b, h, t, d), dtype),
            jax.random.normal(ks[1], (b, hkv, t, d), dtype),
            jax.random.normal(ks[2], (b, hkv, t, d), dtype))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("streamed", [False, True])
def test_flash_gqa_matches_reference(causal, streamed):
    """Zero-copy GQA (VERDICT r4 #5): K/V carry fewer heads than Q and the
    kernels' index maps do the head grouping — values AND all three grads
    must match the repeat-then-attend reference, through both the resident
    and streamed kernel families."""
    from tony_tpu.ops import attention as att

    q, k, v = rand_gqa()
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=16,
                                block_k=16, interpret=True) * w).sum()

    def loss_ref(q, k, v):
        # reference_attention repeats K/V internally — the semantic spec.
        return (reference_attention(q, k, v, causal=causal) * w).sum()

    old = att._RESIDENT_KV_BYTES
    att._RESIDENT_KV_BYTES = 0 if streamed else old
    try:
        np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                   float(loss_ref(q, k, v)), rtol=1e-4)
        g_f = jax.jit(jax.grad(loss_flash, (0, 1, 2)))(q, k, v)
        g_r = jax.jit(jax.grad(loss_ref, (0, 1, 2)))(q, k, v)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)
    finally:
        att._RESIDENT_KV_BYTES = old


@pytest.mark.parametrize("streamed", [False, True])
def test_flash_gqa_packed_matches_reference(streamed):
    """Packed-layout GQA: K/V packed [B, T, Hkv·D]; query head h reads kv
    lane-block h·Hkv/H. Values and grads vs the classic-layout reference."""
    from tony_tpu.ops import attention as att
    from tony_tpu.ops import flash_attention_packed

    b, h, hkv, t, d = 1, 4, 2, 32, 128
    q, k, v = rand_gqa(b=b, h=h, hkv=hkv, t=t, d=d)
    pack = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b, t, x.shape[1] * d)
    w = jax.random.normal(jax.random.PRNGKey(13), (b, t, h * d))

    def loss_packed(qp, kp, vp):
        return (flash_attention_packed(qp, kp, vp, h, causal=True,
                                       block_q=16, block_k=16,
                                       interpret=True) * w).sum()

    def loss_ref(q, k, v):
        out = reference_attention(q, k, v, causal=True)
        return (pack(out) * w).sum()

    old = att._RESIDENT_KV_BYTES
    att._RESIDENT_KV_BYTES = 0 if streamed else old
    try:
        np.testing.assert_allclose(
            float(loss_packed(pack(q), pack(k), pack(v))),
            float(loss_ref(q, k, v)), rtol=1e-4)
        g_p = jax.jit(jax.grad(loss_packed, (0, 1, 2)))(
            pack(q), pack(k), pack(v))
        g_r = jax.jit(jax.grad(loss_ref, (0, 1, 2)))(q, k, v)
        for a, b_ in zip(g_p, (pack(x) for x in g_r)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5, rtol=2e-5)
    finally:
        att._RESIDENT_KV_BYTES = old


def test_flash_gqa_rejects_ragged_heads():
    q, k, v = rand_gqa(h=4, hkv=3)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v, interpret=True)


# -- what the benchmark's readers and remat stand on, in every flash grid ----

def _spec(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _classic_case(t, **kw):
    q, kv = _spec(1, 4, t, 128), _spec(1, 2, t, 128)
    return (lambda q, k, v: att.flash_attention(q, k, v, **kw)), (q, kv, kv)


def _packed_case(t, **kw):
    q, kv = _spec(1, t, 4 * 128), _spec(1, t, 2 * 128)
    return (lambda q, k, v: att.flash_attention_packed(q, k, v, 4, **kw),
            (q, kv, kv))


def _selected_case(t):
    q, kv = _spec(1, t, 4 * 128), _spec(1, t, 2 * 128)
    sel = _spec(1, -(-t // att.SEL_SPAN), t, att.SEL_LANES, dtype=jnp.int32)
    return (lambda q, k, v, sel: att.flash_attention_selected(
        q, k, v, sel, 4)[0], (q, kv, kv, sel))


def _mla_case(t):
    q = _spec(1, t, 4 * 128)
    return (lambda q, k, v, qs, ks: att.flash_attention_mla(
        q, qs, k, ks, v, 4), (q, q, q, _spec(1, 4, t, 64), _spec(1, t, 64)))


# (layout, residency, variant) with a public entry -> (the case at a length
# on that side of ``_resident_fits`` — a function and its arguments, q, k, v
# first — the scopes' ending, a window that length takes). K/V of 1024 x
# 128 x bf16 stay in VMEM, 8192 stream.
GRIDS = {
    "classic-resident-plain": (_classic_case, 1024, "", 256),
    "classic-streamed-plain": (_classic_case, 8192, "", 512),
    "packed-resident-plain": (_packed_case, 1024, "", 256),
    "packed-streamed-plain": (_packed_case, 8192, "", 512),
    "packed-streamed-sel": (_selected_case, 8192, "_sel", None),
    "packed-streamed-mla": (_mla_case, 8192, "_mla", None),
}
PASSES = {"fwd": "attn_fwd", "dq": "attn_bwd_dq", "dkv": "attn_bwd_dkv"}


def _trace(case, monkeypatch):
    """The jaxpr of the case's gradient with the kernels' branch taken, its
    flash calls by scope, and what ``remat.name`` named."""
    fn, args = case
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wrt = tuple(i for i, a in enumerate(args) if a.dtype == jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), wrt))(*args)
    calls, names = {}, {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                scope = re.findall(
                    r"attn_\w+", str(eqn.source_info.name_stack))[-1]
                calls.setdefault(scope, []).append(
                    [(v.aval.shape, v.aval.dtype) for v in eqn.outvars])
            if eqn.primitive.name == "name":
                names[eqn.params["name"]] = eqn.outvars[0].aval
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return str(jaxpr), calls, names, args


@pytest.mark.parametrize("grid,which,windowed", [
    pytest.param(grid, which, windowed,
                 id=f"{grid}-{which}" + "-window" * windowed)
    for grid in sorted(GRIDS) for which in sorted(PASSES)
    for windowed in (False, True) if GRIDS[grid][3] or not windowed])
def test_every_flash_grid_keeps_the_readers_contract(grid, which, windowed,
                                                     monkeypatch):
    """Scope names, the number, order, shapes and dtypes of each call's
    results and the two named residuals are what ``benchmark/readers`` and
    ``remat`` find the flash calls by: one case a (layout, residency,
    variant, pass), with and without a window where the entry takes one."""
    make, t, tag, window = GRIDS[grid]
    kw = {"window": window} if windowed else {}
    text, calls, names, args = _trace(make(t, **kw), monkeypatch)
    ending = ("_win" if windowed else "") + tag
    assert sorted(calls) == sorted(p + ending for p in PASSES.values())
    q, k, v = args[:3]
    classic = grid.startswith("classic")
    rows = lambda a: ((a.shape[0] * a.shape[1], *a.shape[2:]) if classic
                      else a.shape, a.dtype)
    lse = (4, t, 8) if classic else (1, 4, t, 8)
    want = {"fwd": [rows(q), (lse, jnp.float32)], "dq": [rows(q)],
            "dkv": [rows(k), rows(v)]}[which]
    if tag == "_mla":
        qs, ks = args[3:]
        want += {"fwd": [], "dq": [(qs.shape, qs.dtype)],
                 "dkv": [((1, 4, t, ks.shape[2]), jnp.float32)]}[which]
    assert calls[PASSES[which] + ending] == [want]
    assert (names["flash_out"].shape, names["flash_out"].dtype) == (
        q.shape, q.dtype)
    assert (names["flash_lse"].shape, names["flash_lse"].dtype) == (
        lse, jnp.float32)
    if which == "fwd" and not tag and not windowed:
        # a window of None is the program without the argument, a window
        # reaching the first key is no window, a real one another program
        assert text == _trace(make(t, window=None), monkeypatch)[0]
        assert text == _trace(make(t, window=t), monkeypatch)[0]
        assert text != _trace(make(t, window=window), monkeypatch)[0]
