"""The ``window`` argument of the flash entry points: the windowed kernels
(Pallas bodies under ``interpret=True``; streamed and resident, classic and
packed layouts; forward and all three gradients) against the masked
reference at windows below, equal to and above the block size, the count of
K/V blocks they visit, and ``window=None`` as exactly today's program.

Tolerance 1e-4 absolute on O(1) outputs and O(10) gradients: float32
inputs, the kernel's online softmax against a one-pass softmax."""

import unittest.mock as mock

import jax
import jax.numpy as jnp
import pytest

from tony_tpu.ops import attention as A
from tony_tpu.ops import (flash_attention, flash_attention_packed,
                          reference_attention)

T, BLOCK = 128, 32
WINDOWS = {"below_block": 16, "equal_block": 32, "above_block": 48,
           "off_block": 70}


def qkv(h, hkv, d, t=T):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(ks[0], (1, h, t, d)),
            jax.random.normal(ks[1], (1, hkv, t, d)),
            jax.random.normal(ks[2], (1, hkv, t, d)),
            jax.random.normal(ks[3], (1, h, t, d)))


def run(window, packed, resident, bq=BLOCK, bk=BLOCK):
    h, hkv = 4, 2
    d = 128 if packed else 16
    q, k, v, w = qkv(h, hkv, d)

    def ref(q, k, v):
        return (reference_attention(q, k, v, True, None, window) * w).sum()

    if packed:
        to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(1, T, -1)

        def fl(q, k, v):
            o = flash_attention_packed(
                to3(q), to3(k), to3(v), h, block_q=bq, block_k=bk,
                interpret=True, window=window)
            return (o.reshape(1, T, h, d).transpose(0, 2, 1, 3) * w).sum()
    else:
        def fl(q, k, v):
            return (flash_attention(q, k, v, block_q=bq, block_k=bk,
                                    interpret=True, window=window) * w).sum()
    fits = 1 << 30 if resident else 0
    with mock.patch.object(A, "_RESIDENT_KV_BYTES", fits):
        got = (fl(q, k, v), *jax.grad(fl, (0, 1, 2))(q, k, v))
    want = (ref(q, k, v), *jax.grad(ref, (0, 1, 2))(q, k, v))
    return [float(jnp.max(jnp.abs(g - r))) for g, r in zip(got, want)]


@pytest.mark.parametrize("resident", [False, True],
                         ids=["streamed", "resident"])
@pytest.mark.parametrize("packed", [False, True], ids=["classic", "packed"])
@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_windowed_kernels_match_masked_reference(name, packed, resident):
    errs = run(WINDOWS[name], packed, resident)
    assert max(errs) < 1e-4, dict(zip(("loss", "dq", "dk", "dv"), errs))


def test_windowed_kernels_with_unequal_blocks():
    assert max(run(40, False, False, bq=32, bk=16)) < 1e-4
    assert max(run(70, True, False, bq=16, bk=32)) < 1e-4


@pytest.mark.parametrize("window,visited", [
    (16, 7), (32, 7), (33, 7), (34, 9), (48, 9), (65, 9), (66, 10),
    (None, 10)])
def test_visited_block_count(window, visited):
    """T = 128 in blocks of 32: 4 x 4 = 16 blocks; the causal triangle
    keeps 10; a window keeps the diagonal and what its first query reaches
    back to (window 33 still ends inside the block before, 34 does not)."""
    assert A.kv_blocks(T, T, BLOCK, BLOCK, True, window) == (visited, 16)


def test_window_shrinks_the_streamed_grid():
    """The streamed grids' inner axes hold only the visible span: blocks
    left of the window are not scheduled at all (not fetched, not
    computed)."""
    assert A._window_spans(32, 32, 256, 256, 512) == (3, 3)     # the cell
    assert A._window_spans(4, 4, 32, 32, 16) == (2, 2)
    assert A._window_spans(4, 4, 32, 32, 33) == (2, 2)
    assert A._window_spans(4, 4, 32, 32, 34) == (3, 3)
    nk, kmap = A._visible_k(32, 32, 256, 256, True, 512)
    assert nk == 3 and int(kmap(10, 0)) == 8 and int(kmap(10, 2)) == 10
    # q-block 0 sees its own block alone: steps 1 and 2 are skipped and
    # the map stands on block 0 (before PR 46 it went on to 1 and 2)
    assert int(kmap(0, 2)) == 0 and int(kmap(31, 2)) == 31
    assert A._visible_k(32, 32, 256, 256, True, None)[0] == 32
    # at the cell's shape, blocks of 256: 93 of 1024 blocks against the
    # causal 528; at the rule's blocks (512 under the window, 1024
    # without): 31 of 256 against 36 of 64
    assert A.kv_blocks(8192, 8192, 256, 256, window=512) == (93, 1024)
    assert A.kv_blocks(8192, 8192, 256, 256) == (528, 1024)
    assert A.kv_blocks(8192, 8192, window=512) == (31, 256)
    assert A.kv_blocks(8192, 8192) == (36, 64)


def test_window_reaching_the_first_key_is_no_window():
    q, k, v, _ = qkv(2, 2, 16)
    base = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    for window in (T, T + 5):
        out = flash_attention(q, k, v, block_q=32, block_k=32,
                              interpret=True, window=window)
        assert jnp.array_equal(out, base)


def test_window_needs_causal_self_attention():
    q, k, v, _ = qkv(2, 2, 16)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, interpret=True, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k[:, :, :64], v[:, :, :64], interpret=True,
                        window=8)


def _mistral_grad_jaxpr(**kw):
    b, t, h, hkv, d = 4, 2048, 32, 8, 128
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    g = jax.grad(lambda q, k, v: flash_attention_packed(
        q, k, v, h, causal=True, interpret=False, **kw).astype(
            jnp.float32).sum(), (0, 1, 2))
    return str(jax.make_jaxpr(g)(s(b, t, h * d), s(b, t, hkv * d),
                                 s(b, t, hkv * d)))


def test_window_none_is_todays_program():
    """``window=None`` traces to the program without the argument: on the
    jaxpr (kernel bodies, grids and index maps included) of the Mistral
    cell's packed forward + backward, passing ``window=None`` equals
    leaving it out, a window reaching the first key is no window, and a
    real window is another program. Until PR 28 this also pinned the
    sha256 of the parent's text; PR 28 changes that program by design (the
    blocks now follow from the shape, 512 here, and the six bodies share
    three tile expressions), so the recorded hash went with it."""
    text = _mistral_grad_jaxpr()
    assert text == _mistral_grad_jaxpr(window=None)
    assert text == _mistral_grad_jaxpr(window=2048)
    assert text != _mistral_grad_jaxpr(window=512)
    assert text != _mistral_grad_jaxpr(block_q=256, block_k=256)
