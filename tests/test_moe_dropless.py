"""The dropless expert layer (``models.moe.DroplessMoE``) against the
plain reference's expert layer (``benchmark/reference_keyevl2.experts``):
the **share test** — the outputs of the eight held-expert ranges add up to
the uncut layer (nothing in this model is computed by every share alike,
so nothing is counted once) — and dropless under skew."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_keyevl2 as ref
from tony_tpu.models import moe
from tony_tpu.models.moe import DroplessMoE

D, F, E, K, T = 32, 16, 16, 4, 96
CFG = {"top_k": K}


def _weights(seed, router_scale=1.0):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),
                               jnp.float32)
    return {"w_router": n(D, E) * router_scale, "w_gate": n(E, D, F),
            "w_up": n(E, D, F), "w_down": n(E, F, D)}


@pytest.fixture(autouse=True)
def three_chunks(monkeypatch):
    monkeypatch.setattr(moe, "CHUNK", T // 3)


def _share(x, w, held, offset):
    layer = DroplessMoE(D, F, E, top_k=K, experts_held=held,
                        expert_offset=offset, dtype=jnp.float32)
    params = {"w_router": w["w_router"],
              **{n: w[n][offset:offset + (held or E)]
                 for n in ("w_gate", "w_up", "w_down")}}
    y, sown = layer.apply({"params": params}, x[None], mutable="stats")
    return y[0], sown["stats"]


def _want(x, w, held, offset):
    lw = {"w_router": w["w_router"],
          **{n: w[n][offset:offset + held]
             for n in ("w_gate", "w_up", "w_down")}}
    return ref.experts(x, lw, CFG, held, offset)


@pytest.fixture(scope="module")
def layer_inputs():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(T, D)), jnp.float32)
    return x, _weights(1)


@pytest.mark.parametrize("offset", range(0, E, 2))
def test_a_share_gives_its_own_experts_part(layer_inputs, offset):
    x, w = layer_inputs
    got, _ = _share(x, w, 2, offset)
    want = _want(x, w, 2, offset)
    assert float(jnp.abs(want).max()) > 0
    assert np.allclose(got, want, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer(layer_inputs):
    x, w = layer_inputs
    parts = sum(_share(x, w, 2, offset)[0] for offset in range(0, E, 2))
    whole = _want(x, w, E, 0)
    assert np.allclose(parts, whole, atol=1e-5)
    assert np.allclose(_share(x, w, 0, 0)[0], whole, atol=1e-5)   # 0 = all


@pytest.mark.parametrize("case", ["one_expert_takes_most_rows", "none_held",
                                  "all_held"])
def test_no_token_is_dropped_under_skew(case):
    x = jnp.asarray(np.random.default_rng(2).normal(size=(T, D)),
                    jnp.float32) + 1.0
    w = _weights(3)
    # Every token's first choice is expert 5 (a router column along the
    # inputs' common direction), far beyond any capacity factor.
    w["w_router"] = w["w_router"].at[:, 5].set(2.0)
    # ... and nobody's any choice is expert 10 or 11.
    w["w_router"] = w["w_router"].at[:, 10:12].set(-2.0)
    held, offset = {"one_expert_takes_most_rows": (2, 4),
                    "none_held": (2, 10), "all_held": (E, 0)}[case]
    chosen = jax.lax.top_k(jax.nn.softmax(x @ w["w_router"]), K)[1]
    mine = (chosen >= offset) & (chosen < offset + held)
    got, stats = _share(x, w, held, offset)
    assert np.allclose(got, _want(x, w, held, offset), atol=1e-5)
    assert int(stats["moe_rows_held"][0]) == int(mine.sum())
    # (chunk, held expert) pairs that got a row: what the grouped kernels'
    # time can follow once an expert starves
    fed = sum(len(set(np.asarray(c[m]).tolist())) for c, m in zip(
        chosen.reshape(3, -1), np.asarray(mine).reshape(3, -1)))
    assert int(stats["moe_groups_fed"][0]) == fed
    if case == "one_expert_takes_most_rows":
        assert int(stats["moe_rows_max_expert"][0]) == T    # all of them
    if case == "none_held":
        assert int(mine.sum()) == 0 and float(jnp.abs(got).max()) == 0.0
    if case == "all_held":
        assert int(mine.sum()) == T * K


def test_gradients_match_the_reference_share(layer_inputs):
    x, w = layer_inputs
    held, offset = 4, 8
    names = ("w_gate", "w_up", "w_down")

    def prog(x, w):
        layer = DroplessMoE(D, F, E, top_k=K, experts_held=held,
                            expert_offset=offset, dtype=jnp.float32)
        return jnp.sum(jnp.sin(layer.apply({"params": w}, x[None])))

    def plain(x, w):
        return jnp.sum(jnp.sin(ref.experts(x, w, CFG, held, offset)))

    lw = {"w_router": w["w_router"],
          **{n: w[n][offset:offset + held] for n in names}}
    got, want = jax.grad(prog, (0, 1))(x, lw), jax.grad(plain, (0, 1))(x, lw)
    assert np.allclose(got[0], want[0], atol=1e-5)
    for n in ("w_router", *names):
        assert np.allclose(got[1][n], want[1][n], atol=1e-5), n


# ------------------------------------------------ the grouped kernels

GROUPS = {
    "ragged": [10, 0, 100, 3, 50],
    "no_rows": [0, 0, 0, 0, 0],
    "one_group_takes_all": [256, 0, 0, 0, 0],
    "only_the_last": [0, 0, 0, 0, 200],
    "whole_tiles": [64, 64, 64, 32, 32],
    "one_row_each": [1, 1, 1, 1, 1],
}


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_the_grouped_kernels_against_ragged_dot(case):
    """``ops.gmm``'s three kernel bodies (``interpret=True``): the result,
    ``lhs``'s cotangent (the transposed kernel) and ``rhs``'s (the
    weight-gradient kernel, groups of no rows written as zeros); rows past
    the last group are named zero on both sides, as the layer names
    them."""
    from tony_tpu.ops.gmm import grouped_matmul

    m, k, n = 256, 32, 48
    rng = np.random.default_rng(5)
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(GROUPS[case]), k, n)),
                      jnp.float32)
    live = (jnp.arange(m) < sizes.sum())[:, None]

    def run(matmul):
        def loss(lhs, rhs):
            out = matmul(jnp.where(live, lhs, 0), rhs, sizes)
            return jnp.sum(jnp.sin(jnp.where(live, out, 0)))
        return jax.value_and_grad(loss, (0, 1))(lhs, rhs)

    got = run(lambda *a: grouped_matmul(*a, interpret=True))
    want = run(jax.lax.ragged_dot)
    assert np.allclose(got[0], want[0], atol=1e-4)
    assert np.allclose(got[1][0], want[1][0], atol=1e-4)
    assert np.allclose(got[1][1], want[1][1], atol=1e-4)
    assert np.all(np.isfinite(got[1][1]))


def test_the_grid_walks_the_groups():
    """What makes a call cost its rows and its groups, never its buffer:
    the steps counted from ``sizes``, each step's (group, tile) pair, a
    group of no rows one step (a call costs the same whether an expert got
    a few rows or none)."""
    from tony_tpu.ops.gmm import group_visits

    sizes = jnp.asarray([10, 0, 100, 3, 50], jnp.int32)   # tiles of 64
    _, _, group, tile, steps = group_visits(sizes, 256, 64)
    assert int(steps) == 7
    assert list(zip(group[:7].tolist(), tile[:7].tolist())) == [
        (0, 0), (1, 0), (2, 0), (2, 1), (3, 1), (4, 1), (4, 2)]
    assert group.shape == (256 // 64 + 5 - 1,)
    # the worst cases fit the bound: every group straddles a tile edge;
    # no rows at all; all rows in one group
    for worst in ([63, 64, 64, 64, 1], [0] * 5, [256, 0, 0, 0, 0],
                  [0, 0, 0, 0, 256]):
        n = int(group_visits(jnp.asarray(worst, jnp.int32), 256, 64)[4])
        assert 5 <= n <= 8, worst


def test_the_layer_through_the_kernels(layer_inputs, monkeypatch):
    import functools

    from tony_tpu.ops import gmm

    monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
        gmm.grouped_matmul, interpret=True))
    x, w = layer_inputs
    assert np.allclose(_share(x, w, 4, 8)[0], _want(x, w, 4, 8), atol=1e-5)
