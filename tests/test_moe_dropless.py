"""The dropless expert layer (``models.moe.DroplessMoE``) against the
plain reference's expert layer (``benchmark/reference_keyevl2.experts``):
the **share test** — the outputs of the eight held-expert ranges add up to
the uncut layer (nothing in this model is computed by every share alike,
so nothing is counted once) — dropless under skew, and a chunk whose held
rows overflow the buffers sized to the held share (``moe.rows_buffer``):
the further passes, through XLA's grouped call and the kernels' bodies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_keyevl2 as ref
from tony_tpu import profiler
from tony_tpu.models import moe
from tony_tpu.models.moe import DroplessMoE

D, F, E, K, T = 32, 16, 16, 4, 96
CFG = {"top_k": K}
CHUNK_RULE = moe.chunk_tokens       # the fixture below patches the name


def _weights(seed, router_scale=1.0):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),
                               jnp.float32)
    return {"w_router": n(D, E) * router_scale, "w_gate": n(E, D, F),
            "w_up": n(E, D, F), "w_down": n(E, F, D)}


@pytest.fixture(autouse=True)
def three_chunks(monkeypatch):
    monkeypatch.setattr(moe, "chunk_tokens", lambda top_k, n_experts: T // 3)


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
    # of the call, not of a chunk or a pass: the fullest held expert
    assert int(stats["moe_rows_max_expert"][0]) == max(
        int((chosen == e).sum()) for e in range(offset, offset + held))
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


# ------------------------------- more held rows than a pass's buffers

# 32 experts, 4 held, 4 a token, one chunk of 96 tokens: 384 routed rows,
# 48 expected here, buffers of 96 (``rows_buffer``), at most four passes.
E2, HELD2, OFFSET2, ROWS2 = 32, 4, 8, 96
# Rows sent to each held expert by the first chunk (the second chunk
# always gets 20, 10, 5, 5: one pass) -> passes the first chunk runs.
OVERFLOW = {
    "within": ((20, 10, 5, 5), 1),
    "exactly_a_buffer": ((50, 46, 0, 0), 1),
    "a_buffer_and_one_row": ((50, 47, 0, 0), 2),
    "two_passes": ((60, 60, 30, 0), 2),
    "an_expert_straddles_the_edge": ((90, 12, 0, 0), 2),
    "three_passes": ((90, 96, 56, 8), 3),
    "every_row_held": ((96, 96, 96, 96), 4),
}


def _routed(per_expert, seed):
    """``x [96, 32]`` whose tokens' four experts are known: held expert
    ``j`` for the first ``per_expert[j]`` tokens, experts 0.. for the
    rest of a token's four (the router is eight times the identity, so a
    token's logits are its own entries)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(96, E2)) * 0.05
    for t in range(96):
        mine = [OFFSET2 + j for j, n in enumerate(per_expert) if t < n]
        chosen = mine + list(range(K - len(mine)))
        x[t, chosen] += 1.0 + 0.1 * rng.permutation(K)
    return x


@pytest.fixture
def overflowing(request, monkeypatch):
    monkeypatch.setattr(moe, "chunk_tokens", lambda top_k, n_experts: 96)
    per_expert, passes = OVERFLOW[request.param]
    x = jnp.asarray(np.concatenate([_routed(per_expert, 7),
                                    _routed((20, 10, 5, 5), 8)]),
                    jnp.float32)
    rng = np.random.default_rng(9)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),
                               jnp.float32)
    w = {"w_router": 8.0 * jnp.eye(E2, dtype=jnp.float32),
         "w_gate": n(HELD2, E2, F), "w_up": n(HELD2, E2, F),
         "w_down": n(HELD2, F, E2)}
    return x, w, per_expert, passes


def _overflow_checks(x, w, per_expert, passes):
    assert moe.rows_buffer(96, K, HELD2, E2) == ROWS2
    ends = np.cumsum(per_expert)
    assert -(-int(ends[-1]) // ROWS2) == passes     # what the case says
    if per_expert in ((90, 12, 0, 0), (90, 96, 56, 8)):
        # an expert's rows lie on both sides of every edge between passes
        assert all(any(a < ROWS2 * p < b for a, b in zip(
            ends - per_expert, ends)) for p in range(1, passes))

    def prog(x, w):
        layer = DroplessMoE(E2, F, E2, top_k=K, experts_held=HELD2,
                            expert_offset=OFFSET2, dtype=jnp.float32)
        y, sown = layer.apply({"params": w}, x[None], mutable="stats")
        return jnp.sum(jnp.sin(y)), (y[0], sown["stats"])

    def plain(x, w):
        y = ref.experts(x, w, CFG, HELD2, OFFSET2)
        return jnp.sum(jnp.sin(y)), y

    (_, (y, stats)), got = jax.value_and_grad(prog, (0, 1), has_aux=True)(
        x, w)
    (_, y_want), want = jax.value_and_grad(plain, (0, 1), has_aux=True)(x, w)
    assert np.allclose(y, y_want, atol=1e-5)
    assert np.allclose(got[0], want[0], atol=1e-5)
    for n in w:
        assert np.allclose(got[1][n], want[1][n], atol=1e-5), n
    assert int(stats["moe_passes_run"][0]) == passes + 1
    assert int(stats["moe_rows_held"][0]) == sum(per_expert) + 40
    assert int(stats["moe_rows_max_expert"][0]) == max(
        a + b for a, b in zip(per_expert, (20, 10, 5, 5)))
    assert int(stats["moe_groups_fed"][0]) == sum(
        n > 0 for n in per_expert) + 4


@pytest.mark.parametrize("overflowing", sorted(OVERFLOW), indirect=True)
def test_an_overflowing_chunk_takes_further_passes(overflowing):
    """Value and every gradient against the reference's share, and the
    passes that ran: an expert whose rows straddle a pass's edge is two
    groups in two calls whose weight gradients add."""
    _overflow_checks(*overflowing)


@pytest.mark.parametrize("overflowing", sorted(OVERFLOW), indirect=True)
def test_an_overflowing_chunk_through_the_kernels(overflowing, monkeypatch):
    """The same through the kernels' own bodies, with what they leave
    unwritten — rows past a pass's last group, in the result and in
    ``lhs``'s cotangent — poisoned on both sides of every call: each pass
    has to name its own zeros."""
    from tony_tpu.ops import gmm

    def poisoned_matmul(lhs, rhs, sizes):
        live = (jnp.arange(lhs.shape[0]) < sizes.sum())[:, None]

        @jax.custom_vjp
        def unwritten(a):
            return jnp.where(live, a, jnp.nan)

        unwritten.defvjp(lambda a: (unwritten(a), None),
                         lambda _, g: (jnp.where(live, g, jnp.nan),))
        return unwritten(gmm.grouped_matmul(unwritten(lhs), rhs, sizes,
                                            interpret=True))

    monkeypatch.setattr(moe, "grouped_matmul", poisoned_matmul)
    _overflow_checks(*overflowing)


def test_every_expert_held_is_one_pass_over_every_row(layer_inputs):
    x, w = layer_inputs
    chunk = moe.chunk_tokens(K, E)
    assert moe.rows_buffer(chunk, K, E, E) == chunk * K
    assert moe.rows_buffer(1024, 8, 128, 128) == 8192
    _, stats = _share(x, w, 0, 0)
    assert int(stats["moe_passes_run"][0]) == T // chunk
    assert int(stats["moe_rows_held"][0]) == T * K


def _shapes_in(jaxpr):
    """Every array shape a jaxpr computes, sub-jaxprs (loops, remat,
    custom derivatives) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes_in(sub)


def test_no_worst_case_buffer_at_the_keye_shapes(monkeypatch):
    """Trace only, nothing computed: at 16384 tokens of width 2048, 16 of
    128 experts held, 8 a token (a chunk of 1024 by ``moe.chunk_tokens``),
    no array of ``chunk * top_k`` rows by the model or the expert width is
    in the layer's forward or backward, and a pass's buffers are 2048
    rows."""
    monkeypatch.setattr(moe, "chunk_tokens", CHUNK_RULE)
    d, f, chunk_rows = 2048, 768, 1024 * 8
    layer = DroplessMoE(d, f, 128, top_k=8, experts_held=16)
    x = jax.ShapeDtypeStruct((1, 16384, d), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]

    def loss(params, x):
        y, sown = layer.apply({"params": params}, x, mutable="stats")
        return y.astype(jnp.float32).sum(), sown

    profiler.reset_timeline()
    shapes = set(_shapes_in(jax.make_jaxpr(
        jax.grad(loss, (0, 1), has_aux=True))(params, x).jaxpr))
    assert (2048, d) in shapes and (2048, f) in shapes
    wide = [s for s in shapes if chunk_rows in s and (d in s or f in s)]
    assert not wide, wide
    counters = profiler.counters()
    assert counters["moe:rows_buffer"] == 2048
    assert counters["moe:passes_max"] == 4
    assert counters["moe:chunks"] == 16
