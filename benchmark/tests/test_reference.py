"""The plain reference computes the program's function: with the program
switched to float32 the two agree to rounding; and the reference's AdamW
is optax's."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import modelcfg, reference, weights
from tony_tpu.models import get_model


def test_forward_and_gradient_match_the_program_in_float32():
    cfg = modelcfg.tiny(modelcfg.load("mistral-7b-v0.3"))
    w = weights.make_weights(cfg, 3, jnp.float32)
    model = get_model(cfg["program"]["model"], attention="reference",
                      remat=False, dtype=jnp.float32,
                      **modelcfg.program_kwargs(cfg, 32))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab"], (2, 32))
    params = weights.to_program_tree(w)

    def program_loss(p):
        with jax.default_matmul_precision("highest"):
            logits = model.apply({"params": p}, jnp.asarray(tokens))
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(tokens)[:, 1:, None], -1)), logits

    (l_prog, logits), g_prog = jax.value_and_grad(program_loss,
                                                  has_aux=True)(params)
    want = jnp.stack([reference.forward_row(w, jnp.asarray(t), cfg)
                      for t in tokens])
    np.testing.assert_allclose(logits, want, atol=2e-4)
    l_ref, g_ref = jax.value_and_grad(
        lambda w: reference.loss(w, jnp.asarray(tokens), cfg))(w)
    assert float(l_prog) == pytest.approx(float(l_ref), abs=1e-5)
    g_prog = weights.from_program_tree(g_prog)
    for name in g_ref:
        np.testing.assert_allclose(g_prog[name], g_ref[name], atol=1e-5,
                                   err_msg=name)


def test_adamw_is_optax_adamw():
    key = jax.random.PRNGKey(0)
    w = {"a": jax.random.normal(key, (8, 4)), "b": jnp.ones((4,))}
    grads = [jax.tree.map(lambda x, i=i: jnp.sin(x + i), w) for i in range(3)]
    tx = optax.adamw(3e-4)
    state, want, got = tx.init(w), w, w
    for i, g in enumerate(grads):
        updates, state = tx.update(g, state, want)
        want = optax.apply_updates(want, updates)
        got = reference.adamw(got, grads[:i + 1], 3e-4)
        for name in w:
            np.testing.assert_allclose(got[name], want[name], rtol=1e-6,
                                       atol=1e-7)


def test_weights_are_a_function_of_the_seed():
    cfg = modelcfg.tiny(modelcfg.load("mistral-7b-v0.3"))
    a = weights.make_weights(cfg, 2 ** 31 + 5, jnp.bfloat16)
    b = weights.make_weights(cfg, 2 ** 31 + 5, jnp.bfloat16)
    c = weights.make_weights(cfg, 5, jnp.bfloat16)
    assert all((a[n] == b[n]).all() for n in a)
    assert not (a["wq"] == c["wq"]).all()
    back = weights.from_program_tree(weights.to_program_tree(a))
    assert set(back) == set(a) and all(back[n] is a[n] for n in a)
