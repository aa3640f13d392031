"""A user's training script in miniature — dist.initialize ->
create_train_state -> train_loop with one save — so that the task's
timeline (tony_tpu.profiler) has its set-up spans, builds and counters."""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"

import tony_tpu.distributed as dist

dist.initialize()

import jax
import jax.numpy as jnp
import optax

from tony_tpu import train
from tony_tpu.models import get_model

state = train.create_train_state(
    get_model("llama-tiny", attention="flash"), optax.adamw(1e-3),
    jnp.zeros((2, 16), jnp.int32), jax.random.PRNGKey(0))
step = train.make_train_step(
    loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]))
batches = [{"x": jnp.full((2, 16), i, jnp.int32)} for i in range(3)]
with tempfile.TemporaryDirectory() as ckpt_dir:
    train.train_loop(state, step, batches=iter(batches), ckpt_dir=ckpt_dir,
                     save_every=2, save_final=False)
print("timeline workload done")
