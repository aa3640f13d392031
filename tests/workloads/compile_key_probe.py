"""What the compile cache's key is made of, for a job's train step and for
a kernel the job's script calls itself: builds the small train step with
the flash kernels forced on, calls it from THIS file, lowers it for the
TPU (a lowering, never a compile: no chip and no libtpu needed) and prints
the hash of the bytes ``jax._src.cache_key._canonicalize_ir`` gives the
key, first under the environment the process was started with and then
with jax's source-file canonicalisation switched off; then the same for
``own_kernel`` below. ``tests/test_compile_key.py`` copies it into
sandboxes as the executor lays them out.

Prints one ``KEY {json}`` line.
"""

import hashlib
import json
import os
import sys

sys.path.insert(0, os.environ["TONY_REPO_ROOT"])

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax._src import cache_key  # noqa: E402

from tony_tpu import train  # noqa: E402
from tony_tpu.models import get_model  # noqa: E402

REGEX = "jax_hlo_source_file_canonicalization_regex"
B, S = 2, 256


def own_kernel(q, k, v):
    """A Pallas call two frames from the script: a job that brings its own
    model calls the kernels from its own files."""
    from tony_tpu.ops import flash_attention_packed

    return flash_attention_packed(q, k, v, 2, causal=True, interpret=False)


def key_bytes_hash(step, *args) -> tuple:
    lowered = step.trace(*args).lower(lowering_platforms=("tpu",))
    module = lowered.compiler_ir("stablehlo")
    data = cache_key._canonicalize_ir(module, cache_key.IgnoreCallbacks.NO)
    return (hashlib.sha256(data).hexdigest(),
            str(module).count("stablehlo.custom_call @tpu_custom_call"))


def main() -> None:
    model = get_model("llama-tiny", dim=256, n_heads=2, n_kv_heads=1,
                      ffn_hidden=256, max_seq=S, attention="flash",
                      remat=True)
    state = train.create_train_state(
        model, optax.adamw(1e-3), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    # From here on the dispatch in ops/attention.py takes the kernels, as
    # it does on the chip; nothing below runs a program.
    jax.default_backend = lambda: "tpu"
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]))
    batch = {"x": jnp.zeros((B, S), jnp.int32)}
    as_started, calls = key_bytes_hash(step, state, batch)
    jax.config.update(REGEX, None)
    without, _ = key_bytes_hash(step, state, batch)
    q = jnp.zeros((B, S, 256), jnp.bfloat16)
    kv = jnp.zeros((B, S, 128), jnp.bfloat16)
    own_without, _ = key_bytes_hash(jax.jit(own_kernel), q, kv, kv)
    jax.config.update(REGEX, os.environ.get(REGEX.upper()))
    own_as_started, _ = key_bytes_hash(jax.jit(own_kernel), q, kv, kv)
    print("KEY " + json.dumps({
        "file": __file__, "regex": os.environ.get(REGEX.upper()),
        "as_started": as_started, "without": without,
        "own_as_started": own_as_started, "own_without": own_without,
        "tpu_custom_calls": calls}))


if __name__ == "__main__":
    main()
