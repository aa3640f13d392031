"""Model zoo for the TPU compute plane.

The reference ships models only as *examples* (``tony-examples/``: TF MNIST,
Keras MNIST, PyTorch MNIST — SURVEY.md §2.2); the orchestrator itself has no
model code. The TPU rebuild's north star (BASELINE.json via SURVEY.md §6)
adds two first-class model families this package owns:

* :mod:`~tony_tpu.models.resnet` — ResNet-50 for the ImageNet DP target;
* :mod:`~tony_tpu.models.transformer` — a Llama-style decoder for the
  ``pjit``/GSPMD graduation config (SURVEY.md §6 config ⑤), with logical
  sharding axes wired for dp/fsdp/tp/sp meshes; the same block with
  q/k-norm, a learned sparse-attention indexer and dropless experts of
  which a chip holds a range (``keye-vl-2.0-30b-a3b``);
* :mod:`~tony_tpu.models.hybrid` — a decoder built from a tuple of layer
  kinds (state-space, windowed / full differential attention, gated memory
  units, cross-attention to one shared K/V);
* :mod:`~tony_tpu.models.mnist` — the small nets the examples train.

All models are flax ``linen`` modules: params in f32, compute in bf16 by
default (MXU-native), logical axis metadata resolved through
:data:`tony_tpu.parallel.RULES`.
"""

from typing import Any, Callable, Dict

_REGISTRY: Dict[str, Callable[..., Any]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_model(name: str, **kw):
    """Build a registered model by name (``resnet50``, ``llama2-7b``,
    ``llama-tiny``, ``mixtral-8x7b``, ``keye-vl-2.0-30b-a3b``,
    ``keye-tiny``, ``zaya1-8b``, ``zaya-tiny``, ``hybrid-decoder``,
    ``hybrid-tiny``, ``mnist-mlp``, ``mnist-cnn``)."""
    # Import for registration side effects: the model files, and through
    # them flax and tony_tpu.ops' kernels, under one set-up span the first
    # time.
    from tony_tpu import profiler

    with profiler.importing("tony_tpu.models.transformer"):
        from tony_tpu.models import (hybrid, mnist, resnet,  # noqa: F401
                                     transformer)
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kw)
