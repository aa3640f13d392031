"""Deterministic sharded input-data plane.

The reference delegates the input pipeline entirely to user scripts (each
worker hand-rolls ``tf.data`` from ``TASK_INDEX`` — SURVEY.md §1 L7); this
package is the framework-owned replacement the TPU rebuild needs once the
train loop, checkpoint plane, and overlap engine are all framework-owned
too. Four pieces:

* **deterministic sharding** (:mod:`~tony_tpu.data.sharding`) — a
  :class:`ShardSpec` derived from the executor's gang identity
  (``TONY_PROCESS_ID``/``TONY_NUM_PROCESSES`` env on real gangs,
  standalone fallback); all index math is computed GLOBALLY on every host
  and the shard selects a contiguous block of each global batch, so any
  (host-count, shard) layout yields the same global example order;
* **a composable pipeline** (:mod:`~tony_tpu.data.pipeline`) —
  array/memmap/file :class:`Source`\\ s → shuffle (per-epoch Philox
  permutation or counter-based shuffle buffer) → repeat → batch → map,
  with the whole cursor exposed as a small JSON-able ``state()``;
* **double-buffered device prefetch** (:mod:`~tony_tpu.data.prefetch`) —
  a background thread stages the next K batches host→device through
  ``train.global_batch`` so the step never blocks on the feed; the stall
  it does pay is recorded per step in
  ``tony_tpu.profiler.report("input")``;
* **checkpointable iterator state** (:mod:`~tony_tpu.data.ckptio`) — the
  cursor rides the PR 3 ``ckpt`` manifest in the same atomic commit as
  the train state (``train_loop(data=...)``), and restores elastically
  across a CHANGED host count: the state is global, the new gang's
  ShardSpecs just re-slice it.
"""

from __future__ import annotations

from tony_tpu.data.ckptio import (DATA_ITER_KEY, MODEL_KEY, decode_state,
                                  encode_state, has_iter_state,
                                  load_iter_state, wrap_for_save)
from tony_tpu.data.pipeline import (ArraySource, Dataset, FileListSource,
                                    MemmapSource, PipelineIterator, Source)
from tony_tpu.data.prefetch import DeviceIterator
from tony_tpu.data.sharding import ShardSpec

__all__ = [
    "ArraySource", "DATA_ITER_KEY", "Dataset", "DeviceIterator",
    "FileListSource", "MODEL_KEY", "MemmapSource", "PipelineIterator",
    "ShardSpec", "Source", "decode_state", "encode_state", "has_iter_state",
    "load_iter_state", "wrap_for_save",
]
