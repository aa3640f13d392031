"""Hot-path TPU ops: pallas kernels + their portable references.

The reference framework has NO native compute (SURVEY.md §2: TonY is ~100%
JVM orchestration; kernels live in the frameworks it launches). This package
is where the TPU rebuild's compute plane keeps its hand-written kernels —
only the ops where beating XLA's fusion is realistic (attention, the scans;
XLA fuses most elementwise chains and layernorms well, and where a trace
showed it does not — a delta-rule mixer's convolution, SiLU and head norm,
``ssm.conv_silu_unit`` — the chain is a kernel too). Every op ships with a
pure-JAX reference implementation used for CPU tests and as the autodiff
backward.
"""

from tony_tpu.ops.attention import (
    flash_attention, flash_attention_packed, flash_attention_sharded,
    flash_decode, reference_attention)
from tony_tpu.ops.fused_optim import (FusedOptimizer, fused_bucket_update,
                                      fused_update_step)
from tony_tpu.ops.ssm import (causal_conv1d, conv_silu_unit,
                              selective_scan)
from tony_tpu.ops.quant import (QuantConfig, QuantDense, QuantTrainState,
                                quant_dot, quant_dot_general,
                                with_gather_quant)

__all__ = ["flash_attention", "flash_attention_packed",
           "flash_attention_sharded", "flash_decode",
           "reference_attention", "causal_conv1d", "conv_silu_unit",
           "selective_scan",
           "FusedOptimizer", "fused_bucket_update", "fused_update_step",
           "QuantConfig", "QuantDense", "QuantTrainState", "quant_dot",
           "quant_dot_general", "with_gather_quant"]
