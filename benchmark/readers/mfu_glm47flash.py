"""Model FLOP/s utilisation of the ``glm-4.7-flash`` configuration: its own
FLOPs per trained token (roofline_glm47flash.train_flops_per_token: latent
attention over the causal half at 256 / 256 in all six blocks, the
backward at twice the forward, the low-rank query's two products, the held
experts at **the rows they were sent**, the shared expert, the
multi-token-prediction module's join and block, the untied head twice,
nothing recomputed) x the job's tokens/s over chips x the bf16 peak of the
device kind.

The held experts' rows are counted, not expected: the task's
``moe:rows_held_traced`` over the traced steps, the expert layers
(``model:layers.experts``: the module's among them) and the step's tokens
— the steps just before the window. A timeline without the counter (a
program that does not count them) gets the even share."""

from benchmark import roofline, roofline_glm47flash
from benchmark.readers.mfu_kimilinear import held_rows


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if art.get("kind") != "train" or not art.get("tok_s") \
            or art["device"]["platform"] != "tpu" or "q_rank" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])["bf16_flops"]
    flops = roofline_glm47flash.train_flops_per_token(
        cfg, art["job"]["seq"], held_rows(art))
    return 100.0 * flops * art["tok_s"] / (art["chips"] * peak)
