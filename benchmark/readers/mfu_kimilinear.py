"""Model FLOP/s utilisation of the ``kimi-linear-48b-a3b`` configuration:
its own FLOPs per trained token (roofline_kimilinear.train_flops_per_token:
latent attention over the causal half at 192 / 128, the recurrence's own
work for the linear attention, the held experts at **the rows they were
sent**, the shared expert, the untied head, nothing recomputed) x the
job's tokens/s over chips x the bf16 peak of the device kind.

The held experts' rows are counted, not expected: the task's
``moe:rows_held_traced`` over the traced steps, the expert layers and the
step's tokens — the steps just before the window. The window's training
starves the held range (PERF.md section 6, PR 38: 1/32 of the routed rows
at step 1, none from about the tenth), and an even routing's share would
count 14 MFLOP a token of work that is not done. A timeline without the
counter (a program that does not count them) gets the even share."""

from benchmark import roofline, roofline_kimilinear
from benchmark.readers import timeline


def held_rows(art: dict):
    """Rows the held experts were sent a token a layer in the traced
    steps; None where the program did not count them."""
    counters = (timeline.task_timeline(art) or {}).get("counters") or {}
    rows, layers = (counters.get(n) for n in (
        "moe:rows_held_traced", "model:layers.experts"))
    steps = len((art.get("task") or {}).get("step_walls_s") or ())
    if rows is None or not layers or not steps:
        return None
    return rows / (steps * layers * art["job"]["batch"] * art["job"]["seq"])


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if art.get("kind") != "train" or not art.get("tok_s") \
            or art["device"]["platform"] != "tpu" \
            or "kda_heads" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])["bf16_flops"]
    flops = roofline_kimilinear.train_flops_per_token(
        cfg, art["job"]["seq"], held_rows(art))
    return 100.0 * flops * art["tok_s"] / (art["chips"] * peak)
