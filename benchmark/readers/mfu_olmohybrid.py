"""Model FLOP/s utilisation of the ``olmo-hybrid-7b`` configuration: its own
FLOPs per trained token (roofline_olmohybrid.train_flops_per_token: the
held heads, full attention over the causal half, the recurrence's own work
for the linear attention, the untied head, nothing recomputed) x the job's
tokens/s over chips x the bf16 peak of the device kind."""

from benchmark import roofline, roofline_olmohybrid


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if art.get("kind") != "train" or not art.get("tok_s") \
            or art["device"]["platform"] != "tpu" or "gdn_heads" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])["bf16_flops"]
    flops = roofline_olmohybrid.train_flops_per_token(cfg, art["job"]["seq"])
    return 100.0 * flops * art["tok_s"] / (art["chips"] * peak)
