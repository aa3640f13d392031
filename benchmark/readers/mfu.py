"""Model FLOP/s utilisation: the benchmark's own FLOPs per trained token
(roofline.train_flops_per_token) x the job's tokens/s over chips x the
bf16 peak of the device kind."""

from benchmark import roofline


def read(art: dict, args: dict):
    if art.get("kind") != "train" or not art.get("tok_s") \
            or art["device"]["platform"] != "tpu":   # a rehearsal has no peak
        return None
    peak = roofline.peaks(art["device"]["kind"])["bf16_flops"]
    flops = roofline.train_flops_per_token(art["model_cfg"],
                                           art["job"]["seq"])
    return 100.0 * flops * art["tok_s"] / (art["chips"] * peak)
