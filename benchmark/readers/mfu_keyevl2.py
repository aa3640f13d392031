"""Model FLOP/s utilisation of the ``keye-vl-2.0-30b-a3b`` configuration:
its own FLOPs per trained token (roofline_keyevl2.train_flops_per_token:
the selected pairs, the indexer, the held experts' expected rows, nothing
recomputed) x the job's tokens/s over chips x the bf16 peak of the device
kind."""

from benchmark import roofline, roofline_keyevl2


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if art.get("kind") != "train" or not art.get("tok_s") \
            or art["device"]["platform"] != "tpu" or "index_topk" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])["bf16_flops"]
    flops = roofline_keyevl2.train_flops_per_token(cfg, art["job"]["seq"])
    return 100.0 * flops * art["tok_s"] / (art["chips"] * peak)
