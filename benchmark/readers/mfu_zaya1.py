"""Model FLOP/s utilisation of the ``zaya1-8b`` configuration: its own FLOPs
per trained token (roofline_zaya1.train_flops_per_token: attention over the
causal half in the latent, the router MLP, the held experts' expected rows,
the tied head once, nothing recomputed) x the job's tokens/s over chips x
the bf16 peak of the device kind."""

from benchmark import roofline, roofline_zaya1


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if art.get("kind") != "train" or not art.get("tok_s") \
            or art["device"]["platform"] != "tpu" \
            or "router_hidden" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])["bf16_flops"]
    flops = roofline_zaya1.train_flops_per_token(cfg, art["job"]["seq"])
    return 100.0 * flops * art["tok_s"] / (art["chips"] * peak)
