"""What the ``glm-4.7-flash`` configuration brings to the benchmark: its
configuration file against the published keys, its parameter count against
the file's table and ISSUE 45's lines, its roofline arithmetic and the
cell's numbers against hand counts, its plain reference against
hand-written ``numpy`` on tiny cases (the rotation under a low-rank query,
the module that predicts a second token), its readers on made-up calls, the
limits against the chip's readings, and a rehearsal of the cell on the CPU
through the real control flow."""

import importlib
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, modelcfg_glm47flash as mc
from benchmark import roofline, roofline_glm47flash as rg

CELL = "glm47flash.train-16k"
NAME = "glm-4.7-flash"
CFG = mc.load(NAME)
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# The catalog's ``config`` of the architecture, as published.
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
NEW = ("mfu.glm47flash", "attn_ms.glm47flash", "mla_attn_roofline.glm47flash",
       "mtp_ms.glm47flash", "moe_ms.glm47flash",
       "moe_gmm_roofline.glm47flash", "moe_rows_max.glm47flash",
       "moe_rows_drift.glm47flash")


# ------------------------------------------------------------- configuration

def test_configuration_file_holds_the_published_keys():
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert raw["source"] == entry["source"] \
        == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    assert sorted(raw["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in raw["reduced"]:
            assert raw[key] == value, key
    # the floors: the leading dense layer and four of the period of one,
    # eight routed experts, an eighth of the vocabulary; the MTP module whole
    assert raw["num_hidden_layers"] == 5 and CFG["mtp"] == 1
    assert CFG["kinds"] == ["mla"] * 5
    assert CFG["ffns"] == ["dense"] + ["experts"] * 4
    assert raw["n_routed_experts"] == 8 \
        and raw["held"]["router_outputs"] == 64
    assert raw["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "eight chips share each layer" in raw["deployment"]
    assert set(raw["assumed"]) >= {"mla", "rotation", "router", "router_bias",
                                   "mtp", "training_recipe", "init"}
    # every width the cut may not touch
    assert (CFG["hidden"], CFG["heads"], CFG["q_rank"], CFG["kv_rank"],
            CFG["nope"], CFG["rope"], CFG["v_dim"], CFG["ffn"], CFG["top_k"],
            CFG["experts"], CFG["route_scale"], CFG["dense_ffn"],
            CFG["theta"], CFG["mtp_weight"]) == (
        2048, 20, 768, 512, 192, 64, 256, 1536, 4, 64, 1.8, 10240, 1e6, 0.3)


def test_parameter_count_is_the_files_table_and_the_issues_lines():
    count = mc.param_count(CFG)
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    for part, n in count.items():
        assert raw["parameters"][part] == n, part
    d = 2048
    assert count["mla_mixer"] == 1_572_864 + 768 + 3_932_160 + 1_179_648 \
        + 512 + 4_587_520 + 10_485_760 == 21_759_232
    assert count["one_expert"] == 9_437_184
    assert count["expert_ffn_held"] == 9 * 9_437_184 + 131_072 + 64 \
        == 85_065_792
    assert count["expert_block"] == 106_829_120
    assert count["dense_block"] == 84_677_888
    assert count["embedding_head_final_norm"] == 79_300_608
    assert count["mtp_module"] == 2 * d + 8_388_608 + 106_829_120 + d \
        == 115_223_872
    assert count["total"] == 706_518_848
    # 16 B a parameter: 11.30 GB, 67-71% of the chip
    assert count["total"] * 16 / 1e9 == pytest.approx(11.30, abs=0.01)


def test_weights_layout_is_the_programs_tree():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmark import weights_glm47flash as wg
    from tony_tpu.models import get_model

    tiny = mc.tiny(CFG)
    model = get_model(tiny["program"]["model"], **mc.program_kwargs(tiny, 64))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 64), jnp.int32))["params"]
    mine = wg.to_program_tree(wg.make_weights(tiny, 3), tiny)
    shapes = lambda t: jax.tree.map(lambda a: tuple(a.shape), t)
    assert shapes(nn.unbox(tree)) == shapes(mine)
    back = wg.from_program_tree(mine, tiny)
    assert sorted(back) == sorted(wg.leaf_specs(tiny))
    again = wg.make_weights(tiny, 3)
    assert all(bool((again[n] == a).all()) for n, a in back.items())
    assert {"M.e_norm", "M.h_norm", "M.w_eh", "M.final_norm", "M.wq_a",
            "M.w_router"} <= set(back)
    # a seed past 32 signed bits is a seed
    assert wg.make_weights(tiny, 2**31 + 11)["embed"].shape == (256, 64)


def test_the_cells_numbers():
    """ISSUE 45's arithmetic (MFLOP a trained token, forward) and what the
    workload file says of it."""
    d, h, s = 2048, 20, 16384
    attn = rg.mla_fwd(1, h, s, 256, 256, 64)[0] / s / 1e6
    assert 6 * attn == pytest.approx(1007, abs=1)
    assert 6 * 8192 * 20 * 1024 / 1e6 == pytest.approx(1007, abs=1)
    mla = d * 768 + 768 * h * 256 + d * 576 + 512 * h * 448 + h * 256 * d
    assert 6 * 2 * mla / 1e6 == pytest.approx(261, abs=0.5)
    assert 2 * 3 * d * 10240 / 1e6 == pytest.approx(126, abs=0.5)
    expert = 3 * d * 1536
    assert 5 * 2 * expert / 1e6 == pytest.approx(94, abs=0.5)      # shared
    assert 5 * 2 * expert * 4 * 8 / 64 / 1e6 == pytest.approx(47, abs=0.5)
    assert 5 * 2 * d * 64 / 1e6 == pytest.approx(1.3, abs=0.1)     # router
    assert 2 * 2 * d * CFG["vocab"] / 1e6 == pytest.approx(159, abs=0.5)
    assert 2 * 2 * d * d / 1e6 == pytest.approx(17, abs=0.5)       # W_eh
    total = rg.train_flops_per_token(CFG, s)
    assert total / 3e9 == pytest.approx(1.71, abs=5e-3)
    assert total * s / 1e12 == pytest.approx(84, abs=0.5)
    kernels = rg.mixer_flops_per_token(CFG, s)
    assert kernels / total == pytest.approx(0.59, abs=0.005)
    assert (kernels + 6 * 6 * mla) / total == pytest.approx(0.74, abs=0.005)
    # the MTP module: a block, W_eh, the head's second pass
    block = 6 * rg.block_params(CFG, "experts", 0.5) + kernels / 6
    mtp = block + 6 * (2 * d * d + d * CFG["vocab"])
    assert mtp / total == pytest.approx(0.20, abs=0.005)
    assert s * 4 // 64 == 1024                      # rows a held expert
    # counted rows change the experts' share and nothing else
    assert rg.train_flops_per_token(CFG, s, 0.5) == pytest.approx(total)
    assert total - rg.train_flops_per_token(CFG, s, 0.0) \
        == pytest.approx(5 * 6 * expert * 0.5)


def test_the_kernels_work_is_the_published_shapes():
    """q.k over 192 + 64, p.v over 256, half the square; the backward
    twice the forward; the shared key part's bytes once, nothing padded."""
    flops, nbytes = rg.mla_fwd(1, 20, 16384, 256, 256, 64)
    assert flops == 2 * 20 * (256 + 256) * 16384 * 16384 // 2
    assert nbytes == 16384 * (20 * (256 + 192 + 512) + 64) * 2 \
        + 20 * 16384 * 4
    back = rg.mla_bwd(1, 20, 16384, 256, 256, 64)
    assert back[0] == 2 * flops
    assert back[1] == 2 * 16384 * (20 * 960 + 64) * 2 + 20 * 16384 * 4
    peak = roofline.peaks("TPU v5 lite")
    least, bound = roofline.least_seconds(flops, nbytes, peak)
    assert bound == "compute" and least == pytest.approx(13.95e-3, rel=0.01)
    # a held matrix read once a pass bounds a chunk's grouped calls
    work = rg.grouped_matmul(128 * 8, 8, 8, 2048, 1536)
    assert roofline.least_seconds(*work, peak)[1] in ("memory", "compute")


# ----------------------------------------------------------- the reference

def test_reference_mla_against_numpy_by_hand():
    """The query through its own latent and norm, neighbouring pairs
    rotated by position at the configured theta, ONE shared rotated key
    part that is not normed, scores over nope + rope."""
    import jax.numpy as jnp
    from benchmark import reference_glm47flash as ref

    rng = np.random.default_rng(1)
    t, dm, h, qr, r, dn, ds, dv = 6, 8, 2, 5, 4, 4, 4, 8
    theta = 100.0
    cfg = {"heads": h, "kv_rank": r, "nope": dn, "rope": ds, "v_dim": dv,
           "eps": 1e-5, "theta": theta}
    lw = {"wq_a": rng.normal(size=(dm, qr)),
          "q_norm": 1 + 0.1 * rng.normal(size=qr),
          "wq_b": rng.normal(size=(qr, h * (dn + ds))),
          "wkv_a": rng.normal(size=(dm, r + ds)),
          "kv_norm": 1 + 0.1 * rng.normal(size=r),
          "wkv_b": rng.normal(size=(r, h * (dn + dv))),
          "wo": rng.normal(size=(h * dv, dm))}
    x = rng.normal(size=(t, dm))
    got = ref.mla(jnp.asarray(x, jnp.float32),
                  {n: jnp.asarray(w, jnp.float32) for n, w in lw.items()}, cfg)
    norm = lambda a, s: a / np.sqrt((a * a).mean(-1, keepdims=True)
                                    + 1e-5) * s

    def turn(vec, pos):
        out = vec.copy()
        for i in range(ds // 2):
            ang = pos * theta ** (-2 * i / ds)
            a, b = vec[2 * i], vec[2 * i + 1]
            out[2 * i] = a * np.cos(ang) - b * np.sin(ang)
            out[2 * i + 1] = a * np.sin(ang) + b * np.cos(ang)
        return out

    q = (norm(x @ lw["wq_a"], lw["q_norm"]) @ lw["wq_b"]).reshape(
        t, h, dn + ds)
    kva = x @ lw["wkv_a"]
    kv = (norm(kva[:, :r], lw["kv_norm"]) @ lw["wkv_b"]).reshape(
        t, h, dn + dv)
    shared = np.stack([turn(kva[i, r:], i) for i in range(t)])
    out = np.zeros((t, h, dv))
    for head in range(h):
        for i in range(t):
            qi = np.concatenate([q[i, head, :dn], turn(q[i, head, dn:], i)])
            keys = np.concatenate([kv[:i + 1, head, :dn], shared[:i + 1]], -1)
            s = keys @ qi / np.sqrt(dn + ds)
            p = np.exp(s - s.max())
            out[i, head] = (p / p.sum()) @ kv[:i + 1, head, dn:]
    np.testing.assert_allclose(got, out.reshape(t, -1) @ lw["wo"],
                               rtol=3e-4, atol=3e-5)


def test_reference_mtp_by_hand():
    """The embedding's half first, the stack's output before its final
    norm, labels two tokens ahead, the one head; the weight 0.3."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference_glm47flash as ref
    from benchmark import weights_glm47flash as wg

    tiny = mc.tiny(CFG)
    w = wg.make_weights(tiny, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 64))
    x = w["embed"][tokens]
    for i, ffn in enumerate(tiny["ffns"]):
        x = ref.block(x, ref.leaves(w, f"L{i}"), ffn, tiny)
    lw = ref.leaves(w, "M")
    norm = lambda a, s: a * jax.lax.rsqrt(
        jnp.mean(a * a, -1, keepdims=True) + 1e-5) * s
    ahead = w["embed"][jnp.concatenate([tokens[1:], tokens[:1]])]
    join = jnp.concatenate([norm(ahead, lw["e_norm"]),
                            norm(x, lw["h_norm"])], -1)
    np.testing.assert_allclose(ref.mtp_input(w, x, tokens, tiny),
                               ref.matmul(join, lw["w_eh"]), atol=1e-5)
    second = norm(ref.block(ref.matmul(join, lw["w_eh"]), lw, "experts",
                            tiny), lw["final_norm"])
    logp = jax.nn.log_softmax(ref.matmul(second, w["lm_head"]), -1)
    want = -np.mean([float(logp[i, tokens[i + 2]]) for i in range(62)])
    lm, mtp = ref.row_losses(w, tokens, tiny)
    assert float(mtp) == pytest.approx(want, rel=1e-5)
    total, (lm2, mtp2) = ref.loss(w, tokens[None], tiny)
    assert float(total) == pytest.approx(float(lm) + 0.3 * float(mtp),
                                         rel=1e-6)
    assert (float(lm2), float(mtp2)) == pytest.approx(
        (float(lm), float(mtp)), rel=1e-6)


# ------------------------------------------------------------------- readers

def metric(name, art):
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(art, spec.get("args", {}))


def test_the_manifest_finds_every_new_file():
    bench = manifest.load()
    assert manifest.validate(bench) == []
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    assert cell["traffic"] == "train-16k" and len(cell["why"]) <= 200
    wl = manifest.workload_file(CELL)
    assert wl["driver"] == "train_glm47flash" and wl["job"]["seq"] == 16384
    assert wl["job"]["batch"] == 1
    importlib.import_module(f"benchmark.drivers.{wl['driver']}")
    assert (manifest.HERE / "tasks" / "train_glm47flash_task.py").is_file()
    mine = {m["name"] for m in manifest.cell_metrics(bench, cell,
                                                     "per_layer")}
    assert set(NEW) <= mine
    assert {"step_ms", "device_idle.train", "optimizer_ms.train",
            "head_loss_ms.train", "programs_built.train", "launch_s.train",
            "task_init_s", "state_init_s.train", "build_s.train",
            "loop_step_ms.train", "import_s.train", "backend_init_s.train",
            "first_step_s.train", "init_unspanned_s.train"} <= mine
    assert len(mine) == 14 + len(NEW)
    assert not {"mfu", "mfu.kimilinear", "mla_attn_roofline.kimilinear"} \
        & mine
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
            manifest.metric_file(m["name"])
    assert {m["name"] for m in manifest.cell_metrics(
        bench, cell, "end_to_end")} == {"train_tok_s", "setup_s"}
    # the new entries are the last of their lists
    assert bench["configs"][-1]["name"] == NAME
    # one line of at most 200 characters each: `validate` holds a cell's
    # `why` to it and not a configuration's (206 refused this PR once)
    conf = bench["configs"][-1]
    for text in (conf["why"], conf["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_program_has_nothing(name):
    """The parent commit, another configuration, a rehearsal or an
    untraced run: None, never an exception."""
    from benchmark import modelcfg
    base = {"kind": "train", "cell": "no-such-run", "chips": 1,
            "device": V5E, "job": {"seq": 16384, "batch": 1}, "tok_s": None,
            "trace": None, "trace_events": None,
            "task": {"step_walls_s": [1.0]}}
    assert metric(name, dict(base, model_cfg=CFG)) is None
    fusion = {"planes": [{"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops", "events": [["%fusion.1 = f32[8] fusion()", 0, 5]]}]}]}
    dense = dict(base, model_cfg=modelcfg.load("mistral-7b-v0.3"),
                 tok_s=30000.0, trace=fusion)
    assert metric(name, dense) is None
    if "roofline" in name:
        assert metric(name, dict(base, model_cfg=CFG, trace=fusion)) is None


def test_scope_metrics_read_the_new_scopes():
    from benchmark import scoperead
    from benchmark.readers import scope_ms
    table = {"attn_mla": 1e8, "mla_proj": 3e8, "mla_rope": 1e8,
             "attn_fwd": 4e8, "attn_bwd_dq": 2e8, "attn_bwd_dkv": 4e8,
             "mtp_proj": 0.5e8, "mlp": 3e8, "moe": 1e8, "moe_route": 0.5e8,
             "moe_shared": 1.5e8, "moe_experts": 1e8, "moe_gmm": 1e8,
             "moe_gmm_t": 1e8, "moe_tgmm": 1e8, "optimizer": 1e8}
    for name, want in (("attn_ms.glm47flash", 375.0),
                       ("moe_ms.glm47flash", 175.0)):
        spec = manifest.metric_file(name)["args"]
        art = {"task": {"step_walls_s": [1.0] * 4},
               "scope_self_ns:" + ",".join(spec["known"]): table}
        assert scope_ms.read(art, spec) == pytest.approx(want)
    known = tuple(manifest.metric_file("attn_ms.glm47flash")["args"]["known"])
    assert tuple(manifest.metric_file("moe_ms.glm47flash")["args"][
        "known"]) == known
    # everything under ``mtp`` is the module's, whatever its own scope: the
    # metric reads by the one name, so its block's attention is in it (and
    # in attn_ms too, which reads by the mixer's scopes in all six blocks)
    spec = manifest.metric_file("mtp_ms.glm47flash")["args"]
    assert spec["known"] == spec["scopes"] == ["mtp"]
    art = {"task": {"step_walls_s": [1.0] * 2},
           "scope_self_ns:mtp": {"mtp": 5e8, "": 9e9}}
    assert scope_ms.read(art, spec) == pytest.approx(250.0)
    step = "jit(step)/transpose(jvp(HybridDecoder))/"
    for path, scope, under_mtp in (
            ("layer_2/attn_mla/mla_proj/wq_b/dot_general", "mla_proj", False),
            ("layer_2/attn_mla/mla_rope/concatenate", "mla_rope", False),
            ("layer_2/attn_mla/attn_fwd/pallas_call", "attn_fwd", False),
            ("layer_2/attn_mla/attn_bwd_dkv/pallas_call", "attn_bwd_dkv",
             False),
            ("layer_2/attn_mla/wo/dot_general", "attn_mla", False),
            ("layer_0/mlp/w_gate/dot_general", "mlp", False),
            ("mtp/mtp_proj/w_eh/dot_general", "mtp_proj", True),
            ("mtp/layer/attn_mla/attn_fwd/pallas_call", "attn_fwd", True),
            ("mtp/layer/moe_mlp/moe/moe_experts/moe_gmm/pallas_call",
             "moe_gmm", True),
            ("mtp/lm_head/dot_general", "lm_head", True),
            ("lm_head/dot_general", "lm_head", False)):
        assert scoperead.scope_of(step + path, known) == scope
        assert (scoperead.scope_of(step + path, ("mtp",)) == "mtp") \
            is under_mtp


def _art(calls, dur=400_000, steps=2):
    return {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
            "device": V5E, "job": {"seq": 16384, "batch": 1},
            "task": {"step_walls_s": [1.0] * steps}, "trace": {"planes": [{
                "name": "/device:TPU:0", "lines": [{
                    "name": "XLA Ops",
                    "events": [[c, 1000 * i, dur]
                               for i, c in enumerate(calls)]}]}]}}


def test_mla_roofline_charges_the_backward_once():
    """dq and dk/dv are two kernels of one backward: its work (twice the
    forward's) is charged to the first of them, the time of all counts; a
    forward run twice (the layer's and the remat's) is charged twice."""
    peak = roofline.peaks("TPU v5 lite")
    dims = (1, 20, 16384, 256, 256, 64)
    fwd = roofline.least_seconds(*rg.mla_fwd(*dims), peak)
    bwd = roofline.least_seconds(*rg.mla_bwd(*dims), peak)
    assert fwd[1] == bwd[1] == "compute" and bwd[0] == 2 * fwd[0]
    calls = ["%attn_fwd.1 = (bf16[1,16384,5120]) custom-call(bf16[1,16384",
             "%attn_fwd.2 = (bf16[1,16384,5120]) custom-call(bf16[1,16384",
             "%attn_bwd_dq.1 = (bf16[1,16384,5120]) custom-call(bf16[1,",
             "%attn_bwd_dkv.1 = (bf16[1,16384,5120]) custom-call(bf16[1",
             "%fusion.3 = bf16[1,16384,5120] fusion(%attn_fwd.1)"]
    got = metric("mla_attn_roofline.glm47flash", _art(calls, 50_000_000))
    assert got == pytest.approx(100 * (2 * fwd[0] + bwd[0]) / 0.200,
                                rel=1e-6)
    assert 0 < got < 100


def test_grouped_matmul_roofline_counts_the_rows_that_ran(monkeypatch):
    """Work from the traced steps' own sown rows over steps x the FIVE
    expert layers (the module's among them) x the chunks."""
    from benchmark.readers import timeline
    calls = [
        "%moe_gmm.7 = bf16[2048,1536] custom-call(s32[8] %a, s32[8] %b",
        "%moe_gmm_t.3 = bf16[2048,2048] custom-call(s32[8] %a, s32[8]",
        "%moe_tgmm.2 = bf16[8,2048,1536] custom-call(s32[8] %a, s32[8",
        "%fusion.9 = bf16[2048,1536] fusion(bf16[2048,1536] %moe_gmm.7)"]
    art = _art(calls, 100_000)
    counters = {"moe:chunks": 8, "model:layers.experts": 5}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    assert metric("moe_gmm_roofline.glm47flash", art) is None   # no rows
    counters["moe:rows_held_traced"] = 2 * 5 * 8 * 1024
    work = rg.grouped_matmul(1024, 8, 8, 2048, 1536)
    least, _ = roofline.least_seconds(*work, roofline.peaks("TPU v5 lite"))
    assert metric("moe_gmm_roofline.glm47flash", art) == pytest.approx(
        100 * least * 3 / 300e-6, rel=1e-6)
    art["trace"]["planes"][0]["lines"][0]["events"] = [[calls[3], 0, 5]]
    assert metric("moe_gmm_roofline.glm47flash", art) is None


def test_rows_max_drift_and_mfu_read_the_tasks_counters(monkeypatch):
    from benchmark.readers import timeline
    counters = {"moe:rows_held": 5 * 8192, "moe:rows_max_expert": 1536,
                "moe:experts_held": 8, "model:layers.experts": 5,
                "moe:rows_held_last": 0}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    # the fullest expert over the mean: 40960 / (8 x 5) = 1024
    assert metric("moe_rows_max.glm47flash", {"model_cfg": CFG}) \
        == pytest.approx(1.5)
    assert metric("moe_rows_drift.glm47flash", {"model_cfg": CFG}) == 0.0
    counters["moe:rows_held_last"] = 4096
    assert metric("moe_rows_drift.glm47flash", {"model_cfg": CFG}) \
        == pytest.approx(0.1)
    art = {"kind": "train", "chips": 1, "device": V5E, "model_cfg": CFG,
           "job": {"seq": 16384, "batch": 1}, "tok_s": 12000.0,
           "task": {"step_walls_s": [1.4] * 6}}
    even = rg.train_flops_per_token(CFG, 16384)
    assert metric("mfu.glm47flash", art) == pytest.approx(
        100 * even * 12000 / 197e12)                 # no counter: even share
    counters["moe:rows_held_traced"] = 0             # a count: starved
    assert metric("mfu.glm47flash", art) == pytest.approx(
        100 * rg.train_flops_per_token(CFG, 16384, 0.0) * 12000 / 197e12)
    assert 0 < metric("mfu.glm47flash", art) < 100


# ---------------------------------------------------- the limits of correct

# The room a limit keeps over the widest sound seed: 1.3x for the guards;
# 1.25x for the median leaf's gradient gap (the twenty-second seed read
# 0.000219 under 0.00028, the control's quieter seed 0.000370) and 1.2x for
# the widest leaf's, whose twentieth seed read 0.00441 where the control's
# quieter seed reads 0.00637 (the geometric middle is 1.2x from both).
ROOM = {"loss_gap": 1.3, "mtp_loss_gap": 1.3, "grad_median_gap": 1.25,
        "grad_norm_gap": 1.2, "param_change_gap": 1.3}


def _readings():
    wl = manifest.workload_file(CELL)
    return wl["limits"], wl["readings"]["sound"], wl["readings"]["int8"]


def test_every_sound_reading_is_under_every_limit_with_room():
    """The chip's readings are data beside the limits
    (``workloads/glm47flash.train-16k.json`` ``readings``, each with its
    call): the harness's own rule, ``value <= limit``, holds on every sound
    seed with room (the driver draws fresh seeds)."""
    limits, sound, _ = _readings()
    assert len(sound) >= 9
    assert set(limits) == {"loss_gap", "mtp_loss_gap", "grad_median_gap",
                           "grad_norm_gap", "param_change_gap"}
    for name, entry in limits.items():
        widest = max(seed[name] for seed in sound.values())
        assert ROOM[name] * widest <= entry["limit"], (name, widest)


def test_the_int8_control_ends_incorrect_on_every_seed():
    """``--control int8``: both seeds are over the two limits that hear a
    precision here, the median leaf's and the widest leaf's gradient gap,
    each between its sound and its control readings with ``ROOM`` (1.25x,
    1.2x) on both sides (the int8 lane covers a quarter of this cell's
    FLOPs: less room than the Kimi Linear cell's 2x); the losses and the
    sign-like update are guards the control passes."""
    limits, sound, int8 = _readings()
    assert len(int8) == 2
    for name in ("grad_median_gap", "grad_norm_gap"):
        limit = limits[name]["limit"]
        room = ROOM[name]
        assert all(v[name] >= room * limit for v in int8.values()), name
        assert all(room * v[name] <= limit for v in sound.values()), name
    for seed, numbers in int8.items():
        assert any(numbers[n] > limits[n]["limit"] for n in limits), seed
    assert all(v["param_change_gap"] < limits["param_change_gap"]["limit"]
               < 1 for v in sound.values())


def test_the_spread_readings_are_what_the_chip_read():
    """Three sets of six fresh seeds, ``--trace 0``: the quartile distance
    of ``train_tok_s`` over its median. ISSUE 45 asks for under 0.5 % in
    each of two; read: 0.22 %, **0.83 %** and 0.25 % — the second set holds
    one run 2.2 % under the other seventeen (PERF.md section 6, PR 45: not
    its seed's work). All are under the bound itself; the criterion is NOT
    met in the second set."""
    import statistics
    spread = manifest.workload_file(CELL)["readings"]["spread"]
    bound = next(m["bound"] for m in manifest.load()["end_to_end"]
                 if m["name"] == "train_tok_s")
    assert len(spread["sets"]) == 3
    for runs in spread["sets"]:
        values = [r["train_tok_s"] for r in runs]
        assert len(values) == 6 and len({r["seed"] for r in runs}) == 6
        q = statistics.quantiles(values, n=4)
        assert (q[2] - q[0]) / statistics.median(values) < bound
    every = [r["train_tok_s"] for runs in spread["sets"] for r in runs]
    assert sorted(every)[1] > 0.995 * max(every)    # seventeen within 0.5 %


# ------------------------------------------------------------------- a run

TINY_LIMITS = {"loss_gap": 0.2, "mtp_loss_gap": 0.2, "grad_median_gap": 0.05,
               "grad_norm_gap": 0.9, "param_change_gap": 0.5}


def drive(**over):
    from benchmark.drivers import train_glm47flash
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=2.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(CELL)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return train_glm47flash.run(manifest.cell(bench, CELL), wl, args,
                                time.time())


@pytest.fixture(scope="module")
def sound_run():
    return drive()


def test_rehearsal_walks_the_whole_cell(sound_run):
    """`tony submit` of the task, the check steps, warm-up, the window and
    the reference, at a tiny size (limits of the tiny size's own)."""
    assert sound_run["correct"] is True
    assert sound_run["attempted"] > 0 and sound_run["failed"] == 0
    assert sound_run["end_to_end"]["train_tok_s"] > 0
    task = sound_run["artifacts"]["task"]
    assert task["compiled_in_window"] == 0
    assert set(task["compared"]) == set(TINY_LIMITS)
    # both losses apart, near ln 256 at seeded weights
    for name in ("losses_check", "mtp_losses_check", "reference_losses",
                 "reference_mtp_losses"):
        assert len(task[name]) == 2 and all(4 < x < 8 for x in task[name])
    # one expert layer in the tiny stack and the module's
    assert len(task["moe_rows_held_layers"]) == 2
    assert sum(abs(a - b) for a, b in zip(
        task["moe_rows_held_layers"],
        task["reference_rows_held_layers"])) <= 16


def test_the_cell_reports_its_metrics(sound_run):
    bench = manifest.load()
    got = manifest.read_layer_metrics(bench, manifest.cell(bench, CELL),
                                      sound_run["artifacts"])
    assert {"launch_s.train", "task_init_s", "moe_rows_max.glm47flash",
            "moe_rows_drift.glm47flash"} <= set(got)
    assert not {"mfu.glm47flash", "mla_attn_roofline.glm47flash",
                "moe_gmm_roofline.glm47flash", "mtp_ms.glm47flash"} & set(got)


def test_int8_control_runs_the_other_lane(sound_run):
    control = drive(control="int8")
    gaps = lambda r: r["artifacts"]["task"]["compared"]
    assert set(gaps(control)) == set(TINY_LIMITS)
    assert all(gaps(control)[k] != gaps(sound_run)[k] for k in TINY_LIMITS)
