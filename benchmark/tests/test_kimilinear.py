"""What the ``kimi-linear-48b-a3b`` configuration brings to the benchmark:
its configuration file against the published keys, its parameter count
against the file's table, its roofline arithmetic and the cell's numbers,
its plain reference against hand-written ``numpy`` on tiny cases (the
recurrence, the latent attention's shared key part, the sigmoid router),
its readers on made-up calls and on a recorded excerpt of a chip run
(PR 38's traced run of ``kimilinear.train-32k``), and a rehearsal of the
cell on the CPU through the real control flow."""

import importlib
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, modelcfg_kimilinear as mc
from benchmark import roofline, roofline_kimilinear as rk

DATA = manifest.HERE / "tests" / "data"
CELL = "kimilinear.train-32k"
NAME = "kimi-linear-48b-a3b"
CFG = mc.load(NAME)
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# The catalog's ``config`` of the architecture, as published.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
NEW = ("mfu.kimilinear", "kda_ms.kimilinear", "kda_chunk_roofline.kimilinear",
       "attn_ms.kimilinear", "mla_attn_roofline.kimilinear",
       "moe_ms.kimilinear", "moe_gmm_roofline.kimilinear",
       "moe_rows_max.kimilinear", "moe_rows_drift.kimilinear")


# ------------------------------------------------------------- configuration

def test_configuration_file_holds_the_published_keys():
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert raw["source"] == entry["source"]
    assert sorted(raw["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in raw["reduced"]:
            assert raw[key] == value, key
    # the floors: the leading dense layer and one whole period, eight
    # routed experts, an eighth of the vocabulary
    assert raw["num_hidden_layers"] == 5
    assert CFG["kinds"] == ["kda", "kda", "kda", "mla", "kda"]
    assert CFG["ffns"] == ["dense"] + ["experts"] * 4
    assert raw["num_experts"] == 8 and raw["held"]["router_outputs"] == 256
    assert raw["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "32 chips share each layer" in raw["deployment"]
    # every width the cut may not touch
    assert (CFG["hidden"], CFG["kda_heads"], CFG["kda_head_dim"], CFG["conv"],
            CFG["kv_rank"], CFG["nope"], CFG["rope"], CFG["v_dim"],
            CFG["ffn"], CFG["top_k"], CFG["experts"], CFG["route_scale"],
            CFG["dense_ffn"]) == (2304, 32, 128, 4, 512, 128, 64, 128, 1024,
                                  8, 256, 2.446, 9216)


def test_parameter_count_is_the_files_table():
    count = mc.param_count(CFG)
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    stated = raw["parameters"]
    for part, n in count.items():
        assert stated[part] == n, part
    assert count["total"] == 602_450_816
    d = CFG["hidden"]
    assert stated["layer_1_kda_dense"] == count["kda_mixer"] \
        + count["dense_mlp"] + 2 * d
    assert stated["layers_2_3_5_kda_experts"] == count["kda_mixer"] \
        + count["expert_layer_held"] + 2 * d
    assert stated["layer_4_mla_experts"] == count["mla_mixer"] \
        + count["expert_layer_held"] + 2 * d
    # 16 B a parameter: 9.64 GB, 60% of the chip
    assert 0.60 < count["total"] * 16 / 16e9 < 0.61


def test_weights_layout_is_the_programs_tree():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmark import weights_kimilinear as wk
    from tony_tpu.models import get_model

    tiny = mc.tiny(CFG)
    model = get_model(tiny["program"]["model"], **mc.program_kwargs(tiny, 64))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 64), jnp.int32))["params"]
    mine = wk.to_program_tree(wk.make_weights(tiny, 3), tiny)
    shapes = lambda t: jax.tree.map(lambda a: tuple(a.shape), t)
    assert shapes(nn.unbox(tree)) == shapes(mine)
    back = wk.from_program_tree(mine, tiny)
    assert sorted(back) == sorted(wk.leaf_specs(tiny))
    # the same seed, the same weights; the decay's draws inside their range
    again = wk.make_weights(tiny, 3)
    assert all(bool((again[n] == a).all()) for n, a in back.items())
    a_log = np.asarray(back["L0.a_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16.0) + 1e-6).all()
    dt = np.log1p(np.exp(np.asarray(back["L0.dt_bias"])))
    assert (dt > 0.0009).all() and (dt < 0.11).all()


def test_the_cells_numbers():
    """The workload file's arithmetic (MFLOP a token, forward) and the
    cut's shares."""
    d, e, hd = 2304, 4096, 128
    kda = 3 * d * e + 2 * (d * hd + hd * e) + d * 32 + e * d
    assert 4 * 2 * kda / 1e6 == pytest.approx(316, abs=0.5)
    assert 4 * rk.kda_recurrence(1, 32, 128, 128)[0] / 1e6 \
        == pytest.approx(15, abs=0.5)
    attn = lambda s: rk.mla_fwd(1, 32, s, 192, 128, 64)[0] / s / 1e6
    assert (attn(32768), attn(16384), attn(8192)) == pytest.approx(
        (335, 168, 84), abs=0.6)
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert 2 * mla / 1e6 == pytest.approx(58, abs=0.5)
    assert 2 * 3 * d * 9216 / 1e6 == pytest.approx(127, abs=0.5)
    expert = 3 * d * 1024
    assert 4 * 2 * expert / 1e6 == pytest.approx(57, abs=0.5)       # shared
    assert 4 * 2 * expert * 8 * 8 / 256 / 1e6 == pytest.approx(14, abs=0.5)
    assert 4 * 2 * d * 256 / 1e6 == pytest.approx(5, abs=0.5)       # router
    assert 2 * d * CFG["vocab"] / 1e6 == pytest.approx(94, abs=0.5)
    total = rk.train_flops_per_token(CFG, 32768)
    assert total / 1e9 == pytest.approx(3.06, abs=5e-3)
    assert total * 32768 / 1e12 == pytest.approx(100, abs=0.5)
    mixers = 6 * (4 * kda + mla) + rk.mixer_flops_per_token(CFG, 32768)
    assert mixers / total == pytest.approx(0.71, abs=0.01)
    assert 32768 * 8 // 256 == 1024                 # rows a held expert
    # a chunk reads the held matrices once a pass: 8 x 3 x 2304 x 1024 bf16
    assert 8 * expert * 2 / 1e6 == pytest.approx(113, abs=0.5)


def test_the_recurrences_work_knows_no_chunk():
    """7 multiply-adds a state element a step forward, 14 backward; bytes
    are the operands' and the results', once. At the cell's size a layer's
    forward is bound by memory at ~2 ms: what any kernel is read against."""
    flops, nbytes = rk.kda_recurrence(32768, 32, 128, 128)
    assert flops == 32768 * 32 * 7 * 128 * 128
    assert nbytes == 32768 * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    back = rk.kda_recurrence(32768, 32, 128, 128, backward=True)
    assert back[0] == 2 * flops and back[1] == 2 * nbytes + 32768 * 32 * 256
    least, bound = roofline.least_seconds(flops, nbytes,
                                          roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(1.97e-3, rel=0.01)
    # latent attention: q.k over 192, p.v over 128, on half the square
    flops, nbytes = rk.mla_fwd(1, 32, 32768, 192, 128, 64)
    assert flops == 2 * 32 * (192 + 128) * 32768 * 32768 // 2
    assert nbytes == 32768 * (32 * (192 + 128 + 256) + 64) * 2 \
        + 32 * 32768 * 4
    assert rk.mla_bwd(1, 32, 32768, 192, 128, 64)[0] \
        == 2 * 32 * (3 * 192 + 2 * 128) * 32768 * 32768 // 2


# ----------------------------------------------------------- the reference

def test_reference_recurrence_against_numpy_by_hand():
    """The delta rule with a per-channel decay, float64 loops written from
    the equation: S_t = (I - b k k^T) Diag(exp g) S + b k v^T, o = S^T q."""
    import jax.numpy as jnp
    from benchmark import reference_kimilinear as ref

    t, h, d = 7, 2, 4
    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(t, h, d)) for _ in range(3))
    g = -np.abs(rng.normal(size=(t, h, d)))
    beta = 1 / (1 + np.exp(-rng.normal(size=(t, h))))
    got = ref.recurrence(*(jnp.asarray(a, jnp.float32)
                           for a in (q, k, v, g, beta)))
    want = np.zeros((t, h, d))
    for head in range(h):
        s = np.zeros((d, d))
        for i in range(t):
            kk, b = k[i, head], beta[i, head]
            s = (np.eye(d) - b * np.outer(kk, kk)) \
                @ (np.exp(g[i, head])[:, None] * s) \
                + b * np.outer(kk, v[i, head])
            want[i, head] = s.T @ q[i, head]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_reference_conv_and_mla_against_numpy_by_hand():
    """The depthwise convolution's last tap multiplies the current step;
    latent attention's heads share one unrotated key part."""
    import jax.numpy as jnp
    from benchmark import reference_kimilinear as ref

    rng = np.random.default_rng(1)
    a, taps = rng.normal(size=(5, 3)), rng.normal(size=(4, 3))
    want = np.zeros_like(a)
    for s in range(5):
        for j in range(4):
            if s - j >= 0:
                want[s] += taps[3 - j] * a[s - j]
    np.testing.assert_allclose(
        ref.conv(jnp.asarray(a, jnp.float32), jnp.asarray(taps, jnp.float32)),
        want, rtol=1e-5, atol=1e-6)

    t, dm, h, r, dn, ds, dv = 6, 8, 2, 4, 4, 2, 4
    cfg = {"mla_heads": h, "kv_rank": r, "nope": dn, "rope": ds, "v_dim": dv,
           "eps": 1e-5}
    lw = {"wq": rng.normal(size=(dm, h * (dn + ds))),
          "wkv_a": rng.normal(size=(dm, r + ds)),
          "kv_norm": 1 + 0.1 * rng.normal(size=r),
          "wkv_b": rng.normal(size=(r, h * (dn + dv))),
          "wo": rng.normal(size=(h * dv, dm))}
    x = rng.normal(size=(t, dm))
    got = ref.mla(jnp.asarray(x, jnp.float32),
                  {n: jnp.asarray(w, jnp.float32) for n, w in lw.items()}, cfg)
    q = (x @ lw["wq"]).reshape(t, h, dn + ds)
    kva = x @ lw["wkv_a"]
    c, shared = kva[:, :r], kva[:, r:]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-5) * lw["kv_norm"]
    kv = (c @ lw["wkv_b"]).reshape(t, h, dn + dv)
    out = np.zeros((t, h, dv))
    for head in range(h):
        for i in range(t):
            keys = np.concatenate([kv[:i + 1, head, :dn], shared[:i + 1]], -1)
            s = keys @ q[i, head] / np.sqrt(dn + ds)
            p = np.exp(s - s.max())
            out[i, head] = (p / p.sum()) @ kv[:i + 1, head, dn:]
    np.testing.assert_allclose(got, out.reshape(t, -1) @ lw["wo"],
                               rtol=2e-4, atol=2e-5)


def test_reference_router_by_hand():
    """One token: the bias moves the choice and is no part of the gate,
    the chosen gates add up to the scale, a share that holds none of the
    chosen gives the shared expert alone."""
    import jax.numpy as jnp
    from benchmark import reference_kimilinear as ref

    rng = np.random.default_rng(2)
    dm, e, f = 6, 8, 3
    cfg = {"top_k": 2, "route_scale": 2.446, "shared": 1, "experts_held": e,
           "expert_offset": 0}
    lw = {"w_router": rng.normal(size=(dm, e)), "router_bias": np.zeros(e),
          "w_gate": rng.normal(size=(e, dm, f)),
          "w_up": rng.normal(size=(e, dm, f)),
          "w_down": rng.normal(size=(e, f, dm)),
          "shared_gate": rng.normal(size=(dm, f)),
          "shared_up": rng.normal(size=(dm, f)),
          "shared_down": rng.normal(size=(f, dm))}
    y = rng.normal(size=(1, dm))
    s = 1 / (1 + np.exp(-(y @ lw["w_router"])[0]))
    order = np.argsort(-s)
    # lift the third-best over the second with the bias alone
    lw["router_bias"][order[2]] = s[order[1]] - s[order[2]] + 0.01
    jl = {n: jnp.asarray(a, jnp.float32) for n, a in lw.items()}
    chosen, gates = ref.route(jnp.asarray(y, jnp.float32), jl, cfg)
    assert sorted(int(c) for c in chosen[0]) == sorted(
        [int(order[0]), int(order[2])])
    picked = s[np.asarray(chosen[0])]
    np.testing.assert_allclose(gates[0], 2.446 * picked / picked.sum(),
                               rtol=1e-5)
    silu = lambda a: a / (1 + np.exp(-a))
    ffn = lambda g, u, dn: (silu(y @ g) * (y @ u)) @ dn
    want = sum(float(gate) * ffn(lw["w_gate"][c], lw["w_up"][c],
                                 lw["w_down"][c])
               for c, gate in zip(np.asarray(chosen[0]), np.asarray(gates[0])))
    shared = ffn(lw["shared_gate"], lw["shared_up"], lw["shared_down"])
    np.testing.assert_allclose(
        ref.experts(jnp.asarray(y, jnp.float32), jl, cfg), want + shared,
        rtol=1e-4, atol=1e-5)
    other = int(order[1])           # passed over: a share holding only it
    share = {n: (a[other:other + 1] if n in ("w_gate", "w_up", "w_down")
                 else a) for n, a in jl.items()}
    np.testing.assert_allclose(
        ref.experts(jnp.asarray(y, jnp.float32), share, cfg, 1, other),
        shared, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------- readers

def metric(name, art):
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(art, spec.get("args", {}))


def test_the_manifest_finds_every_new_file():
    bench = manifest.load()
    assert manifest.validate(bench) == []
    assert len(bench["workloads"]) == 5
    assert all(w["chips"] == 1 for w in bench["workloads"])
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    wl = manifest.workload_file(CELL)
    assert wl["driver"] == "train_kimilinear" and wl["job"]["seq"] == 32768
    assert wl["job"]["batch"] == 1
    importlib.import_module(f"benchmark.drivers.{wl['driver']}")
    assert (manifest.HERE / "tasks" / "train_kimilinear_task.py").is_file()
    mine = {m["name"] for m in manifest.cell_metrics(bench, cell,
                                                     "per_layer")}
    assert set(NEW) <= mine
    assert {"step_ms", "device_idle.train", "optimizer_ms.train",
            "head_loss_ms.train", "programs_built.train", "launch_s.train",
            "task_init_s", "state_init_s.train", "build_s.train",
            "loop_step_ms.train"} <= mine
    assert len(mine) == 14 + len(NEW)
    assert not {"mfu", "flash_roofline.train", "mfu.zaya1"} & mine
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
            manifest.metric_file(m["name"])
    assert {m["name"] for m in manifest.cell_metrics(
        bench, cell, "end_to_end")} == {"train_tok_s", "setup_s"}


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_program_has_nothing(name):
    """The parent commit, another configuration, a rehearsal or an
    untraced run: None, never an exception."""
    from benchmark import modelcfg
    base = {"kind": "train", "cell": "no-such-run", "chips": 1,
            "device": V5E, "job": {"seq": 32768, "batch": 1}, "tok_s": None,
            "trace": None, "trace_events": None,
            "task": {"step_walls_s": [1.0]}}
    assert metric(name, dict(base, model_cfg=CFG)) is None
    fusion = {"planes": [{"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops", "events": [["%fusion.1 = f32[8] fusion()", 0, 5]]}]}]}
    dense = dict(base, model_cfg=modelcfg.load("mistral-7b-v0.3"),
                 tok_s=30000.0, trace=fusion)
    assert metric(name, dense) is None
    if "roofline" in name:
        # this configuration on a program without the kernels
        assert metric(name, dict(base, model_cfg=CFG, trace=fusion)) is None


def test_scope_metrics_read_the_new_scopes():
    from benchmark import scoperead
    from benchmark.readers import scope_ms
    table = {"kda": 1e8, "kda_proj": 4e8, "kda_conv": 2e8, "kda_gate": 1e8,
             "kda_out": 2e8, "kda_chunk_fwd": 4e8, "kda_chunk_bwd": 6e8,
             "attn_mla": 1e8, "mla_proj": 1e8, "attn_fwd_mla": 4e8,
             "attn_bwd_dq_mla": 2e8, "attn_bwd_dkv_mla": 4e8, "mlp": 3e8,
             "moe": 1e8, "moe_route": 0.5e8, "moe_shared": 1.5e8,
             "moe_experts": 1e8, "moe_gmm": 1e8, "moe_gmm_t": 1e8,
             "moe_tgmm": 1e8, "optimizer": 1e8}
    for name, want in (("kda_ms.kimilinear", 500.0),
                       ("attn_ms.kimilinear", 300.0),
                       ("moe_ms.kimilinear", 175.0)):
        spec = manifest.metric_file(name)["args"]
        art = {"task": {"step_walls_s": [1.0] * 4},
               "scope_self_ns:" + ",".join(spec["known"]): table}
        assert scope_ms.read(art, spec) == pytest.approx(want)
    # one table a run: the three scope metrics share the list they read by;
    # an operation belongs to the innermost scope on its path
    known = tuple(manifest.metric_file("kda_ms.kimilinear")["args"]["known"])
    for other in ("attn_ms.kimilinear", "moe_ms.kimilinear"):
        assert tuple(manifest.metric_file(other)["args"]["known"]) == known
    layer = "jit(step)/transpose(jvp(HybridDecoder))/layer_2/"
    for path, scope in (
            ("kda/kda_proj/wq/dot_general", "kda_proj"),
            ("kda/kda_conv/ssm_conv/mul", "kda_conv"),
            ("kda/kda_gate/wf2/dot_general", "kda_gate"),
            ("kda/kda_chunk_fwd/pallas_call", "kda_chunk_fwd"),
            ("kda/kda_chunk_bwd/pallas_call", "kda_chunk_bwd"),
            ("kda/kda_out/wo/dot_general", "kda_out"),
            ("attn_mla/mla_proj/wkv_b/dot_general", "mla_proj"),
            ("attn_mla/attn_bwd_dkv_mla/pallas_call", "attn_bwd_dkv_mla"),
            ("attn_mla/wo/dot_general", "attn_mla"),
            ("mlp/w_gate/dot_general", "mlp"),
            ("moe_mlp/moe/moe_shared/dot_general", "moe_shared"),
            ("moe_mlp/moe/moe_experts/moe_gmm/pallas_call", "moe_gmm")):
        assert scoperead.scope_of(layer + path, known) == scope


def _art(calls, dur=400_000, steps=2):
    return {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
            "device": V5E, "job": {"seq": 32768, "batch": 1},
            "task": {"step_walls_s": [1.0] * steps}, "trace": {"planes": [{
                "name": "/device:TPU:0", "lines": [{
                    "name": "XLA Ops",
                    "events": [[c, 1000 * i, dur]
                               for i, c in enumerate(calls)]}]}]}}


def test_kda_roofline_is_the_recurrences_work_over_the_calls_time():
    """Each forward call is charged a layer's recurrence forward, each
    backward call its backward, whatever the kernel's chunk: two forwards
    (the layer's and the remat's) and a backward at 40 ms each."""
    peak = roofline.peaks("TPU v5 lite")
    fwd = roofline.least_seconds(*rk.kda_recurrence(32768, 32, 128, 128),
                                 peak)[0]
    bwd = roofline.least_seconds(
        *rk.kda_recurrence(32768, 32, 128, 128, True), peak)[0]
    calls = ["%kda_chunk_fwd.1 = (bf16[1,32768,4096]) custom-call(bf16[1,32",
             "%kda_chunk_fwd.2 = (bf16[1,32768,4096]) custom-call(bf16[1,32",
             "%kda_chunk_bwd.1 = (bf16[1,32768,4096]) custom-call(bf16[1,32",
             "%fusion.3 = bf16[1,32768,4096] fusion(%kda_chunk_fwd.1)"]
    got = metric("kda_chunk_roofline.kimilinear", _art(calls, 40_000_000))
    assert got == pytest.approx(100 * (2 * fwd + bwd) / 0.120, rel=1e-6)
    assert 0 < got < 100


def test_mla_roofline_charges_the_backward_once():
    """dq and dk/dv are two kernels of one backward: its work is charged
    to the first of them, the time of both counts."""
    peak = roofline.peaks("TPU v5 lite")
    dims = (1, 32, 32768, 192, 128, 64)
    fwd = roofline.least_seconds(*rk.mla_fwd(*dims), peak)
    bwd = roofline.least_seconds(*rk.mla_bwd(*dims), peak)
    assert fwd[1] == bwd[1] == "compute"
    calls = ["%attn_fwd_mla.1 = (bf16[1,32768,4096]) custom-call(bf16[1,327",
             "%attn_bwd_dq_mla.1 = (bf16[1,32768,4096]) custom-call(bf16[1,",
             "%attn_bwd_dkv_mla.1 = (bf16[1,32768,4096]) custom-call(bf16[1"]
    got = metric("mla_attn_roofline.kimilinear", _art(calls, 150_000_000))
    assert got == pytest.approx(100 * (fwd[0] + bwd[0]) / 0.450, rel=1e-6)
    assert 0 < got < 100


def test_grouped_matmul_roofline_counts_the_rows_that_ran(monkeypatch):
    """Work from the traced steps' own sown rows and the program's chunk
    count, not from a call's shape: 2 steps x 4 layers x 32 chunks sent
    8192 rows, so a call multiplied 32 of its 1024-row buffer — and reads
    the eight held matrices whatever the rows: bound by memory."""
    from benchmark.readers import timeline
    calls = [
        "%moe_gmm.7 = bf16[1024,1024] custom-call(s32[8] %a, s32[8] %b",
        "%moe_gmm_t.3 = bf16[1024,2304] custom-call(s32[8] %a, s32[8]",
        "%moe_tgmm.2 = bf16[8,2304,1024] custom-call(s32[8] %a, s32[8",
        "%fusion.9 = bf16[1024,1024] fusion(bf16[1024,1024] %moe_gmm.7)"]
    art = _art(calls, 100_000)
    counters = {"moe:chunks": 32, "model:layers.experts": 4}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    assert metric("moe_gmm_roofline.kimilinear", art) is None   # no rows
    counters["moe:rows_held_traced"] = 8192
    work = rk.grouped_matmul(32, 32, 8, 2304, 1024)
    least, bound = roofline.least_seconds(*work,
                                          roofline.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert metric("moe_gmm_roofline.kimilinear", art) == pytest.approx(
        100 * least / 100e-6, rel=1e-6)
    # a program without the kernels (the parent): nothing to read
    art["trace"]["planes"][0]["lines"][0]["events"] = [[calls[3], 0, 5]]
    assert metric("moe_gmm_roofline.kimilinear", art) is None


def test_rows_max_and_drift_read_the_tasks_counters(monkeypatch):
    from benchmark.readers import timeline
    counters = {"moe:rows_held": 4096, "moe:rows_max_expert": 192,
                "moe:experts_held": 8, "model:layers.experts": 4,
                "moe:rows_held_last": 12288}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    # the fullest expert over the mean: 4096 / (8 x 4) = 128
    assert metric("moe_rows_max.kimilinear", {"model_cfg": CFG}) \
        == pytest.approx(1.5)
    assert metric("moe_rows_drift.kimilinear", {"model_cfg": CFG}) \
        == pytest.approx(3.0)          # 12288 / 4096


def test_drift_reads_zero_where_the_held_range_starved(monkeypatch):
    """``timeline_counter`` leaves a counter of 0 out; the last step of a
    window holds 0-2 rows here, and a traced line has to carry the number."""
    from benchmark.readers import timeline
    counters = {"moe:rows_held": 39810, "moe:rows_held_last": 0}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    assert metric("moe_rows_drift.kimilinear", {"model_cfg": CFG}) == 0.0
    counters["moe:rows_held_last"] = 2
    assert metric("moe_rows_drift.kimilinear", {"model_cfg": CFG}) \
        == pytest.approx(2 / 39810)


@pytest.fixture
def traced(monkeypatch):
    """Artifacts as ``drivers/train_kimilinear.py`` hands them to the
    readers, with an excerpt of PR 38's traced run (seed 3800000031): every
    KDA, flash and grouped-matmul custom call of the first of its six
    fenced steps (names cut after ``custom-call(``), one fusion, and the
    run's own counters."""
    from benchmark.readers import timeline
    rec = json.loads((DATA / "kimilinear_trace_excerpt.json").read_text())
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": rec["counters"]})
    return {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
            "device": V5E, "job": {"seq": 32768, "batch": 1},
            "task": {"step_walls_s": [2.72] * 6}, "tok_s": 32768 / 2.7109,
            "trace": {"planes": rec["planes"]}}


def test_readers_on_the_recorded_run(traced):
    """What the run itself printed over all six steps: 4.3276 %, 48.540 %,
    3.057 %, 5.827, 0.000058, 18.80 %; the excerpt's one step reads the
    same but for the grouped kernels, whose rows follow the step (the held
    range is starving: 18,947 rows over six steps, 34,495 at step 1
    alone). No share near 100 %."""
    assert metric("kda_chunk_roofline.kimilinear", traced) == pytest.approx(
        4.3276, abs=0.001)
    assert metric("mla_attn_roofline.kimilinear", traced) == pytest.approx(
        48.541, abs=0.005)
    assert metric("moe_gmm_roofline.kimilinear", traced) == pytest.approx(
        2.92, abs=0.01)
    assert metric("moe_rows_max.kimilinear", traced) == pytest.approx(
        5.827, abs=0.001)
    assert metric("moe_rows_drift.kimilinear", traced) == pytest.approx(
        5.8e-05, abs=1e-06)
    # 18.80 at an even routing's share; the held experts' 18,947 rows over
    # six steps are a tenth of it: 38 MFLOP a token less
    assert metric("mfu.kimilinear", traced) == pytest.approx(18.56, abs=0.01)


def test_mfu_counts_the_rows_the_held_experts_were_sent(traced, monkeypatch):
    """``mfu.kimilinear`` charges the routed experts the rows of the traced
    steps (``moe:rows_held_traced``), not an even routing's share: a
    starved held range is no model FLOP. A counter of 0 is a count; only a
    timeline without it gets the even share."""
    from benchmark import roofline_kimilinear as rk
    from benchmark.readers import timeline
    rate = 100 * traced["tok_s"] / 197e12
    counters = dict(timeline.task_timeline(traced)["counters"])
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    even = rk.train_flops_per_token(CFG, 32768)
    a_row = 6 * 3 * CFG["hidden"] * CFG["ffn"]   # fwd + bwd, three matrices
    layers = counters["model:layers.experts"]
    counters["moe:rows_held_traced"] = 6 * layers * 32768 // 4   # even
    assert metric("mfu.kimilinear", traced) == pytest.approx(rate * even)
    counters["moe:rows_held_traced"] = 0
    assert metric("mfu.kimilinear", traced) == pytest.approx(
        rate * (even - layers * a_row * 0.25))
    del counters["moe:rows_held_traced"]
    assert metric("mfu.kimilinear", traced) == pytest.approx(rate * even)
    assert rk.train_flops_per_token(CFG, 32768, 0.25) == pytest.approx(even)


def test_the_recorded_calls_are_those_of_the_five_layers(traced):
    from benchmark import traceread
    from benchmark.readers import timeline
    ops = [name for plane in traceread.device_planes(traced["trace"])
           for name, _, _ in traceread.op_events(plane)]
    count = lambda prefix: sum(n.startswith(prefix + ".") for n in ops)
    # a step: four KDA layers, the forward twice a layer (remat), one
    # backward; one MLA layer, the forward twice, dq and dk/dv once
    assert count("%kda_chunk_fwd") == 8 and count("%kda_chunk_bwd") == 4
    assert count("%attn_fwd_mla") == 2
    assert count("%attn_bwd_dq_mla") == count("%attn_bwd_dkv_mla") == 1
    # a (layer, chunk): as ZAYA1's, six forward calls, three transposed,
    # three weight-gradient: 4 expert layers x 32 chunks, one pass each
    counters = timeline.task_timeline(traced)["counters"]
    chunks = counters["model:layers.experts"] * counters["moe:chunks"]
    assert chunks == 128 and counters["moe:rows_buffer"] == 1024
    assert count("%moe_gmm") == 6 * chunks
    assert count("%moe_gmm_t") == count("%moe_tgmm") == 3 * chunks


def test_mfu_is_the_models_flops_at_the_rate():
    art = {"kind": "train", "chips": 1, "device": V5E, "model_cfg": CFG,
           "job": {"seq": 32768}, "tok_s": 15000.0}
    assert metric("mfu.kimilinear", art) == pytest.approx(
        100 * 3.0642e9 * 15000 / 197e12, rel=1e-3)


# ---------------------------------------------------- the limits of correct

def _readings():
    wl = manifest.workload_file(CELL)
    return wl["limits"], wl["readings"]["sound"], wl["readings"]["int8"]


def test_every_sound_reading_is_under_every_limit_with_room():
    """The chip's readings are data beside the limits
    (``workloads/kimilinear.train-32k.json`` ``readings``, each with its
    call): the harness's own rule, ``value <= limit``, holds on every sound
    seed with at least 1.8x of room (the driver draws fresh seeds)."""
    limits, sound, _ = _readings()
    assert len(sound) >= 9
    for name, entry in limits.items():
        widest = max(seed[name] for seed in sound.values())
        assert 1.8 * widest <= entry["limit"], (name, widest)


def test_the_int8_control_ends_incorrect_on_both_seeds():
    """``--control int8``: both seeds are over the limit that hears a
    precision, the median leaf's gradient gap, which lies between its two
    readings with room on both sides (the other three are guards: the
    widest leaf's gap is heavy-tailed, the loss and the sign-like update
    hardly move with the precision)."""
    limits, sound, int8 = _readings()
    assert len(int8) == 2
    limit = limits["grad_median_gap"]["limit"]
    assert all(v["grad_median_gap"] >= 2 * limit for v in int8.values())
    assert all(2 * v["grad_median_gap"] <= limit for v in sound.values())
    for seed, numbers in int8.items():
        assert any(numbers[n] > limits[n]["limit"] for n in limits), seed
    assert all(v["param_change_gap"] < limits["param_change_gap"]["limit"]
               < 1 for v in int8.values())


# ------------------------------------------------------------------- a run

TINY_LIMITS = {"loss_gap": 0.2, "grad_median_gap": 0.05,
               "grad_norm_gap": 0.9, "param_change_gap": 0.5}


def drive(**over):
    from benchmark.drivers import train_kimilinear
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=2.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(CELL)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return train_kimilinear.run(manifest.cell(bench, CELL), wl, args,
                                time.time())


@pytest.fixture(scope="module")
def sound_run():
    return drive()


def test_rehearsal_walks_the_whole_cell(sound_run):
    """`tony submit` of the task, the check steps, warm-up, the window and
    the reference, at a tiny size (limits of the tiny size's own: a
    64-wide model in bfloat16 reads far wider gaps than the cell)."""
    assert sound_run["correct"] is True
    assert sound_run["attempted"] > 0 and sound_run["failed"] == 0
    assert sound_run["end_to_end"]["train_tok_s"] > 0
    task = sound_run["artifacts"]["task"]
    assert task["compiled_in_window"] == 0
    assert set(task["compared"]) == set(TINY_LIMITS)
    # four of sixteen experts held, two a token: about half of each tiny
    # expert layer's 64 tokens send a row here; the reference counts the
    # same rows but for the tokens whose expert flips on bfloat16 rounding
    assert len(task["moe_rows_held_layers"]) == 4
    assert 0 < task["moe_rows_held"] < 4 * 2 * 64
    assert sum(abs(a - b) for a, b in zip(
        task["moe_rows_held_layers"],
        task["reference_rows_held_layers"])) <= 16


def test_the_cell_reports_its_metrics(sound_run):
    bench = manifest.load()
    got = manifest.read_layer_metrics(bench, manifest.cell(bench, CELL),
                                      sound_run["artifacts"])
    # no trace and no TPU in a rehearsal: the host-side metrics only, and
    # every device reader returns None instead of raising
    assert {"launch_s.train", "task_init_s", "moe_rows_max.kimilinear",
            "moe_rows_drift.kimilinear"} <= set(got)
    assert not {"mfu.kimilinear", "kda_chunk_roofline.kimilinear",
                "mla_attn_roofline.kimilinear",
                "moe_gmm_roofline.kimilinear"} & set(got)


def test_int8_control_runs_the_other_lane(sound_run):
    """The control walks the same flow on the int8 lane and reads other
    numbers. (How much wider is read on the chip: a 64-wide model in
    bfloat16 rounds as hard as the int8 grid does.)"""
    control = drive(control="int8")
    gaps = lambda r: r["artifacts"]["task"]["compared"]
    assert set(gaps(control)) == set(TINY_LIMITS)
    assert all(gaps(control)[k] != gaps(sound_run)[k] for k in TINY_LIMITS)
