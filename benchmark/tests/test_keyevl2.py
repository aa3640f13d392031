"""What the ``keye-vl-2.0-30b-a3b`` configuration brings to the benchmark:
its configuration file against the published keys, its roofline
arithmetic, its plain reference on cases small enough to reason about, its
readers on a recorded excerpt of a chip run (PR 31's traced run of
``keyevl2.train-16k``), and a rehearsal of the cell on the CPU through the
real control flow."""

import importlib
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, modelcfg_keyevl2 as mc
from benchmark import roofline_keyevl2 as rk

DATA = manifest.HERE / "tests" / "data"
CELL = "keyevl2.train-16k"
NAME = "keye-vl-2.0-30b-a3b"
CFG = mc.load(NAME)

# The catalog's ``config`` of the architecture, as published.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


# ------------------------------------------------------------- configuration

def test_configuration_file_holds_the_published_keys():
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert sorted(raw["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in raw["reduced"]:
            assert raw[key] == value, key
    # the floors: a whole period and four more layers, eight experts, an
    # eighth of the vocabulary
    assert 5 <= raw["num_hidden_layers"] <= 48
    assert 8 <= raw["num_experts"] <= 128
    assert raw["vocab_size"] * 8 >= 151936
    assert {"qk_norm", "indexer_rotary", "indexer_scale", "chunk_sizes",
            "training_recipe", "init"} <= set(raw["assumed"])
    assert "eight chips" in raw["deployment"]


def test_parameter_count_is_the_files():
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    count = mc.param_count(CFG)
    said = raw["parameters"]
    assert count["total"] == said["total"]
    assert count["layer"] == said["per_layer"] == 96899328
    assert count["experts_held"] == said["per_layer_experts_held"]
    # 16 B a parameter: two thirds of the chip, before activations
    assert 0.25 * 16e9 < 16 * count["total"] < 0.7 * 16e9


def test_program_kwargs_make_the_registry_model():
    kw = mc.program_kwargs(CFG, 16384)
    assert (kw["dim"], kw["n_heads"], kw["n_kv_heads"],
            kw["attn_head_dim"]) == (2048, 32, 4, 128)
    assert (kw["moe_experts"], kw["moe_experts_held"], kw["moe_top_k"],
            kw["ffn_hidden"]) == (128, 16, 8, 768)
    assert (kw["index_heads"], kw["index_dim"], kw["index_topk"]) \
        == (16, 64, 2048)
    assert kw["rope_theta"] == 1e7 and kw["norm_eps"] == 1e-6


# ------------------------------------------------------------------ roofline

def test_selected_pairs_are_counted_pair_by_pair():
    for t, k in ((5, 2), (16, 16), (16, 40), (100, 7)):
        want = sum(min(i + 1, k) for i in range(t))
        assert rk.selected_pairs(t, k) == want
        assert rk.selected_pairs(t, k, 3, 2) == sum(
            min(i + 1, k) for i in range(3, 5))
        assert rk.causal_pairs(t) == t * (t + 1) // 2
        assert rk.causal_pairs(t, 3, 2) == 4 + 5


def test_the_cells_numbers():
    """The workload file's arithmetic: 31.5 M of 134.2 M causal pairs are
    selected (23%), so a pass over the whole triangle cannot read a quarter
    of the selected-attention roofline."""
    sel, tri = rk.selected_pairs(16384, 2048), rk.causal_pairs(16384)
    assert round(sel / 1e6, 1) == 31.5 and round(tri / 1e6, 1) == 134.2
    assert 0.23 < sel / tri < 0.24
    flops, nbytes = rk.sel_attn_fwd(1, 16384, 32, 4, 128, 2048)
    assert flops == 4 * 32 * 128 * sel                  # 0.52 TFLOP
    assert 0.51e12 < flops < 0.53e12
    assert rk.sel_attn_bwd(1, 16384, 32, 4, 128, 2048)[0] == 2.5 * flops
    # the indexer's scores: 0.28 TFLOP over the causal half
    assert 0.27e12 < rk.index_scores(1, 16384, 16384, 16, 64)[0] < 0.29e12
    # row blocks add up to the whole
    parts = [rk.index_scores(1, 1024, r0 + 1024, 16, 64)[0]
             for r0 in range(0, 16384, 1024)]
    assert sum(parts) == rk.index_scores(1, 16384, 16384, 16, 64)[0]
    parts = [rk.head_probs(1, 1024, r0 + 1024, 32, 4, 128, 2048)[0]
             for r0 in range(0, 16384, 1024)]
    assert sum(parts) == 2 * 32 * 128 * sel


def test_model_flops_per_token():
    per_token = rk.train_flops_per_token(CFG, 16384)
    # ~36 TFLOP a step of 16384 tokens
    assert 30e12 < per_token * 16384 < 40e12
    # held experts at an even routing: one of the eight a token is sent to
    params = rk.matmul_params(CFG)
    more = rk.matmul_params(dict(CFG, experts_held=32))
    assert more - params == pytest.approx(
        CFG["layers"] * 3 * 2048 * 768 * 8 * 16 / 128)
    # below the top-k every key is selected: the causal count
    short = dict(CFG, index_topk=1 << 20)
    assert rk.train_flops_per_token(short, 16384) > per_token


# ----------------------------------------------------------------- reference

def test_reference_selection_keeps_the_top_k_and_breaks_ties_low():
    import jax.numpy as jnp

    from benchmark import reference_keyevl2 as ref
    scores = jnp.asarray([[5.0, 9.0, 9.0, 9.0],
                          [1.0, 2.0, 3.0, 4.0],
                          [7.0, 7.0, 7.0, 7.0]])
    keep = np.asarray(ref.selection(scores, jnp.asarray([1, 3, 3]), 2))
    assert keep.tolist() == [[True, True, False, False],    # position 1: all
                             [False, False, True, True],
                             [True, True, False, False]]    # ties: the lower


def test_reference_expert_layer_of_one_token_by_hand():
    import jax.numpy as jnp

    from benchmark import reference_keyevl2 as ref
    rng = np.random.default_rng(0)
    d, f, e = 3, 2, 4
    x = rng.normal(size=(1, d))
    lw = {"w_router": rng.normal(size=(d, e)),
          "w_gate": rng.normal(size=(e, d, f)),
          "w_up": rng.normal(size=(e, d, f)),
          "w_down": rng.normal(size=(e, f, d))}
    r = np.exp(x @ lw["w_router"])[0]
    r /= r.sum()
    top = np.argsort(-r)[:2]
    gates = r[top] / r[top].sum()
    silu = lambda a: a / (1 + np.exp(-a))
    ffn = lambda i: (silu(x @ lw["w_gate"][i]) * (x @ lw["w_up"][i])) \
        @ lw["w_down"][i]
    jl = {n: jnp.asarray(a, jnp.float32) for n, a in lw.items()}
    whole = ref.experts(jnp.asarray(x, jnp.float32), jl, {"top_k": 2}, e, 0)
    want = sum(g * ffn(i) for g, i in zip(gates, top))
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-6)
    # a share that holds only the lesser of the two experts gives its part
    lesser = int(top[1])
    share = {n: (a if n == "w_router" else a[lesser:lesser + 1])
             for n, a in jl.items()}
    part = ref.experts(jnp.asarray(x, jnp.float32), share, {"top_k": 2}, 1,
                       lesser)
    np.testing.assert_allclose(part, gates[1] * ffn(lesser), rtol=1e-5,
                               atol=1e-6)


# ------------------------------------------------------------------- readers

def metric(name, art):
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(art, spec.get("args", {}))


NEW = ("moe_ms.keyevl2", "attn_index_ms.keyevl2", "attn_ms.keyevl2",
       "sel_attn_roofline.keyevl2", "index_scores_roofline.keyevl2",
       "attn_probs_roofline.keyevl2", "mfu.keyevl2", "moe_rows_max.keyevl2",
       "moe_gmm_roofline.keyevl2", "moe_rows_drift.keyevl2",
       "moe_groups_fed.keyevl2")


def test_the_cell_lists_its_metrics():
    bench = manifest.load()
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    mine = {m["name"] for m in manifest.cell_metrics(bench, cell,
                                                     "per_layer")}
    assert set(NEW) <= mine
    assert {"step_ms", "device_idle.train", "optimizer_ms.train",
            "head_loss_ms.train", "programs_built.train"} <= mine
    assert not {"mfu", "flash_roofline.train", "mfu.phi4flash"} & mine
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_program_has_nothing(name):
    """The parent commit, another configuration, a rehearsal or an
    untraced run: None, never an exception."""
    from benchmark import modelcfg
    base = {"kind": "train", "cell": "no-such-run", "chips": 1,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "job": {"seq": 16384}, "tok_s": None, "trace": None,
            "trace_events": None, "task": {"step_walls_s": [2.0]}}
    assert metric(name, dict(base, model_cfg=CFG)) is None
    dense = dict(base, model_cfg=modelcfg.load("mistral-7b-v0.3"),
                 tok_s=30000.0, trace={"planes": [{
                     "name": "/device:TPU:0", "lines": [{
                         "name": "XLA Ops", "events": [
                             ["%fusion.1 = f32[8] fusion()", 0, 5]]}]}]})
    assert metric(name, dense) is None


@pytest.fixture(scope="module")
def traced():
    """Artifacts as ``drivers/train_keyevl2.py`` hands them to the readers,
    with an excerpt of PR 31's traced run (seed 2500000019, the first of
    its six fenced steps: every selected-attention call of the step, the
    first 32 ``%attn_probs_sel`` and ``%attn_index_scores`` calls, two
    fusions, the step and module lines; names cut after the operands'
    first characters)."""
    trace = json.loads((DATA / "keyevl2_trace_excerpt.json").read_text())
    return {"kind": "train", "cell": CELL, "chips": 1, "trace": trace,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "model_cfg": CFG, "job": {"seq": 16384},
            "tok_s": 16384 / 1.6837}


def test_kernel_rooflines_on_the_recorded_run(traced):
    """What the run itself printed over all six steps: 11.72 %, 45.72 %
    and 16.03 %; the excerpt's subset reads the same to a tenth. None near
    100 %: the grids compute the whole triangle for 23 % of it."""
    assert metric("sel_attn_roofline.keyevl2", traced) == pytest.approx(
        11.72, abs=0.01)
    assert metric("index_scores_roofline.keyevl2", traced) == pytest.approx(
        45.80, abs=0.05)
    assert metric("attn_probs_roofline.keyevl2", traced) == pytest.approx(
        16.09, abs=0.05)


def test_the_recorded_calls_are_those_of_the_six_layers(traced):
    from benchmark import traceread
    from benchmark.readers import keyevl2_roofline as kr
    ops = [name for plane in traceread.device_planes(traced["trace"])
           for name, _, _ in traceread.op_events(plane)]
    count = lambda prefix: sum(n.startswith(prefix) for n in ops)
    # a step: the forward runs twice a layer (remat), dq and dk/dv once
    assert count("%attn_fwd_sel") == 12
    assert count("%attn_bwd_dq_sel") == count("%attn_bwd_dkv_sel") == 6
    fwd = next(n for n in ops if n.startswith("%attn_fwd_sel"))
    assert kr.sel_attn_work(fwd, CFG) == rk.sel_attn_fwd(
        1, 16384, 32, 4, 128, 2048)
    dq = next(n for n in ops if n.startswith("%attn_bwd_dq_sel"))
    assert kr.sel_attn_work(dq, CFG) == (0, 0)   # counted on the dk/dv call
    dkv = next(n for n in ops if n.startswith("%attn_bwd_dkv_sel"))
    assert kr.sel_attn_work(dkv, CFG) == rk.sel_attn_bwd(
        1, 16384, 32, 4, 128, 2048)
    # a scores call names its row block by its result: 1024 rows against
    # the keys up to its last row
    scores = next(n for n in ops if n.startswith("%attn_index_scores"))
    assert "f32[1,1024,3072]" in scores
    assert kr.index_scores_work(scores, CFG) == rk.index_scores(
        1, 1024, 3072, 16, 64)


def test_mfu_of_the_recorded_rate(traced):
    got = metric("mfu.keyevl2", traced)
    assert got == pytest.approx(
        100 * 35.08e12 / 1.6837 / 197e12, rel=2e-3)
    assert 5 < got < 20


def test_scope_metrics_read_the_new_scopes():
    from benchmark.readers import scope_ms
    table = {"attn": 6e8, "attn_fwd_sel": 12e8, "attn_bwd_dq_sel": 6e8,
             "attn_bwd_dkv_sel": 6e8, "attn_index": 1e8, "attn_select": 2e8,
             "attn_index_loss": 3e8, "attn_index_scores": 1e8,
             "attn_probs_sel": 2e8, "moe": 1e8, "moe_experts": 5e8,
             "moe_gmm": 1e8, "moe_gmm_t": 1e8, "moe_tgmm": 1e8,
             "optimizer": 1e8}
    for name, want in (("attn_ms.keyevl2", 500.0),
                       ("attn_index_ms.keyevl2", 150.0),
                       ("moe_ms.keyevl2", 150.0)):
        spec = manifest.metric_file(name)["args"]
        art = {"task": {"step_walls_s": [2.0] * 6},
               "scope_self_ns:" + ",".join(spec["known"]): table}
        assert scope_ms.read(art, spec) == pytest.approx(want)


def test_the_grouped_kernels_are_read_under_their_own_scopes():
    """The grouped kernels' calls carry their scope on the path, forward
    and backward (XLA's own grouped kernel left its path behind and was
    read by the op_name it gave, ``ragged-dot-none:``: my chip run, PR 31,
    257 ms a step under no scope until then)."""
    from benchmark import scoperead
    spec = manifest.metric_file("moe_ms.keyevl2")["args"]
    known = tuple(spec["known"])
    block = "jit(step)/transpose(jvp(Transformer))/layers/block/moe/"
    for scope in ("moe_gmm", "moe_gmm_t", "moe_tgmm"):
        assert scope in spec["scopes"]
        assert scoperead.scope_of(
            f"{block}moe_experts/{scope}/pallas_call", known) == scope
    assert scoperead.scope_of(f"{block}moe_experts/mul", known) \
        == "moe_experts"
    # one table a run: the three scope metrics share the list they read by
    for other in ("attn_ms.keyevl2", "attn_index_ms.keyevl2"):
        assert manifest.metric_file(other)["args"]["known"] == spec["known"]


def test_grouped_matmul_roofline_counts_the_rows_routed(monkeypatch):
    """Work from ``moe:rows_held``, not from the buffer a call was handed:
    1024 rows a call here (98304 over 6 layers x 16 chunks of 8192-row
    buffers), compute-bound, 16.1 us at the v5e's peak; the three kinds of
    call alike."""
    from benchmark.readers import timeline
    calls = [
        "%moe_gmm.7 = bf16[8192,768] custom-call(s32[16] %a, s32[16] %b",
        "%moe_gmm_t.3 = bf16[8192,2048] custom-call(s32[16] %a, s32[16]",
        "%moe_tgmm.2 = bf16[16,2048,768] custom-call(s32[16] %a, s32[16",
        "%fusion.9 = bf16[8192,768] fusion(bf16[8192,768] %moe_gmm.7)"]
    art = {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           "job": {"seq": 16384, "batch": 1}, "trace": {"planes": [{
               "name": "/device:TPU:0", "lines": [{
                   "name": "XLA Ops",
                   "events": [[c, 1000 * i, 100_000]
                              for i, c in enumerate(calls)]}]}]}}
    counters = {}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    assert metric("moe_gmm_roofline.keyevl2", art) is None   # no counter
    counters["moe:rows_held"] = 98304
    flops, nbytes = rk.grouped_matmul(1024, 16, 16, 2048, 768)
    assert flops == 2 * 1024 * 2048 * 768
    assert nbytes == 1024 * 2816 * 2 + 2048 * 768 * 2
    least = max(flops / 197e12, nbytes / 819e9)
    assert metric("moe_gmm_roofline.keyevl2", art) == pytest.approx(
        100 * least / 100e-6, rel=1e-3)
    # a program without the kernels (the parent): nothing to read
    art["trace"]["planes"][0]["lines"][0]["events"] = [[calls[3], 0, 5]]
    assert metric("moe_gmm_roofline.keyevl2", art) is None


def test_grouped_matmul_roofline_on_the_recorded_calls(monkeypatch):
    """The first 192 grouped calls (all forward) of PR 31's traced run
    (seed 3400000014, `moe:rows_held` 93,900; names cut after 110
    characters): 17.43 % of their roofline, where the run itself printed
    18.21 % over all 6,912 calls of its six steps."""
    from benchmark.readers import timeline
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": {"moe:rows_held": 93900}})
    trace = json.loads((DATA / "keyevl2_gmm_excerpt.json").read_text())
    art = {"kind": "train", "cell": CELL, "chips": 1, "trace": trace,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           "model_cfg": CFG, "job": {"seq": 16384}}
    assert metric("moe_gmm_roofline.keyevl2", art) == pytest.approx(
        17.43, abs=0.01)


def test_rows_max_is_the_fullest_expert_over_the_mean(monkeypatch):
    from benchmark.readers import timeline, timeline_counter
    counters = {"moe:rows_held": 98304, "moe:rows_max_expert": 1536,
                "moe:experts_held": 16}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    spec = manifest.metric_file("moe_rows_max.keyevl2")["args"]
    got = timeline_counter.read({"model_cfg": {"layers": 6}}, spec)
    assert got == pytest.approx(1536 / 1024)        # 98304 / (16 x 6)
    # what the window's training did to the routing, from its last step:
    # rows against step 1's, and the (chunk, held expert) pairs still fed
    counters.update({"moe:rows_held_last": 110000, "moe:groups_last": 1536,
                     "moe:groups_fed_last": 1500})
    for name, want in (("moe_rows_drift.keyevl2", 110000 / 98304),
                       ("moe_groups_fed.keyevl2", 1500 / 1536)):
        spec = manifest.metric_file(name)["args"]
        assert timeline_counter.read({}, spec) == pytest.approx(want)


# ------------------------------------------------------------------- a run

TINY_LIMITS = {"loss_gap": 0.2, "index_loss_gap": 0.05,
               "grad_median_gap": 0.05, "grad_norm_gap": 0.5,
               "param_change_gap": 0.5}


def drive(**over):
    from benchmark.drivers import train_keyevl2
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=2.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(CELL)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return train_keyevl2.run(manifest.cell(bench, CELL), wl, args,
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
    assert all(x > 0 for x in task["index_losses_check"])
    # two of eight experts held, two a token: about a quarter of the
    # routed rows of the two tiny layers land here
    assert 0 < task["moe_rows_held"] < 2 * 64 * 2
    assert task["moe_rows_max_expert"] <= 64


def test_the_cell_reports_its_metrics(sound_run):
    bench = manifest.load()
    got = manifest.read_layer_metrics(bench, manifest.cell(bench, CELL),
                                      sound_run["artifacts"])
    # no trace and no TPU in a rehearsal: the host-side metrics only, and
    # every device reader returns None instead of raising
    assert {"launch_s.train", "task_init_s", "moe_rows_max.keyevl2"} \
        <= set(got)
    assert not {"mfu.keyevl2", "sel_attn_roofline.keyevl2"} & set(got)


def test_int8_control_runs_the_other_lane(sound_run):
    """The control walks the same flow on the int8 lane and reads other
    numbers. (How much wider is read on the chip: a 64-wide model in
    bfloat16 rounds as hard as the int8 grid does.)"""
    control = drive(control="int8")
    gaps = lambda r: r["artifacts"]["task"]["compared"]
    assert set(gaps(control)) == set(TINY_LIMITS)
    assert all(gaps(control)[k] != gaps(sound_run)[k] for k in TINY_LIMITS)
