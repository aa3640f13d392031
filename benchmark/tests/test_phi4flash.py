"""What the ``phi-4-mini-flash-reasoning`` configuration brings to the
benchmark: its plain reference against a two-token case computed by hand
(numpy, the equations of the reference's docstring written out), its
roofline arithmetic, its readers on a recorded excerpt of a chip run
(PR 27's first traced run of ``phi4flash.train-8k``), and a rehearsal of
the cell on the CPU through the real control flow."""

import json
import math
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, modelcfg_phi4flash as mc, roofline
from benchmark import roofline_ssm as rs

DATA = manifest.HERE / "tests" / "data"
CELL = "phi4flash.train-8k"
CFG = mc.load("phi-4-mini-flash-reasoning")


# ----------------------------------------------------------------- reference

def silu(x):
    return x / (1 + np.exp(-x))


def test_scan_of_two_tokens_by_hand():
    import jax.numpy as jnp

    from benchmark import reference_phi4flash as ref
    rng = np.random.default_rng(0)
    e, n = 3, 2
    x, dt = rng.normal(size=(2, e)), rng.uniform(0.01, 0.2, size=(2, e))
    a = -rng.uniform(0.5, 2.0, size=(e, n))
    b, c = rng.normal(size=(2, n)), rng.normal(size=(2, n))
    d = rng.normal(size=(e,))
    h1 = (dt[0] * x[0])[:, None] * b[0][None, :]              # h_0 = 0
    y1 = h1 @ c[0] + d * x[0]
    h2 = np.exp(dt[1][:, None] * a) * h1 \
        + (dt[1] * x[1])[:, None] * b[1][None, :]
    y2 = h2 @ c[1] + d * x[1]
    got = ref.selective_scan(*(jnp.asarray(v, jnp.float32)
                               for v in (x, dt, a, b, c, d)))
    np.testing.assert_allclose(got, np.stack([y1, y2]), rtol=2e-5, atol=1e-6)


def test_causal_convolution_of_two_tokens_by_hand():
    import jax.numpy as jnp

    from benchmark import reference_phi4flash as ref
    xs = np.array([[1.0, -2.0], [0.5, 3.0]])
    w = np.array([[9.0, 9.0], [9.0, 9.0], [0.25, -1.0], [2.0, 0.5]])
    bias = np.array([0.1, -0.1])
    want = np.stack([w[3] * xs[0], w[2] * xs[0] + w[3] * xs[1]]) + bias
    got = ref.causal_conv(*(jnp.asarray(v, jnp.float32)
                            for v in (xs, w, bias)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("window", [None, 1])
def test_differential_attention_of_two_tokens_by_hand(window):
    """Two tokens, one query pair over one K/V pair, head size 2. Token 0
    sees itself only, so both softmaxes are 1 and a_0 = (1 - lam) v_0
    before the norm; token 1 weighs the two keys (or, under a window of
    one key, sees itself only)."""
    import jax.numpy as jnp

    from benchmark import reference_phi4flash as ref
    rng = np.random.default_rng(1)
    hd, index = 2, 3
    q, k, v = (rng.normal(size=(2, 2, hd)) for _ in range(3))
    lw = {n: rng.normal(size=(hd,)) * 0.3 for n in
          ("lq1", "lk1", "lq2", "lk2")}
    lw.update(subln=rng.normal(size=(2 * hd,)),
              wo=rng.normal(size=(2 * hd, 3)), bo=rng.normal(size=(3,)))
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = math.exp(lw["lq1"] @ lw["lk1"]) - math.exp(lw["lq2"] @ lw["lk2"]) \
        + lam0
    vv = v.reshape(2, 2 * hd)                                 # [v_1, v_2]
    rows = []
    for t in range(2):
        keys = [t] if (t == 0 or window == 1) else [0, 1]
        halves = []
        for j in range(2):
            s = np.array([q[t, j] @ k[u, j] for u in keys]) / math.sqrt(hd)
            p = np.exp(s - s.max())
            p /= p.sum()
            halves.append(sum(pu * vv[u] for pu, u in zip(p, keys)))
        a = halves[0] - lam * halves[1]
        a = a / np.sqrt(np.mean(a * a) + 1e-5) * lw["subln"] * (1 - lam0)
        rows.append(a @ lw["wo"] + lw["bo"])
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    got = ref.diff_attention(f32(q), f32(k), f32(v),
                             {n: f32(x) for n, x in lw.items()}, index,
                             window, {"eps": 1e-5})
    np.testing.assert_allclose(got, np.stack(rows), rtol=2e-5, atol=1e-6)


def test_layer_norm_mlp_and_gmu_by_hand():
    import jax.numpy as jnp

    from benchmark import reference_phi4flash as ref
    rng = np.random.default_rng(2)
    u = rng.normal(size=(2, 4))
    scale, bias = rng.normal(size=(4,)), rng.normal(size=(4,))
    mean = u.mean(-1, keepdims=True)
    var = ((u - mean) ** 2).mean(-1, keepdims=True)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    np.testing.assert_allclose(
        ref.layer_norm(f32(u), f32(scale), f32(bias), 1e-5),
        (u - mean) / np.sqrt(var + 1e-5) * scale + bias, rtol=1e-5)
    w1, w2 = rng.normal(size=(4, 6)), rng.normal(size=(3, 4))
    gate, up = (u @ w1)[:, :3], (u @ w1)[:, 3:]               # gate first
    np.testing.assert_allclose(ref.mlp(f32(u), f32(w1), f32(w2)),
                               (up * silu(gate)) @ w2, rtol=2e-5)
    m, wg, wo = rng.normal(size=(2, 5)), rng.normal(size=(4, 5)), \
        rng.normal(size=(5, 4))
    np.testing.assert_allclose(
        ref.gmu(f32(u), f32(m), {"w_gate": f32(wg), "w_out": f32(wo)}),
        (m * silu(u @ wg)) @ wo, rtol=2e-5)


# ------------------------------------------------------------------ roofline

def test_configuration_file_holds_the_published_keys():
    raw = json.loads((manifest.HERE / "configs" /
                      "phi-4-mini-flash-reasoning.json").read_text())
    published = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 10240, "layer_norm_eps": 1e-05,
                 "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "model_type": "phi4flash", "num_attention_heads": 40,
                 "num_hidden_layers": 32, "num_key_value_heads": 20,
                 "resid_pdrop": 0, "sliding_window": 512,
                 "tie_word_embeddings": True, "mlp_bias": False,
                 "lm_head_bias": False, "vocab_size": 200064}
    differ = {k for k, v in published.items() if raw[k] != v}
    assert differ == set(raw["reduced"]) == {"num_hidden_layers",
                                             "vocab_size"}
    assert raw["num_hidden_layers"] == len(raw["layer_kinds"]) == 6
    assert raw["vocab_size"] * 8 == published["vocab_size"]
    count = mc.param_count(CFG)
    assert count["total"] == raw["parameters"]["total"] == 697094272
    assert {k: count[k] for k in raw["parameters"]["by_layer_kind"]} \
        == raw["parameters"]["by_layer_kind"]
    assert 11.1e9 < 16 * count["total"] < 11.2e9


def test_a_window_never_counts_more_than_the_causal_call():
    dims = (1, 8192, 40, 20, 64)
    full_f, full_b = rs.diff_attn_fwd(*dims), rs.diff_attn_bwd(*dims)
    for window in (1, 64, 512, 8191, 8192, 10000):
        f, b = rs.diff_attn_fwd(*dims, window), rs.diff_attn_bwd(*dims, window)
        assert f[0] <= full_f[0] and b[0] <= full_b[0]
        assert f[1] == full_f[1] and b[1] == full_b[1]
    assert rs.diff_attn_fwd(*dims, 8192) == full_f
    assert rs.diff_attn_fwd(*dims, 512)[0] * 8 < full_f[0]


@pytest.mark.parametrize("t, window", [(7, 3), (16, 16), (16, 40), (9, 1)])
def test_visited_keys_is_the_count(t, window):
    assert rs.visited_keys(t, window) == sum(
        min(q + 1, window) for q in range(t))
    assert rs.visited_keys(t, None) == t * (t + 1) // 2


def test_attention_counts_the_published_head_size_and_the_wide_v():
    """One key seen by one query in one score map: q.k over 64 and p v
    over 128, two FLOPs each; the backward adds dP, dV (128) and dQ, dK
    (64) and does not count the scores again."""
    assert rs.diff_attn_fwd(1, 1, 1, 2, 64)[0] == 2 * 64 + 2 * 128
    assert rs.diff_attn_bwd(1, 1, 1, 2, 64)[0] == 2 * 2 * 128 + 2 * 2 * 64


def test_scan_bytes_and_bound():
    """xc (bf16), dt and y (f32) once each, B and C beside them; nothing
    for the state. On the peaks the benchmark has the scan is bound by
    memory, by a wide margin."""
    flops, nbytes = rs.ssm_scan_fwd(1, 8192, 5120, 16)
    assert nbytes == 8192 * (5120 * (2 + 4 + 4) + 2 * 16 * 2)
    assert flops == 8192 * 5120 * (7 * 16 + 3)
    state = 8192 * 5120 * 16 * 4
    assert nbytes < state / 6                  # the [T, E, N] state is not in
    bflops, bbytes = rs.ssm_scan_bwd(1, 8192, 5120, 16)
    assert bflops == 2 * flops and nbytes < bbytes < 2 * nbytes
    peak = roofline.peaks("TPU v5 lite")
    for work in ((flops, nbytes), (bflops, bbytes)):
        seconds, bound = roofline.least_seconds(*work, peak)
        assert bound == "memory" and seconds > 10 * work[0] / peak["bf16_flops"]


def test_model_flops_per_token():
    per_token = rs.train_flops_per_token(CFG, 8192)
    assert per_token == pytest.approx(4.585e9, rel=2e-3)
    assert 6 * rs.matmul_params(CFG) < per_token < 6.7 * rs.matmul_params(CFG)
    # a shorter sequence pays less for the full and cross layers only
    assert rs.train_flops_per_token(CFG, 512) < per_token
    no_window = dict(CFG, window=8192)
    assert rs.train_flops_per_token(no_window, 8192) > per_token


# ------------------------------------------------------------------- readers

@pytest.fixture(scope="module")
def traced():
    """Artifacts as ``drivers/train_hybrid.py`` hands them to the readers,
    with the kernels' operations of the six traced steps of PR 27's first
    traced run (seed 2700000012: every ``%ssm_scan*`` and ``%attn*`` custom
    call, a few fusions, the step and module lines)."""
    trace = json.loads((DATA / "phi4flash_trace_excerpt.json").read_text())
    return {"kind": "train", "cell": CELL, "chips": 1, "trace": trace,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "model_cfg": CFG, "job": {"seq": 8192}, "tok_s": 20534.0}


def metric(name, art):
    import importlib
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(art, spec.get("args", {}))


def test_kernel_rooflines_on_the_recorded_run(traced):
    """What the run itself printed: 15.24 % and 28.92 %, neither near
    100 %."""
    assert metric("ssm_scan_roofline.train", traced) == pytest.approx(
        15.243, abs=0.01)
    assert metric("attn_roofline.phi4flash", traced) == pytest.approx(
        28.919, abs=0.01)


def test_the_recorded_calls_are_those_of_the_six_layers(traced):
    from benchmark import traceread
    from benchmark.readers import hybrid_roofline as hr
    ops = [name for plane in traceread.device_planes(traced["trace"])
           for name, _, _ in traceread.op_events(plane)]
    count = lambda prefix: sum(n.startswith(prefix) for n in ops) // 6
    # a step: four forward scans (two recomputed under remat), two
    # backward; one windowed and two causal attention calls, each forward,
    # dq and dk/dv
    assert count("%ssm_scan_fwd") == 4 and count("%ssm_scan_bwd") == 2
    assert count("%attn_fwd_win") == count("%attn_bwd_dq_win") == 1
    assert count("%attn_fwd.") == count("%attn_bwd_dkv.") == 2
    fwd = next(n for n in ops if n.startswith("%attn_fwd."))
    win = next(n for n in ops if n.startswith("%attn_fwd_win"))
    assert hr.attn_work(fwd, CFG) == rs.diff_attn_fwd(1, 8192, 40, 20, 64)
    assert hr.attn_work(win, CFG) == rs.diff_attn_fwd(1, 8192, 40, 20, 64,
                                                      512)
    dq = next(n for n in ops if n.startswith("%attn_bwd_dq."))
    assert hr.attn_work(dq, CFG) == (0, 0)      # counted on the dk/dv call
    scan = next(n for n in ops if n.startswith("%ssm_scan_bwd"))
    assert hr.ssm_work(scan, CFG) == rs.ssm_scan_bwd(1, 8192, 5120, 16)


def test_mfu_of_the_recorded_rate(traced):
    got = metric("mfu.phi4flash", traced)
    assert got == pytest.approx(
        100 * 4.585e9 * 20534.0 / 197e12, rel=2e-3)
    assert 40 < got < 60


def test_readers_find_nothing_where_the_program_has_nothing(traced):
    """The parent commit, another configuration, a rehearsal or an
    untraced run: None, never an exception."""
    from benchmark import modelcfg
    dense = dict(traced, model_cfg=modelcfg.load("mistral-7b-v0.3"))
    for name in ("ssm_scan_roofline.train", "attn_roofline.phi4flash",
                 "mfu.phi4flash"):
        assert metric(name, dense) is None
        assert metric(name, dict(traced, trace=None, tok_s=None)) is None
        assert metric(name, dict(traced, device={
            "platform": "cpu", "kind": "cpu", "count": 1})) is None
    no_calls = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["%fusion.1 = f32[8] fusion()", 0,
                                        5]]}]}]}
    assert metric("ssm_scan_roofline.train",
                  dict(traced, trace=no_calls)) is None
    for name in ("ssm_ms.train", "gmu_ms.train", "attn_ms.phi4flash"):
        assert metric(name, {"task": {"step_walls_s": [0.4]},
                             "trace_events": None}) is None


def test_scope_metrics_read_the_new_scopes():
    """`scope_ms` with this PR's ``args``, on the table PR 27's traced run
    gave (scoperead.py over its six steps, ns)."""
    from benchmark.readers import scope_ms
    table = {"mlp": 1017422637.8, "ssm": 259653948.1, "gmu": 70473783.4,
             "ssm_scan_fwd": 46278427.7, "ssm_scan_bwd": 120184104.1,
             "ssm_conv": 7167663.1, "attn_swa": 163.2e6,
             "attn_full": 221.5e6, "attn_cross": 165.9e6}
    for name, want in (("ssm_ms.train", 72.21), ("gmu_ms.train", 11.75),
                       ("attn_ms.phi4flash", 91.77)):
        spec = manifest.metric_file(name)["args"]
        art = {"task": {"step_walls_s": [0.4] * 6},
               "scope_self_ns:" + ",".join(spec["known"]): table}
        assert scope_ms.read(art, spec) == pytest.approx(want, abs=0.05)


# ------------------------------------------------------------------- a run

TINY_LIMITS = {"loss_gap": 0.01, "grad_median_gap": 0.05,
               "grad_norm_gap": 0.5, "param_change_gap": 0.5}


def drive(**over):
    from benchmark.drivers import train_hybrid
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=2.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(CELL)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return train_hybrid.run(manifest.cell(bench, CELL), wl, args,
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
    assert sound_run["artifacts"]["model_cfg"]["kinds"] == [
        "mamba", "swa", "mamba", "full", "gmu", "cross"]


def test_the_cell_reports_its_metrics(sound_run):
    bench = manifest.load()
    got = manifest.read_layer_metrics(bench, manifest.cell(bench, CELL),
                                      sound_run["artifacts"])
    # no trace and no TPU in a rehearsal: the host-side metrics only, and
    # every device reader returns None instead of raising
    assert {"launch_s.train", "task_init_s"} <= set(got)
    assert not {"mfu", "flash_roofline.train"} & set(got)


def test_int8_control_reads_wider_gaps(sound_run):
    control = drive(control="int8")
    gaps = lambda r: r["artifacts"]["task"]["compared"]
    assert gaps(control)["loss_gap"] != gaps(sound_run)["loss_gap"]
    assert gaps(control)["grad_median_gap"] \
        > 2 * gaps(sound_run)["grad_median_gap"]
