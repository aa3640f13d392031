"""Benchmark: ResNet-50 data-parallel train step on the real TPU chip.

North star (BASELINE.md): ≥55% MFU, images/sec/chip primary. This bench
runs the full training step (forward + backward + SGD update + BatchNorm
stats) on synthetic ImageNet-shaped data in bf16 and prints ONE JSON line::

    {"metric": "resnet50_mfu", "value": ..., "unit": ..., "vs_baseline": ...}

``vs_baseline`` is MFU / 0.55 (≥1.0 beats the target). Peak-FLOPs table per
chip generation, keyed by the ``device_kind`` jax reports
(``tony_tpu.benchmark``); without a chip, or on one not in the table, the
script fails — it never reports a CPU run.

One process: this script imports jax, holds the chip itself for its whole
run and spawns nothing (a chip belongs to one process at a time — a child
that needed it would fail or hang). It exits non-zero if any leg recorded
a ``*_error``.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import jax

from tony_tpu.benchmark import (best_window_time, peak_flops,
                                run_resnet_bench)
from tony_tpu.util import enable_compile_cache


def main() -> int:
    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    # Batch 384: peak of the r3 sweep on v5e (128→0.247, 256→0.266,
    # 384→0.295, 512→0.292, 640→0.281, 768→0.275 MFU). The step profile
    # says why bigger stops helping: ~51% of step time is BatchNorm
    # statistics/backward reductions (bandwidth-bound, linear in batch),
    # ~45% conv fusions, ~2% maxpool backward — past the MXU's saturation
    # point extra batch just adds HBM traffic.
    batch = int(os.environ.get("BENCH_BATCH", "384" if on_tpu else "8"))
    image = int(os.environ.get("BENCH_IMAGE", "224" if on_tpu else "64"))
    # 20 steps/window: one dispatch and one readback per window.
    steps = int(os.environ.get("BENCH_STEPS", "20" if on_tpu else "4"))

    # Fused pallas BN(+add)(+ReLU) epilogues (VERDICT r3 #1). Tried and
    # measured SLOWER than XLA's fusions — see ROOFLINE.md: XLA already
    # runs the BN reductions at/below the standalone-kernel HBM-pass
    # lower bound, so the fused path stays flag-gated off.
    fused_bn = os.environ.get("BENCH_FUSED_BN", "0") == "1"
    # MLPerf-standard space-to-depth stem (r5): mathematically equivalent
    # 4x4/s1 stem on the 112²x12 packing. Measured on v5e at batch 384:
    # see exp/s2d_results.txt and README round-5 notes.
    s2d = os.environ.get("BENCH_S2D", "1") == "1"
    # The step construction, scanned-window protocol, fencing, and MFU
    # accounting live in tony_tpu.benchmark so the tony-submitted bench
    # job (examples/resnet_bench_job) measures the IDENTICAL thing.
    result = run_resnet_bench(batch, image, steps, s2d=s2d,
                              fused_bn=fused_bn)
    peak = peak_flops()
    # One cumulative JSON line per completed leg (the driver/judge read the
    # LAST line): the 7B leg alone compiles for minutes, and a harness
    # timeout mid-leg must not cost the already-measured numbers.
    print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_OVERLAP", "1") != "0":
        # Comm/compute overlap leg: monolithic vs bucketed-accum step on
        # the DP mesh (runs on CPU too — numerics pin; the speedup only
        # means something on hardware with async collectives).
        try:
            from tony_tpu.benchmark import run_overlap_bench
            ov = run_overlap_bench(on_tpu=on_tpu)
            result["overlap_mono_step_s"] = ov["mono_step_s"]
            result["overlap_accum_step_s"] = ov["accum_step_s"]
            result["overlap_speedup"] = ov["speedup"]
            result["overlap_n_buckets"] = ov["n_buckets"]
            result["overlap_bucket_nbytes"] = ov["bucket_nbytes"]
            result["overlap_numerics_ok"] = ov["numerics_ok"]
        except Exception as e:  # secondary metric must not sink the bench
            result["overlap_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    n_dev = len(jax.devices())
    if os.environ.get("BENCH_OVERLAP_HIER", "1") != "0" and n_dev % 2 == 0 \
            and n_dev >= 2:
        # Hierarchical ICI/DCN leg on a (simulated) 2-slice mesh: the
        # per-bucket psum_scatter-over-ICI + DCN-allreduce schedule vs the
        # same accum step with the flat single-level reduce. On one host
        # both axes are ICI — the numerics pin is real, the DCN timing
        # story needs a real multi-slice pod.
        try:
            from tony_tpu.benchmark import run_overlap_bench
            hier = run_overlap_bench(slices=2, on_tpu=on_tpu)
            flat = run_overlap_bench(slices=2, hierarchy="flat",
                                     on_tpu=on_tpu)
            result["overlap_hier_step_s"] = hier["accum_step_s"]
            result["overlap_hier_flat_step_s"] = flat["accum_step_s"]
            result["overlap_hier_numerics_ok"] = (
                hier["numerics_ok"] and flat["numerics_ok"])
            result["overlap_hier_levels"] = hier["overlap_records"][
                "accum_step"]["levels"]
        except Exception as e:
            result["overlap_hier_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    # Largest power-of-two fsdp degree (<=4) the device count divides —
    # min(4, n_dev) broke on counts like 6.
    zero3_fsdp = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    if os.environ.get("BENCH_OVERLAP_ZERO3", "1") != "0" and zero3_fsdp > 1:
        # ZeRO-3 leg: fsdp-sharded params, grads psum_scatter-ed straight
        # into the shard layout inside the accum scan.
        try:
            from tony_tpu.benchmark import run_overlap_bench
            z = run_overlap_bench(fsdp=zero3_fsdp, zero3=True,
                                  on_tpu=on_tpu)
            result["overlap_zero3_step_s"] = z["accum_step_s"]
            result["overlap_zero3_mono_step_s"] = z["mono_step_s"]
            result["overlap_zero3_numerics_ok"] = z["numerics_ok"]
            result["overlap_zero3_scatter_buckets"] = z["n_scatter_buckets"]
        except Exception as e:
            result["overlap_zero3_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    sweep_env = os.environ.get("BENCH_OVERLAP_SWEEP", "")
    if sweep_env:
        # csv of bucket-bytes thresholds, e.g. "65536,1048576,4194304" —
        # prints its own JSON line (the sweep is a tuning curve, not a
        # headline key).
        try:
            from tony_tpu.benchmark import run_overlap_sweep
            sw = run_overlap_sweep(
                tuple(int(s) for s in sweep_env.split(",") if s),
                on_tpu=on_tpu)
            print(json.dumps(sw), flush=True)
        except Exception as e:
            result["overlap_sweep_error"] = f"{type(e).__name__}: {e}"
            print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_CKPT", "1") != "0":
        # Checkpoint-plane leg (tony_tpu.ckpt): blocking save wall time vs
        # the stall an async save charges the train loop, plus the
        # bit-exact restore pin. Runs on CPU too — unlike the overlap
        # legs, the I/O-vs-compute overlap is real on any backend.
        try:
            from tony_tpu.benchmark import run_ckpt_bench
            zero3_ckpt = 2 if n_dev % 2 == 0 else 1
            ck = run_ckpt_bench(fsdp=zero3_ckpt)
            result["ckpt_state_mb"] = ck["state_mb"]
            result["ckpt_blocking_save_s"] = ck["blocking_save_s"]
            result["ckpt_async_stall_s"] = ck["async_stall_s"]
            result["ckpt_stall_vs_blocking"] = ck["stall_vs_blocking"]
            result["ckpt_overlap_ok"] = ck["overlap_ok"]
            result["ckpt_restore_exact"] = ck["restore_exact"]
        except Exception as e:  # secondary metric must not sink the bench
            result["ckpt_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_INPUT", "1") != "0":
        # Input-plane leg (tony_tpu.data): per-step wait-on-data with the
        # prefetching device iterator at depth 0/1/2 over a feed with
        # simulated I/O latency. Runs on CPU too — like the ckpt leg, the
        # feed-vs-compute overlap is real on any backend.
        try:
            from tony_tpu.benchmark import run_input_bench
            di = run_input_bench()
            result["input_stall_ms_depth0"] = di["input_stall_ms_depth0"]
            result["input_stall_ms_depth1"] = di["input_stall_ms_depth1"]
            result["input_stall_ms_depth2"] = di["input_stall_ms_depth2"]
            result["input_stall_hidden"] = di["stall_hidden"]
            result["input_per_depth"] = di["per_depth"]
        except Exception as e:  # secondary metric must not sink the bench
            result["input_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_SCHED", "1") != "0" and n_dev % 2 == 0:
        # Collective-scheduler leg (tony_tpu.parallel.sched): per-leaf vs
        # bucketed+prefetched ZeRO-3 forward gathers (exposed gather
        # time), bit-exact step numerics, and MoE a2a-under-scan vs the
        # GSPMD default. Runs on CPU too — the gather coalescing win
        # (fewer, size-targeted collectives) is real on any backend; the
        # prefetch-overlap share of it needs hardware async collectives.
        try:
            from tony_tpu.benchmark import run_sched_bench
            sc = run_sched_bench(on_tpu=on_tpu)
            result["sched_gather_per_leaf_s"] = sc["gather_per_leaf_s"]
            result["sched_gather_bucketed_s"] = sc["gather_bucketed_s"]
            result["sched_gather_speedup"] = sc["gather_speedup"]
            result["sched_gather_2x_ok"] = sc["gather_2x_ok"]
            result["sched_gather_bitexact"] = sc["gather_bitexact"]
            result["sched_zero3_bitexact"] = sc["zero3_bitexact"]
            result["sched_n_gather_buckets"] = sc["n_gather_buckets"]
            result["sched_moe_numerics_ok"] = sc.get("moe_numerics_ok")
            result["sched_moe_gspmd_s"] = sc.get("moe_gspmd_s")
            result["sched_moe_sched_s"] = sc.get("moe_sched_s")
            result["sched_collective_kinds"] = sorted(
                {r.get("kind") for r in
                 sc["collective_records"].values()})
        except Exception as e:  # secondary metric must not sink the bench
            result["sched_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_OPTIM", "1") != "0" and n_dev % 2 == 0:
        # Fused-optimizer leg (tony_tpu.ops.fused_optim): per-leaf optax
        # update vs the bucket-major fused update on the simulated
        # fsdp mesh — wall time, jaxpr op counts (O(n_leaves) vs
        # O(n_buckets) update chains), f32 bit-exact pin. Runs on CPU too:
        # the dispatch-count win is real on any backend; the HBM
        # bytes-bound floor (ROOFLINE.md) needs metal.
        try:
            from tony_tpu.benchmark import run_optim_bench
            ob = run_optim_bench(on_tpu=on_tpu)
            result["optim_optax_update_s"] = ob["optax_update_s"]
            result["optim_fused_update_s"] = ob["fused_update_s"]
            result["optim_speedup"] = ob["speedup"]
            result["optim_n_leaves"] = ob["n_leaves"]
            result["optim_n_buckets"] = ob["n_buckets"]
            result["optim_optax_jaxpr_eqns"] = ob["optax_jaxpr_eqns"]
            result["optim_fused_jaxpr_eqns"] = ob["fused_jaxpr_eqns"]
            result["optim_numerics_ok"] = ob["numerics_ok"]
        except Exception as e:  # secondary metric must not sink the bench
            result["optim_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_QUANT", "1") != "0":
        # Quantized-lane leg (tony_tpu.ops.quant): int8 matmul vs bf16
        # wall time (on CPU the MXU win can't show — the leg documents
        # that and the metal run rides the hardware debt list), int8
        # gather bytes vs the BENCH_r09 bucketed path (4x for f32
        # params, bit-exact dequant pin), and the quantized-gather loss
        # pin gating both claims.
        try:
            from tony_tpu.benchmark import run_quant_bench
            qb = run_quant_bench(on_tpu=on_tpu)
            result["quant_bf16_matmul_s"] = qb["bf16_matmul_s"]
            result["quant_matmul_s"] = qb["quant_matmul_s"]
            result["quant_matmul_speedup"] = qb["quant_matmul_speedup"]
            result["quant_kernel_bitexact"] = qb["quant_kernel_bitexact"]
            if "quant_matmul_sim_note" in qb:
                result["quant_matmul_sim_note"] = qb["quant_matmul_sim_note"]
            result["quant_gather_raw_nbytes"] = qb.get("gather_raw_nbytes")
            result["quant_gather_int8_nbytes"] = qb.get(
                "gather_int8_nbytes")
            result["quant_gather_bytes_ratio"] = qb.get(
                "gather_bytes_ratio")
            result["quant_gather_2x_fewer_ok"] = qb.get(
                "gather_2x_fewer_ok")
            result["quant_gather_roundtrip_bitexact"] = qb.get(
                "gather_roundtrip_bitexact")
            result["quant_losspin_ok"] = qb.get("losspin_ok")
            result["quant_losspin_rel"] = qb.get("losspin_rel")
        except Exception as e:  # secondary metric must not sink the bench
            result["quant_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_SERVE", "1") != "0":
        # Serving-plane leg (tony_tpu.serve): continuous vs static
        # batching under one Poisson arrival trace — tokens/s, p50/p99
        # request latency, and the token-identity gate (continuous
        # batching must be bit-transparent). CPU numbers measure engine
        # scheduling, not TPU decode (serve_sim_note); BENCH_r12.
        try:
            from tony_tpu.benchmark import run_serve_bench
            result.update(run_serve_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["serve_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_SPEC", "1") != "0":
        # Speculative-decoding leg (tony_tpu.serve.spec): draft-and-
        # verify vs the plain engine on the SAME Poisson trace as the
        # serve leg — tokens per target forward, acceptance rate by
        # draft depth k, p50/p99, and the bitwise token-identity gate.
        # CPU wall numbers measure scheduling (spec_sim_note); the
        # forward-count ratios are the machine-independent claim;
        # BENCH_r13.
        try:
            from tony_tpu.benchmark import run_spec_bench
            result.update(run_spec_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["spec_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)

    if os.environ.get("BENCH_ROUTE", "1") != "0":
        # Routed-serving leg (tony_tpu.serve PR 13): block-level prefix
        # caching + chunked prefill + the 2-replica routed fleet on a
        # shared-prefix workload mix — prefill launch/row reduction and
        # cache hit rate (the machine-independent claims), chunked
        # on/off p50/p99, routed vs single-replica throughput, and the
        # token-identity gate in every configuration. CPU wall numbers
        # measure scheduling (route_sim_note); BENCH_r14.
        try:
            from tony_tpu.benchmark import run_route_bench
            result.update(run_route_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["route_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)

    if os.environ.get("BENCH_DISAGG", "1") != "0":
        # Disaggregated prefill/decode leg (tony_tpu.serve.disagg,
        # PR 15): a decode floor absorbing a prefill burst, colocated
        # chunked vs the split gang with KV-block handoff — decode p99
        # isolation is the headline, the decode side's ZERO prefill
        # launches and the launch split are the machine-independent
        # claims, token identity gated in both configurations. CPU wall
        # numbers measure scheduling (disagg_sim_note); BENCH_r15.
        try:
            from tony_tpu.benchmark import run_disagg_bench
            result.update(run_disagg_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["disagg_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)

    if os.environ.get("BENCH_KVTIER", "1") != "0":
        # KV-memory-hierarchy leg (tony_tpu.serve PR 16): multi-turn
        # conversations on the host-offload engine (park between
        # turns, resume through the atomic import path) vs the
        # recompute engine — turn-resume latency is the headline; the
        # machine-independent claims are the prefill-row ledger (zero
        # rows for the parked-covered extent), the park hit rate, and
        # the bitwise token-identity gate. CPU wall numbers measure
        # scheduling plus saved prefill compute (kvtier_sim_note);
        # BENCH_r16.
        try:
            from tony_tpu.benchmark import run_kvtier_bench
            result.update(run_kvtier_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["kvtier_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)

    if os.environ.get("BENCH_COLDSTART", "1") != "0":
        # Replica cold-start leg (tony_tpu.ckpt.aot, PR 17): grant→
        # first-token for a cold replica (trace+compile, cache
        # populate) vs a cache-hit replica (deserialize-only — ZERO
        # fresh compiles, counter-pinned) vs a warm standby (promote +
        # first request), with the build/warm/first-token wall split
        # broken out and token identity gated bitwise across all three
        # starts. CPU compile walls understate the TPU win
        # (coldstart_sim_note); BENCH_r17.
        try:
            from tony_tpu.benchmark import run_coldstart_bench
            result.update(run_coldstart_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["coldstart_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)

    if os.environ.get("BENCH_QOS", "1") != "0":
        # Multi-tenant QoS leg (tony_tpu.serve.qos, PR 18): a victim
        # tenant's decode floor absorbing an aggressor tenant's
        # long-prompt burst, weighted-fair block budgets on vs off —
        # victim p99 under the burst is the headline; the machine-
        # independent claims are the deferral ledger (back-pressure on
        # the aggressor, zero drops, zero deferrals unbudgeted) and the
        # bitwise victim-stream gate vs an unloaded engine. CPU wall
        # numbers measure scheduling (qos_sim_note); BENCH_r18.
        try:
            from tony_tpu.benchmark import run_qos_bench
            result.update(run_qos_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["qos_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)

    if os.environ.get("BENCH_RESIZE", "1") != "0":
        # Elastic-resize leg (tony_tpu.am.resize, PR 19): the drain →
        # commit → re-gang → restore lifecycle's data-plane walls — a
        # run interrupted mid-schedule by a synchronous drain-commit
        # and an elastic restore vs the same schedule undisturbed. The
        # headline is resize_overhead_s (decomposed into commit +
        # restore); the machine-independent claim is the bitwise
        # final-state gate (resize_numerics_ok). BENCH_r19.
        try:
            from tony_tpu.benchmark import run_resize_bench
            result.update(run_resize_bench(on_tpu=on_tpu))
        except Exception as e:  # secondary metric must not sink the bench
            result["resize_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if on_tpu and os.environ.get("BENCH_LLM", "1") != "0":
        try:
            result.update(bench_llm(peak))
        except Exception as e:  # secondary metric must not sink the bench
            result["llm_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if on_tpu and os.environ.get("BENCH_LLM_GQA", "1") != "0":
        # Zero-copy GQA leg (r5): same proxy shapes, kv_heads = heads/4.
        # MFU accounting counts the SMALLER kv projections, so the delta
        # is genuine kernel efficiency, not bookkeeping (r5 measured:
        # 0.585 MHA → 0.612 GQA, +13% tokens/sec).
        prior = os.environ.get("BENCH_LLM_KV_HEADS")
        try:
            os.environ["BENCH_LLM_KV_HEADS"] = str(
                max(1, int(os.environ.get("BENCH_LLM_HEADS", "8")) // 4))
            gqa = bench_llm(peak)
            result["llm_gqa_mfu"] = gqa["llm_mfu"]
            result["llm_gqa_tokens_per_sec"] = gqa["tokens_per_sec_per_chip"]
        except Exception as e:
            result["llm_gqa_error"] = f"{type(e).__name__}: {e}"
        finally:
            if prior is None:
                os.environ.pop("BENCH_LLM_KV_HEADS", None)
            else:
                os.environ["BENCH_LLM_KV_HEADS"] = prior
        print(json.dumps(result), flush=True)
    if on_tpu and os.environ.get("BENCH_LLM_7B", "1") != "0":
        try:
            result.update(bench_llm_7b(peak))
        except Exception as e:
            result["llm_7b_error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(result), flush=True)
    if on_tpu and os.environ.get("BENCH_LLM_MOE", "1") != "0":
        # Mixtral-proxy sparse-MoE leg: 8 experts / top-2 / GQA kv=heads/4
        # at the proxy decoder shapes — measures the GShard static-capacity
        # dispatch path's single-chip efficiency.
        saved = {k: os.environ.get(k) for k in
                 ("BENCH_LLM_KV_HEADS", "BENCH_LLM_LAYERS",
                  "BENCH_LLM_SCAN", "BENCH_LLM_BATCH", "BENCH_LLM_REMAT")}
        try:
            os.environ["BENCH_LLM_KV_HEADS"] = str(
                max(1, int(os.environ.get("BENCH_LLM_HEADS", "8")) // 4))
            # 6 layers, scanned: 8 experts at the proxy dims are ~104M
            # params/layer — 12 layers of f32 adamw state exceed HBM, and
            # the 12-layer UNROLLED graph kills the AOT compile helper.
            os.environ["BENCH_LLM_LAYERS"] = \
                os.environ.get("BENCH_LLM_MOE_LAYERS", "6")
            os.environ["BENCH_LLM_SCAN"] = "1"
            # b16: the scanned layer stack keeps whole-stack bf16 copies
            # of the 8-expert weights as temps; b32 activations on top of
            # those tip 16 GB HBM.
            os.environ["BENCH_LLM_BATCH"] = \
                os.environ.get("BENCH_LLM_MOE_BATCH", "16")
            # Remat: without it the layer scan saves every layer's MoE
            # dispatch/combine tensors — gigabytes of f32 — and OOMs.
            os.environ["BENCH_LLM_REMAT"] = "1"
            moe = bench_llm(
                peak,
                moe_experts=int(os.environ.get("BENCH_LLM_MOE_EXPERTS",
                                               "8")),
                moe_top_k=int(os.environ.get("BENCH_LLM_MOE_TOPK", "2")))
            result["llm_moe_mfu"] = moe["llm_mfu"]
            result["llm_moe_tokens_per_sec"] = moe["tokens_per_sec_per_chip"]
        except Exception as e:
            result["llm_moe_error"] = f"{type(e).__name__}: {e}"
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        print(json.dumps(result), flush=True)
    failed = sorted(k for k in result if k.endswith("_error"))
    if failed:
        print(f"bench.py: {len(failed)} leg(s) failed: {failed}",
              file=sys.stderr)
        return 1
    return 0


def bench_llm_7b(peak: float) -> dict:
    """True Llama-2-7B LAYER shapes (SURVEY.md §6 config ⑤: dim 4096,
    32 heads, ffn 11008, vocab 32000), measured honestly under the 1-chip
    16 GB HBM constraint: f32 adamw state for 32 such layers needs ~100 GB
    (that is what fsdp shards on a pod), so the chip fits 2–3 layers and a
    small-L proxy over-weights the lm head ~12× vs the real model (24.5%
    of FLOPs at L=2 vs 2% at L=32).

    Protocol: run L=2 and L=3 at identical batch/seq/remat, difference the
    step times → the MARGINAL per-layer time (head/embed/overhead cancel),
    then report (a) the marginal per-layer MFU — the efficiency a 32-layer
    stack's bulk runs at — and (b) the 32-layer extrapolation
    t(32) = fixed + 32·marginal with full-model FLOPs. Round-5 measured:
    82 ms marginal layer, 61% marginal MFU, vs 51.5% raw at L=3.
    """
    import functools as _f

    import optax

    from tony_tpu import train as tr
    from tony_tpu.models import get_model

    batch = int(os.environ.get("BENCH_LLM_7B_BATCH", "16"))
    seq = int(os.environ.get("BENCH_LLM_7B_SEQ", "512"))
    dim, heads, ffn, vocab = 4096, 32, 11008, 32000
    steps = int(os.environ.get("BENCH_LLM_7B_STEPS", "10"))
    times = {}
    for layers in (2, 3):
        model = get_model(
            "llama2-7b", dim=dim, n_layers=layers, n_heads=heads,
            n_kv_heads=heads, ffn_hidden=ffn, vocab=vocab, max_seq=seq,
            attention="flash", scan_layers=False, remat=True,
            xent_chunk=1024)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch, seq), 0, vocab)
        state = tr.create_train_state(
            model, optax.adamw(1e-4), tokens, jax.random.PRNGKey(1))
        step = tr.make_train_step(
            loss_of=lambda out, b: out,
            apply_kwargs_of=lambda b: {"targets": b["x"]})

        def scan_step(state, _):
            state, metrics = step(state, {"x": tokens})
            return state, metrics["loss"]

        @_f.partial(jax.jit, donate_argnums=(0,))
        def window(state):
            state, losses = jax.lax.scan(scan_step, state, None,
                                         length=steps)
            return state, losses[-1]

        best, state, _ = best_window_time(window, state,
                                          params_of=lambda s: s.params,
                                          default_windows=2)
        times[layers] = best / steps
        del state

    marginal_s = times[3] - times[2]
    fixed_s = times[2] - 2 * marginal_s
    tokens_per_step = batch * seq
    # Per-layer matmul FLOPs (fwd+bwd = 6·params + attention seq term).
    layer_flops = (6 * (dim * dim * 4 + 3 * dim * ffn)
                   + 12 * dim * seq) * tokens_per_step
    marginal_mfu = layer_flops / marginal_s / peak
    full_layers = 32
    t32 = fixed_s + full_layers * marginal_s
    flops32 = (full_layers * layer_flops
               + 6 * vocab * dim * tokens_per_step)
    return {
        "llm_7b_marginal_layer_mfu": round(marginal_mfu, 4),
        "llm_7b_extrapolated_32l_mfu": round(flops32 / t32 / peak, 4),
        "llm_7b_raw_3l_mfu_note":
            "see README r5: small-L proxies over-weight the lm head",
        "llm_7b_batch": batch,
        "llm_7b_seq": seq,
        "llm_7b_marginal_layer_ms": round(marginal_s * 1e3, 2),
    }


def bench_llm(peak: float, moe_experts: int = 0,
              moe_top_k: int = 2) -> dict:
    """Secondary metric: a matmul-dominated Llama-style train step (the
    GSPMD graduation config ⑤'s single-chip core), same fencing rules.
    ``moe_experts`` is an explicit PARAMETER, not env: the MoE leg must
    not be able to silently convert the dense headline legs."""
    import optax

    from tony_tpu import train as tr
    from tony_tpu.models import get_model

    # r3 sweep on v5e (dim 1024, 12 layers, adamw, bf16): head_dim 64→128
    # was the big win (MXU contraction depth), 0.375→0.480 MFU; unrolling
    # the layer scan +5.6pt; batch 16 × seq 512 +4.7pt → 0.583; batch 32
    # +3.9pt → 0.622 (b64 OOMs on the f32-logits temp); flash block size
    # 128→256 +5pt → 0.673. An FFN-heavy variant (ffn 8192, BENCH_LLM_FFN)
    # measured 0.659 pre-block-win — reported via env knob, not defaulted:
    # the headline stays Llama-proportioned. heads=16 (head_dim 64) drops
    # to 0.474; seq 1024 at b8 to 0.551.
    batch = int(os.environ.get("BENCH_LLM_BATCH", "32"))
    seq = int(os.environ.get("BENCH_LLM_SEQ", "512"))
    heads = int(os.environ.get("BENCH_LLM_HEADS", "8"))
    # GQA (zero-copy through the flash kernels' index maps — r5):
    # n_kv_heads < n_heads shrinks K/V projections and kernel KV traffic.
    kv_heads = int(os.environ.get("BENCH_LLM_KV_HEADS", str(heads)))
    dim = int(os.environ.get("BENCH_LLM_DIM", "1024"))
    ffn = int(os.environ.get("BENCH_LLM_FFN", "4096"))
    layers = int(os.environ.get("BENCH_LLM_LAYERS", "12"))
    vocab = int(os.environ.get("BENCH_LLM_VOCAB", "32768"))
    remat = os.environ.get("BENCH_LLM_REMAT", "0") == "1"
    remat_policy = os.environ.get("BENCH_LLM_REMAT_POLICY") or None
    scan_layers = os.environ.get("BENCH_LLM_SCAN", "0") == "1"
    # Row-chunked fused head+CE (train.chunked_next_token_xent): the
    # [B,T,V] logits never materialize, lifting the f32-logits HBM cap
    # that limited batch to 32. 0 = plain head + next_token_loss.
    xent_chunk = int(os.environ.get("BENCH_LLM_XENT_CHUNK", "0"))
    model = get_model(
        "llama2-7b", dim=dim, n_layers=layers, n_heads=heads,
        n_kv_heads=kv_heads, ffn_hidden=ffn, vocab=vocab, max_seq=seq,
        attention=os.environ.get("BENCH_LLM_ATTN", "flash"),
        scan_layers=scan_layers, remat=remat, remat_policy=remat_policy,
        xent_chunk=xent_chunk, moe_experts=moe_experts,
        moe_top_k=moe_top_k)
    cfg = model.cfg
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (batch, seq), 0, cfg.vocab)
    state = tr.create_train_state(
        model, optax.adamw(1e-4), tokens, jax.random.PRNGKey(1))
    if xent_chunk:
        step = tr.make_train_step(
            loss_of=lambda out, b: out,
            apply_kwargs_of=lambda b: {"targets": b["x"]})
    else:
        step = tr.make_train_step(
            loss_of=lambda logits, b: tr.next_token_loss(logits, b["x"]))

    steps = int(os.environ.get("BENCH_LLM_STEPS", "20"))
    # One dispatch per timed window (see the resnet window comment).
    def scan_step(state, _):
        state, metrics = step(state, {"x": tokens})
        return state, metrics["loss"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def window(state):
        state, losses = jax.lax.scan(scan_step, state, None, length=steps)
        return state, losses[-1]

    best, state, loss = best_window_time(
        window, state, params_of=lambda s: s.params)
    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / best
    mfu = cfg.flops_per_token() * tokens_per_sec / peak
    return {
        "llm_mfu": round(mfu, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "llm_batch": batch,
        "llm_seq": seq,
        "llm_loss": float(loss),
    }


if __name__ == "__main__":
    sys.exit(main())
