# Developer/CI entry points. `make tier1` is THE gate: the exact ROADMAP.md
# tier-1 verify command (timeout, marker filter, dot accounting included) —
# run it before every push so CI never learns something you didn't.

SHELL := /bin/bash

.PHONY: tier1 tier1-verify tier1-multislice tier1-ckpt tier1-data tier1-sched tier1-optim tier1-quant tier1-analysis tier1-serve tier1-spec tier1-route tier1-conc tier1-disagg tier1-kvtier tier1-aot tier1-qos tier1-elastic tier1-publish tier1-smoke tier1-e2e tier1-slow quick test lint

# THE gate: the verbatim ROADMAP command, then the explicit multislice leg
# (hierarchical ICI/DCN + ZeRO-3 paths on the simulated 2-slice mesh), the
# checkpoint leg (crash consistency / async overlap / elastic restore),
# the data-plane leg (deterministic sharding / prefetch / iterator-state
# resume) and the collective-scheduler leg (bucketed+prefetched forward
# gathers / explicit MoE a2a / unified collective records) so a
# regression there fails the make target by name, not just as one more
# dot. Legs run SEQUENTIALLY (the no-concurrent-pytest rule: e2e timing
# tests flake under CPU contention).
tier1: tier1-verify tier1-multislice tier1-ckpt tier1-data tier1-sched tier1-optim tier1-quant tier1-analysis tier1-serve tier1-spec tier1-route tier1-conc tier1-disagg tier1-kvtier tier1-aot tier1-qos tier1-elastic tier1-publish tier1-smoke tier1-e2e

# Exact ROADMAP.md "Tier-1 verify" command, verbatim.
tier1-verify:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# Multi-slice marker leg (also inside tier1-verify's 'not slow' selection;
# standalone so the hierarchical/ZeRO-3 gate is visible and can be run
# alone while iterating on the overlap engine).
tier1-multislice:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m multislice -p no:cacheprovider -p no:xdist -p no:randomly

# Checkpoint-plane marker leg (fast, tmpdir-backed; also inside
# tier1-verify's selection) — the slow large-state async-save test rides
# tier1-slow instead.
tier1-ckpt:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'ckpt and not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# Input-data-plane marker leg (tmpdir/array-backed; also inside
# tier1-verify's selection) — deterministic sharding, shuffle RNG,
# prefetch overlap, checkpointable iterator resume.
tier1-data:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'data and not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# Collective-scheduler marker leg (also inside tier1-verify's selection) —
# forward-gather bucketing/prefetch bit-exactness, MoE explicit a2a vs
# GSPMD, pipeline-edge records, unified report("collective") schema.
tier1-sched:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'sched and not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# Fused-optimizer marker leg (also inside tier1-verify's selection) —
# bucket-major update kernels pinned vs optax, padded uneven shards,
# bucket-major grad norm/clip, leaf-major ckpt portability across
# changed fsdp topologies.
tier1-optim:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'optim and not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# Quantized-lane marker leg — int8 matmul kernel vs XLA fallback
# bit-exactness, per-channel scales, delayed-scaling windows,
# quantize-on-gather exactness + pad inertness, the LOSS-PIN gate, and
# the scale-state ckpt round-trip. Runs the FULL quant selection (slow
# included): the model loss pins and the cross-topology ckpt round-trip
# are slow-marked to keep tier1-verify inside its timeout, but this
# named leg is the lane's gate and must see all of them.
tier1-quant:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m quant -p no:cacheprovider -p no:xdist -p no:randomly

# Static-analysis marker leg (also inside tier1-verify's selection) — the
# jaxpr invariant analyzer: shipped configs clean, every rule fires on a
# seeded violation, committed step-signature pins, source lint.
tier1-analysis:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'analysis and not slow' -p no:cacheprovider -p no:xdist -p no:randomly

# Serving-plane marker leg — paged KV cache invariants, flash-decoding
# kernel-vs-fallback bit pin, the continuous-batching BITWISE
# decode-vs-full-prefill pin, bf16 restore dtype policy, serve
# heartbeat/autoscale control plane. Runs the FULL serve selection
# (slow included): the train→ckpt→replica e2e is slow-marked to keep
# tier1-verify inside its timeout, but this named leg is the lane's
# gate and must see it.
tier1-serve:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m serve -p no:cacheprovider -p no:xdist -p no:randomly

# Speculative-decoding marker leg — paged-cache spec_reserve/commit/
# rollback invariants + leak-free randomized accept/reject, the BITWISE
# greedy-parity pin vs the non-speculative engine (n-gram and model
# draft lanes, all draft depths), the effective-throughput heartbeat
# round trip, and the seventh analyze config. Runs the FULL spec
# selection (slow included): the train→replica spec e2e is slow-marked
# to keep tier1-verify inside its (already tight — ROADMAP) 870 s
# budget, but this named leg is the lane's gate and must see it.
tier1-spec:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m spec -p no:cacheprovider -p no:xdist -p no:randomly

# Routed-serving marker leg — prefix-cache sharing invariants (refcount/
# COW/LRU partition under a randomized interleave), the BITWISE pins of
# prefix-cached and chunked-prefill admissions vs the unrouted engine,
# the cross-replica router (overlap scoring, sticky affinity, failover),
# the widened heartbeat schema, and the eighth analyze config. Runs the
# FULL route selection (slow included): the multi-replica e2e and
# long-prompt chunking tests are slow-marked to keep tier1-verify inside
# its (tight — ROADMAP) 870 s budget, but this named leg is the lane's
# gate and must see them.
tier1-route:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m route -p no:cacheprovider -p no:xdist -p no:randomly

# Concurrency-plane marker leg — the lock-discipline lint + lock-order
# witness + thread-hygiene audit: seeded violations per rule, the
# package tree clean at HEAD, the witness catching a seeded lock-order
# inversion, and the genuinely multi-threaded randomized kvcache
# interleave (N threads of admit/fork/write/spec/evict with the
# refcount/free/LRU partition pinned at every quiescent point). Runs the
# FULL conc selection (slow included): the threaded stress tests are
# slow-marked to keep tier1-verify inside its (tight — ROADMAP) 870 s
# budget, but this named leg is the lane's gate and must see them.
tier1-conc:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m conc -p no:cacheprovider -p no:xdist -p no:randomly

# Disaggregated-serving marker leg — the KV-block wire tier (export/
# import with per-block CRC, adoption of shipped shared-prefix stems,
# state-unchanged typed rejections), the prefill-only engine mode, the
# BITWISE disagg-vs-colocated pins (ragged lengths, hit/miss
# admissions, spec lane on the decode side), bounded retry/backoff with
# the router's colocated fallback and the OSError-vs-request-error
# failover split, the widened role+handoff heartbeat schema, and the
# ninth analyze config. Runs the FULL disagg selection (slow included):
# the RPC fleet e2e and long-prompt handoff tests are slow-marked to
# keep tier1-verify inside its (tight — ROADMAP) 870 s budget, but this
# named leg is the lane's gate and must see them.
tier1-disagg:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m disagg -p no:cacheprovider -p no:xdist -p no:randomly

# KV-memory-hierarchy marker leg — the host-offload tier (demote/promote
# with bytes verbatim, CRC-guarded host payloads, the extended
# free/LRU/host partition), conversation parking pinned BITWISE vs a
# never-parked engine (ragged lengths, prefix-cache/spec/disagg
# composition), typed pool-pressure degrades, and the persistent prefix
# store's stage-and-rename round trip + replica adoption. Runs the FULL
# kvtier selection (slow included): the heavier parity sweeps are
# slow-marked to keep tier1-verify inside its (tight — ROADMAP) 870 s
# budget, but this named leg is the lane's gate and must see them.
tier1-kvtier:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m kvtier -p no:cacheprovider -p no:xdist -p no:randomly

# Replica cold-start marker leg (tony_tpu.ckpt.aot PR 17) — persisted
# AOT compile cache, warm-standby pool policy, demotion daemon; the
# heavier family sweeps are slow-marked to keep tier1-verify inside its
# timeout, but this named leg is the lane's full gate (slow included).
tier1-aot:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m aot -p no:cacheprovider -p no:xdist -p no:randomly

# History-plane + multi-tenant QoS marker leg (tony_tpu.serve.qos
# PR 18) — weighted-fair budgets + tenant-isolation bitwise pins, the
# widened jhist vocabulary with bounded rotation and the rename-race
# fix, SLO-mode autoscaling + exact decision replay, the tony history
# conf fix + dashboards; the engine-compile isolation pins and the
# threaded reader race are slow-marked to keep tier1-verify inside its
# timeout, but this named leg is the lane's full gate (slow included).
tier1-qos:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m qos -p no:cacheprovider -p no:xdist -p no:randomly

# Elastic-resize marker leg (tony_tpu.am.resize PR 19) — the resize
# state machine's phase/timeout/degrade pins, the chaos-injection
# harness, the drain→commit train-loop exit, the heartbeat-backoff
# regression, the rotation crash sweep, and the headline pin: a run
# with >=3 injected preemptions across changing host counts reproduces
# the undisturbed run's example-id stream exactly with final params
# within tolerance. The chaos/e2e segments are slow-marked to keep
# tier1-verify inside its (tight — ROADMAP) 870 s budget, but this
# named leg is the lane's full gate (slow included).
tier1-elastic:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m elastic -p no:cacheprovider -p no:xdist -p no:randomly

# Continuous-publication marker leg (tony_tpu.publish + tony_tpu.serve.
# swap PR 20) — the published.json pointer's stage-and-rename crash
# sweep (old pointer or new, never torn), resolve_target's pointer/pin/
# race rules, the FleetSwapController rolling-swap policy, the in-place
# hot weight swap pinned BITWISE vs a fresh replica restored from the
# same manifest with ZERO dropped requests under concurrent traffic,
# the four-site swap chaos sweep (exactly one weight version per
# replica), the routed 2-replica rolling-fleet headline, history
# billing windows, and tony aot gc. The replica hot-swap and
# rolling-fleet legs are slow-marked to keep tier1-verify inside its
# (tight — ROADMAP) 870 s budget, but this named leg is the lane's
# full gate (slow included).
tier1-publish:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m publish -p no:cacheprovider -p no:xdist -p no:randomly

# chip_smoke.py marker leg — run it BEFORE EVERY CHIP CALL. The cheap
# part (also inside tier1-verify's selection): the smoke's parent and the
# control plane never import jax, the compile-cache helper's fixed path,
# the platform pin in a chip-granted task's env and the task dying
# without its chip. The slow part is rehearsal 1+2 of the
# on-chip-measurement guide: the whole `python chip_smoke.py --rehearse`
# flow at llama-tiny size on the CPU — tony submit -> checkpoint -> tony
# serve -> generate RPCs -> tony kill -> both check children, then
# `--chips 4` on four virtual devices — which must go through every
# phase, exit non-zero and never print `"ok": true`. The chip run
# itself: `chiprun -- python chip_smoke.py` (`--chips 4` with
# `chiprun --chips 4`).
tier1-smoke:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m smoke -p no:cacheprovider -p no:xdist -p no:randomly

# Whole-fleet e2e leg: everything in the e2e tier (real AM, executor and
# user processes through the MiniPod and the client), slow included — the
# multi-process jax.distributed expert-parallel and pipeline-parallel
# trainings are slow-marked to keep tier1-verify inside its 870 s (the dp
# gang, the simplest of the family, stays in the gate). One pytest at a
# time: these are CPU-greedy.
tier1-e2e:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m e2e -p no:cacheprovider -p no:xdist -p no:randomly

# Source lints, machine-checked: (1) the jnp.concatenate/stack pack-site
# lint (the jax-0.4 GSPMD concat-reshard footgun) — every call site
# outside the approved pack planes must carry an audited
# 'packsite: region-local' pragma; (2) the concurrency plane — lock
# discipline (guarded-elsewhere mutations need the lock or an audited
# '# lockfree:' pragma), lock-order cycles over the static nested-with
# graph, and the thread-hygiene audit (daemon or joined), diffed against
# the committed blessings baseline.
lint:
	python -m tony_tpu.analysis.srclint tony_tpu
	python -m tony_tpu.analysis.concurrency tony_tpu --baseline tests/signatures/concurrency.json

# The tests tier-1 excludes to stay inside its timeout (heavy multi-device
# compiles): run them standalone, no timeout.
tier1-slow:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m slow --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Fast pure-logic tier (~35s): the inner-loop smoke run.
quick:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m quick -p no:cacheprovider

test: tier1
