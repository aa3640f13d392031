"""Serving-plane legs (tony_tpu.serve): paged KV cache invariants, the
flash-decoding kernel pin, the continuous-batching bit-transparency pin
(decode logits bitwise vs sequential full prefill, ragged lengths and
block boundaries included), the restore-time dtype policy, the serve
heartbeat/autoscale control plane, and the end-to-end
train→checkpoint→replica→serve path."""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.serve


# ---------------------------------------------------------------------------
# Shared tiny model + params (built once; serving is read-only on params).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    import flax.linen as nn

    from tony_tpu.models import get_model

    model = get_model("llama-tiny", n_layers=2)
    sample = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), sample))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    return model, params


def make_engine(tiny, **kw):
    from tony_tpu.serve import ServeEngine

    model, params = tiny
    kw.setdefault("ctx_max", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("q_block", 16)
    kw.setdefault("decode_buckets", (2, 4))
    kw.setdefault("max_running", 4)
    kw.setdefault("keep_logits", True)
    return ServeEngine(model, params, **kw)


def pin_vs_full_prefill(eng, completions):
    """THE acceptance pin: every request's streamed decode logits must be
    bit-identical to rows of a sequential full prefill of its final
    token sequence."""
    for c in completions:
        full = list(c.prompt) + list(c.tokens)
        ref = eng.full_prefill_logits(full)
        p = len(c.prompt)
        assert len(c.logits) == len(c.tokens)
        for j, row in enumerate(c.logits):
            assert np.array_equal(ref[p - 1 + j], row), (
                f"request {c.rid}: decode logits at position {p - 1 + j} "
                f"differ from the full-prefill reference "
                f"(max abs diff {np.max(np.abs(ref[p - 1 + j] - row))})")


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------

class TestKVCache:
    def _cache(self, n_blocks=8, block_size=4):
        from tony_tpu.serve import PagedKVCache

        return PagedKVCache(2, 8, n_blocks=n_blocks,
                            block_size=block_size)

    def test_alloc_free_reuse_invariants(self):
        c = self._cache()
        t_a = c.reserve("a", 9)      # 3 blocks of 4
        t_b = c.reserve("b", 4)      # 1 block
        assert len(t_a) == 3 and len(t_b) == 1
        assert not set(t_a) & set(t_b), "tables must be disjoint"
        assert c.free_blocks == 4
        owned = c.owned_blocks()
        assert sorted(owned) == ["a", "b"]
        # Growth extends the same table.
        t_a2 = c.reserve("a", 13)
        assert t_a2[:3] == t_a and len(t_a2) == 4
        # Free returns every block; a fresh reservation reuses them.
        assert c.free_seq("a") == 4
        assert c.free_blocks == 7
        t_c = c.reserve("c", 28)     # 7 blocks — only fits if a's returned
        assert len(t_c) == 7
        assert set(t_c) | set(t_b) == set(range(8))
        # Idempotent eviction.
        assert c.free_seq("a") == 0

    def test_exhaustion_is_typed_admission_error_not_oom(self):
        from tony_tpu.serve import AdmissionError

        c = self._cache(n_blocks=4, block_size=4)
        c.reserve("a", 12)           # 3 of 4 blocks
        free_before = c.free_blocks
        with pytest.raises(AdmissionError) as exc:
            c.reserve("b", 8)        # needs 2, only 1 free
        assert exc.value.needed_blocks == 2
        assert exc.value.free_blocks == 1
        assert exc.value.retryable
        # State unchanged: the failed reservation allocated nothing.
        assert c.free_blocks == free_before
        assert "b" not in c.owned_blocks() or not c.owned_blocks()["b"]

    def test_flat_index_and_oob(self):
        c = self._cache()
        table = c.reserve("s", 10)
        assert c.flat_index("s", 0) == table[0] * 4
        assert c.flat_index("s", 5) == table[1] * 4 + 1
        with pytest.raises(IndexError):
            c.flat_index("s", 12)    # beyond the 3-block reservation
        assert c.oob_index == 8 * 4

    def test_table_array_padding_and_overflow(self):
        c = self._cache()
        c.reserve("s", 10)
        arr = c.table_array(["s", "missing"], nb_max=4)
        assert arr.shape == (2, 4) and arr.dtype == np.int32
        assert list(arr[0, :3]) == c.table("s") and arr[0, 3] == 0
        assert (arr[1] == 0).all()
        with pytest.raises(ValueError):
            c.table_array(["s"], nb_max=2)


# ---------------------------------------------------------------------------
# Flash decoding kernel
# ---------------------------------------------------------------------------

class TestFlashDecode:
    @pytest.mark.parametrize("h,hkv,block_k", [(4, 4, 16), (4, 2, 16),
                                               (4, 1, 32)])
    def test_kernel_vs_fallback_bit_identical(self, h, hkv, block_k):
        from tony_tpu.ops import flash_decode

        rng = np.random.RandomState(0)
        b, t, d, ctx = 3, 16, 16, 64
        q = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, hkv, ctx, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, hkv, ctx, d), jnp.bfloat16)
        pos = jnp.asarray(rng.randint(0, ctx, (b, t)), jnp.int32)
        xla = flash_decode(q, k, v, pos, block_k=block_k)
        pal = flash_decode(q, k, v, pos, block_k=block_k, interpret=True)
        assert jnp.all(xla == pal), "pallas kernel != XLA fallback"

    def test_matches_reference_attention(self):
        from tony_tpu.ops import flash_decode, reference_attention

        rng = np.random.RandomState(1)
        b, h, hkv, t, d, ctx = 2, 4, 2, 16, 16, 48
        q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, hkv, ctx, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, hkv, ctx, d), jnp.float32)
        # Rows are the last t positions of a ctx-long causal sequence.
        pos = jnp.broadcast_to(
            jnp.arange(ctx - t, ctx, dtype=jnp.int32)[None], (b, t))
        dec = flash_decode(q, k, v, pos, block_k=16)
        qfull = jnp.zeros((b, h, ctx, d), jnp.float32
                          ).at[:, :, ctx - t:].set(q)
        ref = reference_attention(qfull, k, v, causal=True)[:, :, ctx - t:]
        np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_validation_errors(self):
        from tony_tpu.ops import flash_decode

        q = jnp.zeros((1, 4, 16, 16), jnp.bfloat16)
        k = jnp.zeros((1, 3, 32, 16), jnp.bfloat16)
        pos = jnp.zeros((1, 16), jnp.int32)
        with pytest.raises(ValueError, match="multiple of kv heads"):
            flash_decode(q, k, k, pos)
        k2 = jnp.zeros((1, 2, 32, 16), jnp.bfloat16)
        with pytest.raises(ValueError, match="q_positions"):
            flash_decode(q, k2, k2, jnp.zeros((1, 8), jnp.int32))
        with pytest.raises(ValueError, match="must match"):
            flash_decode(q, k2, jnp.zeros((1, 2, 16, 16), jnp.bfloat16),
                         pos)


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

class TestEngine:
    def test_decode_bitwise_vs_full_prefill_ragged(self, tiny):
        """The core numerics pin over ragged prompt lengths that cross
        the KV block boundary (block_size=8: 7/8/9) and the q-block
        boundary (q_block=16: 15/17)."""
        from tony_tpu.serve import Request

        eng = make_engine(tiny)
        rng = np.random.RandomState(0)
        lengths = [7, 8, 9, 15, 17]
        for i, n in enumerate(lengths):
            eng.submit(Request(rid=f"r{i}",
                               tokens=list(rng.randint(0, 256, n)),
                               max_new_tokens=4))
        done = eng.run()
        assert sorted(c.rid for c in done) == [f"r{i}"
                                               for i in range(len(lengths))]
        pin_vs_full_prefill(eng, done)
        # Every evicted sequence returned its blocks.
        assert eng.cache.free_blocks == eng.cache.n_blocks

    def test_overlapping_joins_are_bit_transparent(self, tiny):
        """Requests arriving MID-decode join the running batch at
        iteration granularity; their logits (and everyone else's) stay
        bit-identical to the isolated full-prefill reference."""
        from tony_tpu.serve import Request

        eng = make_engine(tiny)
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 256, n)) for n in (5, 11, 9, 20)]
        eng.submit(Request(rid="r0", tokens=prompts[0], max_new_tokens=6))
        done = eng.step()                      # r0 prefills + decodes
        eng.submit(Request(rid="r1", tokens=prompts[1], max_new_tokens=5))
        eng.submit(Request(rid="r2", tokens=prompts[2], max_new_tokens=3))
        done += eng.step()                     # r1/r2 join r0 mid-flight
        eng.submit(Request(rid="r3", tokens=prompts[3], max_new_tokens=4))
        done += eng.run()
        assert sorted(c.rid for c in done) == ["r0", "r1", "r2", "r3"]
        pin_vs_full_prefill(eng, done)

    def test_static_and_continuous_emit_identical_tokens(self, tiny):
        from tony_tpu.serve import Request

        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, 256, n)) for n in (4, 13, 8)]

        def tokens_of(policy):
            eng = make_engine(tiny, join_policy=policy, keep_logits=False)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, tokens=p, max_new_tokens=5))
            return {c.rid: c.tokens for c in eng.run()}

        assert tokens_of("continuous") == tokens_of("static")

    def test_never_fits_request_rejected_nonretryable(self, tiny):
        from tony_tpu.serve import AdmissionError, Request

        eng = make_engine(tiny)                # ctx_pad = 64
        with pytest.raises(AdmissionError) as exc:
            eng.submit(Request(rid="big", tokens=list(range(60)),
                               max_new_tokens=10))
        assert not exc.value.retryable
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(Request(rid="empty", tokens=[], max_new_tokens=1))
        # Fits the context but not the ENTIRE pool (explicit small
        # n_blocks): queueing it as retryable would livelock the loop.
        small = make_engine(tiny, n_blocks=4)  # 4 blocks of 8 = 32 slots
        with pytest.raises(AdmissionError) as exc:
            small.submit(Request(rid="poolbig", tokens=list(range(30)),
                                 max_new_tokens=10))
        assert not exc.value.retryable
        assert small.queue_depth == 0

    def test_pool_pressure_queues_then_completes(self, tiny):
        """With a pool sized for ~one sequence, the second request stays
        QUEUED (admission back-pressure, no error) until the first
        evicts — then completes with identical numerics."""
        from tony_tpu.serve import Request

        # 10 blocks of 8 = 80 slots; each request reserves 3 blocks
        # (17 + 4 -> 21 positions), so 2 fit but the pool gate still
        # exercises: size to 5 blocks -> one at a time.
        eng = make_engine(tiny, n_blocks=5)
        rng = np.random.RandomState(3)
        reqs = [Request(rid=i, tokens=list(rng.randint(0, 256, 17)),
                        max_new_tokens=4) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        done = eng.step()
        assert eng.queue_depth == 1            # second couldn't join
        done += eng.run()
        assert sorted(c.rid for c in done) == [0, 1]
        pin_vs_full_prefill(eng, done)
        assert eng.cache.free_blocks == eng.cache.n_blocks

    def test_serve_records_stats_and_stats_file(self, tiny, tmp_path):
        from tony_tpu import profiler
        from tony_tpu.executor import read_serve_stats
        from tony_tpu.serve import Request

        profiler.reset_records("serve")
        eng = make_engine(tiny, tag="serve_test")
        eng.submit(Request(rid="r", tokens=[1, 2, 3], max_new_tokens=2))
        eng.run()
        stats = eng.stats()
        for key in ("qps", "p50_ms", "p99_ms", "queue_depth",
                    "tokens_per_s", "forwards", "tokens_per_forward",
                    "acceptance_rate"):
            assert key in stats
        # Effective throughput (the autoscaler's honest number since the
        # speculative lane): generated tokens per forward launch — this
        # run emitted 2 tokens (max_new_tokens=2).
        assert stats["tokens_per_forward"] == pytest.approx(
            2.0 / stats["forwards"])
        assert stats["acceptance_rate"] == 0.0
        report = profiler.report("serve")
        assert report["serve_test"]["ctx_pad"] == eng.ctx_pad
        assert report["serve_test_stats"]["completed"] == 1.0
        # The planner registration landed in the unified collective
        # schema (ROADMAP: new step-path planes register day one).
        assert profiler.report("collective")["serve_decode"]["plane"] \
            == "serve_decode"
        # Stats file round-trips through the executor's jax-free reader.
        path = tmp_path / "serve-stats.json"
        eng.write_stats(str(path))
        read = read_serve_stats(path)
        assert read is not None and read["completed"] == 1.0

    def test_stats_rates_are_windowed_not_lifetime(self, tiny):
        """A latency spike must age out of qps/p50/p99 (the autoscaler
        reads them as 'now' — a stale p99 would block scale-down
        forever); completed/steps/forwards stay lifetime counters."""
        from tony_tpu.serve import Request

        eng = make_engine(tiny, keep_logits=False, stats_window_s=0.2)
        eng.submit(Request(rid="r", tokens=[1, 2, 3], max_new_tokens=2))
        eng.run()
        busy = eng.stats()
        assert busy["p99_ms"] > 0 and busy["qps"] > 0
        time.sleep(0.3)                       # the window drains
        idle = eng.stats()
        assert idle["p99_ms"] == 0.0 and idle["qps"] == 0.0
        assert idle["completed"] == 1.0       # lifetime counter intact

    def test_mutating_serve_report_does_not_poison_store(self):
        from tony_tpu import profiler

        profiler.reset_records("serve")
        profiler.record("serve", "t", nested={"deep": [1, 2]}, n=1)
        snap = profiler.report("serve")
        snap["t"]["nested"]["deep"].append(99)
        snap["t"]["poison"] = True
        clean = profiler.report("serve")
        assert clean["t"]["nested"] == {"deep": [1, 2]}
        assert "poison" not in clean["t"]
        profiler.reset_records("serve")
        assert profiler.report("serve") == {}


# ---------------------------------------------------------------------------
# Restore-time dtype policy + subtree prefix
# ---------------------------------------------------------------------------

class TestDtypePolicy:
    @pytest.fixture()
    def saved_state(self, tmp_path):
        import optax

        from tony_tpu import ckpt, train
        from tony_tpu.models import get_model

        model = get_model("mnist-mlp", hidden=16)
        x = jnp.ones((4, 784), jnp.float32)
        state = train.create_train_state(
            model, optax.adamw(1e-3), x, jax.random.PRNGKey(0))
        mgr = ckpt.AsyncCheckpointer(tmp_path / "ckpt")
        mgr.save(state, step=1)
        mgr.wait()
        mgr.close()
        return state, tmp_path / "ckpt"

    def test_bf16_policy_casts_params_never_opt_slots(self, saved_state):
        from tony_tpu import ckpt

        state, root = saved_state
        restored = ckpt.restore_pytree(root, state, dtype_policy="bf16")
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                restored.params)[0]:
            assert leaf.dtype == jnp.bfloat16, \
                jax.tree_util.keystr(path)
        # Round trip: the bf16 values are exactly the cast f32 master.
        orig = jax.tree.leaves(state.params)
        got = jax.tree.leaves(restored.params)
        for a, b in zip(orig, got):
            assert jnp.all(a.astype(jnp.bfloat16) == b)
        # Optimizer slots: bit-untouched f32.
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                restored.opt_state)[0]:
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                    leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32, \
                    jax.tree_util.keystr(path)
        for a, b in zip(jax.tree.leaves(state.opt_state),
                        jax.tree.leaves(restored.opt_state)):
            assert jnp.all(jnp.asarray(a) == jnp.asarray(b))

    def test_find_path_prefix_and_subtree_restore(self, saved_state):
        from tony_tpu import ckpt

        state, root = saved_state
        prefix = ckpt.find_path_prefix(root, state.params)
        assert prefix == ".params"
        params = ckpt.restore_pytree(root, state.params,
                                     path_prefix=prefix,
                                     dtype_policy="bf16")
        # A params-only restore through the prefix: correct values, no
        # optimizer resurrection anywhere.
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(params)):
            assert jnp.all(a.astype(jnp.bfloat16) == b)
        assert ckpt.find_path_prefix(root, state) == ""
        with pytest.raises(KeyError):
            ckpt.find_path_prefix(root, {"not": jnp.ones((3, 3))})

    def test_unknown_policy_raises(self, saved_state):
        from tony_tpu import ckpt

        state, root = saved_state
        with pytest.raises(ValueError, match="dtype_policy"):
            ckpt.restore_pytree(root, state, dtype_policy="int4")


# ---------------------------------------------------------------------------
# Control plane: heartbeat schema, executor round trip, scaling policy
# ---------------------------------------------------------------------------

class TestControlPlane:
    def test_executor_heartbeat_piggybacks_serve_stats(self, tmp_path):
        """Executor round trip: the replica's stats file → heartbeat RPC
        → session.serve_metrics (the autoscaler's input)."""
        from tony_tpu import constants
        from tony_tpu.conf import TonyConfig
        from tony_tpu.executor import TaskExecutor
        from tony_tpu.rpc import ApplicationRpcHandler, RpcServer
        from tony_tpu.session import TonySession

        conf = TonyConfig({"tony.serve.instances": "1",
                           "tony.serve.command": "x"})
        session = TonySession(conf, app_id="app_serve_hb")
        session.on_registered("serve", 0, "127.0.0.1", 4000)
        server = RpcServer(ApplicationRpcHandler(session),
                           host="127.0.0.1").start()
        conf_path = tmp_path / "conf.json"
        conf_path.write_text(json.dumps(dict(conf.items())))
        try:
            executor = TaskExecutor(env={
                constants.ENV_JOB_NAME: "serve",
                constants.ENV_TASK_INDEX: "0",
                constants.ENV_AM_ADDRESS: server.address,
                constants.ENV_CONF_PATH: str(conf_path),
                constants.ENV_LOG_DIR: str(tmp_path),
            })
            executor.serve_stats_path().write_text(json.dumps(
                {"qps": 3.5, "p99_ms": 12.0, "queue_depth": 2.0}))
            t = threading.Thread(target=executor._heartbeat_loop,
                                 args=(0.05,), daemon=True)
            t.start()
            deadline = time.monotonic() + 10.0
            task = session.task("serve", 0)
            while time.monotonic() < deadline and not task.serve_metrics:
                time.sleep(0.05)
            executor._hb_stop.set()
            t.join(timeout=5)
            assert task.serve_metrics == {"qps": 3.5, "p99_ms": 12.0,
                                          "queue_depth": 2.0}
            assert session.serve_samples("serve") == [task.serve_metrics]
            assert task.to_info()["serve_metrics"]["qps"] == 3.5
        finally:
            server.stop()

    def test_scaling_decide_matrix(self):
        from tony_tpu.serve import scaling

        pol = scaling.ScalingPolicy(min_replicas=1, max_replicas=4,
                                    queue_high=8.0, queue_low=1.0,
                                    p99_high_ms=500.0, cooldown_s=30.0)
        hot = [{"queue_depth": 12.0, "p99_ms": 100.0}]
        cold = [{"queue_depth": 0.0, "p99_ms": 10.0}]
        tail = [{"queue_depth": 2.0, "p99_ms": 900.0}]
        assert scaling.decide(pol, 1, hot, now=0.0) == 1
        assert scaling.decide(pol, 4, hot, now=0.0) == 0      # at ceiling
        assert scaling.decide(pol, 2, cold, now=0.0) == -1
        assert scaling.decide(pol, 1, cold, now=0.0) == 0     # at floor
        assert scaling.decide(pol, 1, tail, now=0.0) == 1     # p99 trips
        # Cooldown holds both directions; repair ignores it.
        assert scaling.decide(pol, 1, hot, now=10.0,
                              last_action=0.0) == 0
        assert scaling.decide(pol, 0, [], now=10.0,
                              last_action=0.0) == 1
        assert scaling.decide(pol, 1, hot, now=40.0,
                              last_action=0.0) == 1
        # No telemetry yet: hold.
        assert scaling.decide(pol, 2, [], now=0.0) == 0

    def test_scaling_policy_validation_and_conf(self):
        from tony_tpu.conf import TonyConfig
        from tony_tpu.serve import scaling

        with pytest.raises(ValueError):
            scaling.ScalingPolicy(min_replicas=0)
        with pytest.raises(ValueError):
            scaling.ScalingPolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            scaling.ScalingPolicy(queue_low=9.0, queue_high=8.0)
        conf = TonyConfig({"tony.serve.replicas.max": "5",
                           "tony.serve.scale.queue-high": "4.5"})
        pol = scaling.ScalingPolicy.from_conf(conf, instances=2)
        assert pol.min_replicas == 2 and pol.max_replicas == 5
        assert pol.queue_high == 4.5 and pol.enabled
        assert not scaling.ScalingPolicy.from_conf(
            TonyConfig(), instances=2).enabled

    def test_session_elastic_tasks_and_scale_down(self):
        from tony_tpu.conf import TonyConfig
        from tony_tpu.session import JobStatus, TaskStatus, TonySession

        conf = TonyConfig({"tony.serve.instances": "1",
                           "tony.serve.command": "x"})
        s = TonySession(conf, "app_el")
        s.on_registered("serve", 0, "127.0.0.1", 4000)
        assert s.all_registered()
        t1 = s.add_task("serve")
        assert t1.index == 1 and t1.elastic
        # Elastic tasks never re-open the gang barrier.
        assert s.all_registered()
        s.on_registered("serve", 1, "127.0.0.1", 4001)
        s.mark_scaled_down(t1, "scale-down")
        assert t1.status == TaskStatus.KILLED
        assert s.job_status == JobStatus.RUNNING, \
            "a deliberate scale-down must not fail the job"
        with pytest.raises(KeyError):
            s.add_task("nonexistent")

    def test_am_floor_repair_runs_with_autoscale_disabled(self, tmp_path):
        """`tony serve` turns fail-fast off on the promise that the AM
        repairs the replica floor — which must hold even when autoscale
        is NOT armed (no replicas.max above the static count): a crashed
        replica gets an elastic replacement launched."""
        from types import SimpleNamespace

        from tony_tpu.am import ApplicationMaster
        from tony_tpu.conf import TonyConfig
        from tony_tpu.session import TonySession

        class _FakeContainer:
            def __init__(self, cid):
                self.container_id = cid
                self.is_running = True

        class _FakeScheduler:
            def __init__(self):
                self.launched = []

            def launch(self, req):
                self.launched.append(req)
                return _FakeContainer(f"c{len(self.launched)}")

            def stop_container(self, c):
                c.is_running = False

            def poll_completed(self):
                return []

            def stop(self):
                pass

        conf = TonyConfig({"tony.serve.instances": "2",
                           "tony.serve.command": "x",
                           "tony.application.fail-fast": "false"})
        sched = _FakeScheduler()
        am = ApplicationMaster(conf, "app_repair", tmp_path,
                               scheduler=sched)
        session = TonySession(conf, "app_repair")
        am.session = session
        am.handler = SimpleNamespace(_all_registered_fired=True)
        am.server = SimpleNamespace(port=1)
        session.on_registered("serve", 0, "h", 1)
        session.on_registered("serve", 1, "h", 2)
        session.on_task_result("serve", 1, 1, "replica crashed")
        am._autoscale_serve(session)
        assert len(sched.launched) == 1, \
            "below-floor repair must launch a replacement"
        repaired = session.task("serve", 2)
        assert repaired.elastic
        # Back at the floor with autoscale off: no further action.
        am._autoscale_serve(session)
        assert len(sched.launched) == 1

    def test_cli_serve_builds_conf(self, tmp_path):
        from tony_tpu import conf as conf_mod
        from tony_tpu.cli import make_parser

        args = make_parser().parse_args([
            "serve", "--model", "llama-tiny", "--ckpt_dir",
            str(tmp_path), "--replicas", "2", "--max_replicas", "4",
            "--model_kwargs", '{"n_layers": 2}',
            "--conf", "tony.serve.scale.queue-high=3"])
        assert args.fn.__name__ == "cmd_serve"
        # Reuse cmd_serve's conf assembly up to (not including) submit.
        from tony_tpu.conf import TonyConfig
        cfg = TonyConfig()
        cfg.set(conf_mod.APPLICATION_FRAMEWORK, "standalone")
        cfg.set(conf_mod.instances_key("serve"), str(args.replicas))
        cfg.set(conf_mod.SERVE_MODEL, args.model)
        assert cfg.job_types() == ["serve"]
        assert cfg.instances("serve") == 2


# ---------------------------------------------------------------------------
# End to end: train on fsdp=4 → elastic bf16 restore onto a smaller
# serve mesh → overlapping requests → bitwise pin → RPC through the proxy
# ---------------------------------------------------------------------------

class TestEndToEnd:
    @pytest.mark.slow
    def test_train_ckpt_replica_serve_pin(self, tmp_path):
        import optax

        from tony_tpu import ckpt, parallel as par, train
        from tony_tpu.models import get_model
        from tony_tpu.proxy import ProxyServer
        from tony_tpu.rpc import RpcClient
        from tony_tpu.serve import Request
        from tony_tpu.serve.replica import Replica

        # -- train a couple of real steps on a dp2 x fsdp4 mesh ----------
        model = get_model("llama-tiny", n_layers=2)
        mesh = par.make_mesh(fsdp=4)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 256, (8, 16)), jnp.int32)
        state = train.create_train_state(
            model, optax.adamw(1e-3), tokens, jax.random.PRNGKey(0),
            mesh=mesh)
        step = train.make_train_step(
            loss_of=lambda logits, b: train.next_token_loss(
                logits, b["x"]),
            mesh=mesh, donate=False)
        for _ in range(2):
            state, metrics = step(state, {"x": tokens})
        assert np.isfinite(float(metrics["loss"]))
        mgr = ckpt.AsyncCheckpointer(tmp_path / "ckpt")
        mgr.save(state, step=2)
        mgr.wait()
        mgr.close()

        # -- replica: fsdp=4 ckpt onto a SMALLER serve mesh, bf16 -------
        serve_mesh = par.make_mesh(n_devices=2, fsdp=2)
        replica = Replica(
            model_name="llama-tiny", model_kwargs={"n_layers": 2},
            ckpt_dir=str(tmp_path / "ckpt"), dtype_policy="bf16",
            mesh=serve_mesh, ctx_max=64, block_size=8, q_block=16,
            max_running=4, keep_logits=True)
        assert replica.restored_step == 2
        for leaf in jax.tree.leaves(replica.engine.params):
            assert leaf.dtype == jnp.bfloat16
        # The restore really carries the TRAINED values: serve params ==
        # bf16-cast of the training state's master params.
        trained = jax.tree.leaves(
            jax.tree.map(lambda a: np.asarray(a.astype(jnp.bfloat16)),
                         state.params))
        served = jax.tree.leaves(
            jax.tree.map(np.asarray, replica.engine.params))
        for a, b in zip(trained, served):
            assert np.array_equal(a, b)

        # -- overlapping requests through the engine; the bitwise pin ---
        eng = replica.engine
        # Plain ints: these also travel the JSON RPC wire below.
        prompts = [[int(x) for x in rng.randint(0, 256, n)]
                   for n in (6, 9, 14)]
        eng.submit(Request(rid="a", tokens=prompts[0], max_new_tokens=5))
        done = eng.step()
        eng.submit(Request(rid="b", tokens=prompts[1], max_new_tokens=4))
        eng.submit(Request(rid="c", tokens=prompts[2], max_new_tokens=3))
        done += eng.run()
        assert sorted(c.rid for c in done) == ["a", "b", "c"]
        pin_vs_full_prefill(eng, done)

        # -- and the front door: RPC through the existing TCP proxy -----
        from tony_tpu.rpc import RpcServer

        server = RpcServer(replica.rpc_handler(), host="127.0.0.1")
        server.start()
        try:
            with ProxyServer("127.0.0.1", server.port) as proxy:
                with RpcClient(f"{proxy.local_host}:{proxy.local_port}",
                               timeout=60.0) as client:
                    out = client.call("generate", tokens=prompts[0],
                                      max_new_tokens=5)
                    stats = client.call("serve_stats")
            # Greedy decode of the same prompt through the RPC front
            # reproduces the engine run's tokens exactly.
            ref = next(c for c in done if c.rid == "a")
            assert out["tokens"] == ref.tokens
            assert stats["completed"] >= 4.0
        finally:
            server.stop()

    def test_analyze_serve_config_clean_with_pin(self):
        """The acceptance gate: `tony analyze --config serve` is clean
        with zero waivers against the committed pin (also covered by the
        test_analysis parametrization — this is the serve lane's named
        copy)."""
        from tony_tpu.analysis import cli as acli

        report = acli.run_config(
            "serve", signature_path=str(
                Path(__file__).parent / "signatures" / "serve.json"))
        assert report.ok, report.summary()
        assert not report.waived
        assert report.signature["collectives"] == {}


# ---------------------------------------------------------------------------
# Quant lanes at serve time
# ---------------------------------------------------------------------------

class TestQuantServe:
    @pytest.mark.slow
    def test_quant_lane_engine_is_deterministic(self):
        """The quant= transformer lanes serve through the same engine.
        Per-tensor activation scales are batch-dependent, so the cross-
        batching bit pin doesn't apply — the contract here is that the
        lane runs end to end and a repeated identical submission stream
        reproduces identical tokens."""
        import flax.linen as nn

        from tony_tpu.models import get_model
        from tony_tpu.serve import Request, ServeEngine

        model = get_model("llama-tiny", n_layers=2, quant=True)
        sample = jnp.zeros((1, 16), jnp.int32)
        params = nn.unbox(model.init(jax.random.PRNGKey(0),
                                     sample))["params"]
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, 256, n)) for n in (6, 10)]

        def run_once():
            eng = ServeEngine(model, params, ctx_max=64, block_size=8,
                              q_block=16, decode_buckets=(2,),
                              max_running=2)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, tokens=p, max_new_tokens=3))
            return {c.rid: c.tokens for c in eng.run()}

        first = run_once()
        assert sorted(first) == [0, 1]
        assert all(len(t) == 3 for t in first.values())
        assert run_once() == first
