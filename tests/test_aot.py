"""Replica cold-start plane (PR 17): persisted AOT compile cache +
warm-standby pools.

Four claims under test:

* the cache itself (:mod:`tony_tpu.ckpt.aot`): round trip, corruption /
  truncation / fingerprint-drift each a COUNTED state-unchanged miss,
  concurrent populate first-writer-wins through the atomic rename;
* cache-hit engines are BITWISE the fresh-trace engine — token streams
  and per-token logits — across the serve/spec/route/disagg step
  families, and a cache-hit replica start executes ZERO fresh traces or
  compiles (counter-pinned, the machine-independent claim);
* the warm-standby pool policy: ``decide_warm`` matrix, the
  ``ScalingPolicy`` decision matrix pinned UNCHANGED under the widened
  sample schema, standby exclusion from the routable endpoint set, and
  the stats→heartbeat→session round trip of the +4 schema;
* the engine-loop demotion daemon: off by default, counted when armed.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The repo's OWN executable cache is under test, so jax's persistent cache
# stays out from under it. It also has to on this backend: XLA:CPU cannot
# re-serialize an executable it loaded from jax's cache — what comes back
# fails its first call with "Function ... not found". (On the v5e the same
# sequence round-trips bitwise: CHANGES.md, PR 22.)
pytestmark = [pytest.mark.aot,
              pytest.mark.usefixtures("no_jax_compile_cache")]


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


def run_requests(eng, prompts, max_new=4):
    from tony_tpu.serve import Request

    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=list(p), max_new_tokens=max_new))
    return {c.rid: c for c in eng.run()}


def assert_bitwise_equal(got, ref):
    """Token streams AND per-token logits of two completion maps."""
    assert sorted(got) == sorted(ref)
    for rid in ref:
        assert got[rid].tokens == ref[rid].tokens, rid
        assert len(got[rid].logits) == len(ref[rid].logits)
        for a, b in zip(got[rid].logits, ref[rid].logits):
            assert np.array_equal(a, b), rid


PROMPTS = [[3, 5, 7, 11, 13], [2, 4, 6], [1, 2, 3, 4, 5, 6, 7, 8, 9]]


# ---------------------------------------------------------------------------
# The cache itself
# ---------------------------------------------------------------------------

def _tiny_compiled():
    """A real ``jax.stages.Compiled`` cheap enough for unit tests."""
    x = jnp.arange(8, dtype=jnp.float32)
    return jax.jit(lambda a: a * 2 + 1).lower(x).compile(), x


class TestAOTCache:

    def test_round_trip_and_counters(self, tmp_path):
        from tony_tpu.ckpt import AOTCache, make_fingerprint

        cache = AOTCache(str(tmp_path))
        fp = make_fingerprint("unit", geometry={"n": 8})
        assert cache.get(fp) is None and cache.misses == 1
        compiled, x = _tiny_compiled()
        assert cache.put(fp, compiled) and cache.puts == 1
        loaded = cache.get(fp)
        assert loaded is not None and cache.hits == 1
        np.testing.assert_array_equal(np.asarray(loaded(x)),
                                      np.asarray(compiled(x)))
        # Idempotent second put: counted race, store unchanged.
        assert not cache.put(fp, compiled) and cache.put_races == 1
        assert len(cache.entries()) == 1

    def test_fingerprint_drift_is_counted_miss(self, tmp_path):
        from tony_tpu.ckpt import AOTCache, make_fingerprint

        cache = AOTCache(str(tmp_path))
        compiled, _ = _tiny_compiled()
        fp = make_fingerprint("unit", geometry={"b": 2, "t": 16})
        cache.put(fp, compiled)
        # Changed geometry: a different key, so simply absent.
        drifted = make_fingerprint("unit", geometry={"b": 4, "t": 16})
        assert cache.get(drifted) is None and cache.misses == 1
        # Changed jax version string with the SAME key (a hand-forced
        # address collision): the stored full fingerprint must reject.
        skewed = dict(fp, jax="0.0.0-drifted")
        d = cache._dir(fp)
        entry = json.loads((d / "entry.json").read_text())
        entry["fingerprint"] = dict(entry["fingerprint"],
                                    jax="0.0.0-stored")
        (d / "entry.json").write_text(json.dumps(entry))
        assert cache.get(fp) is None and cache.misses == 2
        assert cache.get(skewed) is None and cache.misses == 3
        # State unchanged: the entry is still on disk, untouched.
        assert len(cache.entries()) == 1

    @pytest.mark.parametrize("how", ["flip", "truncate", "entry"])
    def test_corruption_is_counted_miss_state_unchanged(self, tmp_path,
                                                        how):
        from tony_tpu.ckpt import AOTCache, make_fingerprint

        cache = AOTCache(str(tmp_path))
        compiled, _ = _tiny_compiled()
        fp = make_fingerprint("unit", geometry={"case": how})
        cache.put(fp, compiled)
        d = cache._dir(fp)
        if how == "flip":
            raw = bytearray((d / "payload.bin").read_bytes())
            raw[len(raw) // 2] ^= 0xFF
            (d / "payload.bin").write_bytes(bytes(raw))
        elif how == "truncate":
            raw = (d / "payload.bin").read_bytes()
            (d / "payload.bin").write_bytes(raw[:len(raw) // 2])
        else:
            (d / "entry.json").write_text("{not json")
        before = sorted(p.name for p in d.iterdir())
        assert cache.get(fp) is None
        assert cache.misses == 1 and cache.hits == 0
        # get never mutates the store: poison costs a recompile per
        # consult, not a crash and not a repair attempt.
        assert sorted(p.name for p in d.iterdir()) == before

    def test_concurrent_populate_first_writer_wins(self, tmp_path):
        from tony_tpu.ckpt import AOTCache, make_fingerprint

        compiled, x = _tiny_compiled()
        fp = make_fingerprint("unit", geometry={"race": 1})
        caches = [AOTCache(str(tmp_path)) for _ in range(4)]
        barrier = threading.Barrier(4)
        results = [None] * 4

        def writer(i):
            barrier.wait()
            results[i] = caches[i].put(fp, compiled)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(results) == 1            # exactly one commit
        assert sum(c.put_races for c in caches) == 3
        # The committed entry is whole and loads; no staging orphans
        # linger inside the committed dir listing.
        reader = AOTCache(str(tmp_path))
        assert len(reader.entries()) == 1
        loaded = reader.get(fp)
        assert loaded is not None
        np.testing.assert_array_equal(np.asarray(loaded(x)),
                                      np.asarray(compiled(x)))

    def test_payload_only_entry_needs_caller_trees(self, tmp_path,
                                                   monkeypatch):
        """An unpicklable treedef (the train state's optax tx) commits
        a payload-only entry: get without caller trees is a counted
        miss; with them, a working executable."""
        import pickle as _pickle

        from tony_tpu.ckpt import AOTCache, make_fingerprint
        from tony_tpu.ckpt import aot as aot_mod

        class _NoDumps:
            PicklingError = _pickle.PicklingError
            UnpicklingError = _pickle.UnpicklingError
            loads = staticmethod(_pickle.loads)

            @staticmethod
            def dumps(obj):
                raise _pickle.PicklingError("local object")

        monkeypatch.setattr(aot_mod, "pickle", _NoDumps)
        cache = AOTCache(str(tmp_path))
        compiled, x = _tiny_compiled()
        fp = make_fingerprint("unit", geometry={"trees": "none"})
        assert cache.put(fp, compiled)
        monkeypatch.undo()
        entry = json.loads(
            (cache._dir(fp) / "entry.json").read_text())
        assert entry["trees_b64"] is None
        assert cache.get(fp) is None and cache.misses == 1
        from jax.experimental import serialize_executable as se
        _, in_tree, out_tree = se.serialize(compiled)
        loaded = cache.get(fp, in_tree=in_tree, out_tree=out_tree)
        assert loaded is not None and cache.hits == 1
        np.testing.assert_array_equal(np.asarray(loaded(x)),
                                      np.asarray(compiled(x)))


# ---------------------------------------------------------------------------
# Bitwise parity + the zero-fresh-compiles pin (serve family)
# ---------------------------------------------------------------------------

class TestServeFamilyBitwise:

    def test_cache_hit_engine_is_bitwise_and_compiles_nothing(
            self, tiny, tmp_path):
        """THE acceptance pin: a replica starting on a populated cache
        executes ZERO fresh traces/compiles for the step family and its
        streams are bit-identical to a cold-trace engine's."""
        from tony_tpu.ckpt import AOTCache

        ref = run_requests(make_engine(tiny), PROMPTS)
        root = str(tmp_path / "aot")
        # First cache-armed engine: populates (counted misses).
        e1 = make_engine(tiny, aot_cache=AOTCache(root))
        e1.warm(prefill_pads=(16,))
        assert e1.aot_misses > 0 and e1.fresh_compiles > 0
        got1 = run_requests(e1, PROMPTS)
        assert_bitwise_equal(got1, ref)
        # Second engine, same family: every program deserializes.
        c2 = AOTCache(root)
        e2 = make_engine(tiny, aot_cache=c2)
        e2.warm(prefill_pads=(16,))
        got2 = run_requests(e2, PROMPTS)
        assert_bitwise_equal(got2, ref)
        assert e2.fresh_compiles == 0          # zero XLA compiles
        assert e2._fns == {}                   # zero fresh traces
        assert e2.aot_hits > 0 and e2.aot_misses == 0
        assert c2.hits == e2.aot_hits and c2.misses == 0
        assert e2.deserialize_ms >= 0.0 and e2.compile_ms == 0.0

    def test_corrupted_cache_degrades_to_fresh_trace_bitwise(
            self, tiny, tmp_path):
        from tony_tpu.ckpt import AOTCache

        root = str(tmp_path / "aot")
        e1 = make_engine(tiny, aot_cache=AOTCache(root))
        e1.warm(prefill_pads=(16,))
        ref = run_requests(e1, PROMPTS)
        # Poison every payload byte-flip style.
        for d in (tmp_path / "aot").iterdir():
            if d.is_dir():
                raw = bytearray((d / "payload.bin").read_bytes())
                raw[0] ^= 0xFF
                (d / "payload.bin").write_bytes(bytes(raw))
        e2 = make_engine(tiny, aot_cache=AOTCache(root))
        e2.warm(prefill_pads=(16,))
        got = run_requests(e2, PROMPTS)
        assert_bitwise_equal(got, ref)
        assert e2.aot_hits == 0 and e2.aot_misses > 0
        assert e2.fresh_compiles > 0           # recompiled, never wrong

    def test_default_engine_has_no_aot_surface(self, tiny):
        """No cache handle: the hot loop runs the raw jit dict exactly
        as before this PR — the parallel executable dict stays empty
        and the counters stay zero."""
        eng = make_engine(tiny)
        run_requests(eng, PROMPTS[:1])
        assert eng.aot_cache is None and eng._aot_fns == {}
        assert eng.aot_hits == 0 and eng.aot_misses == 0
        s = eng.stats()
        assert s["aot_hits"] == 0.0 and s["aot_misses"] == 0.0
        assert s["compile_ms"] == 0.0 and s["warm_standby"] == 0.0


@pytest.mark.slow
class TestOtherFamiliesBitwise:

    def test_route_family(self, tiny, tmp_path):
        """Prefix cache + chunked prefill (the route composition) under
        a populated cache: bitwise, with the chunk program cached."""
        from tony_tpu.ckpt import AOTCache

        kw = dict(prefix_cache=True, prefill_chunk=16)
        ref = run_requests(make_engine(tiny, **kw), PROMPTS)
        root = str(tmp_path / "aot")
        e1 = make_engine(tiny, aot_cache=AOTCache(root), **kw)
        e1.warm(prefill_pads=(16,))
        assert_bitwise_equal(run_requests(e1, PROMPTS), ref)
        e2 = make_engine(tiny, aot_cache=AOTCache(root), **kw)
        e2.warm(prefill_pads=(16,))
        assert_bitwise_equal(run_requests(e2, PROMPTS), ref)
        assert e2.fresh_compiles == 0 and e2._fns == {}

    def test_spec_family(self, tiny, tmp_path):
        from tony_tpu.ckpt import AOTCache
        from tony_tpu.serve import SpecEngine

        model, params = tiny
        kw = dict(spec_k=3, ctx_max=64, block_size=8, q_block=16,
                  decode_buckets=(2, 4), max_running=4, keep_logits=True)
        ref = run_requests(SpecEngine(model, params, **kw), PROMPTS)
        root = str(tmp_path / "aot")
        e1 = SpecEngine(model, params, aot_cache=AOTCache(root), **kw)
        assert_bitwise_equal(run_requests(e1, PROMPTS), ref)
        assert e1.aot_misses > 0
        e2 = SpecEngine(model, params, aot_cache=AOTCache(root), **kw)
        assert_bitwise_equal(run_requests(e2, PROMPTS), ref)
        assert e2.aot_hits > 0 and e2.fresh_compiles == 0

    def test_disagg_family(self, tiny, tmp_path):
        """Prefill→KV handoff→decode with BOTH halves cache-armed."""
        from tony_tpu.ckpt import AOTCache
        from tony_tpu.serve import EngineFront
        from tony_tpu.serve.disagg import DecodeFront, PrefillFront

        def handoff(aot_root):
            cache_kw = {}
            if aot_root:
                cache_kw = {"aot_cache": AOTCache(aot_root)}
            pf_eng = make_engine(tiny, role="prefill", **cache_kw)
            dc_eng = make_engine(tiny, role="decode", **cache_kw)
            pf = PrefillFront(EngineFront(pf_eng))
            dc = DecodeFront(EngineFront(dc_eng))
            done = {i: pf.prefill_handoff(list(p), 4, rid=i, decode=dc)
                    for i, p in enumerate(PROMPTS)}
            return done, pf_eng, dc_eng

        ref, _, _ = handoff(None)
        root = str(tmp_path / "aot")
        got1, _, _ = handoff(root)
        assert_bitwise_equal(got1, ref)
        got2, pf2, dc2 = handoff(root)
        assert_bitwise_equal(got2, ref)
        assert pf2.aot_hits + dc2.aot_hits > 0
        assert pf2.aot_misses == 0 and dc2.aot_misses == 0

    def test_train_step_cache_bitwise(self, tmp_path):
        """make_accum_train_step(aot_cache=): a second build of the
        same (topology, config, loss) family deserializes instead of
        compiling, and the stepped state is bit-identical."""
        import optax

        from tony_tpu import parallel as par
        from tony_tpu import train
        from tony_tpu.ckpt import AOTCache
        from tony_tpu.models import get_model

        mesh = par.make_mesh()
        model = get_model("mnist-mlp", hidden=32)
        kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (32, 784))
        y = jax.random.randint(ky, (32,), 0, 10)
        state = train.create_train_state(model, optax.sgd(0.1), x, kr)
        batch = {"x": x, "y": y}
        plain = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                            donate=False)
        s0, m0 = plain(state, batch)
        root = str(tmp_path / "aot")
        c1 = AOTCache(root)
        first = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                            donate=False, aot_cache=c1)
        s1, m1 = first(state, batch)
        assert c1.misses == 1 and c1.puts == 1
        c2 = AOTCache(root)
        second = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                             donate=False, aot_cache=c2)
        s2, m2 = second(state, batch)
        assert c2.hits == 1 and c2.misses == 0
        assert float(m0["loss"]) == float(m1["loss"]) == float(m2["loss"])
        for a, b, c in zip(jax.tree.leaves(s0.params),
                           jax.tree.leaves(s1.params),
                           jax.tree.leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        # inspect still hands the analysis plane the RAW jit, not the
        # deserialized executable — the audit surface cannot drift.
        assert second.inspect(state)["jitted"] is not None

    @pytest.mark.slow
    def test_train_step_optstate_reshard_recompiles(self, tmp_path):
        """Step 1's output re-shards the OPTIMIZER state (handed in
        replicated -> the step's out_shardings) while the params keep
        their layout — the executable memo must key on every state
        leaf's sharding, or step 2 calls a stale Compiled and jax
        hard-fails on the input-sharding mismatch (raw jit would have
        silently re-traced). create_train_state itself now shards the
        moments like their params (PR 22), so the replicated state is
        made here, as a state restored from elsewhere could be."""
        import optax

        from tony_tpu import parallel as par
        from tony_tpu import train
        from tony_tpu.ckpt import AOTCache
        from tony_tpu.models import get_model

        mesh = par.make_mesh(fsdp=4)
        model = get_model("llama-tiny", n_layers=2)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 256, (16, 16)), jnp.int32)
        state = train.create_train_state(
            model, optax.adamw(1e-3), tokens, jax.random.PRNGKey(0),
            mesh=mesh)
        state = state.replace(opt_state=jax.device_put(
            state.opt_state, par.replicated(mesh)))
        cache = AOTCache(str(tmp_path / "aot"))
        step = train.make_accum_train_step(
            loss_of=lambda logits, b: train.next_token_loss(
                logits, b["x"]),
            mesh=mesh, microbatches=2, donate=False, aot_cache=cache)
        state, m1 = step(state, {"x": tokens})
        state, m2 = step(state, {"x": tokens})      # re-sharded input
        assert np.isfinite(float(m2["loss"]))
        # Two distinct layouts -> two cache entries, both compiled.
        assert cache.misses == 2 and cache.puts == 2
        # Steady state: the third step hits the step-2 memo entry.
        state, _ = step(state, {"x": tokens})
        assert cache.misses == 2


# ---------------------------------------------------------------------------
# Warm-standby pool policy + schema
# ---------------------------------------------------------------------------

class TestWarmPoolPolicy:

    def test_decide_warm_matrix(self):
        from tony_tpu.serve import scaling

        p = scaling.ScalingPolicy(min_replicas=1, max_replicas=6,
                                  queue_high=4.0, queue_low=1.0,
                                  p99_high_ms=0.0, cooldown_s=0.0)
        cases = [
            # (target, active, warm) -> delta
            ((2, 1, 0), 2),     # empty pool: grant 2
            ((2, 1, 2), 0),     # at target: hold
            ((2, 1, 3), -1),    # over target: retire 1
            ((2, 5, 0), 1),     # ceiling caps: 6-5 leaves room for 1
            ((2, 6, 0), 0),     # full fleet: no standbys
            ((2, 6, 1), -1),    # full fleet drains the pool
            ((0, 3, 2), -2),    # pool off: drain everything
            ((4, 1, 1), 3),
        ]
        for (target, active, warm), want in cases:
            assert scaling.decide_warm(p, target, active, warm) == want, \
                (target, active, warm)

    def test_decide_matrix_pinned_under_new_fields(self):
        """The PR 15 ScalingPolicy decision matrix must not move when
        samples carry the +4 cold-start fields."""
        from tony_tpu.serve import scaling

        p = scaling.ScalingPolicy(min_replicas=1, max_replicas=4,
                                  queue_high=4.0, queue_low=1.0,
                                  p99_high_ms=100.0, cooldown_s=30.0)
        extra = {"aot_hits": 7.0, "aot_misses": 1.0,
                 "compile_ms": 1234.0, "warm_standby": 0.0,
                 "daemon_demotions": 2.0}
        cases = [
            (1, [{"queue_depth": 9.0, "p99_ms": 10.0}], None, 1),
            (2, [{"queue_depth": 0.2, "p99_ms": 10.0}] * 2, None, -1),
            (2, [{"queue_depth": 2.0, "p99_ms": 10.0}] * 2, None, 0),
            (0, [], None, 1),                       # floor repair
            (2, [{"queue_depth": 9.0, "p99_ms": 10.0}] * 2, 100.0, 0),
        ]
        now = 110.0
        for n, samples, last, want in cases:
            bare = scaling.decide(p, n, samples, now=now,
                                  last_action=last)
            widened = scaling.decide(p, n,
                                     [dict(s, **extra) for s in samples],
                                     now=now, last_action=last)
            assert bare == widened == want, (n, samples)

    def test_stats_schema_plus_four(self, tiny, tmp_path):
        """Engine stats carry the new keys (floats, zeros unarmed) and
        write_stats round-trips them through the executor reader."""
        from tony_tpu.executor import read_serve_stats

        eng = make_engine(tiny, warm_standby=True)
        s = eng.stats()
        for k in ("aot_hits", "aot_misses", "compile_ms",
                  "warm_standby", "daemon_demotions"):
            assert isinstance(s[k], float), k
        assert s["warm_standby"] == 1.0
        path = tmp_path / "stats.json"
        eng.write_stats(str(path), extra={"rpc_port": 4321})
        read = read_serve_stats(path)
        assert read["warm_standby"] == 1.0
        assert read["aot_hits"] == 0.0 and read["compile_ms"] == 0.0

    def test_heartbeat_round_trip_and_endpoint_exclusion(self, tmp_path):
        """Stats file → heartbeat RPC → session: the +4 fields land in
        serve_samples, and a live standby is NOT a routable endpoint
        until its heartbeat flips warm_standby off."""
        from tony_tpu import constants
        from tony_tpu.conf import TonyConfig
        from tony_tpu.executor import TaskExecutor
        from tony_tpu.rpc import ApplicationRpcHandler, RpcServer
        from tony_tpu.session import TonySession

        conf = TonyConfig({"tony.serve.instances": "1",
                           "tony.serve.command": "x"})
        session = TonySession(conf, app_id="app_aot_hb")
        session.on_registered("serve", 0, "127.0.0.1", 4000)
        server = RpcServer(ApplicationRpcHandler(session),
                           host="127.0.0.1").start()
        conf_path = tmp_path / "conf.json"
        conf_path.write_text(json.dumps(dict(conf.items())))
        payload = {"qps": 1.0, "p99_ms": 9.0, "queue_depth": 0.0,
                   "aot_hits": 5.0, "aot_misses": 1.0,
                   "compile_ms": 321.5, "warm_standby": 1.0,
                   "daemon_demotions": 0.0, "rpc_port": 5555}
        try:
            executor = TaskExecutor(env={
                constants.ENV_JOB_NAME: "serve",
                constants.ENV_TASK_INDEX: "0",
                constants.ENV_AM_ADDRESS: server.address,
                constants.ENV_CONF_PATH: str(conf_path),
                constants.ENV_LOG_DIR: str(tmp_path),
            })
            executor.serve_stats_path().write_text(json.dumps(payload))
            t = threading.Thread(target=executor._heartbeat_loop,
                                 args=(0.05,), daemon=True)
            t.start()
            task = session.task("serve", 0)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not task.serve_metrics:
                time.sleep(0.05)
            executor._hb_stop.set()
            t.join(timeout=5)
            got = task.serve_metrics
            assert got["aot_hits"] == 5.0 and got["aot_misses"] == 1.0
            assert got["compile_ms"] == 321.5
            assert got["warm_standby"] == 1.0
            # The sample reaches the autoscaler...
            assert session.serve_samples("serve")[0]["warm_standby"] \
                == 1.0
            # ...but a live standby is NOT routable.
            assert session.serve_endpoints("serve") == []
            # Promotion: the next heartbeat says warm_standby=0 and the
            # endpoint appears.
            session.on_heartbeat("serve", 0,
                                 serve=dict(payload, warm_standby=0.0))
            eps = session.serve_endpoints("serve")
            assert len(eps) == 1 and eps[0]["host"] == "127.0.0.1"
        finally:
            server.stop()

    def test_engine_promote_is_idempotent(self, tiny):
        eng = make_engine(tiny, warm_standby=True)
        assert eng.stats()["warm_standby"] == 1.0
        assert eng.promote() is True
        assert eng.promote() is False
        assert eng.stats()["warm_standby"] == 0.0


# ---------------------------------------------------------------------------
# The AM's warm-pool mechanics (fake scheduler, real session + RPC)
# ---------------------------------------------------------------------------

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


def _make_am(conf_pairs, tmp_path, app_id):
    from types import SimpleNamespace

    from tony_tpu.am import ApplicationMaster
    from tony_tpu.conf import TonyConfig
    from tony_tpu.session import TonySession

    conf = TonyConfig(conf_pairs)
    sched = _FakeScheduler()
    am = ApplicationMaster(conf, app_id, tmp_path, scheduler=sched)
    session = TonySession(conf, app_id)
    am.session = session
    am.handler = SimpleNamespace(_all_registered_fired=True)
    am.server = SimpleNamespace(port=1)
    return am, session, sched


class TestWarmPoolAM:

    def test_backfill_launches_standbys(self, tmp_path):
        """Pool below target: the AM grants elastic standbys without
        touching the active set (decide said hold)."""
        am, session, sched = _make_am(
            {"tony.serve.instances": "1", "tony.serve.command": "x",
             "tony.serve.replicas.max": "4",
             "tony.serve.warm-standby": "2"}, tmp_path, "app_warm_bf")
        session.on_registered("serve", 0, "h", 1)
        session.on_heartbeat("serve", 0, serve={
            "qps": 1.0, "p99_ms": 5.0, "queue_depth": 2.0})
        am._autoscale_serve(session)
        assert len(sched.launched) == 2
        assert session.task("serve", 1).elastic
        assert session.task("serve", 2).elastic
        # At target: the next tick holds.
        session.on_heartbeat("serve", 1, serve={"warm_standby": 1.0})
        session.on_heartbeat("serve", 2, serve={"warm_standby": 1.0})
        am._autoscale_serve(session)
        assert len(sched.launched) == 2

    def test_scale_up_promotes_standby_over_rpc(self, tmp_path):
        """Hot queue + a pooled standby: the AM's scale-up flips the
        standby active over its promote RPC instead of a cold grant —
        and the session's endpoint view flips with it this tick."""
        from tony_tpu.rpc import RpcServer

        class _PromoteHandler:
            def __init__(self):
                self.calls = 0

            def rpc_promote(self):
                self.calls += 1
                return True

        handler = _PromoteHandler()
        server = RpcServer(handler, host="127.0.0.1").start()
        try:
            am, session, sched = _make_am(
                {"tony.serve.instances": "1", "tony.serve.command": "x",
                 "tony.serve.replicas.max": "4",
                 "tony.serve.scale.cooldown-s": "0"},
                tmp_path, "app_warm_promo")
            session.on_registered("serve", 0, "127.0.0.1", 1)
            session.on_heartbeat("serve", 0, serve={
                "qps": 1.0, "p99_ms": 5.0, "queue_depth": 50.0})
            standby = session.add_task("serve")
            session.on_registered("serve", standby.index,
                                  "127.0.0.1", 2)
            session.on_heartbeat("serve", standby.index, serve={
                "warm_standby": 1.0, "rpc_port": float(server.port)})
            # Before promotion only the active replica is routable.
            assert len(session.serve_endpoints("serve")) == 1
            am._autoscale_serve(session)
            assert handler.calls == 1
            assert sched.launched == []        # promotion, not a grant
            assert standby.serve_metrics["warm_standby"] == 0.0
            assert len(session.serve_endpoints("serve")) == 2
        finally:
            server.stop()

    def test_promote_rpc_failure_falls_back_to_cold_grant(self,
                                                          tmp_path):
        am, session, sched = _make_am(
            {"tony.serve.instances": "1", "tony.serve.command": "x",
             "tony.serve.replicas.max": "4",
             "tony.serve.scale.cooldown-s": "0"},
            tmp_path, "app_warm_fb")
        session.on_registered("serve", 0, "127.0.0.1", 1)
        session.on_heartbeat("serve", 0, serve={
            "qps": 1.0, "p99_ms": 5.0, "queue_depth": 50.0})
        standby = session.add_task("serve")
        session.on_registered("serve", standby.index, "127.0.0.1", 2)
        # A dead promote port: dial fails, the AM cold-grants instead.
        session.on_heartbeat("serve", standby.index, serve={
            "warm_standby": 1.0, "rpc_port": 1.0})
        am._autoscale_serve(session)
        assert len(sched.launched) == 1
        assert standby.serve_metrics["warm_standby"] == 1.0

    def test_full_fleet_drains_pool(self, tmp_path):
        """Active set at the ceiling: decide_warm retires standbys —
        every budget slot serves traffic."""
        am, session, sched = _make_am(
            {"tony.serve.instances": "2", "tony.serve.command": "x",
             "tony.serve.replicas.max": "2",
             "tony.serve.warm-standby": "1"}, tmp_path, "app_warm_dr")
        session.on_registered("serve", 0, "h", 1)
        session.on_registered("serve", 1, "h", 2)
        for i in (0, 1):
            session.on_heartbeat("serve", i, serve={
                "qps": 1.0, "p99_ms": 5.0, "queue_depth": 2.0})
        standby = session.add_task("serve")
        session.on_registered("serve", standby.index, "h", 3)
        session.on_heartbeat("serve", standby.index,
                             serve={"warm_standby": 1.0})
        am._autoscale_serve(session)
        assert standby.status.is_terminal
        assert sched.launched == []


# ---------------------------------------------------------------------------
# Demotion daemon
# ---------------------------------------------------------------------------

class TestDemotionDaemon:

    def test_off_by_default(self, tiny):
        eng = make_engine(tiny, host_blocks=8, prefix_cache=True)
        run_requests(eng, PROMPTS)
        assert eng.demote_watermark == 0.0
        assert eng.daemon_demotions == 0
        assert eng.stats()["daemon_demotions"] == 0.0

    def test_watermark_demotes_published_stems(self, tiny):
        """Armed daemon: once pool occupancy crosses the watermark the
        step loop pre-drains refcount-0 (published) blocks into the
        host tier — counted, bitwise-invisible to the streams. The
        schedule staggers completions: r0 finishes early, publishing a
        refcount-0 stem that the daemon demotes while r1 keeps
        stepping."""
        from tony_tpu.serve import Request

        def staggered(eng):
            eng.submit(Request(rid="r0", tokens=[3, 5, 7, 11, 13, 17,
                                                 19, 23, 29],
                               max_new_tokens=2))
            eng.submit(Request(rid="r1", tokens=[2, 4, 6],
                               max_new_tokens=16))
            return {c.rid: c for c in eng.run()}

        ref = staggered(make_engine(tiny, prefix_cache=True))
        eng = make_engine(tiny, prefix_cache=True, host_blocks=16,
                          demote_watermark=0.05, demote_batch=2)
        got = staggered(eng)
        assert_bitwise_equal(got, ref)
        assert eng.daemon_demotions > 0
        assert eng.stats()["daemon_demotions"] \
            == float(eng.daemon_demotions)

    def test_watermark_validation(self, tiny):
        with pytest.raises(ValueError, match="demote_watermark"):
            make_engine(tiny, demote_watermark=1.5)
