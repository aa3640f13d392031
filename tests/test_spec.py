"""Speculative decoding lane (tony_tpu.serve.spec): paged-cache
speculative reservation/rollback invariants (block-table truncation,
write cursor, LIFO reuse, leak-free pool accounting under randomized
accept/reject), the n-gram draft lane, the BITWISE greedy-parity pin
against the non-speculative PR 10 engine (token streams AND per-token
logits, overlapping/ragged/block-boundary request mixes, n-gram and
model-draft lanes), the tokens_per_forward / acceptance-rate heartbeat
fields through the executor round trip, the seventh `tony analyze`
config, and the replica construction path."""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.spec


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


ENGINE_KW = dict(ctx_max=64, block_size=8, q_block=16,
                 decode_buckets=(2, 4), max_running=4, keep_logits=True)


def make_plain(tiny, **kw):
    from tony_tpu.serve import ServeEngine

    model, params = tiny
    return ServeEngine(model, params, **{**ENGINE_KW, **kw})


def make_spec(tiny, **kw):
    from tony_tpu.serve import SpecEngine

    model, params = tiny
    return SpecEngine(model, params, **{**ENGINE_KW, **kw})


def drive_overlapping(eng, prompts, new_tokens):
    """The shared overlapping-arrival schedule both engines run for the
    parity pin: r0 alone for a step, then r1/r2 join mid-flight, then
    r3 late."""
    from tony_tpu.serve import Request

    done = []
    eng.submit(Request(rid="r0", tokens=prompts[0],
                       max_new_tokens=new_tokens[0]))
    done += eng.step()
    for i in (1, 2):
        eng.submit(Request(rid=f"r{i}", tokens=prompts[i],
                           max_new_tokens=new_tokens[i]))
    done += eng.step()
    eng.submit(Request(rid="r3", tokens=prompts[3],
                       max_new_tokens=new_tokens[3]))
    done += eng.run()
    return {c.rid: c for c in done}


def assert_bitwise_equal(base, spec):
    assert sorted(base) == sorted(spec)
    for rid in base:
        assert base[rid].tokens == spec[rid].tokens, (
            f"{rid}: token streams diverge")
        assert len(base[rid].logits) == len(spec[rid].logits)
        for j, (a, b) in enumerate(zip(base[rid].logits,
                                       spec[rid].logits)):
            assert np.array_equal(a, b), (
                f"{rid}: logits at generated position {j} differ "
                f"(max abs diff {np.max(np.abs(a - b))})")


# ---------------------------------------------------------------------------
# Paged-cache speculative reservation / rollback
# ---------------------------------------------------------------------------

class TestSpecCache:
    def _cache(self, n_blocks=8, block_size=4):
        from tony_tpu.serve import PagedKVCache

        return PagedKVCache(2, 8, n_blocks=n_blocks, block_size=block_size)

    def test_reserve_reject_rollback_invariants(self):
        c = self._cache()
        c.reserve("s", 6)                  # 2 permanent blocks
        c.commit("s", 6)
        assert c.committed_len("s") == 6
        table_before = c.table("s")
        free_before = c.free_blocks
        # Speculative extension across a block boundary: +2 blocks.
        c.spec_reserve("s", 14)
        assert len(c.table("s")) == 4
        assert c.free_blocks == free_before - 2
        spec_blocks = c.table("s")[2:]
        # Rejection: table truncates back to the committed extent, the
        # extension returns to the pool, cursor untouched.
        assert c.rollback("s") == 2
        assert c.table("s") == table_before
        assert c.free_blocks == free_before
        assert c.committed_len("s") == 6
        # LIFO reuse: rollback returns the extension in reverse
        # allocation order, so re-reserving hands back the SAME blocks
        # in the SAME order — rollback-then-redo reproduces the table.
        again = c.spec_reserve("s", 14)[2:]
        assert again == spec_blocks
        c.rollback("s")

    def test_commit_promotes_covering_blocks(self):
        c = self._cache()
        c.reserve("s", 4)                  # 1 permanent block
        c.spec_reserve("s", 12)            # +2 speculative
        # Accept through position 6: the first speculative block is now
        # load-bearing and must survive the rollback.
        c.commit("s", 7)
        freed = c.rollback("s")
        assert freed == 1
        assert len(c.table("s")) == 2
        assert c.committed_len("s") == 7
        # The cursor never moves backwards.
        c.commit("s", 5)
        assert c.committed_len("s") == 7

    def test_spec_exhaustion_typed_and_state_unchanged(self):
        from tony_tpu.serve import AdmissionError

        c = self._cache(n_blocks=4, block_size=4)
        c.reserve("a", 12)                 # 3 of 4
        free = c.free_blocks
        with pytest.raises(AdmissionError) as exc:
            c.spec_reserve("a", 24)        # needs 3 more, 1 free
        assert exc.value.retryable
        assert c.free_blocks == free and len(c.table("a")) == 3

    def test_permanent_reserve_refuses_interleaving(self):
        c = self._cache()
        c.spec_reserve("s", 4)
        with pytest.raises(ValueError, match="speculative extension"):
            c.reserve("s", 8)
        c.rollback("s")
        c.reserve("s", 8)                  # clean after rollback

    def test_free_seq_returns_speculative_tail(self):
        c = self._cache()
        c.reserve("s", 4)
        c.spec_reserve("s", 16)
        assert c.free_seq("s") == 4
        assert c.free_blocks == c.n_blocks
        assert c.committed_len("s") == 0   # bookkeeping fully cleared

    def test_randomized_accept_reject_never_leaks(self):
        """Pool accounting under a random interleave of reserve /
        spec_reserve / commit / rollback / free across sequences: free +
        owned always partitions the pool, tables stay disjoint, and a
        full drain returns every block."""
        rng = np.random.RandomState(7)
        c = self._cache(n_blocks=16, block_size=4)
        from tony_tpu.serve import AdmissionError

        live: dict = {}
        for _ in range(300):
            op = rng.randint(5)
            sid = int(rng.randint(6))
            try:
                if op == 0:
                    if not c._spec.get(sid):
                        c.reserve(sid, int(rng.randint(1, 24)))
                        live[sid] = True
                elif op == 1:
                    c.spec_reserve(sid, int(rng.randint(1, 32)))
                    live[sid] = True
                elif op == 2 and sid in live:
                    covered = len(c.table(sid)) * c.block_size
                    if covered:
                        c.commit(sid, int(rng.randint(0, covered + 1)))
                elif op == 3 and sid in live:
                    c.rollback(sid)
                elif op == 4 and sid in live:
                    c.free_seq(sid)
                    live.pop(sid)
            except AdmissionError:
                pass
            owned = c.owned_blocks()
            flat = [b for t in owned.values() for b in t]
            assert len(flat) == len(set(flat)), "tables overlap"
            assert len(flat) + c.free_blocks == c.n_blocks, "leak"
        for sid in list(live):
            c.free_seq(sid)
        assert c.free_blocks == c.n_blocks

    def test_rollback_then_regenerate_is_bit_identical(self, tiny):
        """The stale-bytes contract, end to end: run a request through
        the speculative engine (rejected drafts DID scatter rows into
        the pool before rolling back), then reuse the same engine for a
        fresh request that regenerates over those stale blocks — its
        logits must equal the never-speculated reference bitwise."""
        from tony_tpu.serve import Request

        eng = make_spec(tiny, spec_k=4)
        rng = np.random.RandomState(3)
        p1 = list(rng.randint(0, 256, 9))
        eng.submit(Request(rid="warm", tokens=p1, max_new_tokens=6))
        eng.run()
        # Second pass reuses rolled-back blocks (LIFO pool).
        p2 = list(rng.randint(0, 256, 11))
        eng.submit(Request(rid="re", tokens=p2, max_new_tokens=5))
        done = {c.rid: c for c in eng.run()}
        full = p2 + done["re"].tokens
        ref = eng.full_prefill_logits(full)
        for j, row in enumerate(done["re"].logits):
            assert np.array_equal(ref[len(p2) - 1 + j], row)


# ---------------------------------------------------------------------------
# N-gram draft lane
# ---------------------------------------------------------------------------

class TestNgramDraft:
    def test_prompt_lookup_continuation(self):
        from tony_tpu.serve import NgramDraft

        d = NgramDraft(max_n=3)

        class S:
            rid = "s1"
            tokens = [1, 2, 3, 9, 1, 2, 3]

        # Suffix (1,2,3) matched at the front -> continues with 9, then
        # the draft's own history extends the match.
        assert d.propose([S()], [3])[0] == [9, 1, 2]
        # The persistent index only ever holds REAL history: a second
        # round over unchanged tokens proposes identically (the round's
        # draft overlay died with it).
        assert d.propose([S()], [3])[0] == [9, 1, 2]
        d.evict(S())
        assert not d._index

    def test_repeat_last_fallback_and_validation(self):
        from tony_tpu.serve import NgramDraft

        d = NgramDraft(max_n=3)

        class S:
            rid = "s2"
            tokens = [5]

        assert d.propose([S()], [2])[0] == [5, 5]
        with pytest.raises(ValueError):
            NgramDraft(max_n=0)
        with pytest.raises(ValueError):
            NgramDraft(max_n=2, min_n=3)


# ---------------------------------------------------------------------------
# The bitwise greedy-parity pin
# ---------------------------------------------------------------------------

class TestGreedyParity:
    def test_ragged_lengths_bitwise_vs_plain_engine(self, tiny):
        """THE acceptance pin: the speculative engine's token streams
        and per-token logits equal the non-speculative engine's BITWISE,
        over prompt lengths crossing the KV block boundary (7/8/9) and
        the q-block boundary (15/17)."""
        from tony_tpu.serve import Request

        rng = np.random.RandomState(0)
        lengths = [7, 8, 9, 15, 17]
        prompts = [list(rng.randint(0, 256, n)) for n in lengths]

        def run(eng):
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=f"r{i}", tokens=p,
                                   max_new_tokens=6))
            return {c.rid: c for c in eng.run()}

        base = run(make_plain(tiny))
        spec_eng = make_spec(tiny, spec_k=4)
        spec = run(spec_eng)
        assert_bitwise_equal(base, spec)
        # Speculation actually engaged and the pool drained clean.
        assert spec_eng.spec_proposed > 0
        assert spec_eng.verify_launches > 0
        assert spec_eng.cache.free_blocks == spec_eng.cache.n_blocks

    def test_overlapping_joins_bitwise(self, tiny):
        """Mixed batches with variable per-iteration advance: requests
        joining mid-flight stay bit-transparent, exactly like decode."""
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 256, n)) for n in (5, 11, 9, 20)]
        new = [6, 5, 3, 4]
        base = drive_overlapping(make_plain(tiny), prompts, new)
        spec = drive_overlapping(make_spec(tiny, spec_k=4), prompts, new)
        assert_bitwise_equal(base, spec)

    # Slow-marked variants: each builds fresh engines (fresh jit
    # families), and the tier-1 870 s budget is already tight at HEAD
    # (ROADMAP) — `make tier1-spec` is the lane's named gate and runs
    # them; the core ragged/overlapping bitwise pins above stay in the
    # 'not slow' selection.
    @pytest.mark.slow
    @pytest.mark.parametrize("k", [1, 4, 15])
    def test_depth_sweep_bitwise(self, tiny, k):
        """Every legal draft depth (1 .. q_block-1) preserves parity —
        including k=15 where the verify block has zero padding rows."""
        from tony_tpu.serve import Request

        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, 256, n)) for n in (6, 13)]

        def run(eng):
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, tokens=p, max_new_tokens=7))
            return {c.rid: c for c in eng.run()}

        assert_bitwise_equal(run(make_plain(tiny)),
                             run(make_spec(tiny, spec_k=k)))

    @pytest.mark.slow
    def test_model_draft_same_params_fully_accepts(self, tiny):
        """Draft == target: every draft token matches the target's
        argmax, so acceptance is total, the draft cache's speculative
        extensions commit (never roll back), and parity still holds."""
        model, params = tiny
        rng = np.random.RandomState(4)
        prompts = [list(rng.randint(0, 256, n)) for n in (7, 10, 16, 9)]
        new = [6, 5, 4, 6]
        base = drive_overlapping(make_plain(tiny), prompts, new)
        eng = make_spec(tiny, spec_k=4, draft_model=model,
                        draft_params=params)
        spec = drive_overlapping(eng, prompts, new)
        assert_bitwise_equal(base, spec)
        assert eng.spec_accepted == eng.spec_proposed > 0
        assert eng.draft.forwards > 0
        # Both pools drain clean — the draft lane's lazy reservation and
        # commit/rollback cycling leaked nothing.
        assert eng.cache.free_blocks == eng.cache.n_blocks
        assert eng.draft.cache.free_blocks == eng.draft.cache.n_blocks

    @pytest.mark.slow
    def test_model_draft_different_params_partial_accept(self, tiny):
        """A draft that disagrees with the target (fresh init) still
        preserves parity — the accept/reject path, draft-cache rollback,
        and resync machinery all engage."""
        import flax.linen as nn

        from tony_tpu.models import get_model

        model, params = tiny
        draft_model = get_model("llama-tiny", n_layers=1)
        sample = jnp.zeros((1, 16), jnp.int32)
        draft_params = nn.unbox(draft_model.init(
            jax.random.PRNGKey(9), sample))["params"]
        draft_params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, draft_params)
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, 256, n)) for n in (8, 12, 6, 15)]
        new = [6, 4, 6, 5]
        base = drive_overlapping(make_plain(tiny), prompts, new)
        eng = make_spec(tiny, spec_k=4, draft_model=draft_model,
                        draft_params=draft_params)
        spec = drive_overlapping(eng, prompts, new)
        assert_bitwise_equal(base, spec)
        assert eng.draft.cache.free_blocks == eng.draft.cache.n_blocks

    def test_draft_pool_pressure_degrades_never_wedges(self, tiny):
        """A draft pool too small for the batch must degrade per
        sequence (empty proposal = plain decode row that round) and
        retry — never leak an AdmissionError out of step() or wedge the
        draft cache with an uncommitted extension. Parity holds
        throughout: speculation depth is a performance knob, never a
        correctness one."""
        from tony_tpu.serve import Request
        from tony_tpu.serve.spec import ModelDraft

        model, params = tiny
        rng = np.random.RandomState(8)
        prompts = [list(rng.randint(0, 256, n)) for n in (9, 12, 7)]

        def run(eng):
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, tokens=p, max_new_tokens=5))
            return {c.rid: c for c in eng.run()}

        base = run(make_plain(tiny))
        # 3 blocks of 8 = 24 draft positions: one sequence syncs, the
        # rest see AdmissionError on sync or extension every round.
        draft = ModelDraft(model, params, ctx_max=64, block_size=8,
                           q_block=16, decode_buckets=(2, 4),
                           max_running=4, n_blocks=3)
        eng = make_spec(tiny, spec_k=4, draft=draft)
        spec = run(eng)
        assert_bitwise_equal(base, spec)
        # The draft pool survived the pressure cycles leak-free.
        assert draft.cache.free_blocks == draft.cache.n_blocks

    def test_spec_tokens_match_full_prefill_reference(self, tiny):
        """Transitivity check straight against the PR 10 reference: the
        speculative engine's logits are bitwise rows of a sequential
        full prefill (the same pin the plain engine carries)."""
        from tony_tpu.serve import Request

        eng = make_spec(tiny, spec_k=4)
        rng = np.random.RandomState(6)
        prompts = [list(rng.randint(0, 256, n)) for n in (7, 16)]
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=p, max_new_tokens=5))
        for c in eng.run():
            full = list(c.prompt) + list(c.tokens)
            ref = eng.full_prefill_logits(full)
            p = len(c.prompt)
            for j, row in enumerate(c.logits):
                assert np.array_equal(ref[p - 1 + j], row)

    def test_validation_errors(self, tiny):
        model, params = tiny
        with pytest.raises(ValueError, match="spec_k"):
            make_spec(tiny, spec_k=0)
        with pytest.raises(ValueError, match="spec_k"):
            make_spec(tiny, spec_k=16)     # == q_block
        from tony_tpu.serve import NgramDraft

        with pytest.raises(ValueError, match="not both"):
            make_spec(tiny, spec_k=2, draft=NgramDraft(),
                      draft_model=model, draft_params=params)


# ---------------------------------------------------------------------------
# Telemetry: stats fields, heartbeat round trip, profiler records
# ---------------------------------------------------------------------------

class TestSpecTelemetry:
    def test_stats_fields_and_records(self, tiny):
        from tony_tpu import profiler
        from tony_tpu.serve import Request

        profiler.reset_records("serve")
        eng = make_spec(tiny, spec_k=3, tag="spec_test")
        eng.submit(Request(rid="r", tokens=[1, 2, 3, 1, 2, 3],
                           max_new_tokens=5))
        eng.run()
        stats = eng.stats()
        for key in ("tokens_per_forward", "acceptance_rate",
                    "spec_proposed", "spec_accepted", "verify_launches",
                    "draft_forwards", "tokens_per_verify",
                    "tokens_per_seq_round"):
            assert key in stats, key
        assert stats["verify_launches"] > 0
        assert stats["tokens_per_forward"] > 0
        # One launch per iteration emits >= 1 token per sequence.
        assert stats["tokens_per_seq_round"] >= 1.0
        report = profiler.report("serve")
        assert report["spec_test_spec"]["k"] == 3
        assert report["spec_test_spec"]["draft"] == "ngram"
        assert report["spec_test_stats"]["verify_launches"] == \
            stats["verify_launches"]
        # The plain engine publishes the same schema (zeros) so the
        # autoscaler sees one field set fleet-wide.
        plain = make_plain(tiny, keep_logits=False, tag="plain_test")
        pstats = plain.stats()
        assert pstats["acceptance_rate"] == 0.0
        assert "tokens_per_forward" in pstats
        profiler.reset_records("serve")

    def test_executor_heartbeat_carries_effective_throughput(
            self, tmp_path):
        """Executor round trip with the NEW fields: stats file →
        heartbeat RPC → session.serve_metrics — the autoscaler's input
        now sees tokens_per_forward / acceptance_rate."""
        from tony_tpu import constants
        from tony_tpu.conf import TonyConfig
        from tony_tpu.executor import TaskExecutor
        from tony_tpu.rpc import ApplicationRpcHandler, RpcServer
        from tony_tpu.session import TonySession

        conf = TonyConfig({"tony.serve.instances": "1",
                           "tony.serve.command": "x"})
        session = TonySession(conf, app_id="app_spec_hb")
        session.on_registered("serve", 0, "127.0.0.1", 4000)
        server = RpcServer(ApplicationRpcHandler(session),
                           host="127.0.0.1").start()
        conf_path = tmp_path / "conf.json"
        conf_path.write_text(json.dumps(dict(conf.items())))
        sample = {"qps": 2.0, "p99_ms": 9.0, "queue_depth": 1.0,
                  "tokens_per_forward": 3.4, "acceptance_rate": 0.8}
        try:
            executor = TaskExecutor(env={
                constants.ENV_JOB_NAME: "serve",
                constants.ENV_TASK_INDEX: "0",
                constants.ENV_AM_ADDRESS: server.address,
                constants.ENV_CONF_PATH: str(conf_path),
                constants.ENV_LOG_DIR: str(tmp_path),
            })
            executor.serve_stats_path().write_text(json.dumps(sample))
            t = threading.Thread(target=executor._heartbeat_loop,
                                 args=(0.05,), daemon=True)
            t.start()
            deadline = time.monotonic() + 10.0
            task = session.task("serve", 0)
            while time.monotonic() < deadline and not task.serve_metrics:
                time.sleep(0.05)
            executor._hb_stop.set()
            t.join(timeout=5)
            assert task.serve_metrics == sample
            assert session.serve_samples("serve") == [sample]
            # The scaling decision matrix is unchanged by the extra
            # fields: the same sample decides exactly as before.
            from tony_tpu.serve import scaling

            pol = scaling.ScalingPolicy(min_replicas=1, max_replicas=4)
            assert scaling.decide(pol, 2, [sample], now=0.0) == 0
            hot = dict(sample, queue_depth=12.0)
            assert scaling.decide(pol, 2, [hot], now=0.0) == 1
        finally:
            server.stop()

    def test_mutating_spec_report_does_not_poison_store(self):
        from tony_tpu import profiler

        profiler.reset_records("serve")
        profiler.record("serve", "spec_t",
                        nested={"accept": [1, 0, 1]}, k=4)
        snap = profiler.report("serve")
        snap["spec_t"]["nested"]["accept"].append(9)
        snap["spec_t"]["poison"] = True
        clean = profiler.report("serve")
        assert clean["spec_t"]["nested"] == {"accept": [1, 0, 1]}
        assert "poison" not in clean["spec_t"]
        profiler.reset_records("serve")


# ---------------------------------------------------------------------------
# Static analysis: the seventh config
# ---------------------------------------------------------------------------

class TestAnalyzeSpec:
    def test_analyze_spec_config_clean_with_pin(self):
        """`tony analyze --config spec` is clean with zero waivers
        against the committed pin: zero inter-chip collectives in the
        verify program, KV pools donated (also covered by the
        test_analysis parametrization — this is the lane's named
        copy)."""
        from tony_tpu.analysis import cli as acli

        report = acli.run_config(
            "spec", signature_path=str(
                Path(__file__).parent / "signatures" / "spec.json"))
        assert report.ok, report.summary()
        assert not report.waived
        assert report.signature["collectives"] == {}
        assert report.config["plane"] == "serve_verify"
        assert report.config["spec_k"] == 4
        assert report.config["draft"] == "ngram"

    def test_unknown_step_rejected(self, tiny):
        # "prefill" joined the step family in PR 13 (the route config);
        # the reject path needs a genuinely unknown name.
        from tony_tpu import analysis

        eng = make_spec(tiny, spec_k=2)
        with pytest.raises(ValueError, match="unknown serve step"):
            analysis.analyze_serve_step(eng, step="sample")


# ---------------------------------------------------------------------------
# CLI + replica construction
# ---------------------------------------------------------------------------

class TestSpecControlPlane:
    def test_cli_serve_spec_flags(self, tmp_path):
        from tony_tpu import conf as conf_mod
        from tony_tpu.cli import make_parser

        args = make_parser().parse_args([
            "serve", "--model", "llama-tiny", "--ckpt_dir",
            str(tmp_path), "--spec_k", "4", "--draft_model",
            "llama-tiny", "--draft_model_kwargs", '{"n_layers": 1}'])
        assert args.spec_k == 4 and args.draft_model == "llama-tiny"
        # Bad flag combinations are rejected at SUBMIT time, not replica
        # launch: --draft_model without --spec_k, orphaned draft flags
        # (they would silently serve the n-gram lane), out-of-range k.
        for argv in (["--draft_model", "llama-tiny"],
                     ["--spec_k", "2", "--draft_ckpt_dir", str(tmp_path)],
                     ["--spec_k", "2", "--draft_model_kwargs", "{}"],
                     ["--spec_k", "16"],
                     ["--spec_k", "-1"]):
            bad = make_parser().parse_args(
                ["serve", "--model", "llama-tiny", "--ckpt_dir",
                 str(tmp_path)] + argv)
            with pytest.raises(SystemExit):
                bad.fn(bad)
        assert conf_mod.SERVE_SPEC_K == "tony.serve.spec-k"

    @pytest.mark.slow
    def test_replica_spec_engine_parity(self, tmp_path):
        """Train → ckpt → two replicas off the same save (plain and
        speculative with a model draft restored through the same elastic
        path) → identical greedy token streams."""
        import optax

        from tony_tpu import ckpt, train
        from tony_tpu.models import get_model
        from tony_tpu.serve import Request
        from tony_tpu.serve.replica import Replica
        from tony_tpu.serve.spec import SpecEngine

        model = get_model("llama-tiny", n_layers=2)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32)
        state = train.create_train_state(
            model, optax.adamw(1e-3), tokens, jax.random.PRNGKey(0))
        step = train.make_train_step(
            loss_of=lambda logits, b: train.next_token_loss(
                logits, b["x"]), donate=False)
        state, _ = step(state, {"x": tokens})
        mgr = ckpt.AsyncCheckpointer(tmp_path / "ckpt")
        mgr.save(state, step=1)
        mgr.wait()
        mgr.close()

        common = dict(model_name="llama-tiny",
                      model_kwargs={"n_layers": 2},
                      ckpt_dir=str(tmp_path / "ckpt"),
                      dtype_policy="bf16", ctx_max=64, block_size=8,
                      q_block=16, max_running=4, keep_logits=False)
        plain = Replica(**common)
        spec = Replica(**common, spec_k=4, draft_model_name="llama-tiny",
                       draft_model_kwargs={"n_layers": 2}, tag="spec")
        assert isinstance(spec.engine, SpecEngine)
        assert spec.draft_restored_step == 1
        prompts = [[int(x) for x in rng.randint(0, 256, n)]
                   for n in (6, 11)]

        def run(replica):
            eng = replica.engine
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, tokens=p, max_new_tokens=5))
            return {c.rid: c.tokens for c in eng.run()}

        base = run(plain)
        out = run(spec)
        assert base == out
        # Draft == target (same ckpt): total acceptance.
        assert spec.engine.spec_accepted == spec.engine.spec_proposed > 0
        # The heartbeat file a spec replica publishes carries the
        # effective-throughput fields end to end.
        stats_path = tmp_path / "stats.json"
        spec.engine.write_stats(str(stats_path))
        from tony_tpu.executor import read_serve_stats

        read = read_serve_stats(stats_path)
        assert read["acceptance_rate"] == 1.0
        assert read["tokens_per_seq_round"] > 1.0
