"""One serving replica: elastic-restored params + engine + RPC front.

A replica is the serve job type's user process (``python -m
tony_tpu.serve.replica``, launched by the executor like any other
workload). Startup:

1. build the registered model (``tony.serve.model`` + JSON kwargs —
   including ``quant=`` lanes, which serve through the same projections
   training used);
2. restore ONLY the params subtree of the training checkpoint through
   elastic restore onto the replica's own mesh
   (:func:`tony_tpu.ckpt.find_path_prefix` locates the subtree whatever
   the save's wrapping; ``dtype_policy="bf16"`` casts the f32 master to
   the serving dtype during shard assembly — optimizer slots are never
   even read);
3. run a :class:`~tony_tpu.serve.engine.ServeEngine` behind the
   control-plane RPC wire (same JSON-lines protocol as the AM — and the
   existing :class:`tony_tpu.proxy.ProxyServer` fronts it for gateway
   access, exactly like notebooks);
4. publish the engine's qps/p99/queue-depth to the ``TONY_SERVE_STATS``
   file the executor's heartbeat piggybacks to the AM — the signal the
   replica autoscaler acts on.

Concurrent ``generate`` RPCs drive ONE shared engine: each call submits
its request and then takes turns advancing the loop until its own
completion lands, so overlapping calls naturally join the continuous
batch.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from tony_tpu import profiler
from tony_tpu.conf import (CKPT_DIR, SERVE_AOT_CACHE, SERVE_BLOCK_SIZE,
                           SERVE_CKPT_DIR, SERVE_CTX_MAX,
                           SERVE_DEMOTE_BATCH, SERVE_DEMOTE_WATERMARK,
                           SERVE_DRAFT_CKPT_DIR, SERVE_DRAFT_MODEL,
                           SERVE_DRAFT_MODEL_KWARGS,
                           SERVE_DRAFT_NGRAM_MAX, SERVE_DTYPE_POLICY,
                           SERVE_HOST_BLOCKS, SERVE_MAX_RUNNING,
                           SERVE_MESH, SERVE_MODEL, SERVE_MODEL_KWARGS,
                           SERVE_PORT, SERVE_PREFILL_CHUNK,
                           SERVE_PREFIX_CACHE, SERVE_PREFIX_STORE,
                           SERVE_SPEC_K, SERVE_WARM_STANDBY,
                           serve_role_key, serve_warm_standby_key)
from tony_tpu.serve.engine import Completion, EngineFront, ServeEngine


class Replica:
    """Build (restore + engine) and front one serving replica."""

    def __init__(self, *, model_name: str,
                 model_kwargs: Optional[Dict[str, Any]] = None,
                 ckpt_dir: str, dtype_policy: Optional[str] = "bf16",
                 mesh: Optional[Any] = None, ctx_max: int = 2048,
                 block_size: int = 16, q_block: int = 16,
                 n_blocks: Optional[int] = None, max_running: int = 16,
                 keep_logits: bool = False, tag: str = "serve",
                 spec_k: int = 0,
                 draft_model_name: Optional[str] = None,
                 draft_model_kwargs: Optional[Dict[str, Any]] = None,
                 draft_ckpt_dir: Optional[str] = None,
                 ngram_max: int = 3,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 role: str = "colocated", host_blocks: int = 0,
                 prefix_store: Optional[str] = None,
                 aot_cache: Optional[str] = None,
                 warm_standby: bool = False,
                 demote_watermark: float = 0.0,
                 demote_batch: int = 0,
                 qos: Optional[Any] = None):
        from tony_tpu.models import get_model
        from tony_tpu.serve.disagg import DecodeFront, PrefillFront

        # Cold-start plane (tony_tpu.ckpt.aot): a cache DIR in the conf
        # becomes a live AOTCache shared by every step family the
        # engine compiles. Built before the engine so the very first
        # bucket resolution can hit.
        self._aot = None
        if aot_cache:
            from tony_tpu.ckpt import AOTCache

            self._aot = AOTCache(aot_cache)
        self.model = get_model(model_name, **(model_kwargs or {}))
        self.mesh = mesh
        # Continuous publication (tony_tpu.publish): a published pointer
        # outranks "latest committed" — the pointer is the train gang's
        # statement of which step the fleet should serve, and a replica
        # that came up mid-stream must match the fleet it joins.
        self.ckpt_dir = ckpt_dir
        self.dtype_policy = dtype_policy
        self.q_block = q_block
        self.ctx_max = ctx_max
        from tony_tpu.publish import latest_publication

        pub = latest_publication(ckpt_dir)
        t_restore = time.monotonic()
        params, step, prefix = self._restore_params(
            self.model, ckpt_dir, dtype_policy=dtype_policy, mesh=mesh,
            q_block=q_block, step=pub["step"] if pub else None)
        restore_s = time.monotonic() - t_restore
        self.restored_step = step
        if spec_k:
            # Speculative lane (tony_tpu.serve.spec): draft-and-verify.
            # A named draft model restores through the SAME elastic path
            # as the target (its own ckpt dir, or the target's when the
            # two share a save); no draft model = self-drafting n-gram.
            from tony_tpu.serve.spec import SpecEngine

            draft_kw: Dict[str, Any] = {"ngram_max": ngram_max}
            if draft_model_name:
                draft_model = get_model(draft_model_name,
                                        **(draft_model_kwargs or {}))
                draft_params, draft_step, _ = self._restore_params(
                    draft_model, draft_ckpt_dir or ckpt_dir,
                    dtype_policy=dtype_policy, mesh=mesh, q_block=q_block)
                draft_kw.update(draft_model=draft_model,
                                draft_params=draft_params)
                self.draft_restored_step = draft_step
            self.engine = SpecEngine(
                self.model, params, spec_k=spec_k, ctx_max=ctx_max,
                block_size=block_size, q_block=q_block, n_blocks=n_blocks,
                max_running=max_running, mesh=mesh,
                keep_logits=keep_logits, tag=tag,
                prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
                role=role, host_blocks=host_blocks,
                async_offload=host_blocks > 0, aot_cache=self._aot,
                warm_standby=warm_standby,
                demote_watermark=demote_watermark,
                demote_batch=demote_batch, qos=qos, **draft_kw)
        else:
            self.engine = ServeEngine(
                self.model, params, ctx_max=ctx_max,
                block_size=block_size, q_block=q_block, n_blocks=n_blocks,
                max_running=max_running, mesh=mesh,
                keep_logits=keep_logits, tag=tag,
                prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
                role=role, host_blocks=host_blocks,
                async_offload=host_blocks > 0, aot_cache=self._aot,
                warm_standby=warm_standby,
                demote_watermark=demote_watermark,
                demote_batch=demote_batch, qos=qos)
        # Seed the serving version: a replica restored from a published
        # step advertises that version on its very first heartbeat, so
        # the AM's rolling swap never re-swaps a replica that already
        # came up on the target.
        self.engine.weight_step = int(step)
        self.engine.restore_s = restore_s
        if pub is not None and pub["step"] == step:
            self.engine.weight_version = pub["version"]
        profiler.record("serve", "replica", model=model_name,
                        ckpt_step=step, path_prefix=prefix,
                        dtype_policy=dtype_policy, spec_k=int(spec_k),
                        draft_model=draft_model_name or
                        ("ngram" if spec_k else None),
                        prefix_cache=bool(prefix_cache),
                        prefill_chunk=prefill_chunk, role=role,
                        mesh_axes=dict(getattr(mesh, "shape", {}) or {}))
        self.role = role
        self._front = EngineFront(self.engine)
        # Disaggregated handoff halves (tony_tpu.serve.disagg). Every
        # replica carries BOTH: the router's role-aware dispatch decides
        # which verbs see traffic, and a colocated replica answering a
        # stray kv_offer is harmless — capability is not policy.
        self._prefill_front = PrefillFront(self._front)
        self._decode_front = DecodeFront(self._front)
        # Persistent prefix store (tony_tpu.serve.kvstore): adopt the
        # persisted hot stems NOW — before the first request — so a
        # fresh replica (or a scale-up grant naming the store) serves
        # its first shared-stem prompt from disk-warmed KV instead of
        # recompute; the stats publisher exports newly-hot stems back.
        self._store = None
        if prefix_store:
            from tony_tpu.serve.kvstore import PrefixStore

            self._store = PrefixStore(prefix_store)
            self._load_stems()
        # Pre-resolve the step family when the cold-start plane is on:
        # a warm STANDBY must hold executables before promotion (that
        # is the whole point of the pool), and a cache-armed active
        # replica resolves now so its first request pays deserialize
        # milliseconds — and its misses populate the cache for every
        # later grant of the family.
        if self._aot is not None or warm_standby:
            n = self.engine.warm()
            print(f"[tony-serve-replica] warmed {n} step program(s) "
                  f"(aot hits {self.engine.aot_hits}, "
                  f"misses {self.engine.aot_misses})", flush=True)
        self._publish: Optional[Any] = None
        self.port: Optional[int] = None

    def _load_stems(self) -> None:
        """Warm the engine's prefix tier from the store — best-effort:
        a corrupt or geometry-skewed stem is skipped (that prefix
        recomputes), never a startup failure."""
        header = self.engine.cache.wire_header()
        adopted = 0
        for tip in self._store.stems():
            rec = self._store.get(tip)
            if rec is None or rec.get("header") != header:
                continue
            adopted += self.engine.adopt_stem(rec["keys"], rec["blocks"])
        if adopted:
            print(f"[tony-serve-replica] adopted {adopted} KV block(s) "
                  f"from the prefix store", flush=True)

    @staticmethod
    def _restore_params(model: Any, ckpt_dir: str, *,
                        dtype_policy: Optional[str], mesh: Optional[Any],
                        q_block: int, step: Optional[int] = None):
        """Elastic params-only restore onto the replica's mesh — shared
        by the target and the speculative lane's draft model (both are
        trained checkpoints; neither may initialize fresh weights).
        ``step`` pins a specific committed step — the hot-swap path and
        the published-pointer startup both restore a NAMED manifest,
        never whatever happens to be latest when the restore runs."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from tony_tpu import ckpt

        sample = jnp.zeros((1, q_block), jnp.int32)

        def init():
            return nn.unbox(model.init(jax.random.PRNGKey(0),
                                       sample))["params"]

        # Template: structure/shapes only — every value is replaced by
        # the restore below (and the restore is what the e2e test pins).
        # Meshless, that is literally all it is: abstract shapes pinned to
        # the default device; running the init for values nobody reads is
        # minutes of compilation at a real model's width.
        with profiler.span("tony:restore") as sp:
            t0 = time.monotonic()
            if mesh is not None:
                with jax.set_mesh(mesh):
                    template = jax.jit(init)()
                jax.block_until_ready(template)
            else:
                one = jax.sharding.SingleDeviceSharding(
                    profiler.backend_devices()[0])
                template = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=one),
                    jax.eval_shape(init))
            t1 = time.monotonic()
            if step is None:
                step = ckpt.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {ckpt_dir} — a replica "
                    f"serves a trained model, it does not initialize one")
            prefix = ckpt.find_path_prefix(ckpt_dir, template, step=step)
            params = ckpt.restore_pytree(
                ckpt_dir, template, step=step, mesh=mesh,
                dtype_policy=dtype_policy, path_prefix=prefix)
            jax.block_until_ready(params)
            sp.attrs.update(step=step, bytes=sum(
                x.nbytes for x in jax.tree.leaves(params)))
            # Grant -> first token starts here: say where the seconds went.
            print(f"[tony-serve-replica] restored step {step}: template "
                  f"{t1 - t0:.1f}s, read+place {time.monotonic() - t1:.1f}s",
                  flush=True)
        return params, step, prefix

    # -- request path ------------------------------------------------------
    def generate(self, tokens: Sequence[int], max_new_tokens: int,
                 rid: Optional[Any] = None,
                 conv: Optional[Any] = None,
                 tenant: Optional[str] = None) -> Completion:
        """Submit one request and drive the shared engine until it
        completes. Thread-safe: concurrent callers interleave on the
        drive lock (:class:`~tony_tpu.serve.engine.EngineFront` — the
        same loop the router's in-process transport runs), so their
        requests ride one continuous batch. ``conv`` is the
        conversation handle arming park/resume on a host-tier engine;
        ``tenant`` is the QoS class the engine's admission budgets
        meter (tony_tpu.serve.qos — ignored on an unloaded engine)."""
        return self._front.generate(tokens, max_new_tokens, rid=rid,
                                    conv=conv, tenant=tenant)

    # -- disaggregated handoff (tony_tpu.serve.disagg) ---------------------
    def prefill_handoff(self, tokens: Sequence[int], max_new_tokens: int,
                        rid: Optional[Any] = None,
                        decode: Any = None,
                        conv: Optional[Any] = None,
                        tenant: Optional[str] = None) -> Completion:
        """Prefill-role request path: prefill ``tokens``, ship the KV
        blocks to ``decode`` (an address or an in-process receiver),
        return the completion the decode side drove to the end."""
        return self._prefill_front.prefill_handoff(
            tokens, max_new_tokens, rid=rid, decode=decode, conv=conv,
            tenant=tenant)

    def kv_offer(self, keys: Sequence[str]) -> int:
        return self._decode_front.kv_offer(keys)

    def kv_import(self, payload: Dict[str, Any]) -> Completion:
        return self._decode_front.kv_import(payload)

    # -- warm-standby promotion (tony_tpu.serve.scaling) -------------------
    def promote(self) -> bool:
        """AM scale-up path: leave warm standby and republish stats
        IMMEDIATELY — the session routes on warm_standby=0, and waiting
        a publish tick to become routable would hand back the very
        cold-start latency the pool exists to hide."""
        was = self.engine.promote()
        if was and self._publish is not None:
            self._publish()
        return was

    # -- hot weight swap (tony_tpu.serve.swap) -----------------------------
    def hot_swap(self, *, version: Optional[int] = None,
                 step: Optional[int] = None) -> Dict[str, Any]:
        """Swap this replica onto a published manifest IN PLACE —
        no container restart, no dropped request, no recompile.

        Three phases, and only the last needs the drive lock:

        1. resolve the target (the published pointer, or an explicit
           ``step`` pin) — pure pointer reads;
        2. restore the params subtree through the SAME elastic/dtype-
           policy path startup used, onto the live mesh, while the
           engine KEEPS SERVING the old weights (the disk + device_put
           minutes cost zero downtime);
        3. quiesce to an iteration boundary under the front's drive
           lock and flip (``EngineFront.quiesce_and_swap`` →
           ``ServeEngine.swap_params``): in-flight sequences finished
           under the old weights, the queued backlog admits under the
           new, the prefix/host tiers flushed, parked conversations
           kept.

        Any failure raises :class:`SwapError` with the old weights
        still serving (atomic-or-rolled-back); success republishes
        stats immediately so the router's down-mark lifts on the next
        heartbeat, not the next publish tick. The speculative lane's
        draft model is NOT swapped — it is a different checkpoint
        lineage; republish it by rolling the replica."""
        from tony_tpu import chaos
        from tony_tpu.serve.swap import SwapError, resolve_target

        t0 = time.monotonic()
        to_version, to_step = resolve_target(self.ckpt_dir,
                                             version=version, step=step)
        from_version = self.engine.weight_version
        chaos.crash_point("swap_before_restore")
        try:
            params, rstep, _ = self._restore_params(
                self.model, self.ckpt_dir, dtype_policy=self.dtype_policy,
                mesh=self.mesh, q_block=self.q_block, step=to_step)
        except SwapError:
            raise
        except Exception as exc:   # noqa: BLE001 — typed rollback contract
            raise SwapError(f"restore of step {to_step} failed: "
                            f"{type(exc).__name__}: {exc}") from exc
        chaos.crash_point("swap_after_restore")

        def flip() -> None:
            chaos.crash_point("swap_before_flip")
            self.engine.swap_params(params, version=to_version,
                                    step=to_step)
            chaos.crash_point("swap_after_flip")

        self._front.quiesce_and_swap(flip)
        self.restored_step = rstep
        if self._publish is not None:
            self._publish()
        return {"ok": True, "from_version": from_version,
                "to_version": to_version, "step": to_step,
                "wall_s": time.monotonic() - t0}

    def tune_warm_pads(self, history_dir: str, *,
                       limit: int = 4) -> List[int]:
        """warm() pad self-tuning (tony_tpu.serve.swap): read the
        prompt-length histograms earlier serve windows logged under
        ``history_dir`` and precompile the prefill pads the traffic
        actually used — the data-driven replacement for a caller-named
        ``prefill_pads=`` guess. Best-effort: an unreadable log warms
        nothing extra, never fails startup."""
        from tony_tpu import events as ev
        from tony_tpu.serve.swap import derive_prefill_pads

        records: List[Dict[str, Any]] = []
        try:
            for job in ev.list_jobs(history_dir):
                try:
                    records += [r for r in ev.read_events(job["path"])
                                if r.get("type") == ev.SERVE_WINDOW]
                except (OSError, ValueError):
                    continue
        except OSError:
            return []
        pads = derive_prefill_pads(
            records, q_block=self.engine.q_block,
            ctx_max=self.ctx_max, limit=limit)
        if pads:
            n = self.engine.warm(prefill_pads=pads)
            print(f"[tony-serve-replica] self-tuned prefill pads "
                  f"{pads} from the serve history ({n} program(s) "
                  f"resolved)", flush=True)
        return pads

    # -- RPC front ---------------------------------------------------------
    def rpc_handler(self) -> "_ReplicaRpcHandler":
        return _ReplicaRpcHandler(self)

    def serve_forever(self, *, host: str = "0.0.0.0", port: int = 0,
                      stats_path: Optional[str] = None,
                      stats_every_s: float = 2.0,
                      stop: Optional[threading.Event] = None) -> None:
        """Run the RPC server and the stats publisher until ``stop``."""
        from tony_tpu.rpc import RpcServer

        server = RpcServer(self.rpc_handler(), host=host, port=port)
        server.start()
        self.port = server.port
        print(f"[tony-serve-replica] listening on {server.address} "
              f"(ckpt step {self.restored_step})", flush=True)
        stop = stop or threading.Event()
        import jax

        devs = jax.devices()
        # Every published window names the device it was measured on.
        device = {"platform": devs[0].platform,
                  "device_kind": devs[0].device_kind,
                  "device_count": len(devs)}

        def publish() -> None:
            if not stats_path:
                return
            try:
                # rpc_port rides the stats file → heartbeat →
                # session so the request router can DIAL this
                # replica (task.port is the rendezvous port,
                # not the serve RPC) — and the prefix digest
                # rides the same payload for overlap scoring.
                self.engine.write_stats(
                    stats_path, extra={"rpc_port": server.port, **device})
            except OSError:
                pass
            if self._store is not None:
                # Persist newly-hot stems on the publish cadence —
                # under the drive lock (the export reads the pool,
                # and the pool is only safe under one driver).
                try:
                    with self._front._drive:
                        self.engine.export_stems(self._store)
                except OSError:
                    pass

        # The promote RPC republishes through this hook so a promotion
        # is routable on the next heartbeat, not the next publish tick.
        self._publish = publish
        try:
            # First publish BEFORE the first interval: the router can
            # only dial a replica whose rpc_port reached the AM, and a
            # freshly-granted scale-up that waits a full publish tick
            # to become routable pays that tick as cold-start latency.
            publish()
            while not stop.wait(stats_every_s):
                publish()
        finally:
            # Deterministic teardown (the concurrency plane's shutdown-
            # hygiene contract): server.stop() joins the accept thread,
            # and cache.close() joins the host-offload encode worker,
            # so by the time serve_forever returns no replica thread is
            # left running.
            server.stop()
            self.engine.cache.close()


class _ReplicaRpcHandler:
    """RPC verbs of one replica (JSON-lines wire, same as the AM's)."""

    def __init__(self, replica: Replica):
        self.replica = replica

    @staticmethod
    def _wire(c: Completion) -> Dict[str, Any]:
        return c.wire()

    def rpc_generate(self, tokens: List[int], max_new_tokens: int = 16,
                     rid: Optional[str] = None,
                     conv: Optional[str] = None,
                     tenant: Optional[str] = None) -> Dict[str, Any]:
        return self._wire(self.replica.generate(tokens, max_new_tokens,
                                                rid=rid, conv=conv,
                                                tenant=tenant))

    def rpc_serve_stats(self) -> Dict[str, float]:
        return self.replica.engine.stats()

    # -- disaggregated handoff verbs (tony_tpu.serve.disagg) ---------------
    def rpc_prefill_handoff(self, tokens: List[int],
                            max_new_tokens: int = 16,
                            rid: Optional[str] = None,
                            decode_address: Optional[str] = None,
                            conv: Optional[str] = None,
                            tenant: Optional[str] = None
                            ) -> Dict[str, Any]:
        """The router's disaggregated dispatch verb: prefill here, ship
        the KV replica-to-replica to ``decode_address``, return the
        decode side's completion. Typed failures transport as
        ``"HandoffError: ..."`` on the JSON-lines wire — the router
        re-types them for its fallback split."""
        out = self.replica.prefill_handoff(tokens, max_new_tokens,
                                           rid=rid, decode=decode_address,
                                           conv=conv, tenant=tenant)
        return out if isinstance(out, dict) else self._wire(out)

    def rpc_kv_offer(self, keys: List[str]) -> int:
        return self.replica.kv_offer(keys)

    def rpc_kv_import(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        return self._wire(self.replica.kv_import(payload))

    def rpc_promote(self) -> bool:
        """The AM's scale-up verb against a warm standby (idempotent —
        a retried promotion of an already-active replica returns
        False and changes nothing)."""
        return self.replica.promote()

    def rpc_swap(self, version: Optional[int] = None,
                 step: Optional[int] = None) -> Dict[str, Any]:
        """The AM's rolling-fleet verb: hot-swap this replica onto the
        published manifest (or an explicit ``step`` pin). A failure
        transports as ``"SwapError: ..."`` on the JSON-lines wire —
        the replica is still serving its OLD weights when the AM
        reads it (atomic-or-rolled-back)."""
        return self.replica.hot_swap(version=version, step=step)


def main() -> int:
    """``python -m tony_tpu.serve.replica`` — the serve job type's user
    command. Config comes from the job conf (``TONY_CONF_PATH``, written
    by ``tony serve``); the stats file path from ``TONY_SERVE_STATS``
    (exported by the executor)."""
    from tony_tpu import constants
    from tony_tpu.conf import TonyConfig
    from tony_tpu.distributed import _maybe_start_profiler
    from tony_tpu.util import enable_compile_cache

    enable_compile_cache()
    # What a training task gets from distributed.initialize: the build
    # counters, and a profiler server where the job asked for one
    # (tony.task.profiler.enabled), so `tony profile` captures a replica.
    profiler.watch_builds()
    _maybe_start_profiler()
    conf_path = os.environ.get(constants.ENV_CONF_PATH)
    if not conf_path:
        print("[tony-serve-replica] no TONY_CONF_PATH; run under a tony "
              "serve job")
        return 1
    conf = TonyConfig.load(conf_path)
    model_name = conf.get(SERVE_MODEL)
    ckpt_dir = conf.get(SERVE_CKPT_DIR) or conf.get(CKPT_DIR)
    if not model_name or not ckpt_dir:
        print(f"[tony-serve-replica] need {SERVE_MODEL} and "
              f"{SERVE_CKPT_DIR} in the job conf")
        return 1
    mesh = None
    mesh_kw = conf.get(SERVE_MESH)
    if mesh_kw:
        from tony_tpu import parallel as par
        mesh = par.MeshSpec(**json.loads(mesh_kw)).build()
    # Disaggregated role: the executor exports the jobtype
    # (TONY_JOB_NAME), the conf maps jobtype -> role — the per-jobtype
    # role spec `tony serve --role` writes. A classic one-jobtype serve
    # job has no role key and runs colocated.
    job_type = os.environ.get(constants.ENV_JOB_NAME) or "serve"
    role = conf.get(serve_role_key(job_type)) or "colocated"
    # Warm-standby membership is decided HERE, by position: the AM's
    # backfill grants elastic tasks above the jobtype's configured
    # instance count, so an index at-or-past that count with a warm
    # pool configured came up as a standby — it precompiles, donates
    # prefix stems, and waits for the promote RPC. The base gang
    # (index < instances) always starts active.
    warm_conf = conf.get(serve_warm_standby_key(job_type))
    if warm_conf is None:
        warm_conf = conf.get(SERVE_WARM_STANDBY)
    warm_pool = int(warm_conf or 0)
    # QoS plane (tony_tpu.serve.qos): a tenant spec in the conf arms
    # weighted-fair admission budgets; absent, from_conf returns None
    # and the engine runs the untagged path byte-identical to before.
    from tony_tpu.serve.qos import QosPolicy

    qos = QosPolicy.from_conf(conf)
    task_index = int(os.environ.get(constants.ENV_TASK_INDEX) or 0)
    warm_standby = warm_pool > 0 and task_index >= conf.instances(job_type)
    replica = Replica(
        model_name=model_name,
        model_kwargs=json.loads(conf.get(SERVE_MODEL_KWARGS) or "{}"),
        ckpt_dir=ckpt_dir,
        dtype_policy=conf.get(SERVE_DTYPE_POLICY, "bf16"),
        mesh=mesh,
        ctx_max=conf.get_int(SERVE_CTX_MAX, 2048),
        block_size=conf.get_int(SERVE_BLOCK_SIZE, 16),
        max_running=conf.get_int(SERVE_MAX_RUNNING, 16),
        spec_k=conf.get_int(SERVE_SPEC_K, 0),
        draft_model_name=conf.get(SERVE_DRAFT_MODEL),
        draft_model_kwargs=json.loads(
            conf.get(SERVE_DRAFT_MODEL_KWARGS) or "{}"),
        draft_ckpt_dir=conf.get(SERVE_DRAFT_CKPT_DIR),
        ngram_max=conf.get_int(SERVE_DRAFT_NGRAM_MAX, 3),
        prefix_cache=conf.get_bool(SERVE_PREFIX_CACHE, False),
        prefill_chunk=conf.get_int(SERVE_PREFILL_CHUNK, 0) or None,
        role=role,
        host_blocks=conf.get_int(SERVE_HOST_BLOCKS, 0),
        prefix_store=conf.get(SERVE_PREFIX_STORE) or None,
        aot_cache=conf.get(SERVE_AOT_CACHE) or None,
        warm_standby=warm_standby,
        demote_watermark=float(conf.get(SERVE_DEMOTE_WATERMARK) or 0.0),
        demote_batch=conf.get_int(SERVE_DEMOTE_BATCH, 0),
        qos=qos)
    # warm() pad self-tuning (tony_tpu.serve.swap): when the cold-start
    # plane is armed and a history root is configured, precompile the
    # prefill pads earlier serve traffic actually used — the histogram
    # in the SERVE_WINDOW records replaces the caller-named
    # prefill_pads= guess.
    from tony_tpu.conf import HISTORY_LOCATION

    history_dir = conf.get(HISTORY_LOCATION)
    if history_dir and (conf.get(SERVE_AOT_CACHE) or warm_standby):
        replica.tune_warm_pads(history_dir)
    profiler.write_timeline()       # end of set-up: restore and warm done
    replica.serve_forever(
        port=conf.get_int(SERVE_PORT, 0),
        stats_path=os.environ.get(constants.ENV_SERVE_STATS))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
