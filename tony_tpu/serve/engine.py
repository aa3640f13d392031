"""Continuous-batching inference engine over the paged KV cache.

One engine owns one replica's decode loop. The structure inverts the
data plane's prefetcher (PR 4): there, a producer thread stages batches
AHEAD of the training step; here, callers queue requests BEHIND the
decode loop (the admission queue) and the loop pulls them into the
running batch at iteration granularity — a request joins as soon as pool
blocks and a batch slot are free, and leaves (eviction) the step its
generation completes, with every other sequence's decode undisturbed.

Static shapes are bucketed so join/evict never recompiles:

* **row blocks** — every forward processes query rows in blocks of
  ``q_block`` (default 16, the bf16 sublane tile): prefill pads the
  prompt to a whole number of blocks, decode processes one block per
  sequence (1 real new token + padding rows whose cache writes are
  dropped). Fixed-tile row counts are ALSO the numerics contract: every
  serve op is row-independent at tile-multiple shapes, which is what
  makes continuous-batching decode bit-identical to a sequential full
  prefill of the same tokens (the tests pin it; single-row GEMV paths
  are where XLA CPU breaks row invariance, so the engine never issues
  one);
* **decode buckets** — the joined batch pads up to the next bucket size,
  so the decode step compiles once per bucket, not per batch
  composition;
* **one context extent** — the KV buffer gathered per step is always
  ``ctx_pad = nb_max · block_size`` positions, so ragged sequence
  lengths never change a shape (masking by absolute position does the
  rest).

Two admission-path features ride those shapes since PR 13, both OFF by
default so the unrouted engine is byte-for-byte the PR 10/12 one:

* ``prefix_cache=True`` — prompts chain-hash per full KV block and
  adopt published pool blocks (:mod:`tony_tpu.serve.kvcache`'s prefix
  tier) instead of recomputing the shared prefix: the corresponding
  prefill launches are simply never issued. Bitwise transparent — an
  adopted block holds exactly the bytes the skipped launch would have
  written (row independence at tile multiples), and every KV scatter
  goes through the cache's copy-on-write ``write_index`` so a shared
  block is never mutated;
* ``prefill_chunk=N`` — prompts prefill in fixed ``N``-row chunks (a
  ``q_block`` multiple), one chunk per engine iteration, interleaved
  with decode: a long admission costs the running batch one extra
  launch per token step instead of a whole-prompt stall. The chunk
  geometry is the only new compiled shape, pinned by the ``route``
  analyze signature.

The decode step is registered with the collective planner at build time
(``tony_tpu.profiler.record("collective", ...)``, plane ``serve_decode``)
with an EMPTY expected set: a replica's decode touches no inter-chip
collective — its mesh exists for memory, not for cross-replica math —
and ``tony analyze --config serve`` audits the traced step against that
promise (a GSPMD-inserted reshard is a finding, not a slowdown).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
import time
import uuid
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from tony_tpu import profiler

with profiler.importing("jax"):         # set-up span tony:import
    import jax
    import jax.numpy as jnp
import numpy as np
from tony_tpu.serve import prefix as prefix_mod
from tony_tpu.serve.disagg import HandoffError, decode_f32, encode_f32
from tony_tpu.serve.kvcache import AdmissionError, PagedKVCache

_record = functools.partial(profiler.record, "serve")


@dataclasses.dataclass
class Request:
    """One generation request. ``max_new_tokens`` is a hard cap; the
    engine reserves pool blocks for ``len(tokens) + max_new_tokens`` at
    admission so decode can never exhaust the pool mid-flight."""
    rid: Any
    tokens: List[int]
    max_new_tokens: int
    # Conversation handle (opaque; the router passes its session id).
    # Non-None arms parking on an engine with the host tier: eviction
    # parks the sequence's KV under this handle instead of dropping it,
    # and the NEXT request carrying the same handle resumes from the
    # parked blocks instead of re-prefilling the shared history.
    conv: Optional[Any] = None
    # Tenant tag (tony_tpu.serve.qos): names the request's QoS class on
    # a budget-armed engine and keys the per-tenant heartbeat breakdown.
    # None (the default) bypasses budgets entirely — the untagged path
    # is byte-identical to an engine without QoS.
    tenant: Optional[str] = None


@dataclasses.dataclass
class Completion:
    """One finished request: the generated tokens, per-position f32
    logits when the engine keeps them (``keep_logits=True`` — the test
    pin surface), the request's wall latency, and when each token was
    known to the host (``token_s``: seconds from submit on the engine's
    clock, one per generated token, read once per launch — the first is
    the time to first token, the differences the inter-token gaps)."""
    rid: Any
    prompt: List[int]
    tokens: List[int]
    logits: Optional[List[np.ndarray]]
    latency_s: float
    token_s: Optional[List[float]] = None

    def wire(self) -> Dict[str, Any]:
        """THE serving wire form (the replica RPC verbs all speak it;
        the jax-free router duck-types the same shape in
        ``router._wire_completion`` since it cannot import this
        class)."""
        return {"rid": self.rid, "tokens": list(self.tokens),
                "latency_ms": round(1e3 * self.latency_s, 3),
                "token_ms": [round(1e3 * t, 3)
                             for t in self.token_s or ()]}


class _Seq:
    __slots__ = ("rid", "tokens", "n_prompt", "remaining", "logits",
                 "t_submit", "pf_pos", "published", "hkey", "conv",
                 "tenant", "qcharge", "token_s")

    def __init__(self, req: Request, t_submit: float):
        self.rid = req.rid
        self.conv = req.conv
        self.tenant = getattr(req, "tenant", None)
        # Device blocks charged to this sequence's tenant at admission
        # (0 on untagged or un-budgeted engines); _evict releases it.
        self.qcharge = 0
        self.tokens: List[int] = list(req.tokens)
        self.n_prompt = len(req.tokens)
        self.remaining = int(req.max_new_tokens)
        self.logits: List[np.ndarray] = []
        self.t_submit = t_submit
        # Seconds from submit at which each generated token was emitted.
        self.token_s: List[float] = []
        # Prefill cursor: the next position whose row is still
        # uncomputed (admission sets it past an adopted shared prefix;
        # chunked prefill advances it chunk by chunk).
        self.pf_pos = 0
        # Prefix-publication cursor: blocks [0, published) are indexed
        # under their chain keys; ``hkey`` is the chain state (the last
        # published block's key) so extension never rehashes history.
        self.published = 0
        self.hkey = ""


def _bucket_of(buckets: Sequence[int], n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch {n} exceeds the largest decode bucket "
                     f"{max(buckets)}")


def build_step_fn(model: Any, *, n_layers: int, n_blocks: int,
                  block_size: int, kv_dim: int, ctx_pad: int, b: int,
                  t: int) -> Callable:
    """The (b, t)-shaped jitted serve step over a paged pool: gather
    each sequence's blocks into the fixed-extent KV buffers, run the
    serve forward, commit the fresh rows back to the pool through the
    host-computed flat scatter indices (OOB rows drop). Pools are
    donated — callers immediately rebind them, so the update is
    in-place-ish.

    Module-level so the speculative lane's draft model
    (:mod:`tony_tpu.serve.spec`) runs the IDENTICAL program over its own
    pool: one builder, one jaxpr shape family, one signature pin."""
    L, nb, bs, kvd, ctx = n_layers, n_blocks, block_size, kv_dim, ctx_pad

    def fn(params, pool_k, pool_v, tokens, positions, tables,
           flat_idx):
        # mode="clip", NOT the default NaN-fill: table padding (and
        # the scratch reference's contiguous table on a small pool)
        # may point past the pool, and those positions are masked by
        # the attention — but only 0 x FINITE is exactly 0; a
        # NaN-filled block would poison every masked row.
        kbuf = jnp.take(pool_k, tables, axis=1,
                        mode="clip").reshape(L, b, ctx, kvd)
        vbuf = jnp.take(pool_v, tables, axis=1,
                        mode="clip").reshape(L, b, ctx, kvd)
        logits, (knew, vnew) = model.apply(
            {"params": params}, tokens, positions=positions,
            kv=(kbuf, vbuf))
        pk = pool_k.reshape(L, nb * bs, kvd).at[:, flat_idx].set(
            knew.astype(pool_k.dtype), mode="drop")
        pv = pool_v.reshape(L, nb * bs, kvd).at[:, flat_idx].set(
            vnew.astype(pool_v.dtype), mode="drop")
        return (logits, pk.reshape(L, nb, bs, kvd),
                pv.reshape(L, nb, bs, kvd))

    return jax.jit(fn, donate_argnums=(1, 2))


class PagedModelRunner:
    """Shared geometry + jitted-step plumbing over ONE model and ONE
    paged KV pool: the base of both the serve engine and the
    speculative lane's draft model (:class:`tony_tpu.serve.spec.
    ModelDraft`). Owning it here keeps the two lanes on one jit cache
    shape, one mesh/donation discipline, and one forward counter idiom —
    a change to how a step runs cannot drift between them."""

    def _init_paged(self, model: Any, params: Any, *, ctx_max: int,
                    block_size: int, q_block: int,
                    decode_buckets: Sequence[int], max_running: int,
                    n_blocks: Optional[int], mesh: Optional[Any],
                    host_blocks: int = 0,
                    async_offload: bool = False,
                    aot_cache: Optional[Any] = None) -> None:
        cfg = model.cfg
        if q_block % 8:
            raise ValueError(f"q_block must be a sublane-tile multiple "
                             f"(8), got {q_block}")
        self.model = model
        self.params = params
        self.mesh = mesh
        self.q_block = int(q_block)
        self.decode_buckets = tuple(sorted(set(
            list(decode_buckets) + [max_running])))
        self.max_running = int(max_running)
        self.n_layers = cfg.n_layers
        self.kv_dim = cfg.n_kv_heads * cfg.head_dim
        self.block_size = int(block_size)
        nb_max = -(-int(ctx_max) // self.block_size)
        self.nb_max = nb_max
        self.ctx_pad = nb_max * self.block_size
        if n_blocks is None:
            n_blocks = nb_max * self.max_running
        self.cache = PagedKVCache(self.n_layers, self.kv_dim,
                                  n_blocks=n_blocks,
                                  block_size=self.block_size,
                                  dtype=cfg.dtype,
                                  host_blocks=host_blocks,
                                  async_offload=async_offload)
        self._fns: Dict[Tuple[int, int], Callable] = {}
        # AOT compile cache (tony_tpu.ckpt.aot — the replica cold-start
        # plane): executables resolved through the cache live in a
        # PARALLEL dict so the raw jitted Wrapped in _fns stays what the
        # analysis hooks (decode_traced / prefill_traced) trace — the
        # cache must never change the traced program, only who compiles
        # it. With no cache and no warm(), _aot_fns stays empty and the
        # launch path is byte-for-byte the raw jit.
        self.aot_cache = aot_cache
        self._aot_fns: Dict[Tuple[int, int], Callable] = {}
        self.aot_hits = 0
        self.aot_misses = 0
        self.fresh_compiles = 0
        self.compile_ms = 0.0
        self.deserialize_ms = 0.0
        # Start-up seconds: warm() adds its own; the replica sets what the
        # params restore took.
        self.warm_s = 0.0
        self.restore_s = 0.0
        # Forward-launch counter (prefills + decode/verify steps): the
        # machine-independent cost of a schedule — on an accelerator the
        # forward dominates wall time, so fewer launches for the same
        # tokens IS the batching/speculation win.
        self.forwards = 0
        # Weight publication (tony_tpu.publish / serve.swap): which
        # published pointer version (and its ckpt step) the live params
        # came from — 0/0 until a publication is known. The version
        # rides every stats publish so the router and the history plane
        # can prove which weights answered which request; ``swapping``
        # gates admission during a hot swap's quiesce window (and rides
        # the heartbeat so the router down-marks the replica).
        self.weight_version = 0
        self.weight_step = 0
        self.weight_swaps = 0
        self.swapping = False

    def _fn(self, b: int, t: int) -> Callable:
        """The cached view of :func:`build_step_fn` — prefill, decode,
        AND the speculative lane's k+1-row verification all share these
        entries (verification is a decode-shaped launch with more real
        rows, so it adds zero compiles)."""
        key = (b, t)
        if key not in self._fns:
            self._fns[key] = build_step_fn(
                self.model, n_layers=self.n_layers,
                n_blocks=self.cache.n_blocks, block_size=self.block_size,
                kv_dim=self.kv_dim, ctx_pad=self.ctx_pad, b=b, t=t)
        return self._fns[key]

    def _example_args(self, b: int, t: int) -> Tuple:
        """Shape-exact example arguments of the (b, t) step — the ONE
        aval source for lowering (:meth:`_compile_step`) and the
        analysis hooks (:meth:`ServeEngine.decode_traced` /
        :meth:`ServeEngine.prefill_traced`), so what the AOT path
        compiles can never drift from what the analyzer audits."""
        return (self.params, self.cache.k, self.cache.v,
                jnp.zeros((b, t), jnp.int32),
                jnp.zeros((b, t), jnp.int32),
                jnp.zeros((b, self.nb_max), jnp.int32),
                jnp.full((b, t), self.cache.oob_index, jnp.int32))

    def _aot_fingerprint(self, b: int, t: int) -> Dict[str, Any]:
        """The (b, t) step program's cache identity: mesh topology, the
        full build_step_fn geometry, the model config, and the
        params/pool aval digest — plus the jax/jaxlib/XLA runtime half
        make_fingerprint adds. Anything here drifting is a MISS."""
        from tony_tpu.ckpt import aot
        cfg = getattr(self.model, "cfg", None)
        return aot.make_fingerprint(
            "serve_step", mesh=self.mesh,
            geometry={"n_layers": self.n_layers,
                      "n_blocks": self.cache.n_blocks,
                      "block_size": self.block_size,
                      "kv_dim": self.kv_dim, "ctx_pad": self.ctx_pad,
                      "b": int(b), "t": int(t), "donate": [1, 2]},
            model=f"{type(self.model).__name__}:{cfg!r}",
            tree=(self.params, self.cache.k, self.cache.v))

    def _compile_step(self, b: int, t: int) -> Callable:
        """Lower + compile the (b, t) program ahead of time (counted in
        ``fresh_compiles``/``compile_ms``) — the same jitted function
        the default path runs, so the resulting executable is the
        IDENTICAL program, just compiled now instead of at first
        launch."""
        t0 = time.monotonic()
        jitted = self._fn(b, t)
        args = self._example_args(b, t)
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                compiled = jitted.lower(*args).compile()
        else:
            compiled = jitted.lower(*args).compile()
        self.compile_ms += 1e3 * (time.monotonic() - t0)
        self.fresh_compiles += 1
        return compiled

    def _resolve_aot(self, b: int, t: int) -> Callable:
        """One (b, t) executable through the AOT cache: deserialize on
        hit (milliseconds), trace+compile AND populate on miss. The
        cache degrades to a counted miss on any corruption, fingerprint
        drift, or unsupported backend — it may cost a compile, never a
        wrong program."""
        fp = self._aot_fingerprint(b, t)
        t0 = time.monotonic()
        compiled = self.aot_cache.get(fp)
        if compiled is not None:
            self.deserialize_ms += 1e3 * (time.monotonic() - t0)
            self.aot_hits += 1
            return compiled
        self.aot_misses += 1
        compiled = self._compile_step(b, t)
        self.aot_cache.put(fp, compiled)
        return compiled

    def _step_callable(self, b: int, t: int) -> Callable:
        """What :meth:`_run_fn` launches for shape (b, t): the raw
        jitted Wrapped when nothing armed the AOT plane (the default
        engine, byte for byte), else the resolved Compiled — from the
        cache on hit, freshly compiled (and persisted) on miss."""
        fn = self._aot_fns.get((b, t))
        if fn is None:
            if self.aot_cache is None:
                return self._fn(b, t)
            fn = self._resolve_aot(b, t)
            self._aot_fns[(b, t)] = fn
        return fn

    def warm(self, prefill_pads: Sequence[int] = ()) -> int:
        """Resolve the engine's enumerable step family NOW — every
        decode bucket (the speculative verify launch rides the same
        shapes), the chunked-prefill program, and any caller-named
        extra prefill pads — so a warm-standby replica holds compiled
        executables BEFORE its first request: cache hits deserialize in
        milliseconds; cold misses pay the trace+compile here, ahead of
        the traffic curve, and populate the cache for the whole fleet.
        Returns programs resolved."""
        shapes = [(int(b), self.q_block) for b in self.decode_buckets]
        chunk = getattr(self, "prefill_chunk", None)
        if chunk:
            shapes.append((1, int(chunk)))
        for p in prefill_pads:
            shapes.append((1, int(p)))
        n = 0
        t0 = time.monotonic()
        with profiler.span("tony:warm") as sp:
            for key in dict.fromkeys(shapes):
                if key not in self._aot_fns:
                    self._aot_fns[key] = (
                        self._resolve_aot(*key)
                        if self.aot_cache is not None
                        else self._compile_step(*key))
                    n += 1
            sp.attrs.update(programs=n)
        self.warm_s += time.monotonic() - t0
        return n

    def _run_fn(self, b, t, tokens, positions, tables, flat_idx):
        fn = self._step_callable(b, t)
        args = (self.params, self.cache.k, self.cache.v,
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), jnp.asarray(flat_idx))
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                logits, pk, pv = fn(*args)
        else:
            logits, pk, pv = fn(*args)
        self.cache.k, self.cache.v = pk, pv
        self.forwards += 1
        return logits

    def swap_params(self, new_params: Any, *, version: int,
                    step: int) -> None:
        """Flip the live param tree to ``new_params`` — the hot-swap
        plane's commit point (tony_tpu.serve.swap). The CALLER owns the
        iteration-boundary contract: no launch may be in flight (the
        replica runs this under the front's drive lock after a
        quiesce), because ``_run_fn`` reads ``self.params`` fresh per
        launch and the flip is a single reference store — the next
        launch runs the new weights whole, no launch ever sees a mix.

        Atomic-or-rolled-back: the new tree must match the old one's
        structure, shapes, and dtypes EXACTLY — any drift raises
        :class:`~tony_tpu.serve.swap.SwapError` with the old params
        still live (a publication whose manifest changed geometry needs
        a restart, not a swap). A same-geometry flip is what keeps the
        compiled plane valid: the AOT fingerprint digests avals, not
        values, so every jitted/AOT executable survives — a swap costs
        zero recompiles."""
        from tony_tpu.serve.swap import SwapError

        old_leaves, old_def = jax.tree.flatten(self.params)
        new_leaves, new_def = jax.tree.flatten(new_params)
        if old_def != new_def:
            raise SwapError(
                f"param tree structure changed: {len(old_leaves)} vs "
                f"{len(new_leaves)} leaves — the published manifest is "
                f"not this engine's geometry; old weights kept")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            if o.shape != n.shape or o.dtype != n.dtype:
                raise SwapError(
                    f"param leaf {i} changed aval: {o.shape}/{o.dtype} "
                    f"-> {n.shape}/{n.dtype}; old weights kept")
        self.params = new_params
        self.weight_version = int(version)
        self.weight_step = int(step)
        self.weight_swaps += 1


class ServeEngine(PagedModelRunner):
    """Continuous-batching loop for one replica.

    ``model`` is a serve-capable flax module (today:
    :class:`tony_tpu.models.transformer.Transformer` — its ``kv=``
    forward); ``params`` its (restored, typically bf16) param tree.
    ``mesh`` wraps every jitted call in the replica's mesh context so
    sharded params compute in place; ``None`` runs on the default
    device placement.
    """

    def __init__(self, model: Any, params: Any, *, ctx_max: int,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 q_block: int = 16, decode_buckets: Sequence[int] = (4, 16),
                 max_running: int = 16, mesh: Optional[Any] = None,
                 keep_logits: bool = False, join_policy: str = "continuous",
                 stats_window_s: float = 60.0, tag: str = "serve",
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 role: str = "colocated", host_blocks: int = 0,
                 async_offload: bool = False,
                 aot_cache: Optional[Any] = None,
                 warm_standby: bool = False,
                 demote_watermark: float = 0.0, demote_batch: int = 0,
                 qos: Optional[Any] = None):
        if join_policy not in ("continuous", "static"):
            raise ValueError(f"unknown join_policy {join_policy!r} "
                             "(continuous|static)")
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r} "
                             "(colocated|prefill|decode)")
        if not 0.0 <= float(demote_watermark) <= 1.0:
            raise ValueError(f"demote_watermark must be a pool fraction "
                             f"in [0, 1], got {demote_watermark}")
        self._init_paged(model, params, ctx_max=ctx_max,
                         block_size=block_size, q_block=q_block,
                         decode_buckets=decode_buckets,
                         max_running=max_running, n_blocks=n_blocks,
                         mesh=mesh, host_blocks=host_blocks,
                         async_offload=async_offload,
                         aot_cache=aot_cache)
        # Prefix caching (off by default — the unrouted PR 10/12
        # behavior): admission chain-hashes the prompt's full blocks and
        # adopts published matches instead of recomputing them. Bitwise
        # transparent by the row-independence contract; the route tests
        # pin hit and miss against this engine with the knob off.
        self.prefix_cache = bool(prefix_cache)
        # Chunked prefill (None = monolithic): long prompts prefill in
        # fixed row-block-multiple chunks interleaved with decode
        # iterations, so one long admission never stalls every running
        # sequence's next token for a whole-prompt launch.
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk <= 0 or prefill_chunk % self.q_block:
                raise ValueError(
                    f"prefill_chunk must be a positive q_block="
                    f"{self.q_block} multiple, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # Disaggregated serving role (tony_tpu.serve.disagg): telemetry
        # + router dispatch semantics. The engine itself stays fully
        # capable whatever the role — a "decode" replica still prefills
        # for itself on the colocated-fallback path, and "colocated"
        # (the default) is byte-for-byte the PR 10/12/13 engine.
        self.role = role
        # Handoff counters (the widened heartbeat schema — zeros on
        # colocated engines so the fleet schema stays uniform).
        self.blocks_shipped = 0
        self.handoff_ms = 0.0
        self.imports_failed = 0
        self.handoffs_out = 0
        self.handoffs_in = 0
        # KV memory hierarchy (PR 16): with a host tier armed
        # (host_blocks > 0), eviction PARKS a conversation-tagged
        # sequence instead of dropping its KV, and the conversation's
        # next turn resumes from the parked blocks through the atomic
        # import path — no re-prefill of the shared history. The map is
        # conversation handle -> {"tokens": full parked token history,
        # "rid": the parked cache record's id}.
        self.host_offload = host_blocks > 0
        # Warm-standby membership (the cold-start plane's pool half):
        # a standby replica is compiled-and-idle — it heartbeats
        # warm_standby=1 so the session keeps it out of the routable
        # endpoint set and the autoscaler's active count, until the AM
        # promotes it (rpc_promote -> engine.promote()) on a scale-up
        # instead of paying a cold grant.
        self.warm_standby = bool(warm_standby)
        # Demotion daemon (ROADMAP KV follow-on; OFF by default): at
        # the high watermark the engine loop demotes a batch of cold
        # cached-tier blocks to host RAM ahead of pool pressure — the
        # same "be ready before the work arrives" story as the warm
        # pool. Batch default nb_max: one context extent per sweep,
        # the ROOFLINE §12 link unit (a demotion is one batched
        # device->host fetch, so the batch sizes the PCIe transfer).
        self.demote_watermark = float(demote_watermark)
        self.demote_batch = int(demote_batch) or self.nb_max
        self.daemon_demotions = 0
        self._parked: Dict[Any, Dict[str, Any]] = {}
        self.park_hits = 0
        self.park_lookups = 0
        # Typed degrades: promotion/resume failures that fell back to
        # re-prefill (pool pressure or a corrupt host payload) — the
        # hierarchy may cost recompute, never a wedge or a wrong byte.
        self.host_degraded = 0
        # Persistent prefix store bookkeeping: chain-parent links (to
        # walk a hot tip back to its root when exporting a stem) and
        # the most-recently-adopted tips (the export candidates).
        self._chain_parent: "OrderedDict[str, str]" = OrderedDict()
        self._hot_tips: "OrderedDict[str, None]" = OrderedDict()
        self._stored_tips: set = set()
        self.store_adopted = 0
        self.keep_logits = keep_logits
        self.join_policy = join_policy
        self.tag = tag
        # Per-tenant QoS (tony_tpu.serve.qos.QosPolicy; None = off — the
        # byte-identical untagged path). The policy gates the ADMISSION
        # scan only: the paged pool's refcount/free/LRU partition never
        # sees tenants; an over-budget tenant's requests simply wait in
        # the queue while later tenants' requests admit past them.
        self.qos = qos
        # Device blocks currently reserved per tenant (admission extent,
        # released at eviction) + lifetime per-tenant completions — the
        # heartbeat breakdown and the budget denominator's active set.
        self._tenant_blocks: Dict[str, int] = {}
        self._tenant_completed: Dict[str, int] = {}
        self.admission_rejections = 0
        self.qos_deferrals = 0
        self._queue: deque = deque()
        self._lock = threading.Lock()
        self._running: List[_Seq] = []
        self._prefilling: List[_Seq] = []
        # Prefix/prefill telemetry (lifetime counters; the heartbeat
        # schema publishes the derived rate — zeros when the features
        # are off, so the fleet schema stays uniform).
        self.prefix_lookup_blocks = 0
        self.prefix_hit_blocks = 0
        self.prefill_launches = 0
        self.prefill_rows = 0
        self.prefill_chunks = 0
        # Prompt-length histogram, bucketed by the PADDED prefill length
        # (the compile-relevant quantity): submit-time counts keyed by
        # the q_block-multiple pad a monolithic prefill of that prompt
        # launches at. Rides stats() as the one dict-of-scalars next to
        # tenants, and the SERVE_WINDOW event log accumulates it — the
        # warm() pad self-tuner (serve.swap.derive_prefill_pads) reads
        # the logged histogram back instead of a caller guessing
        # prefill_pads by hand.
        self._prompt_hist: Dict[int, int] = {}
        # Telemetry: completion ring for p50/p99, monotonic counters for
        # rates — O(1) per step, million-request safe.
        # (t_done, latency_s, n_tokens) per completion: rates and
        # percentiles are computed over a TIME window, not lifetime —
        # the autoscaler reads p99/qps as "now", and a latency spike
        # from an hour-old burst must age out or scale-down never fires.
        self._events: deque = deque(maxlen=512)
        self.stats_window_s = float(stats_window_s)
        self._completed = 0
        self._tokens_out = 0           # tokens of COMPLETED requests
        self._emitted = 0              # every generated token, at emit
        self._t0 = time.monotonic()
        self._steps = 0
        self._t_emit = time.monotonic()   # when the last launch's rows landed
        self.register_plan()

    # -- planner/profiler registration ------------------------------------
    def register_plan(self) -> None:
        """Register the decode step's (empty) collective schedule with
        the unified planner record plus the engine geometry — the
        day-one registration ROADMAP asks of every new step-path plane;
        ``tony analyze --config serve`` audits the traced decode against
        exactly this promise."""
        profiler.record("collective", "serve_decode", kind="none",
                        plane="serve_decode", axes=[], nbytes=[],
                        note="replica-local decode: zero inter-chip "
                             "collectives")
        _record(self.tag, ctx_pad=self.ctx_pad,
                block_size=self.block_size, nb_max=self.nb_max,
                n_blocks=self.cache.n_blocks, q_block=self.q_block,
                decode_buckets=list(self.decode_buckets),
                max_running=self.max_running,
                join_policy=self.join_policy,
                prefix_cache=self.prefix_cache,
                prefill_chunk=self.prefill_chunk,
                role=self.role)

    def expected_collectives(self) -> list:
        """The planner-registered expected collective set of the decode
        step: empty — a replica mesh shards memory, never the decode
        math. The analyzer reconciles the traced program against this."""
        return []

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request (thread-safe). Requests that can NEVER fit
        the context buffer are rejected now with a non-retryable
        :class:`AdmissionError`; pool pressure is handled later, at
        join time, by leaving the request queued."""
        total = len(req.tokens) + req.max_new_tokens
        if not req.tokens:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        needed = self.cache.blocks_for(total)
        if total > self.ctx_pad or needed > self.cache.n_blocks:
            # Over the context extent OR over the ENTIRE pool (an
            # explicit small n_blocks): queueing it as retryable would
            # livelock the loop — join would re-raise forever with
            # nothing ever freeing enough.
            raise AdmissionError(
                f"request {req.rid!r} needs {total} positions "
                f"({needed} blocks) > engine capacity (context "
                f"{self.ctx_pad}, pool {self.cache.n_blocks} blocks); "
                f"it can never be admitted",
                needed_blocks=needed,
                free_blocks=self.cache.free_blocks, retryable=False)
        tenant = getattr(req, "tenant", None)
        with self._lock:
            if self.qos is not None and tenant is not None \
                    and self.qos.max_queue:
                depth = sum(1 for r, _ in self._queue
                            if getattr(r, "tenant", None) == tenant)
                if depth >= self.qos.max_queue:
                    # Typed, retryable back-pressure to the BURSTING
                    # tenant only: its pending queue is full, so the
                    # caller backs off — the victim tenant's submits
                    # never see this path.
                    self.admission_rejections += 1
                    raise AdmissionError(
                        f"request {req.rid!r}: tenant {tenant!r} queue "
                        f"full ({depth}/{self.qos.max_queue} pending)",
                        needed_blocks=needed,
                        free_blocks=self.cache.free_blocks)
            self._queue.append((req, time.monotonic()))
            # Histogram at the padded prefill length (the shape a
            # monolithic prefill of this prompt compiles), counted only
            # for ACCEPTED submissions — the pad self-tuner must learn
            # the shapes the engine actually launches.
            pad = -(-len(req.tokens) // self.q_block) * self.q_block
            self._prompt_hist[pad] = self._prompt_hist.get(pad, 0) + 1

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def running(self) -> int:
        # Chunk-prefilling sequences hold pool blocks and engine work —
        # they are in-flight for every queue/occupancy consumer.
        return len(self._running) + len(self._prefilling)

    # -- prefill -----------------------------------------------------------
    def _prefill_span(self, seq: _Seq, c1: int, t_pad: int) -> None:
        """One prefill launch over positions ``[seq.pf_pos, c1)`` padded
        to ``t_pad`` rows — the whole remaining prompt (monolithic), one
        chunk (chunked), or the tail re-computation after a full prefix
        hit. Rows attend to earlier positions through the pool gather
        and to each other through the forward's in-buffer scatter, so
        the split point cannot change a bit (the route tests pin chunked
        vs monolithic). Emits the first token when ``c1`` completes the
        prompt."""
        c0 = seq.pf_pos
        t_real = c1 - c0
        n = len(seq.tokens)
        with profiler.span("serve:prefill", step=self._steps, batch=1,
                           rows=t_pad):
            tokens = np.zeros((1, t_pad), np.int32)
            tokens[0, :t_real] = seq.tokens[c0:c1]
            positions = (c0 + np.arange(t_pad, dtype=np.int32))[None].copy()
            flat = np.full((1, t_pad), self.cache.oob_index, np.int32)
            for j in range(t_real):
                # write_index, not flat_index: a fully-matched admission's
                # tail row lands in an adopted block — the writer must own
                # a private copy first (COW; pre-copied at admission).
                flat[0, j] = self.cache.write_index(seq.rid, c0 + j)
            tables = self.cache.table_array([seq.rid], self.nb_max)
            logits = self._run_fn(1, t_pad, tokens, positions, tables, flat)
            self.prefill_launches += 1
            self.prefill_rows += t_pad
            seq.pf_pos = c1
            if c1 >= n:
                last = np.asarray(logits[0, n - 1 - c0], np.float32)
                self._t_emit = time.monotonic()
                self._emit_token(seq, last)
            else:
                self._publish(seq)

    def _prefill(self, seq: _Seq) -> None:
        """Monolithic prefill of everything past the prefill cursor."""
        t_real = len(seq.tokens) - seq.pf_pos
        t_pad = -(-t_real // self.q_block) * self.q_block
        self._prefill_span(seq, len(seq.tokens), t_pad)

    def _prefill_chunk_step(self, seq: _Seq) -> bool:
        """Advance one chunk; True when the prompt completed (and the
        first token was emitted). Non-final chunks launch at the fixed
        ``(1, prefill_chunk)`` shape; the final chunk pads its remainder
        to a row-block multiple — the whole declared chunk geometry the
        ``route`` analyze signature pins."""
        n = len(seq.tokens)
        c1 = min(n, seq.pf_pos + self.prefill_chunk)
        t_real = c1 - seq.pf_pos
        t_pad = (self.prefill_chunk if c1 < n
                 else -(-t_real // self.q_block) * self.q_block)
        self._prefill_span(seq, c1, t_pad)
        self.prefill_chunks += 1
        return seq.pf_pos >= n

    # -- prefix publication ------------------------------------------------
    def _publish(self, seq: _Seq) -> None:
        """Index every newly-completed block under its chain key. The
        publishable extent is ``len(tokens) - 1``: rows strictly below
        it are verified-written on every path (after prefill+emit, after
        a decode emit, and after a verify round's commit — the spec
        engine's accepted rows were computed from true tokens), so a
        published block can never leak a draft byte."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        # Written extent: the prefill cursor until the prompt is done,
        # then every row below the newest token (each decode/verify
        # feeds and writes the row below the token it emits).
        limit = (len(seq.tokens) - 1 if seq.pf_pos >= seq.n_prompt
                 else seq.pf_pos)
        while (seq.published + 1) * bs <= limit:
            i = seq.published
            key = prefix_mod.chain_keys(
                seq.tokens[i * bs:(i + 1) * bs], bs, prior=seq.hkey)[0]
            self.cache.publish_block(seq.rid, i, key)
            self._note_parent(key, seq.hkey)
            seq.hkey = key
            seq.published += 1

    def _note_parent(self, key: str, prior: str) -> None:
        """Record one chain link (bounded) so a hot tip can be walked
        back to its root when the persistent store exports the stem."""
        self._chain_parent[key] = prior
        self._chain_parent.move_to_end(key)
        while len(self._chain_parent) > 4096:
            self._chain_parent.popitem(last=False)

    def _note_parents(self, keys: Sequence[str]) -> None:
        for i, key in enumerate(keys):
            self._note_parent(key, keys[i - 1] if i else "")

    def _note_chain(self, keys: Sequence[str], matched: int) -> None:
        """An adoption PROVED blocks shared — remember the links and
        mark the adopted tip hot (the persistent prefix store exports
        the hottest few tips, i.e. exactly the stems a second
        conversation reused)."""
        self._note_parents(keys[:matched])
        tip = keys[matched - 1]
        self._hot_tips[tip] = None
        self._hot_tips.move_to_end(tip)
        while len(self._hot_tips) > 64:
            self._hot_tips.popitem(last=False)

    # -- decode ------------------------------------------------------------
    def _decode(self) -> None:
        seqs = list(self._running)
        b = _bucket_of(self.decode_buckets, len(seqs))
        t = self.q_block
        at = {"step": self._steps, "batch": len(seqs)}
        with profiler.span("serve:build_inputs", **at):
            tokens = np.zeros((b, t), np.int32)
            positions = np.zeros((b, t), np.int32)
            tables = np.zeros((b, self.nb_max), np.int32)
            flat = np.full((b, t), self.cache.oob_index, np.int32)
            for i, s in enumerate(seqs):
                p0 = len(s.tokens) - 1      # the newest, not-yet-fed token
                tokens[i, 0] = s.tokens[-1]
                positions[i] = p0 + np.arange(t, dtype=np.int32)
                flat[i, 0] = self.cache.write_index(s.rid, p0)
            # Tables AFTER the write-index pass: write_index may COW-
            # repoint a table slot, and the gather must see the repointed
            # table.
            tables[:len(seqs)] = self.cache.table_array(
                [s.rid for s in seqs], self.nb_max)
        with profiler.span("serve:launch", **at):
            logits = self._run_fn(b, t, tokens, positions, tables, flat)
        with profiler.span("serve:readback", **at):
            rows = np.asarray(logits[:len(seqs), 0], np.float32)
        self._t_emit = time.monotonic()
        with profiler.span("serve:emit", **at):
            for i, s in enumerate(seqs):
                self._emit_token(s, rows[i])

    def _emit_token(self, seq: _Seq, row: np.ndarray) -> None:
        if self.keep_logits:
            seq.logits.append(row.copy())
        seq.tokens.append(int(np.argmax(row)))   # greedy: deterministic
        # One clock read per launch (its rows read back), not per row.
        seq.token_s.append(self._t_emit - seq.t_submit)
        seq.remaining -= 1
        self._emitted += 1
        self._publish(seq)

    # -- scheduling --------------------------------------------------------
    def _admit(self, req: Request,
               total: Optional[int] = None) -> Tuple[int, int, Sequence[str]]:
        """Reserve the request's full extent, adopting any published
        prefix blocks first; returns ``(start, matched, keys)`` — the
        prefill start position (past the adopted extent: those launches
        are simply never issued), the adopted block count, and the
        prompt's chain keys (so publication seeding never rehashes
        them). Raises :class:`AdmissionError` with the cache unchanged
        on pool pressure, so a queued request retries whole. ``total``
        overrides the reservation extent (the prefill-only mode
        reserves the PROMPT alone — the decode extent belongs to the
        replica that decodes)."""
        if total is None:
            total = len(req.tokens) + req.max_new_tokens
        if self.host_offload and req.conv is not None:
            res = self._try_resume(req, total)
            if res is not None:
                return res
        if not self.prefix_cache:
            self.cache.reserve(req.rid, total)
            return 0, 0, ()
        keys = prefix_mod.chain_keys(req.tokens, self.block_size)
        if self.host_offload:
            # Re-stage any demoted stretch of this prompt's chain from
            # the host tier before matching — the admission then adopts
            # it like any published stem. A corrupt host payload
            # degrades to recompute (the poison entry dropped so it
            # cannot fail every later admission), never an error.
            try:
                self.cache.promote(keys)
            except HandoffError:
                self.cache.discard_host(keys)
                self.host_degraded += 1
        matched = self.cache.admit_shared(req.rid, total, keys)
        m = matched * self.block_size
        if m >= len(req.tokens):
            # Full cover: the last prompt row still re-computes (its
            # logits seed generation), and its KV write lands in an
            # adopted block — take the private copy NOW, inside the
            # admission transaction, so the one COW this sequence can
            # ever need cannot fail mid-flight. If even that one spare
            # block can't be supplied, DEGRADE the match by the tail
            # block (its rows compute fresh into the reservation's own
            # blocks — no COW needed) rather than queue-spinning a
            # request the capacity check already accepted.
            try:
                self.cache.cow_block(req.rid,
                                     (len(req.tokens) - 1)
                                     // self.block_size)
            except AdmissionError:
                self.cache.free_seq(req.rid)
                matched = self.cache.admit_shared(req.rid, total,
                                                  keys[:-1])
                m = matched * self.block_size
        # Counters only after the admission definitively succeeded —
        # a pressure-retried request must not skew the published
        # prefix_cache_hit_rate with every retry.
        self.prefix_lookup_blocks += len(keys)
        self.prefix_hit_blocks += matched
        if matched:
            self._note_chain(keys, matched)
        return min(m, len(req.tokens) - 1), matched, keys

    def _park_keys(self, tokens: Sequence[int], length: int
                   ) -> List[str]:
        """Chain keys of the FULL blocks inside ``tokens[:length]`` —
        the parked record's resume-time adoption probe (one key per
        full block; a partial tail block ships keyless, exactly the
        wire contract)."""
        bs = self.block_size
        return prefix_mod.chain_keys(
            list(tokens)[:(int(length) // bs) * bs], bs)

    def _park(self, seq: _Seq) -> bool:
        """Park ``seq``'s KV under its conversation handle instead of
        freeing it. The parked extent is ``len(tokens) - 1`` — every
        row strictly below the newest token is verified-written (the
        final emitted token's row is never computed), the same bound
        :meth:`_publish` trusts. A re-park of the same conversation
        drops the stale turn first; a full host tier returns False
        (state unchanged) and eviction degrades to the plain free."""
        length = len(seq.tokens) - 1
        if length <= 0:
            return False
        old = self._parked.pop(seq.conv, None)
        if old is not None:
            self.cache.unpark(old["rid"])
        try:
            self.cache.park(seq.rid, length,
                            keys=self._park_keys(seq.tokens, length))
        except AdmissionError:
            return False
        self._parked[seq.conv] = {"tokens": list(seq.tokens),
                                  "rid": seq.rid}
        return True

    def _try_resume(self, req: Request, total: int
                    ) -> Optional[Tuple[int, int, Sequence[str]]]:
        """Resume ``req`` from its conversation's parked KV: adopt what
        is still on device, re-stage the rest from the host payloads,
        and start the prefill cursor at the parked extent — the shared
        history's launches are simply never issued. Bitwise transparent
        by the chunked-prefill split-point contract: rows from the
        cursor on compute exactly what a full prefill would compute
        there. ``None`` (nothing changed beyond dropping a dead record)
        falls through to fresh admission: no parked record, a diverged
        prompt, or a typed resume failure (pool pressure / host
        corruption — counted in ``host_degraded``; the conversation
        pays a re-prefill, never a wedge)."""
        self.park_lookups += 1
        rec = self._parked.get(req.conv)
        if rec is None:
            return None
        ptoks = rec["tokens"]
        length = len(ptoks) - 1
        if len(req.tokens) < len(ptoks) \
                or list(req.tokens)[:len(ptoks)] != ptoks:
            # The turn does not extend the parked history (edited or
            # truncated conversation): the record can never be resumed
            # by a later turn either — drop it.
            self._parked.pop(req.conv, None)
            self.cache.unpark(rec["rid"])
            return None
        try:
            self.cache.resume(req.rid, total, rec["rid"])
        except (AdmissionError, HandoffError):
            self._parked.pop(req.conv, None)
            self.cache.unpark(rec["rid"])
            self.host_degraded += 1
            return None
        self._parked.pop(req.conv, None)
        self.park_hits += 1
        keys = self._park_keys(ptoks, length)
        if self.prefix_cache and keys:
            # The resumed blocks hold verified rows — index them so
            # other prompts adopt the shared history, and seed the
            # publication cursor past them (the admit_handoff idiom).
            for i, key in enumerate(keys):
                self.cache.publish_block(req.rid, i, key)
            self._note_parents(keys)
            self.prefix_lookup_blocks += len(keys)
            self.prefix_hit_blocks += len(keys)
            return length, len(keys), keys
        return length, 0, ()

    def _seed_publication(self, seq: _Seq, matched: int,
                          keys: Sequence[str]) -> None:
        """An adopted prefix is already indexed — advance the
        publication cursor past it so the sequence publishes only what
        it computes (``keys`` are the admission's chain keys; no
        rehash)."""
        if matched:
            seq.published = matched
            seq.hkey = keys[matched - 1]

    def _join(self, results: List[Completion]) -> None:
        # Hot-swap quiesce (tony_tpu.serve.swap): admission pauses while
        # the swap drains the batch — in-flight sequences complete under
        # the OLD weights, queued requests stay queued and admit AFTER
        # the flip under the new ones, so no request ever spans weight
        # versions and none is dropped.
        if self.swapping:
            return
        if self.join_policy == "static" and (self._running
                                             or self._prefilling):
            return
        if self.qos is not None:
            self._join_qos(results)
            return
        while len(self._running) + len(self._prefilling) \
                < self.max_running:
            with self._lock:
                if not self._queue:
                    return
                req, t_submit = self._queue[0]
            try:
                start, matched, keys = self._admit(req)
            except AdmissionError:
                return                      # pool pressure: stay queued
            with self._lock:
                self._queue.popleft()
            seq = _Seq(req, t_submit)
            seq.pf_pos = start
            self._seed_publication(seq, matched, keys)
            if self.prefill_chunk is not None:
                # Chunked: the prompt advances one chunk per engine
                # iteration, interleaved with decode — admission never
                # stalls the running batch for a whole-prompt launch.
                self._prefilling.append(seq)
                continue
            self._prefill(seq)
            if seq.remaining <= 0:          # max_new_tokens == 1
                self._evict(seq, results)
            else:
                self._running.append(seq)

    def _qos_active(self) -> set:
        """The budget denominator's active-tenant set: tenants holding
        device blocks or waiting in the queue (caller holds the lock).
        Work conservation falls out — an idle tenant leaves the set and
        its share redistributes."""
        active = {t for t, n in self._tenant_blocks.items() if n > 0}
        for r, _ in self._queue:
            t = getattr(r, "tenant", None)
            if t is not None:
                active.add(t)
        return active

    def _join_qos(self, results: List[Completion]) -> None:
        """The budget-armed admission scan: walk the queue in FIFO
        order, DEFER requests whose tenant is over its weighted-fair
        block budget (and every later request of that tenant — per-
        tenant order is preserved), admit the first request that fits.
        Untagged requests bypass budgets. Pool pressure from ``_admit``
        ends the scan whole, exactly like the unarmed path — the
        deferral mechanism is skip-over, never reorder-within-tenant
        and never eviction."""
        blocked: set = set()
        while len(self._running) + len(self._prefilling) \
                < self.max_running:
            picked = None
            with self._lock:
                if not self._queue:
                    return
                active = self._qos_active()
                for i, (req, t_submit) in enumerate(self._queue):
                    tenant = getattr(req, "tenant", None)
                    if tenant is None:
                        picked = (i, req, t_submit)
                        break
                    if tenant in blocked:
                        continue
                    needed = self.cache.blocks_for(
                        len(req.tokens) + req.max_new_tokens)
                    budget = self.qos.budget(
                        tenant, self.cache.n_blocks, active)
                    if self._tenant_blocks.get(tenant, 0) + needed \
                            > budget:
                        blocked.add(tenant)
                        self.qos_deferrals += 1
                        continue
                    picked = (i, req, t_submit)
                    break
            if picked is None:
                return                     # every waiter is over budget
            i, req, t_submit = picked
            try:
                start, matched, keys = self._admit(req)
            except AdmissionError:
                return                      # pool pressure: stay queued
            with self._lock:
                # Index i is still req's slot: submit only APPENDS and
                # this drive thread is the only popper (the front's
                # single-driver contract).
                del self._queue[i]
                tenant = getattr(req, "tenant", None)
                if tenant is not None:
                    charge = self.cache.blocks_for(
                        len(req.tokens) + req.max_new_tokens)
                    self._tenant_blocks[tenant] = \
                        self._tenant_blocks.get(tenant, 0) + charge
            seq = _Seq(req, t_submit)
            if seq.tenant is not None:
                seq.qcharge = self.cache.blocks_for(
                    len(req.tokens) + req.max_new_tokens)
            seq.pf_pos = start
            self._seed_publication(seq, matched, keys)
            if self.prefill_chunk is not None:
                self._prefilling.append(seq)
                continue
            self._prefill(seq)
            if seq.remaining <= 0:          # max_new_tokens == 1
                self._evict(seq, results)
            else:
                self._running.append(seq)

    def _evict(self, seq: _Seq, results: List[Completion]) -> None:
        # Conversation parking: a host-tier engine keeps a finished
        # conversation-tagged turn's KV (demoted to host RAM) instead
        # of dropping it — the next turn resumes where this one ended.
        # cache.park frees the device reservation itself; a full host
        # tier degrades to the plain free below.
        if not (self.host_offload and seq.conv is not None
                and self._park(seq)):
            self.cache.free_seq(seq.rid)
        now = time.monotonic()
        # Under the lock: the stats publisher thread (replica heartbeat)
        # iterates this ring concurrently with the drive thread, and a
        # deque mutated mid-iteration raises — found by the concurrency
        # lint's guarded-elsewhere rule, pinned by test_concurrency.
        with self._lock:
            self._events.append((now, now - seq.t_submit,
                                 len(seq.tokens) - seq.n_prompt,
                                 seq.tenant))
            if seq.tenant is not None:
                if seq.qcharge:
                    left = self._tenant_blocks.get(seq.tenant, 0) \
                        - seq.qcharge
                    if left > 0:
                        self._tenant_blocks[seq.tenant] = left
                    else:
                        self._tenant_blocks.pop(seq.tenant, None)
                self._tenant_completed[seq.tenant] = \
                    self._tenant_completed.get(seq.tenant, 0) + 1
        self._completed += 1
        self._tokens_out += len(seq.tokens) - seq.n_prompt
        results.append(Completion(
            rid=seq.rid, prompt=seq.tokens[:seq.n_prompt],
            tokens=seq.tokens[seq.n_prompt:],
            logits=seq.logits if self.keep_logits else None,
            latency_s=now - seq.t_submit, token_s=seq.token_s))

    def _advance_prefill(self, results: List[Completion]) -> None:
        """One chunk for the oldest prefilling sequence (FIFO — one
        chunk per iteration keeps the decode cadence: a long prompt
        costs ONE extra launch per running-batch token step, not a
        whole-prompt stall)."""
        if not self._prefilling:
            return
        seq = self._prefilling[0]
        if self._prefill_chunk_step(seq):
            self._prefilling.pop(0)
            if seq.remaining <= 0:          # max_new_tokens == 1
                self._evict(seq, results)
            else:
                self._running.append(seq)

    # -- disaggregated prefill/decode (tony_tpu.serve.disagg) --------------
    def prefill_only(self, req: Request) -> Dict[str, Any]:
        """The prefill-role engine mode: run ``req``'s prompt through
        the normal admission + prefill path (prefix adoption, the
        chunked ``(1, chunk)`` launch family — the IDENTICAL program a
        colocated engine runs, so the handoff cannot change a bit),
        emit the FIRST token, then export the sequence's KV blocks as
        the handoff wire payload and free the sequence — the output is
        KV + one token, never a generation loop, and the engine is free
        for the next prompt the moment this returns.

        Single-driver contract: the caller (``serve.disagg.
        PrefillFront``) holds the front's drive lock — the same lock
        that serializes colocated ``generate`` callers — because every
        line here mutates the paged pool."""
        n = len(req.tokens)
        if not req.tokens:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        needed = self.cache.blocks_for(n)
        if n > self.ctx_pad or needed > self.cache.n_blocks:
            raise AdmissionError(
                f"request {req.rid!r}: {n}-token prompt ({needed} "
                f"blocks) > engine capacity (context {self.ctx_pad}, "
                f"pool {self.cache.n_blocks} blocks)",
                needed_blocks=needed,
                free_blocks=self.cache.free_blocks, retryable=False)
        start, matched, keys = self._admit(req, total=n)
        seq = _Seq(req, time.monotonic())
        seq.pf_pos = start
        self._seed_publication(seq, matched, keys)
        if self.prefill_chunk is not None:
            while not self._prefill_chunk_step(seq):
                pass
        else:
            self._prefill(seq)
        first = int(seq.tokens[n])
        # Chain keys of the full prompt blocks — the decode side's
        # adoption probe AND its publication seed (always shipped:
        # adoption on the importer works even when THIS engine runs
        # with the prefix cache off).
        wire_keys = (list(keys) if self.prefix_cache
                     else prefix_mod.chain_keys(req.tokens,
                                                self.block_size))
        t_export = time.monotonic()
        payload: Dict[str, Any] = {
            "rid": req.rid,
            "tokens": [int(t) for t in req.tokens],
            "first_token": first,
            "max_new_tokens": int(req.max_new_tokens),
            "length": n,
            "conv": req.conv,
            "tenant": getattr(req, "tenant", None),
            "keys": wire_keys,
            "blocks": self.cache.export_blocks(req.rid, n),
            **self.cache.wire_header(),
        }
        if self.keep_logits:
            payload["logits_b64"] = encode_f32(seq.logits[0])
        # handoff_ms counts the time THIS engine spent moving KV bytes
        # (export here, import on the decode side) — not the shipped
        # sequence's downstream generation.
        self.handoff_ms += 1e3 * (time.monotonic() - t_export)
        self.cache.free_seq(req.rid)
        # The prefill replica's ONLY load telemetry: a handoff never
        # queues or joins the running batch, so without this event the
        # gang would heartbeat qps=0/p99=0 forever — the per-gang
        # autoscaler and the router's load scoring could never see a
        # prefill burst. The event shape mirrors _evict's (latency from
        # admission, one emitted token).
        now = time.monotonic()
        with self._lock:
            self._events.append((now, now - seq.t_submit, 1,
                                 seq.tenant))
        self._completed += 1
        self._tokens_out += 1
        return payload

    def admit_handoff(self, payload: Dict[str, Any]
                      ) -> Tuple[Any, Optional[Completion]]:
        """The decode-role admission path: import a shipped prefill's
        KV blocks into this engine's pool (:meth:`PagedKVCache.
        import_blocks` — adopting any offered shared-prefix stem) and
        join the sequence to the running batch with its prompt already
        computed, so the next iteration decodes its second token exactly
        where a colocated engine would. Returns ``(rid, completion)`` —
        ``completion`` non-None only for the degenerate
        ``max_new_tokens == 1`` handoff, whose one token the prefill
        side already produced.

        Back-pressure is a typed, state-unchanged rejection (the
        shipper's retry surface): a full decode batch or an exhausted
        pool raises :class:`AdmissionError` with NOTHING changed, and a
        corrupt payload raises :class:`~tony_tpu.serve.disagg.
        HandoffError` the same way. Single-driver contract: the caller
        (``serve.disagg.DecodeFront``) holds the front's drive lock —
        this runs on an RPC receiver thread while another thread drives
        decode, which is exactly the mutation the PR 14 concurrency
        plane gates."""
        try:
            try:
                rid = payload["rid"]
                tokens = [int(t) for t in payload["tokens"]]
                max_new = int(payload["max_new_tokens"])
                first = int(payload["first_token"])
                offset = int(payload.get("offset", 0))
            except (KeyError, TypeError, ValueError) as e:
                # A version-skewed or truncated payload must reject the
                # same way every other malformed field does — typed and
                # counted — not escape as a bare KeyError past the
                # shipper's _classify and the router's fallback split.
                raise HandoffError(
                    f"malformed handoff payload: missing or mistyped "
                    f"field ({type(e).__name__}: {e})",
                    retryable=False) from e
            n = len(tokens)
            if n != int(payload.get("length", n)) or not tokens \
                    or max_new < 1:
                raise HandoffError(
                    f"malformed handoff for {rid!r}: length "
                    f"{payload.get('length')} vs {n} prompt token(s), "
                    f"max_new_tokens {max_new}", retryable=False)
            header = self.cache.wire_header()
            got = {k: payload.get(k) for k in header}
            if got != header:
                raise HandoffError(
                    f"handoff geometry mismatch for {rid!r}: {got} vs "
                    f"this pool's {header}", retryable=False)
            total = n + max_new
            needed = self.cache.blocks_for(total)
            if total > self.ctx_pad or needed > self.cache.n_blocks:
                raise AdmissionError(
                    f"handoff {rid!r} needs {total} positions "
                    f"({needed} blocks) > engine capacity (context "
                    f"{self.ctx_pad}, pool {self.cache.n_blocks} "
                    f"blocks); it can never be admitted",
                    needed_blocks=needed,
                    free_blocks=self.cache.free_blocks, retryable=False)
            if self.running >= self.max_running:
                raise AdmissionError(
                    f"handoff {rid!r} rejected: decode batch full "
                    f"({self.running}/{self.max_running} running)",
                    needed_blocks=needed,
                    free_blocks=self.cache.free_blocks)
            # A shipped rid that is already live HERE (a caller-supplied
            # duplicate — minted rids carry a per-front namespace) must
            # reject typed before any import: admitting it would tear
            # the front's rid-keyed completion routing, and the cache's
            # own fresh-admission ValueError is not part of the
            # (AdmissionError, HandoffError) failover split.
            live = {s.rid for s in self._running} \
                | {s.rid for s in self._prefilling} \
                | set(self.cache.owned_blocks())
            with self._lock:
                live |= {r.rid for r, _ in self._queue}
            if rid in live:
                raise HandoffError(
                    f"handoff rid {rid!r} collides with a live sequence "
                    f"on this engine — rids must be unique fleet-wide",
                    retryable=False)
            # The shipped blocks (plus the adopted stem) must cover the
            # prompt EXACTLY: a truncated or absent blocks field would
            # otherwise pass every typed check — the per-block CRC only
            # guards blocks that are present — and the uncovered prompt
            # extent would decode from uninitialized pool blocks,
            # silently wrong.
            shipped = list(payload.get("blocks") or ())
            if offset + len(shipped) != self.cache.blocks_for(n):
                raise HandoffError(
                    f"handoff {rid!r} blocks do not cover the prompt: "
                    f"{offset} adopted + {len(shipped)} shipped != "
                    f"{self.cache.blocks_for(n)} prompt block(s) for "
                    f"{n} token(s)", retryable=False)
            keys = [str(k) for k in payload.get("keys") or ()]
            # The chain keys outlive this request — they index imported
            # blocks into the SHARED prefix tier below — so unlike the
            # CRC (which guards the wire, not content identity) they
            # must be verified against the tokens they claim to cover:
            # a version-skewed shipper's wrong keys would otherwise
            # poison adoptions for unrelated future prompts, silently.
            true_keys = prefix_mod.chain_keys(tokens, self.block_size)
            if keys and keys != true_keys:
                raise HandoffError(
                    f"handoff chain keys for {rid!r} do not match the "
                    f"shipped tokens ({len(keys)} key(s) vs "
                    f"{len(true_keys)} derived) — key-scheme skew "
                    f"between the gangs", retryable=False)
            first_row: Optional[np.ndarray] = None
            if self.keep_logits and payload.get("logits_b64"):
                # Decode BEFORE the import mutates the pool: logits
                # ride outside the per-block CRC, and a corrupt row
                # must reject typed and state-unchanged like every
                # other malformed field — not leak an admitted table.
                try:
                    first_row = decode_f32(payload["logits_b64"])
                except (ValueError, TypeError) as e:
                    raise HandoffError(
                        f"malformed handoff logits for {rid!r}: {e}",
                        retryable=False) from e
            t_import = time.monotonic()
            self.cache.import_blocks(rid, total, shipped, keys=keys,
                                     offset=offset)
            self.handoff_ms += 1e3 * (time.monotonic() - t_import)
        except (AdmissionError, HandoffError):
            self.imports_failed += 1
            raise
        seq = _Seq(Request(rid=rid, tokens=tokens,
                           max_new_tokens=max_new,
                           conv=payload.get("conv"),
                           tenant=payload.get("tenant")),
                   time.monotonic())
        seq.pf_pos = n                     # the prompt arrived computed
        seq.tokens.append(first)
        seq.token_s.append(0.0)            # known here on arrival
        seq.remaining -= 1                 # the prefill side emitted it
        if first_row is not None:
            seq.logits.append(first_row)
        if self.prefix_cache and keys:
            # The imported prompt blocks hold verified rows — index
            # them under the shipped chain keys (adopted ones are
            # already indexed; publish_block no-ops) and seed the
            # publication cursor past them so decode publishes only
            # what it computes.
            for i, key in enumerate(keys):
                self.cache.publish_block(rid, i, key)
            self._note_parents(keys)
            seq.published = len(keys)
            seq.hkey = keys[-1]
        self.handoffs_in += 1
        if seq.remaining <= 0:             # max_new_tokens == 1
            done: List[Completion] = []
            self._evict(seq, done)
            return rid, done[0]
        self._running.append(seq)
        return rid, None

    def note_handoff_shipped(self, blocks: int) -> None:
        """Bank one completed outbound handoff's shipped-block count.
        Called by the shipping front (``serve.disagg.PrefillFront``) —
        possibly from CONCURRENT RPC receiver threads, the one handoff
        counter path not serialized by the front's drive lock, hence
        the engine lock here (a bare ``+=`` is a torn RMW)."""
        with self._lock:
            self.blocks_shipped += int(blocks)
            self.handoffs_out += 1

    # -- persistent prefix store (tony_tpu.serve.kvstore) ------------------
    def adopt_stem(self, keys: Sequence[str],
                   blocks: Sequence[Dict[str, Any]]) -> int:
        """Seed the prefix tier from a persisted stem (replica startup,
        or a scale-up grant naming the store): import the chain's
        payloads through the SAME verify-then-commit path a handoff
        rides, publish them, and release the scratch reservation so the
        blocks land in the refcount-0 cached tier — exactly where a
        local conversation's published stem would sit. Best-effort by
        design: a corrupt chunk or pool pressure returns 0 adopted
        blocks (the replica warms from recompute instead), never an
        error. Returns blocks newly indexed."""
        keys = [str(k) for k in keys]
        if not self.prefix_cache or not keys \
                or len(keys) != len(blocks):
            return 0
        matched = len(self.cache.match_prefix(keys))
        if matched >= len(keys):
            return 0
        sid = ("stem", keys[-1])
        try:
            self.cache.import_blocks(
                sid, len(keys) * self.block_size,
                list(blocks)[matched:], keys=keys, offset=matched)
        except (AdmissionError, HandoffError):
            return 0
        for i, key in enumerate(keys):
            self.cache.publish_block(sid, i, key)
        self.cache.free_seq(sid)
        self._note_parents(keys)
        self.store_adopted += len(keys) - matched
        return len(keys) - matched

    def export_stems(self, store: Any, limit: int = 8) -> int:
        """Persist the hottest adopted stems (chains a SECOND prompt
        proved shared) into ``store`` (:class:`tony_tpu.serve.kvstore.
        PrefixStore`) — idempotent per tip, skipping chains whose
        blocks aged out of the device index. The caller owns the drive
        lock (the export reads the pool). Returns stems written."""
        wrote = 0
        for tip in list(self._hot_tips)[-limit:]:
            if tip in self._stored_tips:
                continue
            chain: List[str] = []
            key = tip
            while key:
                chain.append(key)
                key = self._chain_parent.get(key)
                if key is None or len(chain) > self.cache.n_blocks:
                    chain = []
                    break
            if not chain:
                continue
            chain.reverse()
            if len(self.cache.match_prefix(chain)) < len(chain):
                continue                 # partly aged out: not exportable
            store.put(chain, self.cache.export_keys(chain),
                      self.cache.wire_header())
            self._stored_tips.add(tip)
            wrote += 1
        return wrote

    def step(self) -> List[Completion]:
        """One engine iteration: join what fits, advance one prefill
        chunk (chunked mode), decode one token for every running
        sequence, evict what finished. Returns the completions this
        step produced."""
        results: List[Completion] = []
        with profiler.span("serve:admit", step=self._steps,
                           batch=len(self._running)):
            self._join(results)
        self._advance_prefill(results)
        if self._running:
            self._decode()
            still = []
            for s in self._running:
                if s.remaining <= 0:
                    self._evict(s, results)
                else:
                    still.append(s)
            self._running = still
        # Demotion daemon (off unless a watermark armed it): above the
        # high watermark, demote one batch of cold cached-tier blocks
        # to host RAM — freeing device blocks BEFORE the next admission
        # needs them, at batch granularity so the device->host fetch
        # amortizes the link (ROOFLINE §12). demote() only ever takes
        # refcount-0 published blocks off the ref-aware LRU, so a live
        # sequence can never lose KV to the daemon.
        if self.demote_watermark > 0.0 and self.host_offload:
            used = self.cache.n_blocks - self.cache.free_blocks
            if used >= self.demote_watermark * self.cache.n_blocks:
                self.daemon_demotions += self.cache.demote(
                    self.demote_batch)
        self._steps += 1
        return results

    def run(self, max_steps: Optional[int] = None) -> List[Completion]:
        """Drive :meth:`step` until queue and batch drain (or
        ``max_steps``)."""
        out: List[Completion] = []
        while (self.queue_depth or self._running or self._prefilling) \
                and (max_steps is None or self._steps < max_steps):
            out.extend(self.step())
        return out

    # -- the sequential reference ------------------------------------------
    def full_prefill_logits(self, tokens: Sequence[int]) -> np.ndarray:
        """Sequential full-prefill reference: process ``tokens`` as ONE
        isolated prefill on a scratch pool (same jitted shape family,
        same ops) and return the real rows' f32 logits ``[len, vocab]``.
        The continuous-batching pin compares each request's streamed
        decode logits against rows of THIS, bit for bit."""
        t_real = len(tokens)
        if t_real > self.ctx_pad:
            raise ValueError(f"{t_real} tokens > engine context "
                             f"{self.ctx_pad}")
        t_pad = -(-t_real // self.q_block) * self.q_block
        toks = np.zeros((1, t_pad), np.int32)
        toks[0, :t_real] = list(tokens)
        positions = np.broadcast_to(
            np.arange(t_pad, dtype=np.int32)[None], (1, t_pad)).copy()
        # Contiguous scratch table on a zero pool of the SAME geometry,
        # so the jit cache is shared with live prefills (clipped: the
        # pool may hold fewer blocks than the context extent, and the
        # tail positions are masked anyway).
        tables = np.minimum(np.arange(self.nb_max, dtype=np.int32),
                            self.cache.n_blocks - 1)[None].copy()
        flat = np.full((1, t_pad), self.cache.oob_index, np.int32)
        bs = self.block_size
        for p in range(t_real):
            flat[0, p] = (p // bs) * bs + (p % bs)
        fn = self._fn(1, t_pad)
        scratch_k = jnp.zeros_like(self.cache.k)
        scratch_v = jnp.zeros_like(self.cache.v)
        args = (self.params, scratch_k, scratch_v, jnp.asarray(toks),
                jnp.asarray(positions), jnp.asarray(tables),
                jnp.asarray(flat))
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                logits, _, _ = fn(*args)
        else:
            logits, _, _ = fn(*args)
        return np.asarray(logits[0, :t_real], np.float32)

    # -- telemetry ---------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """The serve heartbeat triple (+ rates): qps, p50/p99 request
        latency, queue depth. Rates and percentiles cover the last
        ``stats_window_s`` only (bounded by engine age), so an idle
        replica's p99 decays to 0 and the autoscaler's scale-down gate
        can actually fire; ``completed``/``steps``/``forwards`` stay
        lifetime counters."""
        now = time.monotonic()
        with self._lock:
            events = list(self._events)
            tenant_blocks = dict(self._tenant_blocks)
            tenant_completed = dict(self._tenant_completed)
            tenant_queued: Dict[str, int] = {}
            for r, _ in self._queue:
                ten = getattr(r, "tenant", None)
                if ten is not None:
                    tenant_queued[ten] = tenant_queued.get(ten, 0) + 1
            rejections = self.admission_rejections
            prompt_hist = dict(self._prompt_hist)
        recent = [(l, n, ten) for t, l, n, ten in events
                  if now - t <= self.stats_window_s]
        lat = sorted(l for l, _, _ in recent)
        dt = max(1e-9, min(self.stats_window_s, now - self._t0))

        def _pct_of(vals: List[float], p: float) -> float:
            if not vals:
                return 0.0
            return vals[min(len(vals) - 1,
                            int(p * (len(vals) - 1) + 0.5))]

        def pct(p: float) -> float:
            return _pct_of(lat, p)

        # Per-tenant breakdown (tony_tpu.serve.qos): same window, same
        # percentile rule as the top-level numbers. Empty dict on an
        # untagged engine — the uniform-schema rule: every engine
        # flavor publishes the key, consumers never branch on kind.
        per_lat: Dict[str, List[float]] = {}
        per_tok: Dict[str, float] = {}
        for l, n, ten in recent:
            if ten is None:
                continue
            per_lat.setdefault(ten, []).append(l)
            per_tok[ten] = per_tok.get(ten, 0.0) + n
        tenants: Dict[str, Dict[str, float]] = {}
        for ten in (set(per_lat) | set(tenant_blocks)
                    | set(tenant_queued) | set(tenant_completed)):
            lats = sorted(per_lat.get(ten, []))
            tenants[ten] = {
                "qps": len(lats) / dt,
                "tokens_per_s": per_tok.get(ten, 0.0) / dt,
                "p99_ms": 1e3 * _pct_of(lats, 0.99),
                "queued": float(tenant_queued.get(ten, 0)),
                "blocks": float(tenant_blocks.get(ten, 0)),
                "completed": float(tenant_completed.get(ten, 0)),
            }

        stats = {
            "qps": len(recent) / dt,
            "tokens_per_s": sum(n for _, n, _ in recent) / dt,
            "p50_ms": 1e3 * pct(0.50),
            "p99_ms": 1e3 * pct(0.99),
            "queue_depth": float(self.queue_depth),
            "running": float(self.running),
            "completed": float(self._completed),
            "steps": float(self._steps),
            "forwards": float(self.forwards),
            # Effective throughput for the autoscaler: generated tokens
            # per TARGET forward launch (lifetime), counted at EMIT time
            # so a replica mid-way through long generations reports what
            # it is actually producing, not zero until first completion.
            # Raw forward counts undercount a speculative replica's real
            # throughput — ScalingPolicy's decision matrix is unchanged,
            # but the heartbeat now carries the honest number (the
            # speculative lane also reports its acceptance rate; 0.0
            # here).
            "tokens_per_forward": (self._emitted / self.forwards
                                   if self.forwards else 0.0),
            "acceptance_rate": 0.0,
            # Prefix-cache / chunked-prefill telemetry (PR 13): zeros
            # when the features are off — every engine flavor publishes
            # the same schema, so the fleet's heartbeat consumers
            # (router, autoscaler, portal) never branch on engine kind.
            "prefix_cache_hit_rate": (
                self.prefix_hit_blocks / self.prefix_lookup_blocks
                if self.prefix_lookup_blocks else 0.0),
            "blocks_shared": float(self.cache.adopted_total),
            "prefill_chunks": float(self.prefill_chunks),
            # Disaggregated-serving telemetry (PR 15): the replica's
            # role rides as a STRING (the schema's second non-scalar
            # next to prefix_digest — normalize_serve_telemetry passes
            # it through), the handoff counters as zeros on colocated
            # engines so the fleet schema stays uniform and the router/
            # autoscaler never branch on engine kind.
            "role": self.role,
            "blocks_shipped": float(self.blocks_shipped),
            "handoff_ms": float(self.handoff_ms),
            "imports_failed": float(self.imports_failed),
            # KV memory hierarchy telemetry (PR 16): zeros on engines
            # without the host tier, so the fleet schema stays uniform
            # (same rule as every widening above). park_hit_rate is the
            # fraction of conversation-tagged admissions that resumed
            # from parked KV instead of re-prefilling.
            "host_blocks": float(self.cache.host_blocks_used),
            "parked_seqs": float(len(self._parked)),
            "demotions": float(self.cache.demoted_total),
            "promotions": float(self.cache.promoted_total),
            "park_hit_rate": (self.park_hits / self.park_lookups
                              if self.park_lookups else 0.0),
            # Cold-start plane telemetry (PR 17): zeros on engines
            # without the AOT cache / warm pool, same uniform-schema
            # rule as every widening above. warm_standby rides the
            # heartbeat so the session excludes standbys from routing
            # and the autoscaler from the active count until the AM
            # promotes them.
            "aot_hits": float(self.aot_hits),
            "aot_misses": float(self.aot_misses),
            "compile_ms": float(self.compile_ms),
            "warm_standby": 1.0 if self.warm_standby else 0.0,
            "daemon_demotions": float(self.daemon_demotions),
            # Multi-tenant QoS telemetry (PR 18): zeros / empty dict on
            # untagged engines — the uniform-schema rule again. The
            # tenants dict is the ONE nested value the heartbeat schema
            # carries (normalize_serve_telemetry normalizes one level
            # of dict-of-scalars); the history plane's SLO dashboards
            # and the per-tenant billing rollups both read it.
            "admission_rejections": float(rejections),
            "qos_deferrals": float(self.qos_deferrals),
            "tenants": tenants,
            # Continuous-publication telemetry (PR 20): which weight
            # version this replica is serving, and whether it is inside
            # a swap window right now. weight_version rides the
            # heartbeat so the AM's rolling fleet swap can tell who
            # still needs the new manifest; swapping=1.0 is the
            # router's down-mark signal (refresh_from_task_infos
            # retires the replica for the window, the next clean beat
            # revives it). prompt_hist is the padded-prefill-length
            # histogram warm() self-tunes from — dict of str(pad) →
            # count, the same one-level dict-of-scalars shape the
            # tenants dict established, so normalize_serve_telemetry
            # passes it through unchanged. All zeros / empty on an
            # unswapped engine: the uniform-schema rule.
            "weight_version": float(self.weight_version),
            "weight_step": float(self.weight_step),
            "weight_swaps": float(self.weight_swaps),
            "swapping": 1.0 if self.swapping else 0.0,
            "prompt_hist": {str(k): float(v)
                            for k, v in prompt_hist.items()},
            # Start-up and build telemetry (PR 25): every program this
            # process compiled or loaded and the seconds that took
            # (profiler.watch_builds — zeros where nothing listens), the
            # restore and warm-up seconds, and the device's peak bytes
            # where the backend reports them. A build_s that grows while
            # serving is a compile inside the engine loop.
            **profiler.build_totals(),
            "restore_s": float(self.restore_s),
            "warm_s": float(self.warm_s),
            "memory_peak_bytes": float(
                (jax.local_devices()[0].memory_stats() or {})
                .get("peak_bytes_in_use", 0)),
        }
        stats.update(self._extra_stats())
        _record(f"{self.tag}_stats", **stats)
        return stats

    def _extra_stats(self) -> Dict[str, float]:
        """Subclass hook (tony_tpu.serve.spec overrides): extra fields
        merged into :meth:`stats` before it is recorded/published."""
        return {}

    def prefix_digest(self, limit: int = 256) -> List[str]:
        """The replica's block-content advertisement: the most recently
        published chain keys. Rides the stats file → heartbeat → session
        so the router can score cache overlap without asking the
        replica; empty when prefix caching is off."""
        if not self.prefix_cache:
            return []
        return self.cache.digest(limit)

    def parked_digest(self, limit: int = 256) -> List[str]:
        """The replica's parked-conversation advertisement: the
        conversation handles whose KV this engine holds in its host
        tier. Rides the heartbeat next to the prefix digest so the
        router re-pins a returning turn to the replica that can resume
        it without a re-prefill; empty without the tier."""
        return [str(c) for c in list(self._parked)[-limit:]]

    def write_stats(self, path: str,
                    extra: Optional[Dict[str, Any]] = None) -> None:
        """Atomically publish :meth:`stats` as JSON — the file the
        executor's heartbeat loop piggybacks to the AM (jax-free on the
        reader side). The payload adds the prefix digest (a list — the
        one non-scalar the heartbeat schema carries) and any caller
        ``extra`` (the replica adds its RPC port so the router can dial
        it)."""
        payload: Dict[str, Any] = dict(self.stats())
        digest = self.prefix_digest()
        if digest:
            payload["prefix_digest"] = digest
        parked = self.parked_digest()
        if parked:
            payload["parked_digest"] = parked
        if extra:
            payload.update(extra)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, path)

    # -- warm-standby promotion (tony_tpu.serve.scaling) -------------------
    def promote(self) -> bool:
        """Leave warm standby: the AM's scale-up path calls this (over
        the replica's ``promote`` RPC verb) instead of cold-granting a
        container — the next stats publish advertises warm_standby=0
        and the session adds the replica to the routable endpoint set.
        Returns whether the engine WAS a standby (idempotent: promoting
        an active replica is a no-op, so a duplicated RPC is
        harmless)."""
        with self._lock:
            was = self.warm_standby
            self.warm_standby = False
        return was

    # -- hot weight swap (tony_tpu.serve.swap) -----------------------------
    def swap_params(self, new_params: Any, *, version: int,
                    step: int) -> None:
        """The serve engine's hot swap: the base flip (geometry-checked,
        atomic-or-rolled-back, zero recompiles) plus the KV hygiene the
        bitwise contract needs — every published prefix block and every
        demoted host stem holds rows COMPUTED UNDER THE OLD WEIGHTS, so
        a post-swap admission adopting them would stream a mixed-version
        answer. The device index and the host stem tier flush (the rows
        recompute fresh, bit-identical to a fresh replica restored from
        the same manifest); parked CONVERSATIONS survive — their records
        are an explicit continuity contract (the resumed turn keeps its
        pre-swap history's KV, the documented tradeoff the re-published
        parked digest advertises)."""
        super().swap_params(new_params, version=version, step=step)
        self.cache.flush_prefix()
        # Stem-export bookkeeping refers to the flushed keys — a
        # post-swap export must only ever name new-weight chains.
        self._chain_parent.clear()
        self._hot_tips.clear()
        self._stored_tips.clear()

    # -- static-analysis hook ---------------------------------------------
    def decode_traced(self, batch: Optional[int] = None):
        """``(jitted, example_args)`` of the canonical decode bucket for
        :func:`tony_tpu.analysis.analyze_serve_step` — the same jit the
        loop runs, traced, never executed. Always the raw jitted
        Wrapped from ``_fns`` — the AOT cache resolves executables in a
        parallel dict precisely so this hook (and its signature pin)
        cannot drift when the cache is armed."""
        b = _bucket_of(self.decode_buckets,
                       batch if batch is not None else 1)
        return self._fn(b, self.q_block), \
            self._example_args(b, self.q_block)

    def prefill_traced(self):
        """``(jitted, example_args)`` of the canonical prefill-chunk
        launch for ``tony analyze --config route`` — the ``(1, chunk)``
        shape every non-final chunk of a chunked prefill rides (the
        monolithic q_block row block when chunking is off). Same
        builder, same rule suite as decode: zero inter-chip collectives
        on the replica mesh, donated KV pools, pinned signature — the
        chunk geometry is the ONLY compiled prefill shape the feature
        declares."""
        t = int(self.prefill_chunk or self.q_block)
        return self._fn(1, t), self._example_args(1, t)


class EngineFront:
    """Thread-safe request front over ONE shared engine: each caller
    submits and then takes turns advancing the loop until its own
    completion lands, so overlapping calls ride one continuous batch.

    Factored out of the replica (which fronts it over RPC) so the
    router's in-process transport, the bench's multi-replica drive, and
    :class:`tony_tpu.serve.replica.Replica` all run the IDENTICAL drive
    discipline — the router tests compare routed against unrouted
    serving through the same loop."""

    def __init__(self, engine: ServeEngine):
        self.engine = engine
        self._drive = threading.Lock()
        self._done: Dict[Any, Completion] = {}
        self._rid = 0
        # Minted rids cross replicas since the disaggregated handoff (a
        # prefill front's rid lands on a decode engine that also mints
        # its own), so a bare counter would collide routinely — every
        # front mints in its own namespace.
        self._rid_ns = uuid.uuid4().hex[:8]
        self._rid_lock = threading.Lock()

    def fresh_rid(self) -> str:
        with self._rid_lock:
            self._rid += 1
            return f"req-{self._rid_ns}-{self._rid}"

    def generate(self, tokens: Sequence[int], max_new_tokens: int,
                 rid: Optional[Any] = None,
                 conv: Optional[Any] = None,
                 tenant: Optional[str] = None) -> Completion:
        """Submit one request and drive the shared engine until it
        completes. ``conv`` tags the request with its conversation
        handle so a host-tier engine parks/resumes it across turns;
        ``tenant`` names its QoS class on a budget-armed engine."""
        if rid is None:
            rid = self.fresh_rid()
        self.engine.submit(Request(rid=rid, tokens=list(tokens),
                                   max_new_tokens=int(max_new_tokens),
                                   conv=conv, tenant=tenant))
        return self._drive_until(rid)

    def _drive_until(self, rid: Any) -> Completion:
        """Take turns advancing the shared loop until ``rid``'s
        completion lands — the one drive discipline ``generate`` and
        the disaggregated receiver (``serve.disagg.DecodeFront``, whose
        handoff admissions join the same continuous batch) share."""
        while True:
            with self._drive:
                if rid in self._done:
                    return self._done.pop(rid)
                for c in self.engine.step():
                    self._done[c.rid] = c
            # Another thread may own the completion we need next round;
            # yield so it can collect.
            time.sleep(0)

    def quiesce_and_swap(self, fn: Callable[[], None]) -> None:
        """Drain the engine to an iteration boundary and run ``fn`` (the
        weight flip) there, without dropping a request. Under the drive
        lock: set ``engine.swapping`` (the ``_join`` gate — queued
        requests stay queued), step the engine until every in-flight
        sequence completes under the OLD weights (completions stash into
        ``_done`` exactly as a caller's own drive turn would, so
        concurrent ``_drive_until`` threads blocked on the lock collect
        them the moment we release), call ``fn`` at the drained
        boundary, then clear the gate — the queued backlog admits on the
        next step under the NEW weights. No request ever spans weight
        versions; none is dropped. A failed flip propagates after the
        gate clears: the engine keeps serving the old weights."""
        with self._drive:
            self.engine.swapping = True
            try:
                while self.engine._running or self.engine._prefilling:
                    for c in self.engine.step():
                        self._done[c.rid] = c
                fn()
            finally:
                self.engine.swapping = False
