"""Disaggregated prefill/decode: split-gang serving with KV-block
handoff over the RPC wire.

PR 13 made the fleet the unit of throughput, but prefill and decode
still shared a replica: a prefill burst and the decode floor contend
for the same chips, and chunked prefill is a mitigation, not an
isolation. This module splits the two phases onto separate
replica ROLES — Arax's framing (PAPERS 2305.01291: workloads decoupled
from concrete accelerator instances) taken one phase deeper than the
router already did:

* a **prefill replica** runs the prompt through the existing chunked
  ``(1, chunk)`` launch family and emits the FIRST token — its output
  is KV + one token, never a generation loop
  (:meth:`~tony_tpu.serve.engine.ServeEngine.prefill_only`);
* the sequence's KV blocks ship over the wire — the paged pool's flat
  block payloads plus the prefix chain-hash keys ARE the wire format
  (:meth:`~tony_tpu.serve.kvcache.PagedKVCache.export_blocks`, per-block
  CRC32 reusing the ckpt plane's chunk-checksum idiom);
* a **decode replica** imports them into its OWN pool
  (:meth:`~tony_tpu.serve.kvcache.PagedKVCache.import_blocks` —
  AdmissionError-typed, state-unchanged on failure, composing with the
  prefix tier so a shipped shared-prefix stem is ADOPTED, not
  re-transferred: the shipper first ``kv_offer``-s the chain keys and
  ships only the blocks past the receiver's match) and continues the
  generation on its continuous batch.

Bitwise contract: the imported bytes are exactly the bytes the prefill
wrote (device → host → wire → host → device round-trips the pool dtype
losslessly, CRC-gated), and every serve op is row-independent at
tile-multiple shapes — so the disaggregated token stream AND per-token
logits are pinned BITWISE against the colocated PR 10/12/13 engine
(tests/test_disagg.py), spec lane riding on the decode side included.

Failure semantics (the one-slow-importer-must-never-wedge-the-prefill-
gang contract): a decode pool under pressure rejects the import with
the cache untouched; :class:`KVShipper` retries with bounded backoff
and surfaces a typed :class:`HandoffError` when the budget is spent —
the router then re-dispatches or falls back to COLOCATED prefill on the
decode replica (its engine prefills for itself), keeping the PR 13
OSError-vs-request-error failover split intact.

Jax-free on purpose (the same layering rule as ``serve.router`` /
``serve.prefix``): the router imports :class:`HandoffError` for its
fallback logic on a gateway host with no accelerator stack, and the
fronts only *hold* an engine-backed :class:`~tony_tpu.serve.engine.
EngineFront` — nothing here imports jax at module level.

Threading contract: every pool mutation the handoff path performs —
the prefill-side export and the decode-side import, both arriving on
RPC receiver threads — happens under the owning front's drive lock,
the same lock that serializes ``generate`` callers onto the engine
loop. The PR 14 concurrency plane (lock-discipline lint + lock-order
witness) gates this module, and the threaded kvcache interleave in
tests/test_concurrency.py drives export/import from N threads with the
refcount/free/LRU partition pinned at every quiescent point.
"""

from __future__ import annotations

import base64
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


class HandoffError(RuntimeError):
    """The KV handoff cannot complete: a CRC/geometry mismatch on the
    wire payload, an offered prefix that evaporated before import, or a
    shipping budget spent against a decode pool under pressure.
    ``retryable`` mirrors :class:`~tony_tpu.serve.kvcache.
    AdmissionError`'s flag; ``matched`` (when set) is the receiver's
    CURRENT prefix-match count so a retry re-ships exactly the missing
    tail instead of starting a fresh offer round."""

    def __init__(self, message: str, *, retryable: bool = True,
                 matched: Optional[int] = None):
        super().__init__(message)
        self.retryable = retryable
        self.matched = matched


def encode_f32(row: np.ndarray) -> str:
    """Wire form of one f32 logits row (the prefill-side first-token
    row a ``keep_logits`` engine ships so the decode side's Completion
    carries every per-token row — the bitwise pin surface)."""
    return base64.b64encode(
        np.ascontiguousarray(row, np.float32).tobytes()).decode("ascii")


def decode_f32(data: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(data), np.float32).copy()


def _classify(exc: Exception) -> tuple:
    """``(retryable, matched)`` of one shipping failure. Typed errors
    carry their own flags; wire errors (an RpcError string from the
    decode replica) are recognized by the transported type prefix —
    the JSON-lines RPC wraps application errors as
    ``"<TypeName>: <message>"`` — and treated as retryable: the retry
    budget is bounded either way, and a genuinely-never-fits request
    fails identically on the colocated fallback."""
    if isinstance(exc, HandoffError):
        return exc.retryable, exc.matched
    retryable = getattr(exc, "retryable", None)
    if retryable is not None:           # AdmissionError without the import
        return bool(retryable), None
    msg = str(exc)
    if msg.startswith(("AdmissionError:", "HandoffError:")):
        return True, None
    if isinstance(exc, OSError):
        # Transport fault mid-handoff: the import may or may not have
        # landed; re-offer from scratch (idempotent — a landed import
        # makes the retry's fresh-admission check fail loudly).
        return True, None
    return False, None


class KVShipper:
    """The prefill-side half of the handoff protocol: offer the chain
    keys, ship only the unmatched block tail, retry with bounded
    backoff, and surface a typed :class:`HandoffError` when the budget
    is spent — the shipper never blocks unboundedly, so one slow
    importer cannot wedge the prefill gang (its engine already freed
    the sequence's blocks before shipping begins)."""

    def __init__(self, *, max_attempts: int = 3, backoff_s: float = 0.05):
        if max_attempts < 1:
            raise ValueError(f"need max_attempts >= 1, got {max_attempts}")
        self.max_attempts = int(max_attempts)
        self.backoff_s = float(backoff_s)

    def ship(self, handoff: Dict[str, Any], decode: Any) -> tuple:
        """Offer/import ``handoff`` against ``decode`` (anything with
        ``kv_offer(keys=...) -> int`` and ``kv_import(payload=...)``,
        an in-process :class:`DecodeFront` or an RPC dial). Returns
        ``(completion, shipped_blocks)`` — the decode side's completion
        (it drives its engine until the resumed generation finishes)
        and the block count that actually crossed the wire. Returned,
        not stashed on ``self``: one shipper serves CONCURRENT
        ``prefill_handoff`` callers (the replica RPC server is
        threaded), and shared mutable per-ship state would tear.

        Known edge: a transport fault AFTER the decode side committed
        the import leaves that sequence decoding on the receiver — the
        retry's rid-collision check rejects typed, the router falls
        back colocated, and the orphaned generation completes on the
        receiver's own handler thread and is dropped there: bounded
        duplicated decode work per incident, never a wedge, a leak, or
        a wrong answer."""
        keys: List[str] = list(handoff.get("keys") or ())
        blocks = list(handoff.get("blocks") or ())
        offset: Optional[int] = None
        last: Optional[Exception] = None
        attempts = 0
        for attempt in range(self.max_attempts):
            attempts = attempt + 1
            if attempt:
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                if offset is None:
                    offset = min(max(0, int(decode.kv_offer(keys=keys))),
                                 len(blocks))
                payload = dict(handoff, offset=offset,
                               blocks=blocks[offset:])
                out = decode.kv_import(payload=payload)
                return out, len(blocks) - offset
            except Exception as e:  # noqa: BLE001 — classified below
                last = e
                retryable, matched = _classify(e)
                if not retryable:
                    break
                # A stale offer re-ships the now-missing tail; anything
                # else re-offers from scratch.
                offset = matched if matched is not None \
                    and not isinstance(e, OSError) else None
        raise HandoffError(
            f"KV handoff failed after {attempts} attempt(s): "
            f"{last}", retryable=False) from last


class DecodeFront:
    """The decode replica's receiver half over one shared
    :class:`~tony_tpu.serve.engine.EngineFront`: ``kv_offer`` answers
    the shipper's prefix probe, ``kv_import`` admits the shipped
    sequence into the engine and drives the shared loop until its
    generation completes (exactly the ``generate`` discipline —
    overlapping handoffs and colocated requests ride one continuous
    batch). Every cache mutation happens under the front's drive lock:
    the import arrives on an RPC receiver thread while another thread
    drives decode, and the paged pool is only safe under one driver —
    the contract the concurrency plane audits."""

    def __init__(self, front: Any):
        self.front = front

    def kv_offer(self, keys: Sequence[str]) -> int:
        with self.front._drive:
            return len(self.front.engine.cache.match_prefix(
                [str(k) for k in keys]))

    def kv_import(self, payload: Dict[str, Any]) -> Any:
        with self.front._drive:
            rid, done = self.front.engine.admit_handoff(payload)
        if done is not None:
            return done
        return self.front._drive_until(rid)

    def generate(self, tokens: Sequence[int], max_new_tokens: int,
                 rid: Optional[Any] = None,
                 conv: Optional[Any] = None,
                 tenant: Optional[str] = None) -> Any:
        """The colocated fallback path (the decode engine prefills for
        itself when a handoff could not be placed)."""
        return self.front.generate(tokens, max_new_tokens, rid=rid,
                                   conv=conv, tenant=tenant)


class PrefillFront:
    """The prefill replica's shipper half over one shared
    :class:`~tony_tpu.serve.engine.EngineFront`: run the prefill-only
    engine mode under the drive lock, then ship the exported KV to the
    decode target OUTSIDE it — the prefill engine is free for the next
    prompt the moment its blocks are exported, whatever the importer
    does. ``decode`` is an in-process :class:`DecodeFront` or a
    ``host:port`` address (dialed over the control-plane RPC)."""

    def __init__(self, front: Any, *, shipper: Optional[KVShipper] = None,
                 dial_timeout_s: float = 15.0):
        self.front = front
        self.shipper = shipper or KVShipper()
        self.dial_timeout_s = float(dial_timeout_s)

    def prefill_handoff(self, tokens: Sequence[int], max_new_tokens: int,
                        rid: Optional[Any] = None,
                        decode: Any = None,
                        conv: Optional[Any] = None,
                        tenant: Optional[str] = None) -> Any:
        if decode is None:
            raise ValueError("prefill_handoff needs a decode target "
                             "(a DecodeFront or a host:port address)")
        if isinstance(decode, str):
            decode = _dial_decode(decode, self.dial_timeout_s)
        from tony_tpu.serve.engine import Request

        if rid is None:
            rid = self.front.fresh_rid()
        from tony_tpu.serve.kvcache import AdmissionError

        eng = self.front.engine
        with self.front._drive:
            try:
                handoff = eng.prefill_only(Request(
                    rid=rid, tokens=[int(t) for t in tokens],
                    max_new_tokens=int(max_new_tokens), conv=conv,
                    tenant=tenant))
            except AdmissionError as e:
                if not getattr(e, "retryable", True):
                    raise               # never fits: same as colocated submit
                # Transient PREFILL-pool pressure: a colocated engine
                # absorbs this by leaving the request queued, but
                # prefill_only has no queue to park it in — re-type as
                # a non-retryable HandoffError so the router's fallback
                # runs colocated prefill on the decode replica instead
                # of hard-failing a request the colocated path would
                # have served.
                raise HandoffError(
                    f"prefill pool pressure for {rid!r}: {e}",
                    retryable=False) from e
        # Counters bank on the ENGINE (its stats() is the fleet's one
        # telemetry surface) through a locked helper: concurrent
        # prefill_handoff callers on the threaded RPC front would tear
        # a bare `+=`. Failed ships bank nothing here — the importer's
        # rejection is visible as the DECODE side's imports_failed, and
        # the raised HandoffError carries the attempt ledger. The
        # engines' handoff_ms accrues inside prefill_only/admit_handoff
        # (export/import wall — NOT the shipped sequence's downstream
        # generation, which ship() blocks on).
        out, shipped = self.shipper.ship(handoff, decode)
        eng.note_handoff_shipped(shipped)
        return out

    def generate(self, tokens: Sequence[int], max_new_tokens: int,
                 rid: Optional[Any] = None,
                 conv: Optional[Any] = None,
                 tenant: Optional[str] = None) -> Any:
        return self.front.generate(tokens, max_new_tokens, rid=rid,
                                   conv=conv, tenant=tenant)


def _dial_decode(address: str, timeout: float) -> Any:
    """RPC transport to a decode replica's receiver verbs (lazy import,
    like the router's ``_rpc_dial`` — the RPC stack only loads when a
    network decode target is actually dialed)."""
    from tony_tpu.rpc import RpcClient

    class _Decode:
        def kv_offer(self, keys):
            with RpcClient(address, timeout=timeout) as client:
                return client.call("kv_offer", keys=list(keys))

        def kv_import(self, payload):
            with RpcClient(address, timeout=timeout) as client:
                return client.call("kv_import", payload=payload)

        def generate(self, tokens, max_new_tokens, rid=None, conv=None,
                     tenant=None):
            with RpcClient(address, timeout=timeout) as client:
                return client.call("generate", tokens=list(tokens),
                                   max_new_tokens=int(max_new_tokens),
                                   rid=rid, conv=conv, tenant=tenant)

    return _Decode()
