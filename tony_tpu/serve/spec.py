"""Speculative decoding lane: draft-and-verify over the serving plane.

The PR 10 engine pays one full target-model forward per generated token;
decode is memory-bandwidth-bound, so the MXU idles while weights stream.
This module multiplies tokens-per-forward WITHOUT changing a single
output bit on the greedy path:

1. a **draft lane** proposes ``k`` tokens autoregressively — either a
   second, smaller transformer (:class:`ModelDraft`, restored by the
   replica alongside the target through the same elastic-restore path,
   optionally on the int8 ``quant=`` lanes) or the self-drafting n-gram
   fallback (:class:`NgramDraft`, the classic prompt-lookup scheme: no
   second model, no extra forwards, surprisingly effective on the
   repetitive tails greedy decoding produces);
2. the **target verifies all k+1 positions in ONE launch**: the verify
   forward is the SAME ``(b, t)``-shaped jitted step the decode loop
   runs (:func:`tony_tpu.serve.engine.build_step_fn`) with ``k+1`` real
   rows instead of 1 — the fixed ``q_block`` row-block tiling that makes
   continuous batching bit-transparent makes verification bit-transparent
   for free, and it adds ZERO new compiles;
3. **greedy accept/reject is deterministic**: draft token ``d_j`` is
   accepted iff it equals the target's argmax at the previous row; the
   first rejected row's own argmax is emitted as the bonus token. Every
   emitted token therefore equals what sequential greedy decode would
   have produced — and because each verify row's logits are bit-identical
   to the plain decode row at that position (row independence at
   tile-multiple shapes, the serve plane's core numerics contract), the
   speculative engine's token streams AND per-token logits are pinned
   BITWISE against the non-speculative engine;
4. **rollback is free**: the verify launch scatters all k+1 candidate KV
   rows into the paged pool, then the per-sequence write cursor rolls
   back to the accepted length (:meth:`PagedKVCache.commit` /
   :meth:`~PagedKVCache.rollback`). Rejected rows sit above every
   committed position, so the stale-bytes-provably-unread contract
   guarantees they are never gathered before the regenerating step
   overwrites them — no device work at all.

Expected speedup (ROOFLINE.md §9): with per-token acceptance rate α and
depth k, tokens per target launch is ``(1 - α^{k+1}) / (1 - α)`` — the
bytes-bound decode floor divides by that factor.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from tony_tpu import profiler
from tony_tpu.serve.engine import (PagedModelRunner, ServeEngine,
                                   _bucket_of, _Seq)
from tony_tpu.serve.kvcache import AdmissionError

_record = functools.partial(profiler.record, "serve")


# ---------------------------------------------------------------------------
# Draft lanes
# ---------------------------------------------------------------------------

class NgramDraft:
    """Self-drafting n-gram proposer (prompt lookup): the continuation
    after the most recent earlier occurrence of the sequence's own
    longest matched suffix. Deterministic, host-side, zero forwards —
    the lane every replica can run without training a second model.
    Greedy tails love it: a generation that enters a repeating cycle is
    predicted perfectly from its own history."""

    kind = "ngram"
    forwards = 0                       # never launches anything

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not 1 <= min_n <= max_n:
            raise ValueError(f"need 1 <= min_n <= max_n, got "
                             f"{min_n}/{max_n}")
        self.max_n = int(max_n)
        self.min_n = int(min_n)
        # Per-sequence persistent index over the REAL history:
        # rid -> ([{ngram: next} per n], indexed_len). Most recent
        # occurrence wins (later writes overwrite), extended
        # incrementally as verified tokens arrive — O(max_n) per new
        # token, so a whole generation costs O(len · max_n) instead of
        # the O(len² · max_n) a per-round rescan would put on the
        # latency path the lane exists to shorten.
        self._index: Dict[Any, Any] = {}

    def _seq_index(self, s: _Seq):
        """The sequence's index, extended over tokens appended since the
        last round (drafted tokens never enter it — rejected ones would
        poison the history; accepted ones arrive here as real)."""
        hist = s.tokens
        index, done = self._index.get(s.rid) or (
            [{} for _ in range(self.max_n + 1)], 0)
        for pos in range(done, len(hist)):
            nxt = hist[pos]
            for n in range(self.min_n, min(self.max_n, pos) + 1):
                index[n][tuple(hist[pos - n:pos])] = nxt
        self._index[s.rid] = (index, len(hist))
        return index

    def propose(self, seqs: Sequence[_Seq],
                ks: Sequence[int]) -> List[List[int]]:
        out: List[List[int]] = []
        for s, k in zip(seqs, ks):
            index = self._seq_index(s)
            hist = list(s.tokens)
            # Draft-round overlay: grams created by this round's drafts
            # are newer than anything persistent (they win lookups) but
            # die with the round — they are unverified.
            overlay: List[Dict[tuple, int]] = [
                {} for _ in range(self.max_n + 1)]
            drafts: List[int] = []
            for _ in range(k):
                nxt = None
                for n in range(min(self.max_n, len(hist) - 1),
                               self.min_n - 1, -1):
                    gram = tuple(hist[-n:])
                    nxt = overlay[n].get(gram, index[n].get(gram))
                    if nxt is not None:
                        break
                if nxt is None:
                    nxt = hist[-1]     # no match: repeat-last fallback
                drafts.append(nxt)
                hist.append(nxt)
                m = len(hist) - 1
                for n in range(self.min_n, min(self.max_n, m) + 1):
                    overlay[n][tuple(hist[m - n:m])] = nxt
            out.append(drafts)
        return out

    def observe(self, seqs: Sequence[_Seq]) -> None:
        # Accepted tokens enter the persistent index lazily on the next
        # propose (the indexed_len cursor); nothing to reconcile here.
        pass

    def evict(self, seq: _Seq) -> None:
        self._index.pop(seq.rid, None)


class ModelDraft(PagedModelRunner):
    """A second (smaller) transformer as the draft lane, run over its
    OWN paged KV cache through the IDENTICAL jitted step family the
    target engine uses (the shared
    :class:`~tony_tpu.serve.engine.PagedModelRunner` plumbing — one jit
    cache shape, one mesh/donation discipline for both lanes).

    The draft cache is managed LAZILY — permanent reservation tracks the
    verified token extent, each proposal round rides a revocable
    :meth:`~PagedKVCache.spec_reserve` extension, and the post-verify
    :meth:`~PagedKVCache.commit`/:meth:`~PagedKVCache.rollback` pair
    truncates it back to the accepted length — so the speculative
    reservation machinery is load-bearing here, not just bookkeeping
    (the target engine's full-extent admission reservation means ITS
    extensions grow nothing).

    Correctness hinge: a draft token is accepted exactly when it equals
    the target's argmax, so the fed-token prefix of an accepted run
    matches the true sequence — the draft cache rows for accepted
    positions are already right and survive the rollback."""

    kind = "model"

    def __init__(self, model: Any, params: Any, *, ctx_max: int,
                 block_size: int = 16, q_block: int = 16,
                 decode_buckets: Sequence[int] = (4, 16),
                 max_running: int = 16, n_blocks: Optional[int] = None,
                 mesh: Optional[Any] = None):
        self._init_paged(model, params, ctx_max=ctx_max,
                         block_size=block_size, q_block=q_block,
                         decode_buckets=decode_buckets,
                         max_running=max_running, n_blocks=n_blocks,
                         mesh=mesh)
        self._cursor: Dict[Any, int] = {}

    # -- cache lifecycle ---------------------------------------------------
    def _sync(self, seq: _Seq) -> bool:
        """Catch the draft cache up to the verified extent: feed
        ``tokens[cursor:p0]`` (everything but the newest, not-yet-fed
        token) as one padded row block. First sight of a sequence runs
        its whole prompt; after a fully-accepted round it is one row.
        Returns False (sequence undraftable this round, retried next)
        when the draft pool cannot host the verified extent — pool
        pressure must degrade to plain decode, never escape the loop."""
        rid = seq.rid
        p0 = len(seq.tokens) - 1
        c = self._cursor.get(rid, 0)
        if c >= p0:
            return True
        try:
            # Permanent: these rows are verified.
            self.cache.reserve(rid, p0)
        except AdmissionError:
            return False
        t_real = p0 - c
        t_pad = -(-t_real // self.q_block) * self.q_block
        tokens = np.zeros((1, t_pad), np.int32)
        tokens[0, :t_real] = seq.tokens[c:p0]
        positions = (c + np.arange(t_pad, dtype=np.int32))[None].copy()
        tables = self.cache.table_array([rid], self.nb_max)
        flat = np.full((1, t_pad), self.cache.oob_index, np.int32)
        for j in range(t_real):
            flat[0, j] = self.cache.flat_index(rid, c + j)
        self._run_fn(1, t_pad, tokens, positions, tables, flat)
        self._cursor[rid] = p0
        return True

    def propose(self, seqs: Sequence[_Seq],
                ks: Sequence[int]) -> List[List[int]]:
        """``k`` batched greedy decode steps over the draft cache; each
        step feeds the previous step's argmax (step 0 feeds the target's
        newest real token). Rows past a sequence's own depth still run
        (the batch is uniform) but scatter nowhere and bind nothing.

        Draft-pool pressure degrades PER SEQUENCE, never escapes: a
        sequence whose sync or speculative extension cannot be hosted
        drafts zero tokens this round (its returned list is empty — the
        engine verifies it as a plain decode row) and retries next
        round; extensions already granted to other sequences stay
        intact for the normal commit/rollback cycle."""
        # Effective depth per sequence: 0 when the draft cache cannot
        # host it this round (sync or extension failure).
        ks = [k if self._sync(s) else 0 for s, k in zip(seqs, ks)]
        for i, (s, k) in enumerate(zip(seqs, ks)):
            if k:
                try:
                    # Revocable coverage for the k fed rows at
                    # p0 .. p0+k-1 (atomic: state unchanged on failure).
                    self.cache.spec_reserve(s.rid,
                                            len(s.tokens) - 1 + k)
                except AdmissionError:
                    ks[i] = 0
        n = len(seqs)
        b = _bucket_of(self.decode_buckets, n)
        t = self.q_block
        kmax = max(ks) if ks else 0
        drafts: List[List[int]] = [[] for _ in seqs]
        cur = [s.tokens[-1] for s in seqs]
        # Tables are fixed for the whole round once the reservations are
        # in — build the padded array once, not once per draft step.
        tables = np.zeros((b, self.nb_max), np.int32)
        tables[:n] = self.cache.table_array(
            [s.rid for s in seqs], self.nb_max)
        for j in range(kmax):
            tokens = np.zeros((b, t), np.int32)
            positions = np.zeros((b, t), np.int32)
            flat = np.full((b, t), self.cache.oob_index, np.int32)
            for i, s in enumerate(seqs):
                pj = len(s.tokens) - 1 + j
                tokens[i, 0] = cur[i]
                positions[i] = pj + np.arange(t, dtype=np.int32)
                if j < ks[i]:
                    flat[i, 0] = self.cache.flat_index(s.rid, pj)
            logits = self._run_fn(b, t, tokens, positions, tables, flat)
            rows = np.asarray(logits[:n, 0], np.float32)
            for i in range(n):
                if j < ks[i]:
                    nxt = int(np.argmax(rows[i]))
                    drafts[i].append(nxt)
                    cur[i] = nxt
        for s, k in zip(seqs, ks):
            if k:
                self._cursor[s.rid] = len(s.tokens) - 1 + k
        return drafts

    def observe(self, seqs: Sequence[_Seq]) -> None:
        """Post-verify reconciliation: the engine has appended the
        accepted prefix + bonus to each sequence; roll the draft cache's
        cursor back to the longest fed prefix that is still true (the
        accepted rows — rejected rows' blocks return to the pool)."""
        for s in seqs:
            rid = s.rid
            c = min(self._cursor.get(rid, 0), len(s.tokens) - 1)
            self.cache.commit(rid, c)
            self.cache.rollback(rid)
            self._cursor[rid] = c

    def evict(self, seq: _Seq) -> None:
        self.cache.free_seq(seq.rid)
        self._cursor.pop(seq.rid, None)


# ---------------------------------------------------------------------------
# The speculative engine
# ---------------------------------------------------------------------------

class SpecEngine(ServeEngine):
    """Draft-and-verify continuous batching: identical admission, join,
    and evict semantics to :class:`~tony_tpu.serve.engine.ServeEngine`,
    but each iteration advances every running sequence by a VARIABLE
    number of tokens — the accepted draft prefix plus the target's bonus
    token — for exactly one target forward.

    ``draft`` is a lane object (:class:`NgramDraft` default,
    :class:`ModelDraft` via ``draft_model=``/``draft_params=``) and
    ``spec_k`` the draft depth (``<= q_block - 1``: the verify rows must
    fit the engine's fixed row block). Greedy-path outputs are pinned
    BITWISE against the plain engine — tests/test_spec.py holds token
    streams AND per-token logits across overlapping, ragged,
    block-boundary-crossing request mixes."""

    def __init__(self, model: Any, params: Any, *, spec_k: int = 4,
                 draft: Optional[Any] = None,
                 draft_model: Optional[Any] = None,
                 draft_params: Optional[Any] = None,
                 ngram_max: int = 3, **kw):
        super().__init__(model, params, **kw)
        if not 1 <= int(spec_k) <= self.q_block - 1:
            raise ValueError(
                f"spec_k must be in [1, q_block-1={self.q_block - 1}] "
                f"(the k+1 verify rows ride one row block), got {spec_k}")
        self.spec_k = int(spec_k)
        if draft is None:
            if draft_model is not None:
                draft = ModelDraft(
                    draft_model, draft_params, ctx_max=self.ctx_pad,
                    block_size=self.block_size, q_block=self.q_block,
                    decode_buckets=self.decode_buckets,
                    max_running=self.max_running, mesh=self.mesh)
            else:
                draft = NgramDraft(max_n=ngram_max)
        elif draft_model is not None:
            raise ValueError("pass draft= OR draft_model=, not both")
        self.draft = draft
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.verify_launches = 0
        self.spec_rounds = 0           # (sequence, verify-launch) pairs
        self.spec_tokens_out = 0
        _record(f"{self.tag}_spec", k=self.spec_k, draft=self.draft.kind,
                q_block=self.q_block,
                decode_buckets=list(self.decode_buckets))

    # -- the one-launch verification ---------------------------------------
    def _verify_round(self) -> None:
        seqs = list(self._running)
        ks = [min(self.spec_k, s.remaining) for s in seqs]
        drafts = self.draft.propose(seqs, ks)
        # The lane may degrade a sequence's depth (draft-pool pressure →
        # empty proposal = plain decode for that row this round); the
        # verify geometry follows what was actually drafted.
        ks = [min(k, len(d)) for k, d in zip(ks, drafts)]
        b = _bucket_of(self.decode_buckets, len(seqs))
        t = self.q_block
        tokens = np.zeros((b, t), np.int32)
        positions = np.zeros((b, t), np.int32)
        tables = np.zeros((b, self.nb_max), np.int32)
        flat = np.full((b, t), self.cache.oob_index, np.int32)
        for i, s in enumerate(seqs):
            p0 = len(s.tokens) - 1
            # Revocable coverage for the k+1 candidate rows at
            # p0 .. p0+k. Full-extent admission already covers them, so
            # this grows nothing on the target pool — but it keeps the
            # reserve→commit→rollback cursor contract uniform with the
            # draft cache (and with any future lazily-reserving engine).
            self.cache.spec_reserve(s.rid, p0 + 1 + ks[i])
            tokens[i, 0] = s.tokens[-1]
            tokens[i, 1:1 + ks[i]] = drafts[i]
            positions[i] = p0 + np.arange(t, dtype=np.int32)
            for j in range(ks[i] + 1):
                # write_index: a forked sequence's first verify rows can
                # land in an adopted prefix block (full-cover admission)
                # — COW keeps the donor's bytes untouched.
                flat[i, j] = self.cache.write_index(s.rid, p0 + j)
        tables[:len(seqs)] = self.cache.table_array(
            [s.rid for s in seqs], self.nb_max)
        with profiler.span("serve:launch", step=self._steps,
                           batch=len(seqs)):
            logits = self._run_fn(b, t, tokens, positions, tables, flat)
            jax.block_until_ready(logits)
        # One clock read for the launch: its rows are ready, the accept
        # loop below reads them back one by one.
        self._t_emit = time.monotonic()
        self.verify_launches += 1
        for i, s in enumerate(seqs):
            p0 = len(s.tokens) - 1
            k = ks[i]
            a = 0
            while a < k:
                row = np.asarray(logits[i, a], np.float32)
                if int(np.argmax(row)) != drafts[i][a]:
                    break
                self._emit_token(s, row)     # == the accepted draft token
                a += 1
            if s.remaining > 0:
                # The first non-accepted row's own argmax: the token
                # sequential greedy decode would have produced here.
                self._emit_token(s, np.asarray(logits[i, a], np.float32))
            self.spec_proposed += k
            self.spec_accepted += a
            self.spec_rounds += 1
            self.spec_tokens_out += len(s.tokens) - 1 - p0
            # Verified rows now cover positions [0, p0+a+1); the cursor
            # rolls back to exactly there — rejected rows above it are
            # stale bytes the next launch overwrites before any read.
            self.cache.commit(s.rid, p0 + a + 1)
            self.cache.rollback(s.rid)
        self.draft.observe(seqs)

    def step(self):
        """One engine iteration: join what fits, advance one prefill
        chunk (chunked mode), draft + verify one launch for the whole
        running batch, evict what finished."""
        results = []
        self._join(results)
        self._advance_prefill(results)
        if self._running:
            self._verify_round()
            still = []
            for s in self._running:
                if s.remaining <= 0:
                    self.draft.evict(s)
                    self._evict(s, results)
                else:
                    still.append(s)
            self._running = still
        self._steps += 1
        return results

    # -- telemetry ---------------------------------------------------------
    def _extra_stats(self) -> Dict[str, float]:
        return {
            "acceptance_rate": (self.spec_accepted / self.spec_proposed
                                if self.spec_proposed else 0.0),
            "spec_proposed": float(self.spec_proposed),
            "spec_accepted": float(self.spec_accepted),
            "verify_launches": float(self.verify_launches),
            "draft_forwards": float(getattr(self.draft, "forwards", 0)),
            # Decode tokens per verify launch (batching folded in), and
            # the per-SEQUENCE version = 1 + mean accepted run — the >1
            # multiplier speculation itself earns, batching excluded
            # (prefill-emitted tokens excluded from both, unlike the
            # global tokens_per_forward).
            "tokens_per_verify": (self.spec_tokens_out
                                  / self.verify_launches
                                  if self.verify_launches else 0.0),
            "tokens_per_seq_round": (self.spec_tokens_out
                                     / self.spec_rounds
                                     if self.spec_rounds else 0.0),
        }

    # -- static-analysis hook ---------------------------------------------
    def verify_traced(self, batch: Optional[int] = None):
        """``(jitted, example_args)`` of the canonical verify bucket for
        ``tony analyze --config spec``. The verify step IS the decode
        step family — k+1 real rows ride the same ``(b, q_block)``
        launch — so this traces the identical program the loop runs,
        and the zero-collectives + KV-pool-donation audit covers the
        speculative lane with the same pin mechanics as decode."""
        return self.decode_traced(batch)
