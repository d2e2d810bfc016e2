"""Paged decode engine — prefill/decode split over the block-paged cache.

One engine serves many in-flight sequences through exactly TWO compiled
program families, both bounded by the shape ladder:

* **prefill** — the bucketed encoder forward (the same
  ``CompileShapeCache`` contract training feeds ride: source tokens pad to
  a ``DEFAULT_LADDER`` rung, admitted-group batch rows pad to a
  ``DEFAULT_BATCH_LADDER`` rung) fused with the page scatter: encoder
  memory splits into fixed-size blocks written at the allocator's page
  ids, and the decoder boot state lands in the slot plane.  One compiled
  variant per (batch-rung, source-rung) pair.
* **decode** — ONE fused attention-GRU step (ops/rnn.attention_gru_step —
  the PR-2 scan core's generation face) for EVERY live sequence at once,
  rewired to gather the encoder memory through the page table:
  ``pool[page_table]`` reshapes to the padded attention extent, ragged
  true lengths ride as a mask.  One compiled variant per (slot-rung,
  page-rung) pair; admission and retirement change page-table CONTENTS
  and the live mask, never shapes — continuous batching without a single
  recompile.

Decode outputs are BIT-IDENTICAL per request to the one-shot
``Seq2SeqGenerator.generate_greedy`` path (pinned in tests/test_serving.py):
the gathered pages hold exactly the bytes prefill wrote, masked padding
contributes exact zeros, and every per-row op is batch-row independent.

**Chunked prefill** (``serving_prefill_chunk_tokens``): a prompt whose
padded source extent exceeds the chunk bound no longer prefills as one
monolithic encoder dispatch that stalls every decoding sequence for its
whole duration.  Instead the bi-GRU encoder runs in ladder-rung chunks
with carried recurrent state — a forward pass of chunk scans left to
right, a backward pass right to left, each chunk one bounded dispatch,
page-scattered as the backward pass completes each span — and
:meth:`ServingEngine.step` advances ONE chunk per call before decoding,
so decode stalls are bounded by a chunk, not a prompt.  Bit-identity
holds because a ``lax.scan`` split at chunk boundaries with carried state
executes the identical per-step op sequence as the unsplit scan (pinned
in tests/test_serving.py against the one-shot path).  The chunk programs
are four fixed-shape jits (fw scan, bw scan, scatter+project, boot
write) counted under ``trace_counts['prefill_chunk']``.

**Decode raw speed (PR 17)** adds three faces over the same two pools:

* *COW prefix sharing* (``serving_prefix_cache``): finished prompts park
  their pages + captured boot state in a cache keyed on signature-seeded
  token-block hash chains; an exact-prompt repeat maps the SAME blocks
  into its page table (refcount +1) and decodes immediately — zero
  prefill dispatches, bit-identical by construction.  Exact-prompt-only
  because the bi-GRU's backward direction makes every encoded position
  suffix-dependent; partial overlap instead resumes the chunked
  prefill's FORWARD pass from cached carries (prefix-determined, so
  bit-exact).  Writes go through the :meth:`ServingEngine.ensure_private_pages`
  COW barrier; blocks free only at refcount 0; eviction is LRU over
  refcount-0 blocks under the same ``serving_hbm_budget_mb``.
* *Speculative decoding* (``serving_spec_decode``): an n-gram
  prompt-lookup draft proposes K tokens and ONE dispatch (the decode
  program family's shape, draft-teacher-forced so the embedding GEMM
  hoists out of the scan) verifies them against the target's own argmax
  chain — the emitted tokens ARE the greedy chain's, acceptance only
  changes how many land per dispatch.
* *Paged beam serving*: a request with ``beam_size`` runs
  ops/beam.beam_search over the page-table-gathered memory with the SAME
  fused step closure the one-shot path uses
  (models/seq2seq.make_fused_step) — beam decode as a serving citizen.
"""

from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.batch import (
    DEFAULT_BATCH_LADDER,
    DEFAULT_LADDER,
    batch_shape_key,
    ladder_len,
    pad_batch_rows,
)
from paddle_tpu import obs as _obs
from paddle_tpu.core.compiler import CompileShapeCache
from paddle_tpu.ops import acc_matmul
from paddle_tpu.ops.beam import greedy_token_chain
from paddle_tpu.ops.rnn import attention_gru_step
from paddle_tpu.serving.pages import BlockPagedCache

__all__ = ["ServingEngine"]


class _Slot:
    """One in-flight sequence: its page-table row + host-side decode state.

    ``beam`` > 0 routes the slot through the paged whole-sequence beam
    program instead of the continuous greedy/speculative loop; ``boot_h``
    holds the host copy of the decoder boot state captured after prefill
    (None unless the prefix cache is on and the slot prefilled cleanly) —
    it becomes the cache entry's resume state when the slot retires."""

    __slots__ = (
        "request", "pages", "enc_tokens", "last_id", "tokens", "max_new",
        "admit_seq", "beam", "boot_h",
    )

    def __init__(self, request, pages, enc_tokens, last_id, tokens, max_new,
                 admit_seq, beam=0, boot_h=None):
        self.request = request
        self.pages = pages
        self.enc_tokens = enc_tokens
        self.last_id = last_id
        self.tokens = tokens
        self.max_new = max_new
        self.admit_seq = admit_seq
        self.beam = beam
        self.boot_h = boot_h


class _PendingPrefill:
    """One long prompt mid-chunked-prefill: its slot/pages are held, the
    carried bi-GRU state and per-chunk forward activations live here until
    the backward pass finishes scattering every span, then the slot goes
    live for decode."""

    __slots__ = (
        "request", "pages", "enc_tokens", "max_new", "admit_seq", "ids",
        "length", "rows", "n_chunks", "phase", "cursor", "h", "fw_chunks",
        "resume",
    )

    def __init__(self, request, pages, enc_tokens, max_new, admit_seq,
                 ids, length, rows, n_chunks, h0, resume):
        self.request = request
        self.pages = pages
        self.enc_tokens = enc_tokens
        self.max_new = max_new
        self.admit_seq = admit_seq
        self.ids = ids          # [1, S_pad] int32, host
        self.length = length    # [1] int32, host
        self.rows = rows        # [S_pad // block_tokens] page ids, host
        self.n_chunks = n_chunks
        self.phase = "fw"       # "fw" then "bw"
        self.cursor = 0         # next chunk index (fw ascends, bw descends)
        self.h = h0             # carried GRU state [1, H], device
        self.fw_chunks = [None] * n_chunks  # [1, C, H] forward activations
        self.resume = resume    # preemption save-state or None


class ServingEngine:
    """Continuous-batching decode over a trained :class:`Seq2SeqGenerator`.

    The engine is single-threaded by contract — exactly one thread (the
    scheduler's step thread, or a test driving ``admit``/``step``
    directly) owns it.  Cross-thread coordination lives in
    :class:`~paddle_tpu.serving.scheduler.ServingScheduler`.

    Requires the decoder to match the fused attention-GRU idiom (the same
    structural matcher the training scan and beam stepping use); a
    non-matching topology raises — the serving plane has no interpreted
    fallback, by design.
    """

    def __init__(
        self,
        generator,
        *,
        max_slots: Optional[int] = None,
        block_tokens: Optional[int] = None,
        hbm_budget_mb: Optional[int] = None,
        max_new_tokens: Optional[int] = None,
        block_steps: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        int8_weights: Optional[bool] = None,
        prefix_cache: Optional[bool] = None,
        spec_decode: Optional[bool] = None,
        spec_ngram: Optional[int] = None,
        clock=time.perf_counter,
        stats=None,
    ):
        from paddle_tpu.utils import flags as _flags
        from paddle_tpu.utils.timers import global_stats

        if generator._match is None or not _flags.get_flag("fused_attention_gru"):
            raise ValueError(
                "serving requires the fused attention-GRU decoder step "
                "(the topology did not match, or fused_attention_gru is off)"
            )
        self._gen = generator
        self._clock = clock
        self._stats = stats if stats is not None else global_stats
        self.max_slots = (
            max_slots if max_slots is not None
            else _flags.get_flag("serving_max_slots")
        )
        blk = (
            block_tokens if block_tokens is not None
            else _flags.get_flag("serving_block_tokens")
        )
        if DEFAULT_LADDER[0] % blk != 0:
            raise ValueError(
                f"serving_block_tokens={blk} must divide the base ladder "
                f"rung {DEFAULT_LADDER[0]} so every padded source extent "
                "splits into whole blocks"
            )
        budget_mb = (
            hbm_budget_mb if hbm_budget_mb is not None
            else _flags.get_flag("serving_hbm_budget_mb")
        )
        self.default_max_new_tokens = (
            max_new_tokens if max_new_tokens is not None
            else _flags.get_flag("serving_max_new_tokens")
        )
        # K tokens per dispatch: the make_multi_train_step amortization
        # applied to decode (each dispatch's host sync covers K tokens for
        # every live slot; finished rows clamp to EOS in-graph)
        self.block_steps = max(1, int(
            block_steps if block_steps is not None
            else _flags.get_flag("serving_decode_block_steps")
        ))

        # weight bundle (PR-2 fused extraction, shared with beam stepping)
        gp = generator.net.materialize_shared(generator.params.params)
        self._gp = gp
        self._state = generator.params.state
        self._w = generator.fused_decode_weights(gp)
        # weight-only int8 (the serving_int8_weights flag): the RESIDENT
        # decode bundle holds int8 blocks + f32 scales and every dispatch
        # dequantizes in-graph, so HBM carries ~1/4 the weight bytes while
        # biases/vectors (and the host-side sp_b uses) stay full-precision
        # f32 in self._w.  Bit-drift vs the f32 bundle is bounded by the
        # serving_int8_drift_budget flag (tests/bench assert it).
        from paddle_tpu.ops import quantize as _bsq

        if int8_weights is None:
            int8_weights = bool(_flags.get_flag("serving_int8_weights"))
        self.int8_weights = bool(int8_weights)
        self._w_meta: Dict[str, Any] = {}
        if self.int8_weights:
            self._w_arg, self._w_meta = _bsq.quantize_weight_bundle(self._w)
        else:
            self._w_arg = self._w
        self.weight_bytes = _bsq.weight_bundle_bytes(self._w_arg)
        mt = generator._match
        self._acts = {
            "gate_act": mt.gate_act, "act": mt.act, "att_act": mt.att_act,
        }
        self.hidden_dim = int(self._w["w_c"].shape[0])
        self.trg_vocab = int(self._w["head_w"].shape[1])
        d_enc = int(self._w["w_ctx"].shape[0])
        d_ep = int(self._w["v"].shape[0])
        self._dtype = self._w["w_ctx"].dtype
        # which encoder-subgraph outputs feed the two static placeholders
        pmap = dict(zip(
            [p for p, _ in generator._static_info], ["enc", "enc_proj"]
        ))
        self._enc_layer = pmap[mt.enc_name]
        self._ep_layer = pmap[mt.ep_name]

        # feeder over the pruned encoder graph's single source slot, on the
        # canonical ladder (the prefill half of the shape contract)
        from paddle_tpu.reader.feeder import DataFeeder

        dts = generator._enc_net.topology.data_types()
        seq_slots = [n for n, it in dts if it.seq.name != "NONE"]
        if len(seq_slots) != 1:
            raise ValueError(
                f"serving expects one source sequence slot, got {seq_slots}"
            )
        self.src_slot = seq_slots[0]
        self.src_vocab = int(dict(dts)[self.src_slot].dim)
        self._feeder = DataFeeder(dts, ladder=DEFAULT_LADDER, min_seq_len=1)

        # block-paged cache + device pools (+1 scratch row each; the slot
        # plane gets a scratch row too, absorbing padded-lane writes)
        self._pages = BlockPagedCache(
            blk,
            {"enc": d_enc, "ep": d_ep},
            hbm_budget_bytes=int(float(budget_mb) * (1 << 20)),
            dtype_bytes=jnp.dtype(self._dtype).itemsize,
            stats=self._stats,
        )
        self.block_tokens = blk
        self._enc_pool = jnp.zeros(
            (self._pages.pool_rows, blk, d_enc), self._dtype
        )
        self._ep_pool = jnp.zeros(
            (self._pages.pool_rows, blk, d_ep), self._dtype
        )
        self._h = jnp.zeros((self.max_slots + 1, self.hidden_dim), self._dtype)
        self._scratch_slot = self.max_slots
        # page-count rungs mirror the time ladder: P * block_tokens is
        # always a DEFAULT_LADDER extent, so the gathered attention extent
        # matches what the one-shot path pads to (bit-identity)
        self._page_ladder = tuple(sorted({
            max(1, r // blk) for r in DEFAULT_LADDER
        }))

        self._slots: Dict[int, _Slot] = {}
        self._prefilling: Dict[int, _PendingPrefill] = {}
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._admit_seq = 0

        # -- copy-on-write prefix cache (serving_prefix_cache) ------------
        # Full-prompt entries only: the bi-GRU encoder's BACKWARD direction
        # makes every encoded position depend on the prompt SUFFIX, so a
        # cached block is bit-identical for a new request only when the
        # ENTIRE prompt matches — partial-prefix overlap reuses the cached
        # forward-GRU carries on the chunked path (below) instead.  The
        # key chains per-block token-tuple hashes seeded by the engine
        # signature (topology fingerprint + feed dtype + source slot/vocab
        # + special ids + weight precision): two engines that tokenize or
        # compute differently can NEVER alias an entry, and the stored
        # exact token tuple makes hash collisions a miss, not a wrong hit.
        self.prefix_cache_enabled = bool(
            prefix_cache if prefix_cache is not None
            else _flags.get_flag("serving_prefix_cache")
        )
        net = self._gen.net
        graph = net.topology.serialize() + f"|compute={net.compute_dtype}"
        self._cache_sig = (
            hashlib.sha256(graph.encode()).hexdigest()[:16],
            str(jnp.dtype(self._dtype)),
            self.src_slot,
            self.src_vocab,
            int(self._gen.bos_id),
            int(self._gen.eos_id),
            self.int8_weights,
        )
        self._cache_sig_hash = hash(self._cache_sig)
        # key -> {tokens, pages, boot_h, enc_tokens}; block id -> owning key
        self._prefix_cache: Dict[tuple, Dict[str, Any]] = {}
        self._prefix_owner: Dict[int, tuple] = {}
        self._pages.on_evict = self._on_block_evicted
        self.prefix_hits = 0
        self.prefix_misses = 0
        # forward-GRU carry cache for chunked prefills: prompt-prefix (at
        # chunk boundaries, fully inside the true length) -> carried fw
        # state + per-chunk activations; a new long prompt resumes its fw
        # pass at the longest cached boundary (the bw pass always re-runs —
        # it reads the suffix).  Bounded LRU; device arrays are read-only.
        self._fw_cache: "OrderedDict[tuple, Dict[str, Any]]" = OrderedDict()
        self._fw_cache_cap = 8

        # -- speculative decoding (serving_spec_decode) --------------------
        self.spec_decode = bool(
            spec_decode if spec_decode is not None
            else _flags.get_flag("serving_spec_decode")
        )
        self.spec_ngram = max(1, int(
            spec_ngram if spec_ngram is not None
            else _flags.get_flag("serving_spec_ngram")
        ))
        self.spec_proposed = 0
        self.spec_accepted = 0

        # chunked prefill: validate the chunk bound against the block size
        # and the ladder (every taller rung must split into whole chunks),
        # then extract the encoder weight bundle — an unmatched topology
        # fails HERE, not mid-request
        pc = (
            prefill_chunk_tokens if prefill_chunk_tokens is not None
            else _flags.get_flag("serving_prefill_chunk_tokens")
        )
        self.prefill_chunk_tokens = max(0, int(pc))
        self._enc_w = None
        if self.prefill_chunk_tokens:
            c = self.prefill_chunk_tokens
            if c % blk != 0:
                raise ValueError(
                    f"serving_prefill_chunk_tokens={c} must be a multiple "
                    f"of serving_block_tokens={blk}"
                )
            bad = [r for r in DEFAULT_LADDER if r > c and r % c != 0]
            if bad:
                raise ValueError(
                    f"serving_prefill_chunk_tokens={c} must divide every "
                    f"taller shape-ladder rung; {bad} are not multiples"
                )
            self._enc_w = self._extract_encoder_weights()

        # compile accounting: prefill batches observe the same shape-cache
        # contract training feeds use; decode keys are (slot-rung,
        # page-rung) pairs counted through the same StatSet surface
        self.prefill_shapes = CompileShapeCache("serving_prefill", self._stats)
        self.trace_counts = {
            "prefill": 0, "decode": 0, "prefill_chunk": 0, "verify": 0,
            "beam": 0,
        }
        self._prefill_jit = self._make_prefill()
        self._decode_table: Dict[Tuple[int, int], Any] = {}
        self._verify_table: Dict[Tuple[int, int], Any] = {}
        self._beam_table: Dict[Tuple[int, int, int], Any] = {}
        self._ref_table: Dict[tuple, Any] = {}
        self._chunk_jits: Optional[Dict[str, Any]] = (
            self._make_chunk_programs() if self.prefill_chunk_tokens else None
        )

    # ------------------------------------------------------------------
    @property
    def n_live(self) -> int:
        return len(self._slots)

    @property
    def n_prefilling(self) -> int:
        """Slots held by chunked prefills still scanning their prompt."""
        return len(self._prefilling)

    @property
    def n_free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def pages(self) -> BlockPagedCache:
        return self._pages

    def max_src_tokens(self) -> int:
        """Longest admissible source: its pages must fit the whole pool."""
        return self._pages.n_blocks * self.block_tokens

    def outstanding_requests(self) -> List:
        """Every request holding a slot (live decode or chunked prefill)."""
        return (
            [s.request for s in self._slots.values()]
            + [p.request for p in self._prefilling.values()]
        )

    # -- cancellation ----------------------------------------------------
    def cancel(self, request) -> bool:
        """Release ``request``'s slot and pages WITHOUT finishing it (the
        scheduler's timeout/deadline path): decoding for a client that
        gave up is the orphaned-slot leak this closes.  True when the
        request held a slot here."""
        for sid, s in self._slots.items():
            if s.request is request:
                self._slots.pop(sid)
                self._release_slot_pages(s)
                self._free_slots.append(sid)
                self._stats.incr("serving/canceled")
                return True
        for sid, p in self._prefilling.items():
            if p.request is request:
                # mid-chunked-prefill pages are only PARTIALLY written —
                # never cacheable, straight back to the free list
                self._prefilling.pop(sid)
                self._pages.free(p.pages)
                self._free_slots.append(sid)
                self._stats.incr("serving/canceled")
                return True
        return False

    def cancel_by_id(self, req_id: str):
        """Cancel by ``req_id``; returns the released request, or None."""
        for s in list(self._slots.values()):
            if s.request.req_id == req_id:
                self.cancel(s.request)
                return s.request
        for p in list(self._prefilling.values()):
            if p.request.req_id == req_id:
                self.cancel(p.request)
                return p.request
        return None

    # -- copy-on-write prefix cache ---------------------------------------
    def _prefix_key(self, tokens) -> tuple:
        """Cache key of a full prompt: the per-block hash chain seeded by
        the engine signature.  Chaining block-tuple hashes (not one flat
        hash) is what lets the same arithmetic address block-aligned
        prefixes, and the signature seed is the ISSUE's aliasing guard —
        a different topology fingerprint, feed dtype or tokenizer
        (source slot/vocab, special ids) can never produce this key."""
        h = self._cache_sig_hash
        toks = [int(t) for t in tokens]
        for i in range(0, len(toks), self.block_tokens):
            h = hash((h, tuple(toks[i:i + self.block_tokens])))
        return (h, len(toks))

    def _prefix_lookup(self, src):
        """(key, entry) when the FULL prompt is cached, else None.  The
        stored exact token tuple is compared on every hit, so a hash
        collision degrades to a miss — aliasing is structurally off."""
        key = self._prefix_key(src)
        ent = self._prefix_cache.get(key)
        if ent is None or ent["tokens"] != tuple(int(t) for t in src):
            return None
        return key, ent

    def _release_slot_pages(self, s: _Slot) -> None:
        """Retire/cancel/preempt all funnel here — THE prefix-cache
        insertion point.  A slot whose pages ARE the cache entry releases
        with retain (the entry's blocks park refcount-0 in the warm LRU
        pool); a slot with a captured boot state and no entry yet INSERTS
        one (its fully-written pages become the shared copy); anything
        else — cache off, resumed prefill (no clean boot), a COW'd or
        duplicate-prompt slot — frees normally."""
        if not self.prefix_cache_enabled:
            self._pages.free(s.pages)
            return
        key = self._prefix_key(s.request.src_ids)
        ent = self._prefix_cache.get(key)
        if ent is not None and list(ent["pages"]) == list(s.pages):
            self._pages.release(s.pages, retain=True)
            return
        if ent is None and s.boot_h is not None:
            self._prefix_cache[key] = {
                "tokens": tuple(int(t) for t in s.request.src_ids),
                "pages": list(s.pages),
                "boot_h": s.boot_h,
                "enc_tokens": s.enc_tokens,
            }
            for p in s.pages:
                self._prefix_owner[p] = key
            self._pages.release(s.pages, retain=True)
            self._stats.incr("serving/prefix_inserted")
            return
        self._pages.free(s.pages)

    def _on_block_evicted(self, block: int) -> None:
        """Allocator reclaimed a retained block (LRU, under HBM pressure):
        the entry owning it just lost bytes — drop the WHOLE entry so a
        later hit can never map a half-dead prefix.  Its surviving blocks
        stay in the retained pool as plain reclaimable capacity."""
        key = self._prefix_owner.pop(block, None)
        if key is None:
            return
        ent = self._prefix_cache.pop(key, None)
        if ent is not None:
            for p in ent["pages"]:
                self._prefix_owner.pop(p, None)
            self._stats.incr("serving/prefix_evicted")

    def ensure_private_pages(self, s: _Slot) -> bool:
        """The copy-on-write barrier: called before ANY write into a
        slot's encoder pages, it swaps every block the slot shares with
        another reader (refcount >= 2) for a fresh private copy — pool
        rows copied FIRST, page table remapped after — so a write can
        never mutate bytes another sequence is attending over.  False =
        no blocks for the copies (caller must wait; state untouched).

        The shipped decode/verify/beam programs write only the slot plane
        (``_h``) and READ the encoder pools, so shared pages are safe
        during decode by construction; this barrier is the mandatory
        gate for any pool-writing path (and what the COW-safety tests
        pin)."""
        if all(self._pages.refcount(p) == 1 for p in s.pages):
            return True
        new_pages, copies = self._pages.cow(s.pages)
        if new_pages is None:
            return False
        src = jnp.asarray([a for a, _ in copies], jnp.int32)
        dst = jnp.asarray([b for _, b in copies], jnp.int32)
        self._enc_pool = self._enc_pool.at[dst].set(self._enc_pool[src])
        self._ep_pool = self._ep_pool.at[dst].set(self._ep_pool[src])
        s.pages = new_pages
        self._stats.incr("serving/cow_copies", len(copies))
        return True

    @property
    def prefix_cache_len(self) -> int:
        return len(self._prefix_cache)

    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the target model confirmed (0.0
        before any speculative dispatch ran)."""
        if not self.spec_proposed:
            return 0.0
        return self.spec_accepted / float(self.spec_proposed)

    # -- chunked-prefill weight extraction --------------------------------
    def _extract_encoder_weights(self):
        """Weight bundle + activation names of the bi-GRU encoder idiom
        (embedding -> per-direction gate fc -> gru / reversed gru ->
        concat -> identity projection fc; boot = fc over first_seq(enc)):
        the chunk programs re-run exactly this chain with carried state.
        A topology outside the idiom raises — chunked prefill has no
        interpreted fallback, matching the decode-side contract."""
        topo = self._gen._enc_net.topology
        gp_sub = self._gp

        def conf(name):
            return topo.layers[name]

        enc_c = conf(self._enc_layer)
        if enc_c.type != "concat" or len(enc_c.inputs) != 2:
            raise ValueError(
                "chunked prefill requires enc = concat(fwd GRU, bwd GRU); "
                f"got {enc_c.type} over {enc_c.inputs}"
            )
        dirs = {}
        emb_name = None
        for gname in enc_c.inputs:
            g = conf(gname)
            if g.type != "gru":
                raise ValueError(
                    f"chunked prefill: encoder branch {gname} is {g.type}, "
                    "expected a fused grumemory"
                )
            t = conf(g.inputs[0])
            if t.type != "fc" or len(t.inputs) != 1:
                raise ValueError(
                    f"chunked prefill: gate projection {g.inputs[0]} must "
                    "be a single-input fc"
                )
            e = conf(t.inputs[0])
            if e.type != "embedding":
                raise ValueError(
                    f"chunked prefill: encoder input {t.inputs[0]} must be "
                    "an embedding"
                )
            if emb_name is None:
                emb_name = e.name
            elif emb_name != e.name:
                raise ValueError(
                    "chunked prefill: both GRU directions must share one "
                    "source embedding"
                )
            key = "bw" if g.attr("reverse", False) else "fw"
            if key in dirs:
                raise ValueError(
                    "chunked prefill: expected one forward and one "
                    "reversed GRU direction"
                )
            dirs[key] = (gname, t.name, g)
        if set(dirs) != {"fw", "bw"}:
            raise ValueError(
                "chunked prefill: encoder must pair a forward and a "
                "reversed GRU"
            )
        ep_c = conf(self._ep_layer)
        if (ep_c.type != "fc" or ep_c.inputs != (enc_c.name,)
                or ep_c.act not in ("identity", "linear", "")):
            raise ValueError(
                "chunked prefill: encoded projection must be an identity "
                f"fc over {enc_c.name}"
            )
        boot_names = [
            n for n in topo.output_names
            if n not in (self._enc_layer, self._ep_layer)
        ]
        if len(boot_names) != 1:
            raise ValueError(
                f"chunked prefill: expected one boot output, got {boot_names}"
            )
        boot_c = conf(boot_names[0])
        first_c = conf(boot_c.inputs[0]) if boot_c.inputs else None
        if (boot_c.type != "fc" or first_c is None
                or first_c.type != "seqlastins"
                or not first_c.attr("select_first", False)
                or first_c.inputs != (enc_c.name,)):
            raise ValueError(
                "chunked prefill: decoder boot must be fc(first_seq(enc))"
            )

        net = self._gen._enc_net
        lp = lambda n: net.layer_params(gp_sub, n)
        out = {"emb_w": lp(emb_name)["w"]}
        for key in ("fw", "bw"):
            gname, tname, g = dirs[key]
            tp, gpr = lp(tname), lp(gname)
            out[f"{key}_gates_w"] = tp["w0"]
            out[f"{key}_gates_b"] = tp.get("b")
            out[f"{key}_w_h"] = gpr["w_h"]
            out[f"{key}_w_c"] = gpr["w_c"]
            out[f"{key}_b"] = gpr.get("b")
        pp, bp = lp(ep_c.name), lp(boot_c.name)
        out["proj_w"] = pp["w0"]
        out["proj_b"] = pp.get("b")
        out["boot_w"] = bp["w0"]
        out["boot_b"] = bp.get("b")
        gf, gb = dirs["fw"][2], dirs["bw"][2]
        self._enc_acts = {
            "fw": (gf.attr("gate_act", "sigmoid"),
                   gf.attr("active_type", gf.act or "tanh")),
            "bw": (gb.attr("gate_act", "sigmoid"),
                   gb.attr("active_type", gb.act or "tanh")),
            "boot": boot_c.act or "identity",
        }
        return out

    def _make_chunk_programs(self):
        """The four fixed-shape chunk jits.  Scan splitting preserves
        bit-identity: each chunk executes the identical per-step ops the
        unsplit encoder scan would, from the carried state."""
        from paddle_tpu.layers.base import take_rows_or_zero
        from paddle_tpu.ops.activations import get_activation
        from paddle_tpu.ops.rnn import gru_scan

        acts = self._enc_acts
        blk = self.block_tokens
        c_tokens = self.prefill_chunk_tokens

        def chunk_dir(key, reverse):
            gate_act, act = acts[key]

            def run(w, ids, lk, h):
                self.trace_counts["prefill_chunk"] += 1
                emb = take_rows_or_zero(w["emb_w"], ids)
                gates = acc_matmul(emb, w[f"{key}_gates_w"])
                if w[f"{key}_gates_b"] is not None:
                    gates = gates + w[f"{key}_gates_b"]
                return gru_scan(
                    gates, w[f"{key}_w_h"], w[f"{key}_w_c"], w[f"{key}_b"],
                    lk, gate_act=gate_act, act=act, reverse=reverse, h0=h,
                )

            return jax.jit(run)

        def scatter(enc_pool, ep_pool, fw_hs, bw_hs, rows, w, sp_b):
            self.trace_counts["prefill_chunk"] += 1
            enc = jnp.concatenate([fw_hs, bw_hs], axis=-1)  # [1, C, 2H]
            ep = acc_matmul(enc, w["proj_w"])
            if w["proj_b"] is not None:
                ep = ep + w["proj_b"]
            if sp_b is not None:
                ep = ep + sp_b  # score-key bias folds in at prefill time
            nb = c_tokens // blk
            enc_pool = enc_pool.at[rows].set(
                enc.reshape(nb, blk, enc.shape[-1])
            )
            ep_pool = ep_pool.at[rows].set(ep.reshape(nb, blk, ep.shape[-1]))
            return enc_pool, ep_pool

        boot_act = get_activation(acts["boot"])

        def boot_write(h_state, slot_rows, fw0, bw0, boot_mask, h_override,
                       w):
            self.trace_counts["prefill_chunk"] += 1
            enc0 = jnp.concatenate([fw0, bw0], axis=-1)  # [1, 2H]
            boot = acc_matmul(enc0, w["boot_w"])
            if w["boot_b"] is not None:
                boot = boot + w["boot_b"]
            boot = boot_act(boot)
            h_write = jnp.where(boot_mask[:, None], boot, h_override)
            return h_state.at[slot_rows].set(h_write)

        return {
            "fw": chunk_dir("fw", False),
            "bw": chunk_dir("bw", True),
            "scatter": jax.jit(scatter, donate_argnums=(0, 1)),
            "boot": jax.jit(boot_write, donate_argnums=(0,)),
        }

    # -- compiled program builders --------------------------------------
    def _make_prefill(self):
        enc_net = self._gen._enc_net
        enc_l, ep_l = self._enc_layer, self._ep_layer
        blk = self.block_tokens

        def prefill(gp, state, batch, enc_pool, ep_pool, h_state,
                    page_rows, slot_rows, boot_mask, h_override, sp_b):
            self.trace_counts["prefill"] += 1
            outs, _ = enc_net.apply(gp, batch, state=state, train=False)
            enc = outs[enc_l].data  # [b, S, De]
            ep = outs[ep_l].data
            if sp_b is not None:
                ep = ep + sp_b  # score-key bias folds in at prefill time
            boot = outs["dec_boot"].data
            b, s = enc.shape[0], enc.shape[1]
            nb = s // blk
            flat = page_rows.reshape(-1)
            enc_pool = enc_pool.at[flat].set(
                enc.reshape(b * nb, blk, enc.shape[-1])
            )
            ep_pool = ep_pool.at[flat].set(
                ep.reshape(b * nb, blk, ep.shape[-1])
            )
            # resumed slots keep their saved GRU state instead of the boot
            h_write = jnp.where(boot_mask[:, None], boot, h_override)
            h_state = h_state.at[slot_rows].set(h_write)
            return enc_pool, ep_pool, h_state

        return jax.jit(prefill, donate_argnums=(3, 4, 5))

    def _make_decode(self, b_rung: int, p_rung: int):
        blk = self.block_tokens
        eos = self._gen.eos_id
        acts = self._acts
        w_meta = self._w_meta

        k_steps = self.block_steps

        def decode(h_state, enc_pool, ep_pool, slot_idx, tables, enc_len,
                   ids, live, w):
            self.trace_counts["decode"] += 1
            if w_meta:
                # int8-resident weights: one in-graph dequantize per
                # dispatch (amortized over K tokens x B slots); XLA keeps
                # the f32 materialization in the dispatch working set
                from paddle_tpu.ops import quantize as _bsq

                w = _bsq.dequantize_weight_bundle(w, w_meta)
            h = h_state[slot_idx]  # [B, H]
            enc = enc_pool[tables].reshape(b_rung, p_rung * blk, -1)
            ep = ep_pool[tables].reshape(b_rung, p_rung * blk, -1)
            emask = (
                jnp.arange(p_rung * blk, dtype=jnp.int32)[None, :]
                < enc_len[:, None]
            )

            def inner(carry, _):
                h_p, ids_p, fin = carry
                xg = jnp.take(w["emb_w"], ids_p, axis=0) @ w["w_emb"]
                if w["xg_bias"] is not None:
                    xg = xg + w["xg_bias"]
                h_t = attention_gru_step(
                    xg, h_p, enc, ep, emask, w["w1"], w["v"], w["w_ctx"],
                    w["w_c"], **acts,
                )
                logits = h_t @ w["head_w"]
                if w["head_b"] is not None:
                    logits = logits + w["head_b"]
                # the exact ops/beam greedy chain, for bit-identity
                _, nxt = greedy_token_chain(logits)
                # dead lanes and finished rows only re-emit EOS, and a
                # finished row's state freezes — the host reads tokens up
                # to the FIRST eos, so every visible token rode the exact
                # one-shot chain
                dead = fin | ~live
                nxt = jnp.where(dead, eos, nxt)
                h_n = jnp.where(dead[:, None], h_p, h_t)
                return (h_n, nxt, fin | (nxt == eos)), nxt

            fin0 = jnp.zeros(ids.shape, bool)
            (h_f, _, _), toks = jax.lax.scan(
                inner, (h, ids, fin0), None, length=k_steps
            )
            h_state = h_state.at[slot_idx].set(h_f)
            return h_state, jnp.swapaxes(toks, 0, 1)  # [B, K]

        return jax.jit(decode, donate_argnums=(0,))

    def _make_verify(self, b_rung: int, p_rung: int):
        """The speculative verify-K program — the SAME compiled shape
        family as :meth:`_make_decode` (one (slot-rung, page-rung) jit,
        K = ``serving_decode_block_steps`` inner steps per dispatch), but
        the K step inputs are the DRAFT tokens instead of each step's own
        argmax, so position j's input no longer waits on position j-1's
        output: the embedding+projection half of the chain hoists into ONE
        batched [B, K] GEMM before the scan — the sequential-GRU flops a
        draft actually buys back.

        Emission contract (what keeps the fallback bit-identical): the
        draft is a HYPOTHESIS that these are the greedy tokens.  With
        ``m`` = leading positions where the target's own argmax agreed
        with the draft, steps 0..m all consumed correct context, so the
        first m tokens (== the draft's) AND the target's own token at
        position m are exactly the greedy chain — ``n_emit = min(m+1, K)``
        tokens land per row, and the host consumes EXACTLY that many
        (positions past n_emit rode misdrafted context and are garbage by
        contract, never EOS-clamped into looking final).  Full agreement
        emits all K; total disagreement emits 1 — plain greedy pace, same
        tokens, never slower in tokens-per-dispatch."""
        blk = self.block_tokens
        acts = self._acts
        w_meta = self._w_meta
        k_steps = self.block_steps

        def verify(h_state, enc_pool, ep_pool, slot_idx, tables, enc_len,
                   ids, live, draft, w):
            self.trace_counts["verify"] += 1
            if w_meta:
                from paddle_tpu.ops import quantize as _bsq

                w = _bsq.dequantize_weight_bundle(w, w_meta)
            h = h_state[slot_idx]  # [B, H]
            enc = enc_pool[tables].reshape(b_rung, p_rung * blk, -1)
            ep = ep_pool[tables].reshape(b_rung, p_rung * blk, -1)
            emask = (
                jnp.arange(p_rung * blk, dtype=jnp.int32)[None, :]
                < enc_len[:, None]
            )
            # teacher-forced inputs: step 0 consumes the real last token,
            # step j consumes draft[j-1]; all K embeddings in one GEMM
            inp = jnp.concatenate([ids[:, None], draft[:, :-1]], axis=1)
            xg_all = jnp.take(w["emb_w"], inp, axis=0) @ w["w_emb"]
            if w["xg_bias"] is not None:
                xg_all = xg_all + w["xg_bias"]

            def inner(h_p, xg):
                h_t = attention_gru_step(
                    xg, h_p, enc, ep, emask, w["w1"], w["v"], w["w_ctx"],
                    w["w_c"], **acts,
                )
                logits = h_t @ w["head_w"]
                if w["head_b"] is not None:
                    logits = logits + w["head_b"]
                _, nxt = greedy_token_chain(logits)
                return h_t, (nxt, h_t)

            _, (toks, hs) = jax.lax.scan(
                inner, h, jnp.swapaxes(xg_all, 0, 1)
            )
            toks = jnp.swapaxes(toks, 0, 1)      # [B, K]
            hs = jnp.swapaxes(hs, 0, 1)          # [B, K, H]
            match = jnp.cumprod(
                (toks == draft).astype(jnp.int32), axis=1
            )
            m_full = jnp.sum(match, axis=1)      # leading agreement count
            n_emit = jnp.minimum(m_full + 1, k_steps)
            # h after step n_emit-1 consumed only verified context — the
            # exact greedy state after the emitted tokens; dead lanes
            # freeze (and stamp n_emit 0 so the host skips them)
            h_sel = hs[jnp.arange(b_rung), n_emit - 1]
            h_new = jnp.where(live[:, None], h_sel, h)
            n_emit = jnp.where(live, n_emit, 0)
            h_state = h_state.at[slot_idx].set(h_new)
            return h_state, toks, n_emit, m_full

        return jax.jit(verify, donate_argnums=(0,))

    def _make_beam(self, p_rung: int, beam_k: int, max_new: int):
        """The paged whole-sequence beam program: gathers one request's
        encoder memory through its page-table row (exactly like decode —
        page-table contents, never shapes), expands it to the K beam
        rows, and runs ops/beam.beam_search over the SAME fused step the
        one-shot ``Seq2SeqGenerator.generate`` uses
        (models/seq2seq.make_fused_step — one closure, one chain), from
        the slot's booted decoder state.  The pool's score keys already
        carry the folded sp_b, so the per-step math is identical to the
        one-shot path's statics.  One compiled variant per (page-rung,
        beam-width, max-len)."""
        from paddle_tpu.models.seq2seq import make_fused_step
        from paddle_tpu.ops.beam import beam_search

        blk = self.block_tokens
        acts = self._acts
        w_meta = self._w_meta
        gen = self._gen

        def beam(h_state, enc_pool, ep_pool, sid, table, enc_len, w):
            self.trace_counts["beam"] += 1
            if w_meta:
                from paddle_tpu.ops import quantize as _bsq

                w = _bsq.dequantize_weight_bundle(w, w_meta)
            enc = enc_pool[table].reshape(1, p_rung * blk, -1)
            ep = ep_pool[table].reshape(1, p_rung * blk, -1)
            emask = (
                jnp.arange(p_rung * blk, dtype=jnp.int32)[None, :]
                < enc_len[:, None]
            )
            fused = make_fused_step(
                w,
                jnp.repeat(enc, beam_k, axis=0),
                jnp.repeat(ep, beam_k, axis=0),
                jnp.repeat(emask, beam_k, axis=0),
                gate_act=acts["gate_act"], act=acts["act"],
                att_act=acts["att_act"],
            )

            def step_fn(step_ids, carry):
                logp, h_t = fused(step_ids, carry["h"])
                return logp, {"h": h_t}

            return beam_search(
                step_fn,
                {"h": h_state[sid]},  # [1, H]; beam_search repeats to K
                batch_size=1,
                beam_size=beam_k,
                vocab_size=self.trg_vocab,
                bos_id=gen.bos_id,
                eos_id=gen.eos_id,
                max_len=max_new,
                candidate_adjust_fn=gen.candidate_adjust_fn,
                drop_fn=gen.drop_fn,
                norm_fn=gen.norm_fn,
            )

        return jax.jit(beam)

    def _decode_exe(self, b_rung: int, p_rung: int):
        key = (b_rung, p_rung)
        exe = self._decode_table.get(key)
        if exe is None:
            self._stats.incr("serving_decode/compile_miss")
            exe = self._make_decode(b_rung, p_rung)
            self._decode_table[key] = exe
        else:
            self._stats.incr("serving_decode/compile_hit")
        return exe

    def _verify_exe(self, b_rung: int, p_rung: int):
        key = (b_rung, p_rung)
        exe = self._verify_table.get(key)
        if exe is None:
            self._stats.incr("serving_verify/compile_miss")
            exe = self._make_verify(b_rung, p_rung)
            self._verify_table[key] = exe
        else:
            self._stats.incr("serving_verify/compile_hit")
        return exe

    # -- admission -------------------------------------------------------
    def _chunked_extent(self, src_len: int) -> Optional[int]:
        """Padded extent when ``src_len`` takes the chunked-prefill path
        (its rung exceeds the chunk bound), else None (one-shot batch
        prefill — short prompts keep the fused group dispatch)."""
        if not self.prefill_chunk_tokens:
            return None
        s_pad = ladder_len(src_len, DEFAULT_LADDER)
        return s_pad if s_pad > self.prefill_chunk_tokens else None

    def _admit_chunked(self, r, sid: int, pages, s_pad: int) -> None:
        """Register one long prompt for chunk-at-a-time prefill: pad its
        ids through the same feeder contract the batch path uses, lay out
        its page rows over the padded extent (scratch past its real
        pages), and queue it behind any prefill already in flight."""
        batch = self._feeder([(list(r.src_ids),)])
        ids = np.asarray(batch[self.src_slot].data, np.int32)
        if ids.ndim >= 2 and ids.shape[-1] == 1:
            ids = ids[..., 0]
        length = np.asarray(batch[self.src_slot].lengths, np.int32)
        rows = np.full((s_pad // self.block_tokens,), self._pages.scratch,
                       np.int32)
        rows[: len(pages)] = pages
        resume = getattr(r, "_resume", None)
        if resume is not None:
            r._resume = None
        self._prefilling[sid] = _PendingPrefill(
            request=r,
            pages=pages,
            enc_tokens=len(r.src_ids),
            max_new=min(
                r.max_new_tokens or self.default_max_new_tokens,
                self._gen.max_length,
            ),
            admit_seq=self._admit_seq,
            ids=ids,
            length=length,
            rows=rows,
            n_chunks=s_pad // self.prefill_chunk_tokens,
            h0=jnp.zeros(
                (1, self._enc_w["fw_w_h"].shape[0]), self._dtype
            ),
            resume=resume,
        )
        self._admit_seq += 1
        self._stats.incr("serving/chunked_prefills")
        if not self.prefix_cache_enabled:
            return
        # partial-prefix reuse: resume the FORWARD pass at the longest
        # cached chunk boundary fully inside the true prompt (fw carries
        # depend only on the prefix, so they are bit-exact for any
        # continuation; the bw pass reads the suffix and always re-runs)
        p = self._prefilling[sid]
        C = self.prefill_chunk_tokens
        true_len = int(length[0])
        for j in range(p.n_chunks - 1, -1, -1):
            if (j + 1) * C > true_len:
                continue
            key = (self._cache_sig_hash,
                   tuple(int(t) for t in ids[0, :(j + 1) * C]))
            ent = self._fw_cache.get(key)
            if ent is None:
                continue
            p.cursor = j + 1
            p.h = ent["h"]
            for i in range(j + 1):
                p.fw_chunks[i] = ent["chunks"][i]
            self._fw_cache.move_to_end(key)
            self._stats.incr("serving/prefix_fw_reuse", j + 1)
            if p.cursor == p.n_chunks:
                p.phase = "bw"
                p.cursor = p.n_chunks - 1
                p.h = jnp.zeros_like(ent["h"])
            break

    def admit(self, requests: Sequence) -> List:
        """Admit a FIFO prefix of ``requests`` (free slot + pages for each;
        the first misfit stops admission — strict FCFS, no starvation):
        short prompts prefill as ONE bucketed batch; prompts past the
        chunked-prefill bound register for chunk-at-a-time prefill
        instead.  Returns the admitted list, submission order."""
        group = []  # (slot_id, request, pages)
        admitted = []
        for r in requests:
            if not self._free_slots:
                break
            src = r.src_ids
            beam_k = int(getattr(r, "beam_size", None) or 0)
            if beam_k <= 1:
                beam_k = 0  # beam of one IS greedy — the cheaper loop
            max_new = min(
                r.max_new_tokens or self.default_max_new_tokens,
                self._gen.max_length,
            )
            resume = getattr(r, "_resume", None)
            hit = (
                self._prefix_lookup(src)
                if self.prefix_cache_enabled else None
            )
            if hit is not None:
                # prefill-once: the cached blocks map straight into this
                # request's page table (refcount +1, zero new blocks, ZERO
                # prefill dispatches) and the decoder boots from the
                # entry's captured state — bit-identical because the
                # entry's pages hold exactly what prefilling this prompt
                # would write (same tokens, same engine signature)
                key, ent = hit
                self._pages.share(ent["pages"])
                sid = self._free_slots.pop()
                admitted.append(r)
                self.prefix_hits += 1
                self._stats.incr("serving/prefix_hits")
                if resume is not None:
                    h_row = jnp.asarray(resume["h"], self._dtype)
                    r._resume = None
                else:
                    h_row = jnp.asarray(ent["boot_h"], self._dtype)
                self._h = self._h.at[sid].set(h_row)
                self._slots[sid] = _Slot(
                    request=r,
                    pages=list(ent["pages"]),
                    enc_tokens=ent["enc_tokens"],
                    last_id=(
                        resume["last_id"] if resume is not None
                        else self._gen.bos_id
                    ),
                    tokens=(
                        list(resume["tokens"]) if resume is not None else []
                    ),
                    max_new=max_new,
                    admit_seq=self._admit_seq,
                    beam=beam_k,
                    boot_h=None,
                )
                self._admit_seq += 1
                r.t_admit = self._clock()
                continue
            pages = self._pages.alloc(self._pages.pages_for_tokens(len(src)))
            if pages is None:
                break
            if self.prefix_cache_enabled:
                self.prefix_misses += 1
                self._stats.incr("serving/prefix_misses")
            sid = self._free_slots.pop()
            admitted.append(r)
            chunk_extent = self._chunked_extent(len(src))
            if chunk_extent is not None:
                self._admit_chunked(r, sid, pages, chunk_extent)
                r.t_admit = self._clock()
                continue
            slot = _Slot(
                request=r,
                pages=pages,
                enc_tokens=len(src),
                last_id=(
                    resume["last_id"] if resume is not None
                    else self._gen.bos_id
                ),
                tokens=list(resume["tokens"]) if resume is not None else [],
                max_new=max_new,
                admit_seq=self._admit_seq,
                beam=beam_k,
            )
            self._admit_seq += 1
            self._slots[sid] = slot
            group.append((sid, r, pages))
        if admitted:
            self._stats.incr("serving/admitted", len(admitted))
        if not group:
            return admitted

        batch = self._feeder([(list(r.src_ids),) for _, r, _ in group])
        b_rung = ladder_len(len(group), DEFAULT_BATCH_LADDER)
        batch = pad_batch_rows(batch, b_rung)
        s_pad = batch[self.src_slot].data.shape[1]
        nb = s_pad // self.block_tokens
        scratch = self._pages.scratch
        page_rows = np.full((b_rung, nb), scratch, np.int32)
        slot_rows = np.full((b_rung,), self._scratch_slot, np.int32)
        boot_mask = np.zeros((b_rung,), bool)
        h_override = np.zeros((b_rung, self.hidden_dim), self._dtype)
        for k, (sid, r, pages) in enumerate(group):
            page_rows[k, : len(pages)] = pages
            slot_rows[k] = sid
            resume = getattr(r, "_resume", None)
            if resume is None:
                boot_mask[k] = True
            else:
                h_override[k] = resume["h"]
                r._resume = None
        self.prefill_shapes.observe(batch)
        with _obs.span(
            "prefill", cat="serving", n=len(group), src_pad=int(s_pad),
            reqs=[r.req_id for _, r, _ in group],
        ):
            self._enc_pool, self._ep_pool, self._h = self._prefill_jit(
                self._gp, self._state, batch, self._enc_pool, self._ep_pool,
                self._h, page_rows, slot_rows, boot_mask, h_override,
                self._w["sp_b"],
            )
        if self.prefix_cache_enabled:
            # capture each cleanly-booted slot's decoder boot state (tiny
            # [H] row) — at retire its fully-written pages + this state
            # become the prefix-cache entry; resumed slots carry a mid-
            # decode h, not the boot, so they never seed an entry
            h_host = np.asarray(self._h)
            for k, (sid, _, _) in enumerate(group):
                if boot_mask[k]:
                    self._slots[sid].boot_h = h_host[sid].copy()
        now = self._clock()
        for _, r, _ in group:
            r.t_admit = now
        return admitted

    # -- chunked prefill advance ------------------------------------------
    def _advance_prefill(self) -> None:
        """Run ONE chunk dispatch of the oldest pending chunked prefill:
        the forward pass ascends the chunks carrying fwd GRU state; the
        backward pass descends carrying bwd state, scattering each
        completed span's pages as it goes; the final (leftmost) backward
        chunk writes the decoder boot state and the slot goes live."""
        sid, p = next(iter(self._prefilling.items()))
        jits = self._chunk_jits
        w = self._enc_w
        C = self.prefill_chunk_tokens
        k = p.cursor
        _obs.instant(
            "prefill_chunk", cat="serving", req=p.request.req_id,
            phase=p.phase, chunk=k, n_chunks=p.n_chunks,
        )
        ids = jnp.asarray(p.ids[:, k * C:(k + 1) * C])
        lk = jnp.asarray(np.clip(p.length - k * C, 0, C).astype(np.int32))
        if p.phase == "fw":
            hs, h = jits["fw"](w, ids, lk, p.h)
            p.fw_chunks[k] = hs
            p.h = h
            if (self.prefix_cache_enabled
                    and (k + 1) * C <= int(p.length[0])):
                # chunk fully inside the true length: its activations and
                # the carried state are prefix-determined — cacheable for
                # any future prompt sharing this chunk-aligned prefix
                key = (self._cache_sig_hash,
                       tuple(int(t) for t in p.ids[0, :(k + 1) * C]))
                self._fw_cache.pop(key, None)
                self._fw_cache[key] = {
                    "h": h, "chunks": list(p.fw_chunks[:k + 1]),
                }
                while len(self._fw_cache) > self._fw_cache_cap:
                    self._fw_cache.popitem(last=False)
            p.cursor += 1
            if p.cursor == p.n_chunks:
                p.phase = "bw"
                p.cursor = p.n_chunks - 1
                p.h = jnp.zeros_like(h)
            return
        hs, h = jits["bw"](w, ids, lk, p.h)
        nb = C // self.block_tokens
        rows = jnp.asarray(p.rows[k * nb:(k + 1) * nb])
        self._enc_pool, self._ep_pool = jits["scatter"](
            self._enc_pool, self._ep_pool, p.fw_chunks[k], hs, rows, w,
            self._w["sp_b"],
        )
        if k > 0:
            p.h = h
            p.cursor -= 1
            return
        # leftmost span scattered: write the boot state (or the saved GRU
        # state of a resumed preemption victim) and promote to decode
        boot_mask = np.asarray([p.resume is None])
        h_override = np.zeros((1, self.hidden_dim), self._dtype)
        if p.resume is not None:
            h_override[0] = p.resume["h"]
        self._h = jits["boot"](
            self._h, np.asarray([sid], np.int32), p.fw_chunks[0][:, 0],
            hs[:, 0], jnp.asarray(boot_mask), jnp.asarray(h_override), w,
        )
        self._prefilling.pop(sid)
        boot_h = None
        if self.prefix_cache_enabled and p.resume is None:
            boot_h = np.asarray(self._h[sid]).copy()
        beam_k = int(getattr(p.request, "beam_size", None) or 0)
        if beam_k <= 1:
            beam_k = 0
        self._slots[sid] = _Slot(
            request=p.request,
            pages=p.pages,
            enc_tokens=p.enc_tokens,
            last_id=(
                p.resume["last_id"] if p.resume is not None
                else self._gen.bos_id
            ),
            tokens=list(p.resume["tokens"]) if p.resume is not None else [],
            max_new=p.max_new,
            admit_seq=p.admit_seq,
            beam=beam_k,
            boot_h=boot_h,
        )

    # -- decode ----------------------------------------------------------
    def step(self) -> List:
        """Advance one chunked-prefill dispatch (if any long prompt is mid-
        prefill — the decode interleave that bounds its head-of-line
        stall), then one decode step for every live slot; returns the
        requests that finished this step (EOS emitted or
        ``max_new_tokens`` reached), their pages freed and slots
        recycled."""
        if self._prefilling:
            self._advance_prefill()
        if not self._slots:
            return []
        finished = []
        # beam slots: each one whole-sequence paged beam dispatch, retired
        # immediately (beam requests deliver a complete best hypothesis,
        # not a token stream)
        for sid in sorted(self._slots):
            if self._slots[sid].beam:
                finished.append(self._finish_beam(sid))
        live_ids = sorted(self._slots)
        if not live_ids:
            if finished:
                self._stats.incr("serving/decode_steps")
            return finished
        b_rung = ladder_len(len(live_ids), DEFAULT_BATCH_LADDER)
        max_pages = max(len(self._slots[s].pages) for s in live_ids)
        p_rung = ladder_len(max_pages, self._page_ladder)
        scratch = self._pages.scratch
        slot_idx = np.full((b_rung,), self._scratch_slot, np.int32)
        tables = np.full((b_rung, p_rung), scratch, np.int32)
        enc_len = np.zeros((b_rung,), np.int32)
        ids = np.full((b_rung,), self._gen.eos_id, np.int32)
        live = np.zeros((b_rung,), bool)
        for k, sid in enumerate(live_ids):
            s = self._slots[sid]
            slot_idx[k] = sid
            tables[k, : len(s.pages)] = s.pages
            enc_len[k] = s.enc_tokens
            ids[k] = s.last_id
            live[k] = True
        k_steps = self.block_steps
        if self.spec_decode:
            draft = np.full((b_rung, k_steps), self._gen.eos_id, np.int32)
            for k, sid in enumerate(live_ids):
                draft[k] = self._draft_tokens(self._slots[sid], k_steps)
            args = (
                self._h, self._enc_pool, self._ep_pool, slot_idx, tables,
                enc_len, ids, live, draft, self._w_arg,
            )
            exe = self._verify_exe(b_rung, p_rung)
            self._h, toks, n_emit, m_full = exe(*args)
            toks_host = np.asarray(toks)
            n_emit_host = np.asarray(n_emit)
            m_full_host = np.asarray(m_full)
        else:
            args = (
                self._h, self._enc_pool, self._ep_pool, slot_idx, tables,
                enc_len, ids, live, self._w_arg,
            )
            exe = self._decode_exe(b_rung, p_rung)
            self._h, toks = exe(*args)
            toks_host = np.asarray(toks)  # [B,K]: ONE host sync per K tokens
            n_emit_host = None
        now = self._clock()
        for k, sid in enumerate(live_ids):
            s = self._slots[sid]
            r = s.request
            if r.t_first_token is None:
                r.t_first_token = now
            done = False
            # spec mode: consume EXACTLY the verified tokens — positions
            # past n_emit rode misdrafted context and never reach a client
            limit = (
                int(n_emit_host[k]) if n_emit_host is not None
                else toks_host.shape[1]
            )
            for j in range(limit):
                tok = int(toks_host[k, j])
                if tok == self._gen.eos_id:
                    done = True
                    break
                s.tokens.append(tok)
                s.last_id = tok
                r.token_times.append(now)
                if len(s.tokens) >= s.max_new:
                    done = True
                    break
            if n_emit_host is not None:
                self.spec_proposed += k_steps
                self.spec_accepted += int(m_full_host[k])
            if done:
                finished.append(self._retire(sid))
        self._stats.incr("serving/decode_steps")
        return finished

    def _draft_tokens(self, s: _Slot, k: int) -> List[int]:
        """Prompt-lookup n-gram draft (the flagged draft model): match the
        request's trailing ``serving_spec_ngram`` GENERATED tokens against
        its own earlier generation and propose the continuation after the
        most recent match, padding by repetition.  Draws only from target-
        vocab tokens the request itself emitted — no second network, no
        extra weights, and a wrong guess costs nothing: the verify
        dispatch emits the true greedy tokens either way."""
        n = self.spec_ngram
        hist = s.tokens
        out: List[int] = []
        if len(hist) > n:
            key = tuple(hist[-n:])
            for i in range(len(hist) - n - 1, -1, -1):
                if tuple(hist[i:i + n]) == key:
                    out = list(hist[i + n:i + n + k])
                    break
        fill = out[-1] if out else s.last_id
        while len(out) < k:
            out.append(fill)
        return out[:k]

    def _finish_beam(self, sid: int):
        """Run one beam slot to completion: one paged beam dispatch, best
        hypothesis trimmed at EOS onto the request, slot retired."""
        s = self._slots[sid]
        p_rung = ladder_len(len(s.pages), self._page_ladder)
        table = np.full((1, p_rung), self._pages.scratch, np.int32)
        table[0, : len(s.pages)] = s.pages
        key = (p_rung, s.beam, s.max_new)
        exe = self._beam_table.get(key)
        if exe is None:
            self._stats.incr("serving_beam/compile_miss")
            exe = self._make_beam(p_rung, s.beam, s.max_new)
            self._beam_table[key] = exe
        else:
            self._stats.incr("serving_beam/compile_hit")
        with _obs.span(
            "beam", cat="serving", req=s.request.req_id, beam=s.beam,
        ):
            seqs, scores = exe(
                self._h, self._enc_pool, self._ep_pool,
                np.asarray([sid], np.int32), table,
                np.asarray([s.enc_tokens], np.int32), self._w_arg,
            )
        best = np.asarray(seqs)[0, 0]
        toks: List[int] = []
        for t in best:
            t = int(t)
            if t == self._gen.eos_id:
                break
            toks.append(t)
        s.tokens = toks[: s.max_new]
        now = self._clock()
        r = s.request
        if r.t_first_token is None:
            r.t_first_token = now
        r.token_times.extend([now] * len(s.tokens))
        r.beam_score = float(np.asarray(scores)[0, 0])
        self._stats.incr("serving/beam_requests")
        return self._retire(sid)

    def _retire(self, sid: int):
        s = self._slots.pop(sid)
        self._release_slot_pages(s)
        self._free_slots.append(sid)
        s.request.tokens = s.tokens
        self._stats.incr("serving/completed")
        return s.request

    # -- eviction / preemption -------------------------------------------
    def preempt(self):
        """Evict the NEWEST-admitted live sequence (least progress lost):
        free its pages, save its tiny GRU state + generated prefix on the
        request, and hand it back for re-queueing.  Re-admission re-runs
        prefill (the paged encoder state recomputes deterministically) and
        restores the saved state, so the final tokens stay bit-identical
        to an uninterrupted decode.  Returns the request, or None when
        nothing is live."""
        if not self._slots:
            return None
        sid = max(self._slots, key=lambda s: self._slots[s].admit_seq)
        s = self._slots.pop(sid)
        self._release_slot_pages(s)
        self._free_slots.append(sid)
        s.request._resume = {
            "h": np.asarray(self._h[sid]),
            "last_id": s.last_id,
            "tokens": list(s.tokens),
        }
        self._stats.incr("serving/preempted")
        return s.request

    # -- the one-shot reference path --------------------------------------
    def reference_decode(self, src_ids, max_new_tokens: Optional[int] = None
                         ) -> List[int]:
        """The UNBATCHED one-shot ``Seq2SeqGenerator.generate_greedy`` path
        for one request, through the same bucketed feeder and jitted per
        source rung (the one-shot serving baseline done right, weights as
        arguments per T102) — the bench's one-shot arm AND the golden the
        serving output is bit-compared against."""
        mx = (
            max_new_tokens if max_new_tokens is not None
            else self.default_max_new_tokens
        )
        batch = self._feeder([(list(src_ids),)])
        key = (batch_shape_key(batch), mx)
        exe = self._ref_table.get(key)
        if exe is None:
            exe = jax.jit(
                lambda p, bt: self._gen.generate_greedy(
                    bt, params=p, max_new_tokens=mx
                )
            )
            self._ref_table[key] = exe
        toks, lengths = exe(self._gen.params.params, batch)
        n = int(np.asarray(lengths)[0])
        return [int(t) for t in np.asarray(toks)[0, :n]]

    def weight_drift(self) -> float:
        """Bit-drift of the resident quantized bundle vs its f32 source:
        max over quantized keys of ``max|dequant(q) - w| / max|w|`` — the
        explicit budget the serving_int8_drift_budget flag bounds (0.0 on
        the f32 path)."""
        if not self._w_meta:
            return 0.0
        from paddle_tpu.ops import quantize as _bsq

        deq = _bsq.dequantize_weight_bundle(self._w_arg, self._w_meta)
        worst = 0.0
        for k in self._w_meta:
            a = np.asarray(self._w[k], np.float32)
            d = np.asarray(deq[k], np.float32)
            denom = float(np.max(np.abs(a))) or 1.0
            worst = max(worst, float(np.max(np.abs(d - a))) / denom)
        return worst

    def slots_per_gb(self, src_tokens: Optional[int] = None) -> float:
        """Capacity arithmetic the serving bench gates on: concurrent
        decode slots one GB of HBM holds AFTER the resident weight bundle,
        at the per-slot footprint of a ``src_tokens``-token source (default
        one page).  Weight-only int8 shrinks ``weight_bytes`` ~4x, so this
        rises under the same ``serving_hbm_budget_mb``."""
        pages = (
            self._pages.pages_for_tokens(src_tokens)
            if src_tokens is not None else 1
        )
        per_slot = (
            pages * self._pages.bytes_per_block
            + self.hidden_dim * jnp.dtype(self._dtype).itemsize
        )
        free = max((1 << 30) - self.weight_bytes, 0)
        return free / float(per_slot)

    def summary(self) -> Dict[str, Any]:
        return {
            "live": self.n_live,
            "prefilling": self.n_prefilling,
            "free_slots": self.n_free_slots,
            "pages": self._pages.summary(),
            "prefill_shapes": self.prefill_shapes.n_shapes,
            "decode_shapes": len(self._decode_table),
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "trace_counts": dict(self.trace_counts),
            "int8_weights": self.int8_weights,
            "weight_bytes": self.weight_bytes,
            "slots_per_gb": self.slots_per_gb(),
            "prefix_cache": self.prefix_cache_enabled,
            "prefix_entries": self.prefix_cache_len,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "spec_decode": self.spec_decode,
            "spec_accept_rate": self.spec_accept_rate(),
        }
