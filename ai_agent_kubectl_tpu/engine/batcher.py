"""Continuous-batching engine: admit-at-chunk scheduling over fixed slots.

The reference serves one request per event-loop await (app.py:183-186, a
single remote call in flight); BASELINE config 3 requires bs=32 continuous
batching. TPU-first design (SURVEY.md §7 hard part "continuous batching ×
jit"):

- **Fixed-capacity decode batch**: a persistent KV cache of
  ``batch_size`` slots ([L, N, max_seq, KV, hd]) lives in HBM and is
  donated through every step — jit sees one static shape forever, so there
  is exactly one compiled decode program regardless of load.
- **Admit-at-chunk**: decode runs in jitted ``lax.scan`` chunks of
  ``chunk_len`` tokens for all slots at once (one host round trip per
  chunk, not per token). Between chunks the scheduler admits queued
  requests into free slots: prefill into a scratch single-slot cache
  (B=1, reusing the bucketed prefill programs), then a jitted
  ``dynamic_update_slice`` splices the KV into the slot. Admission never
  recompiles anything.
- **Active-slot masking**: free/finished slots keep decoding garbage into
  their own dead cache region (positions are frozen via the ``active``
  mask); their outputs are discarded host-side. Wasted lanes, zero
  synchronization — the standard static-shape trade.
- **Per-slot sampling state**: positions, last token, and temperature are
  device vectors updated by the splice fn; per-slot temperature sampling
  only pays the categorical cost when some slot is non-greedy.

The scheduler runs on one dedicated worker thread; request coroutines talk
to it through a thread-safe admission queue and per-request asyncio queues
(tokens stream back with ``loop.call_soon_threadsafe``).
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import functools
import logging
import math
import queue as _queue
import threading
import time
import zlib
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.families import (attention_counted, attention_words,
                               kernel_heads, long_prompts,
                               resolved_at_start)
from ..models.transformer import (KVCache, forward, serves_grouped,
                                  state_put_row, state_take_row)
from ..obs.ledger import (CLASS_DELIVERED, CLASS_DRAFT_REJECTED,
                          CLASS_HEDGE_LOSER, CLASS_PREEMPTED,
                          CLASS_QUARANTINE_BURN, CLASS_REPLAYED,
                          CLASS_WASTED_MASKED, GoodputLedger)
from ..obs.slo import (SLO_QUEUE_WAIT, SLO_SESSION_TTFT, SLO_TTFT,
                       SloEngine)
from ..obs.steptime import (PHASE_DECODE, PHASE_PREFILL,
                            PHASE_SPEC_VERIFY, StepTimeSentinel,
                            prefill_bucket)
from ..obs.trace import EngineSpans, RequestSpans, Trace, current_trace
from ..ops.quant import (kv_broadcast_rows, kv_set_slots, kv_slot_update,
                         kv_tokens, kv_update_slice)
from ..ops.ragged_attention import lane_heads
from .containment import (CAUSE_SCHEDULER_DEATH, CAUSE_SCHEDULER_ERROR,
                          CAUSE_SLOT_HEALTH, PROBATION_CLEAN_CHUNKS,
                          REASON_HEALTH, REASON_ISOLATED, EngineSupervisor)
from .jax_engine import JaxEngine, kv_bucket_ladder
from .kv_pool import (BlockPool, CacheCounters, HostBlockStore, StateStore,
                      alloc_with_evict, map_prefix, pages_for, release_state,
                      state_cuts, take_snapshot)
from .radix_cache import RadixCache
from .regime import (DENSE, RAGGED, cache_refusal, resolve_attention_regime,
                     stage_window)
from .protocol import (HEALTH_GRAMMAR_DEAD, HEALTH_NONFINITE,
                       HEALTH_TOKEN_RANGE, EngineOverloaded,
                       EngineResult, EngineUnavailable, GenerationTimeout,
                       RequestExport, RequestQuarantined, TenantOverloaded,
                       consume_chunk_row, describe_health, pack_chunk,
                       scan_chunk_row, unpack_chunk)
from .qos import (ANON_TENANT, LANE_BACKGROUND, LANE_BATCH, LANE_INTERACTIVE,
                  LANES, BrownoutController, QoSQueue, SessionBudgets,
                  current_qos, lane_rank)
from .sampling import eos_mask, greedy_tokens, sample_tokens_seeded
from .tokenizer import StreamDecoder

logger = logging.getLogger(__name__)

def grammar_legal_mask(g_ok: jnp.ndarray, gs: jnp.ndarray,
                       tc: jnp.ndarray) -> jnp.ndarray:
    """THE per-slot legality mask ``[N, vocab]``: entry ``v`` of slot
    ``n`` is bit ``c & 31`` of word ``c >> 5``, for the entry's class
    ``c = tc[n, v]``, of the packed class-legality row of the slot's
    state, ``g_ok[gs[n]]`` (``g_ok`` is ``class_ok_bits [P*S, words]``
    uint32, constrain/runtime.py::pack_class_bits; taking the rows is
    a gather of N rows) — equal bit for bit to
    ``take_along_axis(class_ok[gs], tc, 1)``. The word is picked by a
    chain of compare/selects, one per word of the row
    (``ceil(C_max/32)``, 15 for the shipped grammar), so the whole mask
    is element-wise work that fuses with the ``where`` and the argmax
    that consume it: 5 us of a step at 16 x 32,000 on a v5e. The gather
    it replaces compiled to 512,000 one-element slices a step and took
    5.5 ms (ISSUE 28). ``tc >> 5`` and ``tc & 31`` are recomputed each
    step inside the fusion: hoisted with ``tc`` they were two more
    [N, vocab] arrays to read and no faster."""
    with jax.named_scope("grammar_mask"):
        rows = g_ok[gs]
        tc_word, tc_bit = tc >> 5, (tc & 31).astype(jnp.uint32)
        word = jnp.broadcast_to(rows[:, :1], tc.shape)
        for w in range(1, rows.shape[1]):
            word = jnp.where(tc_word == w, rows[:, w:w + 1], word)
        return ((word >> tc_bit) & 1).astype(jnp.bool_)


def make_termination_chunk_fn(forward_step, chunk_len: int, eos_ids,
                              top_k: int, top_p: float,
                              vocab_size: int = 0,
                              health_check: bool = True,
                              finalize=lambda arr: arr,
                              pool_tables: bool = False,
                              grammar: bool = False,
                              grammar_s_max: int = 0,
                              spec_k: int = 0,
                              spec_steps: int = 0,
                              draft_forward_step=None,
                              ragged_w: int = 0,
                              ragged_forward_step=None):
    """Build THE device-termination decode-chunk body: a ``lax.scan`` of
    ``chunk_len`` steps whose carry folds EOS + per-slot token budgets
    into the live mask (finished slots stop sampling, KV writes, and
    position advances mid-chunk) and whose result is the single packed
    ``[tokens, done_mask, live_lengths, health, n_alive]`` buffer
    (protocol.py v2).

    Fault containment (ISSUE 5) lives in the same scan: per-slot health
    detection (``health_check``) folds NaN/Inf logits and out-of-range
    sampled ids into a carried health word and FREEZES a tripped slot
    mid-chunk — corruption stops propagating into that slot's KV before
    the host has even seen the chunk — and sampling runs per-request RNG
    streams (``sample_tokens_seeded`` over the spliced ``seeds`` vector)
    so a reset-and-replay reproduces transcripts bit-identically. The
    ``corrupt`` vector is the fault-injection seam (``decode:nan``):
    all-False in normal serving, it NaNs a slot's step logits so drills
    exercise the real detection path, not a shortcut.

    ``forward_step(params, tok, pos, cache, live)`` supplies the model
    call (the engine closes over kv_limit/mesh/attn impl per KV bucket);
    ``finalize`` post-processes the packed buffer (the engine pins it
    replicated under a mesh).

    Grammar-constrained decoding (ISSUE 11, ``grammar=True``): the
    carry grows a per-slot FSM state word ``gs`` (global state =
    ``profile_id * grammar_s_max + local_state``, constrain/runtime.py)
    and the dispatch passes the stacked grammar tables
    (``tok_class [P, V]``, the bit-packed ``class_ok_bits [P*S,
    ceil(C/32)]`` as ``g_ok``, ``class_next [P*S, C]``) as plain
    arguments — variant installs update table CONTENTS, never the
    program. Each step takes the current states' packed legality rows
    (a row gather, ``[N, words]``) and tests every vocabulary entry's
    bit in them (``grammar_legal_mask``: element-wise, no per-element
    gather) for the ``[N, vocab]`` mask, freezes dead-end slots via
    ``HEALTH_GRAMMAR_DEAD`` (no legal token — the quarantine lane's
    job, not a garbage emission), samples only over the masked support
    (same key stream, renormalized — engine/sampling.py), and advances
    the state word by the sampled token's class.

    Speculative decoding (ISSUE 12, ``spec_k > 0``): each of the
    ``spec_steps`` scan iterations first runs the DRAFT model
    (``draft_forward_step``, its own dense KV cache riding the carry)
    ``spec_k`` single-token greedy forwards to propose k tokens, then
    runs ONE target forward over the ``k+1``-token window (carry token
    + drafts — intra-window causal attention, exactly a suffix prefill
    that returns every position's logits) and verifies by EXACT MATCH:
    position j's token is sampled from the target's own logits under
    ``fold_in(seed, ngen_j)`` — precisely the token plain decode would
    have produced — and positions stay valid while each draft equals
    the sample it raced. The first mismatch's sample is the resample
    from the 7B's own logits; later positions are dead for the
    iteration and re-draft next round. Rejected positions' KV rows are
    exactly the "last generated row unwritten" pattern the pool replay
    paths already maintain — never attended (causal mask), rewritten as
    decode re-reaches them, never in a radix chain (chains stop at
    emitted[:-1]). Tokens compact into a carried row buffer through a
    per-slot cursor, so the packed contract is unchanged apart from the
    wider row and the two v3 drafted/accepted lanes. EOS / budget /
    health / grammar folds run per verify position — the SAME fold the
    plain body runs per step — which is what makes spec-on transcripts
    byte-identical to spec-off at any k.

    Ragged admission (ISSUE 19, ``ragged_w > 0``): the chunk grows a
    trailing ``adm`` argument tuple — per-slot staged prompt-suffix
    windows ``(tok [N, W], len, start, ngen0, budget, seed, temp[, gs])``
    — and a PROLOGUE step before the scan: one
    ``ragged_forward_step(params, win_tok, win_pos, cache, wmask,
    tables, q_lens)`` call through the ragged paged-attention kernel
    where a staged slot's q_len is its suffix length and every other
    live slot rides along at q_len=1 (its normal decode step). The
    prologue ARMS staged slots in-chunk (seeds/temps/budget/ngen/gs
    splice from the adm vectors — exactly what ``_run_arm`` +
    ``_grammar_first_sample`` did host-side, same fold_in indices) and
    then runs the SAME per-token fold on the last-position logits, so
    mixed prefill+decode(+spec-verify) slots execute in ONE program
    dispatch. The plain scan shortens by one step (row width stays
    chunk_len); the spec buffer widens by one row (ct =
    spec_steps*(k+1)+1)."""

    def ragged_prologue(params, adm, tok, pos, cache, seeds, temps,
                        live, ngen, budget, corrupt, tables, gs, tc,
                        g_ok, g_next):
        """One ragged mixed-window step (ISSUE 19): staged slots
        prefill their prompt suffix (q_len = window length) and sample
        their FIRST token off the last valid position's logits — the
        device-side equivalent of ``_pool_prefill_span`` +
        ``_grammar_first_sample`` — while every other live slot rides
        the same program at q_len=1 (its normal decode step). The fold
        below mirrors ``body``'s position-for-position (see the NOTE
        there); ``wrote`` is live-after-freeze-before-EOS — the spec
        buffer's write gate."""
        is_adm = adm[1] > 0
        cols = jnp.arange(ragged_w, dtype=jnp.int32)[None, :]
        q_len = jnp.where(is_adm, adm[1], 1)
        start = jnp.where(is_adm, adm[2], pos[:, 0])
        win_tok = adm[0].at[:, 0].set(
            jnp.where(is_adm, adm[0][:, 0], tok[:, 0]))
        win_pos = start[:, None] + cols
        wmask = jnp.logical_and(cols < q_len[:, None], live[:, None])
        logits, cache = ragged_forward_step(
            params, win_tok, win_pos, cache, wmask, tables,
            jnp.where(live, q_len, 0))
        step_logits = logits[:, 0]
        step_logits = jnp.where(corrupt[:, None],
                                jnp.float32(jnp.nan), step_logits)
        health = jnp.zeros_like(ngen)
        mask = None
        if grammar:
            mask = grammar_legal_mask(g_ok, gs, tc)
            with jax.named_scope("grammar_mask"):
                dead = jnp.logical_and(
                    live, jnp.logical_not(jnp.any(mask, axis=-1)))
                health = health | jnp.where(
                    dead, HEALTH_GRAMMAR_DEAD, 0)
                live = jnp.logical_and(live, jnp.logical_not(dead))
        nxt = sample_tokens_seeded(step_logits, seeds, ngen, temps,
                                   top_k=top_k, top_p=top_p,
                                   active=live, mask=mask)
        with jax.named_scope("sampling"):
            if health_check:
                bad_logit = jnp.logical_not(
                    jnp.all(jnp.isfinite(step_logits), axis=-1))
                health = health | jnp.where(
                    jnp.logical_and(live, bad_logit),
                    HEALTH_NONFINITE, 0)
                if vocab_size > 0:
                    bad_tok = jnp.logical_or(nxt < 0,
                                             nxt >= vocab_size)
                    health = health | jnp.where(
                        jnp.logical_and(live, bad_tok),
                        HEALTH_TOKEN_RANGE, 0)
                live = jnp.logical_and(live, health == 0)
            if grammar:
                cls = jnp.take_along_axis(
                    tc, jnp.clip(nxt, 0, tc.shape[1] - 1)[:, None],
                    axis=1)[:, 0]
                gs = jnp.where(live, g_next[gs, cls], gs)
            nxt = jnp.where(live, nxt, win_tok[:, 0])
            wrote = live
            hit_eos = jnp.logical_and(eos_mask(nxt, eos_ids), live)
            counted = jnp.logical_and(live, jnp.logical_not(hit_eos))
            ngen = ngen + counted.astype(jnp.int32)
            done_now = jnp.logical_or(
                hit_eos, jnp.logical_and(counted, ngen >= budget))
            live = jnp.logical_and(live, jnp.logical_not(done_now))
            pos = (start + q_len * counted.astype(jnp.int32))[:, None]
        return nxt, pos, cache, live, ngen, health, gs, counted, wrote

    def batched_chunk_impl(params, tok, pos, cache, seeds, temps, force,
                           active, ngen, budget, corrupt, tables=None,
                           gs=None, g_tok_class=None, g_ok=None,
                           g_next=None, adm=None):
        # NOTE: the per-step termination/health/grammar/EOS/budget fold
        # in ``body`` below is mirrored position-for-position by
        # ``spec_chunk_impl``'s verify loop, the two ragged PROLOGUES,
        # and the fake engine's dispatch paths. Any change to the
        # fold's ordering or semantics MUST be applied to all of them —
        # the spec-on == spec-off and ragged-vs-legacy byte-identity
        # suites (tests/test_spec_decode.py, tests/
        # test_ragged_attention.py, fake and jax, temp 0 and 0.9) are
        # the tripwire that catches a divergence.
        if adm is not None:
            # Ragged arming: splice the staged slots' sampling state in
            # BEFORE live0/tc derive from it — device-side what
            # _run_arm's .at[slot].set() writes did between chunks.
            is_adm = adm[1] > 0
            seeds = jnp.where(is_adm, adm[5], seeds)
            temps = jnp.where(is_adm, adm[6], temps)
            budget = jnp.where(is_adm, adm[4], budget)
            ngen = jnp.where(is_adm, adm[3], ngen)
            active = jnp.where(is_adm, adm[4] > adm[3], active)
            if grammar:
                gs = jnp.where(is_adm, adm[7], gs)
        live0 = jnp.logical_and(active, force)
        health0 = jnp.zeros_like(ngen)
        cache = _zero_counts(cache)
        tc = None
        if grammar:
            # Per-slot token→class rows, hoisted OUT of the scan: the
            # profile id is chunk-invariant (class_next maps every
            # state within its own profile block and frozen rows keep
            # gs), and a carry-derived gather would re-materialize
            # [batch, vocab] int32 every step on the hottest loop.
            tc = g_tok_class[gs // grammar_s_max]

        def body(carry, _):
            if grammar:
                tok, pos, cache, live, ngen, health, gs = carry
            else:
                tok, pos, cache, live, ngen, health = carry
                gs = None
            if tables is None:
                logits, cache = forward_step(params, tok, pos, cache, live)
            else:
                # Block-paged pool (ISSUE 10): the per-slot block table
                # rides the dispatch as a plain argument — admissions
                # grow tables on the host between chunks, so it cannot
                # be a trace-time constant.
                logits, cache = forward_step(params, tok, pos, cache,
                                             live, tables)
            step_logits = logits[:, 0]
            step_logits = jnp.where(corrupt[:, None],
                                    jnp.float32(jnp.nan), step_logits)
            mask = None
            if grammar:
                # Per-slot legality over the vocab: the state's packed
                # class-legality row tested through the profile's
                # (hoisted) token→class map. A state with NO legal
                # token is a dead end: freeze the slot on the grammar
                # health bit before anything is emitted.
                mask = grammar_legal_mask(g_ok, gs, tc)
                with jax.named_scope("grammar_mask"):
                    dead = jnp.logical_and(
                        live, jnp.logical_not(jnp.any(mask, axis=-1)))
                    health = health | jnp.where(
                        dead, HEALTH_GRAMMAR_DEAD, 0)
                    live = jnp.logical_and(live,
                                           jnp.logical_not(dead))
            nxt = sample_tokens_seeded(step_logits, seeds, ngen, temps,
                                       top_k=top_k, top_p=top_p,
                                       active=live, mask=mask)
            # Termination fold — a handful of [N]-vector compares the
            # attribution tool bills with the sampling chain.
            with jax.named_scope("sampling"):
                if health_check:
                    # Per-slot corruption detection: a tripped slot is
                    # frozen HERE (its garbage token is never counted,
                    # its KV writes stop next step) and its health bit
                    # rides the packed buffer to the quarantine pass.
                    bad_logit = jnp.logical_not(
                        jnp.all(jnp.isfinite(step_logits), axis=-1))
                    health = health | jnp.where(
                        jnp.logical_and(live, bad_logit),
                        HEALTH_NONFINITE, 0)
                    if vocab_size > 0:
                        bad_tok = jnp.logical_or(nxt < 0,
                                                 nxt >= vocab_size)
                        health = health | jnp.where(
                            jnp.logical_and(live, bad_tok),
                            HEALTH_TOKEN_RANGE, 0)
                    live = jnp.logical_and(live, health == 0)
                if grammar:
                    # Advance the FSM by the sampled token's class for
                    # every row that really sampled this step (frozen
                    # rows keep their state; the EOS class self-loops
                    # so a terminating row parks in place).
                    cls = jnp.take_along_axis(
                        tc, jnp.clip(nxt, 0, tc.shape[1] - 1)[:, None],
                        axis=1)[:, 0]
                    gs = jnp.where(live, g_next[gs, cls], gs)
                nxt = jnp.where(live, nxt, tok[:, 0])
                hit_eos = jnp.logical_and(eos_mask(nxt, eos_ids), live)
                counted = jnp.logical_and(live, jnp.logical_not(hit_eos))
                ngen = ngen + counted.astype(jnp.int32)
                done_now = jnp.logical_or(
                    hit_eos, jnp.logical_and(counted, ngen >= budget))
                live = jnp.logical_and(live, jnp.logical_not(done_now))
                pos = pos + counted.astype(jnp.int32)[:, None]
            if grammar:
                return (nxt[:, None], pos, cache, live, ngen, health,
                        gs), nxt
            return (nxt[:, None], pos, cache, live, ngen, health), nxt

        nxt0 = None
        if adm is not None:
            # Ragged prologue replaces the scan's first step: same row
            # width (chunk_len), one fewer scan iteration.
            (nxt0, pos, cache, live0, ngen, health0, gs, _c0,
             _w0) = ragged_prologue(params, adm, tok, pos, cache,
                                    seeds, temps, live0, ngen, budget,
                                    corrupt, tables, gs, tc, g_ok,
                                    g_next)
            tok = nxt0[:, None]
        carry0 = (tok, pos, cache, live0, ngen, health0)
        if grammar:
            carry0 = carry0 + (gs,)
        carry, toks = jax.lax.scan(
            body, carry0, None,
            length=chunk_len - (1 if adm is not None else 0))
        if grammar:
            tok, pos, cache, live, ngen, health, gs = carry
        else:
            tok, pos, cache, live, ngen, health = carry
        toks = jnp.swapaxes(toks, 0, 1)
        if nxt0 is not None:
            toks = jnp.concatenate([nxt0[:, None], toks], axis=1)
        done = jnp.logical_and(force, jnp.logical_not(live))
        packed = finalize(pack_chunk(toks, done, ngen, jnp.sum(live),
                                     health=health,
                                     experts_read=cache.experts_read,
                                     sel_rows=attention_counted(cache),
                                     xp=jnp))
        out = (packed, tok, pos, cache, live, ngen)
        if grammar:
            out = out + (gs,)
        return out

    def spec_chunk_impl(params, tok, pos, cache, seeds, temps, force,
                        active, ngen, budget, corrupt, tables, dparams,
                        dcache, gs=None, g_tok_class=None, g_ok=None,
                        g_next=None, adm=None):
        """Draft/verify scan body (ISSUE 12). Carry adds the draft KV
        cache, the compacting token buffer + per-slot cursor, and the
        drafted/accepted counters; everything else mirrors the plain
        body position-for-position."""
        k = spec_k
        N = force.shape[0]
        # Ragged admission widens the row by the prologue's one token
        # (ct = spec_steps*(k+1) + 1); CT doubles as the compact
        # write's out-of-bounds drop sentinel, so buffer width and
        # sentinel move together by construction.
        CT = spec_steps * (k + 1) + (1 if adm is not None else 0)
        if adm is not None:
            is_adm = adm[1] > 0
            seeds = jnp.where(is_adm, adm[5], seeds)
            temps = jnp.where(is_adm, adm[6], temps)
            budget = jnp.where(is_adm, adm[4], budget)
            ngen = jnp.where(is_adm, adm[3], ngen)
            active = jnp.where(is_adm, adm[4] > adm[3], active)
            if grammar:
                gs = jnp.where(is_adm, adm[7], gs)
        live0 = jnp.logical_and(active, force)
        health0 = jnp.zeros_like(ngen)
        cache = _zero_counts(cache)
        zeros = jnp.zeros_like(ngen)
        tc = None
        if grammar:
            tc = g_tok_class[gs // grammar_s_max]
        if adm is not None:
            # Keep the draft cache gapless: the prologue's decode step
            # advances the target without a draft forward, which would
            # leave a zero row the next iteration's drafts attend
            # through (proposal quality only — verify is exact — but a
            # free single-token draft forward closes it; for a staged
            # slot it rewrites the admission draft-prefill's own row
            # with the same token).
            wt0 = jnp.where(is_adm, adm[0][:, 0], tok[:, 0])
            st0 = jnp.where(is_adm, adm[2], pos[:, 0])
            _dl, dcache = draft_forward_step(
                dparams, wt0[:, None], st0[:, None], dcache, live0)
            (nxt0, pos, cache, live0, ngen, health0, gs, c0,
             w0) = ragged_prologue(params, adm, tok, pos, cache,
                                   seeds, temps, live0, ngen, budget,
                                   corrupt, tables, gs, tc, g_ok,
                                   g_next)
            # Carry-token semantics match the verify fold's ``cur``:
            # an un-counted prologue (EOS / frozen) keeps the window's
            # first token as carry — EOS never becomes a spec carry.
            tok = jnp.where(c0, nxt0, wt0)[:, None]
            # Garbage row entries repeat the slot's carry token (the
            # packed contract); the prologue token lands at index 0
            # for every row that really sampled (EOS included — the
            # finish-reason entry), and the cursor advances only for
            # counted ones.
            buf0 = jnp.tile(tok, (1, CT))
            buf0 = buf0.at[jnp.arange(N),
                           jnp.where(w0, 0, CT)].set(nxt0, mode="drop")
            cur0 = c0.astype(jnp.int32)
        else:
            # Garbage row entries repeat the slot's carry token (the
            # packed contract): initialize the whole buffer with it —
            # un-written positions then satisfy "never an accidental
            # EOS at index v".
            buf0 = jnp.tile(tok, (1, CT))
            cur0 = zeros

        def body(carry, _):
            if grammar:
                (tok, pos, cache, dcache, live, ngen, health, buf,
                 cur_i, drafted, accepted, gs) = carry
            else:
                (tok, pos, cache, dcache, live, ngen, health, buf,
                 cur_i, drafted, accepted) = carry
                gs = None
            it_live = live
            # --- draft: greedy single-token forwards of the 2B,
            # masked by the same grammar tables, advancing its own
            # speculative FSM walk. k+1 forwards for k proposals: the
            # last forward's proposal is discarded — it runs so the
            # k-th draft token's KV ROW gets written (a fully-accepted
            # window otherwise leaves a permanent hole the next
            # iteration's drafts would attend zeros through). Draft KV
            # rows for rejected tokens are rewritten when decode
            # re-reaches their positions — same discipline as the
            # target cache.
            drafts = []
            dtok, dpos, dgs = tok, pos, gs
            for _j in range(k + 1):
                dlogits, dcache = draft_forward_step(
                    dparams, dtok, dpos, dcache, it_live)
                if _j == k:
                    break
                dl = dlogits[:, 0]
                dmask = None
                if grammar:
                    dmask = grammar_legal_mask(g_ok, dgs, tc)
                d = greedy_tokens(dl, mask=dmask)
                d = jnp.where(it_live, d, dtok[:, 0])
                drafts.append(d)
                if grammar:
                    dcls = jnp.take_along_axis(
                        tc, jnp.clip(d, 0, tc.shape[1] - 1)[:, None],
                        axis=1)[:, 0]
                    dgs = jnp.where(it_live, g_next[dgs, dcls], dgs)
                dtok = d[:, None]
                dpos = dpos + it_live.astype(jnp.int32)[:, None]
            drafted = drafted + jnp.where(it_live, k, 0)
            # --- verify: ONE target forward over the (k+1)-token
            # window — carry token + drafts at consecutive absolute
            # positions, causal within the window (a suffix prefill
            # that keeps every position's logits).
            toks_in = jnp.concatenate(
                [tok] + [d[:, None] for d in drafts], axis=1)
            pos_in = pos + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            logits, cache = forward_step(params, toks_in, pos_in, cache,
                                         it_live, tables)
            # --- accept/reject: per position, the SAME termination /
            # health / grammar fold the plain body runs per step.
            # ``seg`` = still-valid-within-this-window; a draft
            # mismatch kills seg (later logits conditioned on the
            # wrong token) but not ``live`` — the slot re-drafts next
            # iteration from the corrected carry.
            seg = it_live
            cur = tok[:, 0]
            for j in range(k + 1):
                sl = logits[:, j]
                sl = jnp.where(corrupt[:, None], jnp.float32(jnp.nan),
                               sl)
                mask = None
                if grammar:
                    mask = grammar_legal_mask(g_ok, gs, tc)
                    with jax.named_scope("grammar_mask"):
                        dead = jnp.logical_and(
                            seg, jnp.logical_not(
                                jnp.any(mask, axis=-1)))
                        health = health | jnp.where(
                            dead, HEALTH_GRAMMAR_DEAD, 0)
                        live = jnp.logical_and(live,
                                               jnp.logical_not(dead))
                        seg = jnp.logical_and(seg,
                                              jnp.logical_not(dead))
                s = sample_tokens_seeded(sl, seeds, ngen, temps,
                                         top_k=top_k, top_p=top_p,
                                         active=seg, mask=mask)
                with jax.named_scope("sampling"):
                    if health_check:
                        bad_logit = jnp.logical_not(
                            jnp.all(jnp.isfinite(sl), axis=-1))
                        health = health | jnp.where(
                            jnp.logical_and(seg, bad_logit),
                            HEALTH_NONFINITE, 0)
                        if vocab_size > 0:
                            bad_tok = jnp.logical_or(
                                s < 0, s >= vocab_size)
                            health = health | jnp.where(
                                jnp.logical_and(seg, bad_tok),
                                HEALTH_TOKEN_RANGE, 0)
                        live = jnp.logical_and(live, health == 0)
                        seg = jnp.logical_and(seg, health == 0)
                    if grammar:
                        cls = jnp.take_along_axis(
                            tc,
                            jnp.clip(s, 0, tc.shape[1] - 1)[:, None],
                            axis=1)[:, 0]
                        gs = jnp.where(seg, g_next[gs, cls], gs)
                    s = jnp.where(seg, s, cur)
                    hit_eos = jnp.logical_and(eos_mask(s, eos_ids), seg)
                    counted = jnp.logical_and(
                        seg, jnp.logical_not(hit_eos))
                    # Compact write: emitted tokens AND the terminating
                    # EOS land at the cursor (the EOS is the row entry
                    # consume_chunk_row reads the finish reason from);
                    # invalid lanes scatter out of bounds and drop.
                    widx = jnp.where(seg, cur_i, CT)
                    buf = buf.at[jnp.arange(N), widx].set(
                        s, mode="drop")
                    cur_i = cur_i + counted.astype(jnp.int32)
                    ngen = ngen + counted.astype(jnp.int32)
                    done_now = jnp.logical_or(
                        hit_eos,
                        jnp.logical_and(counted, ngen >= budget))
                    live = jnp.logical_and(live,
                                           jnp.logical_not(done_now))
                    seg = jnp.logical_and(seg,
                                          jnp.logical_not(done_now))
                    pos = pos + counted.astype(jnp.int32)[:, None]
                    cur = jnp.where(counted, s, cur)
                    if j >= 1:
                        # Position j>=1 only ever counts when drafts
                        # 1..j all matched — each counted token here
                        # consumed (accepted) one draft proposal.
                        accepted = accepted + counted.astype(jnp.int32)
                    if j < k:
                        seg = jnp.logical_and(seg, s == drafts[j])
            tok = cur[:, None]
            out = (tok, pos, cache, dcache, live, ngen, health, buf,
                   cur_i, drafted, accepted)
            if grammar:
                out = out + (gs,)
            return out, None

        carry0 = (tok, pos, cache, dcache, live0, ngen, health0, buf0,
                  cur0, zeros, zeros)
        if grammar:
            carry0 = carry0 + (gs,)
        carry, _ = jax.lax.scan(body, carry0, None, length=spec_steps)
        if grammar:
            (tok, pos, cache, dcache, live, ngen, health, buf, _cur,
             drafted, accepted, gs) = carry
        else:
            (tok, pos, cache, dcache, live, ngen, health, buf, _cur,
             drafted, accepted) = carry
        done = jnp.logical_and(force, jnp.logical_not(live))
        packed = finalize(pack_chunk(buf, done, ngen, jnp.sum(live),
                                     health=health, drafted=drafted,
                                     accepted=accepted,
                                     experts_read=cache.experts_read,
                                     sel_rows=attention_counted(cache),
                                     xp=jnp))
        out = (packed, tok, pos, cache, live, ngen, dcache)
        if grammar:
            out = out + (gs,)
        return out

    if ragged_w:
        if not pool_tables or ragged_forward_step is None:
            raise ValueError("ragged admission chunk needs pool "
                             "tables and a ragged_forward_step")

    if spec_k > 0:
        if not pool_tables or draft_forward_step is None:
            raise ValueError("speculative decode chunk needs pool "
                             "tables and a draft_forward_step")
        if ragged_w and grammar:
            def spec_chunk_ragged_grammar(params, tok, pos, cache,
                                          seeds, temps, force, active,
                                          ngen, budget, corrupt, tables,
                                          dparams, dcache, gs,
                                          g_tok_class, g_ok, g_next,
                                          adm_tok, adm_len, adm_start,
                                          adm_ngen0, adm_budget,
                                          adm_seed, adm_temp, adm_gs):
                return spec_chunk_impl(
                    params, tok, pos, cache, seeds, temps, force,
                    active, ngen, budget, corrupt, tables, dparams,
                    dcache, gs, g_tok_class, g_ok, g_next,
                    adm=(adm_tok, adm_len, adm_start, adm_ngen0,
                         adm_budget, adm_seed, adm_temp, adm_gs))

            return spec_chunk_ragged_grammar
        if ragged_w:
            def spec_chunk_ragged(params, tok, pos, cache, seeds,
                                  temps, force, active, ngen, budget,
                                  corrupt, tables, dparams, dcache,
                                  adm_tok, adm_len, adm_start,
                                  adm_ngen0, adm_budget, adm_seed,
                                  adm_temp):
                return spec_chunk_impl(
                    params, tok, pos, cache, seeds, temps, force,
                    active, ngen, budget, corrupt, tables, dparams,
                    dcache,
                    adm=(adm_tok, adm_len, adm_start, adm_ngen0,
                         adm_budget, adm_seed, adm_temp))

            return spec_chunk_ragged
        if grammar:
            def spec_chunk_pool_grammar(params, tok, pos, cache, seeds,
                                        temps, force, active, ngen,
                                        budget, corrupt, tables,
                                        dparams, dcache, gs,
                                        g_tok_class, g_ok, g_next):
                return spec_chunk_impl(params, tok, pos, cache, seeds,
                                       temps, force, active, ngen,
                                       budget, corrupt, tables, dparams,
                                       dcache, gs, g_tok_class, g_ok,
                                       g_next)

            return spec_chunk_pool_grammar

        def spec_chunk_pool(params, tok, pos, cache, seeds, temps,
                            force, active, ngen, budget, corrupt,
                            tables, dparams, dcache):
            return spec_chunk_impl(params, tok, pos, cache, seeds,
                                   temps, force, active, ngen, budget,
                                   corrupt, tables, dparams, dcache)

        return spec_chunk_pool

    if ragged_w and grammar:
        def batched_chunk_ragged_grammar(params, tok, pos, cache, seeds,
                                         temps, force, active, ngen,
                                         budget, corrupt, tables, gs,
                                         g_tok_class, g_ok, g_next,
                                         adm_tok, adm_len, adm_start,
                                         adm_ngen0, adm_budget,
                                         adm_seed, adm_temp, adm_gs):
            return batched_chunk_impl(
                params, tok, pos, cache, seeds, temps, force, active,
                ngen, budget, corrupt, tables, gs, g_tok_class, g_ok,
                g_next,
                adm=(adm_tok, adm_len, adm_start, adm_ngen0,
                     adm_budget, adm_seed, adm_temp, adm_gs))

        return batched_chunk_ragged_grammar

    if ragged_w:
        def batched_chunk_ragged(params, tok, pos, cache, seeds, temps,
                                 force, active, ngen, budget, corrupt,
                                 tables, adm_tok, adm_len, adm_start,
                                 adm_ngen0, adm_budget, adm_seed,
                                 adm_temp):
            return batched_chunk_impl(
                params, tok, pos, cache, seeds, temps, force, active,
                ngen, budget, corrupt, tables,
                adm=(adm_tok, adm_len, adm_start, adm_ngen0,
                     adm_budget, adm_seed, adm_temp))

        return batched_chunk_ragged

    if pool_tables and grammar:
        def batched_chunk_pool_grammar(params, tok, pos, cache, seeds,
                                       temps, force, active, ngen,
                                       budget, corrupt, tables, gs,
                                       g_tok_class, g_ok, g_next):
            return batched_chunk_impl(params, tok, pos, cache, seeds,
                                      temps, force, active, ngen, budget,
                                      corrupt, tables, gs, g_tok_class,
                                      g_ok, g_next)

        return batched_chunk_pool_grammar

    if grammar:
        def batched_chunk_grammar(params, tok, pos, cache, seeds, temps,
                                  force, active, ngen, budget, corrupt,
                                  gs, g_tok_class, g_ok, g_next):
            return batched_chunk_impl(params, tok, pos, cache, seeds,
                                      temps, force, active, ngen, budget,
                                      corrupt, None, gs, g_tok_class,
                                      g_ok, g_next)

        return batched_chunk_grammar

    if pool_tables:
        def batched_chunk_pool(params, tok, pos, cache, seeds, temps,
                               force, active, ngen, budget, corrupt,
                               tables):
            return batched_chunk_impl(params, tok, pos, cache, seeds,
                                      temps, force, active, ngen, budget,
                                      corrupt, tables)

        return batched_chunk_pool

    def batched_chunk(params, tok, pos, cache, seeds, temps, force,
                      active, ngen, budget, corrupt):
        return batched_chunk_impl(params, tok, pos, cache, seeds, temps,
                                  force, active, ngen, budget, corrupt)

    return batched_chunk


def _zero_counts(cache):
    """The chunk program counts what its own passes read and kept."""
    zeroed = {name: jnp.zeros_like(getattr(cache, name))
              for name in KVCache.COUNTS
              if getattr(cache, name) is not None}
    return dataclasses.replace(cache, **zeroed) if zeroed else cache


def staged_suffix_len(suffix: int, buckets) -> int:
    """How many of an admission's ``suffix`` unmatched tokens ride the next
    chunk's window; the rest, its head, prefills eagerly. A suffix the
    widest window holds rides whole. A longer one's head is eager
    whatever rides, so it rides the narrowest. That rule was settled
    while a window cost every slot of the batch its width (64 rows
    against 512 read first_chunk 1,207 against 1,568 ms on 11k-token
    prompts: my chip runs, PR 31); since ISSUE 39 a window costs its
    valid rows, and how much of a long prompt's head should ride it is
    open (ROADMAP S1 a)."""
    return suffix if suffix <= buckets[-1] else buckets[0]


@dataclasses.dataclass
class _Request:
    prompt_ids: List[int]
    max_tokens: int
    temperature: float
    deadline: Optional[float]
    loop: asyncio.AbstractEventLoop
    out_queue: asyncio.Queue
    cancel: threading.Event
    t_submit: float
    # Request-lifecycle trace (obs/trace.py), captured from the submitting
    # coroutine's context. ContextVars don't cross threads, so the
    # scheduler annotates THIS reference (Trace.event is lock-guarded) —
    # the flight-recorder timeline shows admissions/first-token/finish
    # as the scheduler saw them.
    trace: Optional[Trace] = None
    # The request's engine phases (obs/trace.py RequestSpans), stamped by
    # the scheduler as each boundary is crossed; made at first use
    # (EngineSpans.of) so every constructor of a _Request gets one.
    spans: Optional[RequestSpans] = None
    # Per-request sampling seed (ISSUE 5): every sampled token is drawn
    # from fold_in(PRNGKey(seed), generation_index) — engine/sampling.py
    # slot_keys — so the token stream is a pure function of (seed,
    # logits), independent of batch composition or engine resets. Minted
    # deterministically from the prompt when the caller doesn't supply
    # one; exposed on the trace so /debug/requests/{id} makes any
    # transcript reproducible offline.
    seed: int = 0
    # Raw prompt text, kept for decode-fault targeting
    # (testing/faults.py target_substr) and trace readability.
    prompt: str = ""
    # Quarantine bookkeeping (engine/containment.py): how many times this
    # request has been solo-implicated in a poisoned step. Survives
    # resets/parking; past QUARANTINE_RETRY_BUDGET → RequestQuarantined.
    suspect_count: int = 0
    # Standing bisection suspicion: True while this request is in the
    # pool a step-wide fault is being narrowed over. Lets early
    # exoneration (PROBATION_CLEAN_CHUNKS) re-mix exonerated cohabitants
    # and new admissions into the batch without widening the next
    # bisection back out to everyone.
    suspect: bool = False
    # Cross-replica migration (engine/fleet.py): ``resume_ids`` imports a
    # generated-so-far prefix from ANOTHER engine — admission re-splices
    # prompt + prefix exactly like a containment replay (ngen0 re-aligns
    # the per-request RNG stream, so the continuation is bit-identical)
    # and re-emits the prefix text, which the fleet relay suppresses.
    # ``export`` is the live outbound view: the scheduler points its
    # ``ids`` at the generated ids after every consume, so the fleet can
    # carry this request to a healthy replica when this engine dies.
    resume_ids: Optional[List[int]] = None
    export: Optional[RequestExport] = None
    # True once _admit_resume has emitted the imported prefix text: a
    # scheduler-death mid-admission requeues the request, and the second
    # _admit_resume pass must not emit the prefix a second time (the
    # fleet's suppression window was already consumed by the first).
    resume_emitted: bool = False
    # QoS ring (ISSUE 7): the fair-share tenant key (API key else client
    # IP) and priority lane this request runs in, read off the
    # qos-context contextvar at submit time. The QoSQueue schedules by
    # these; defaults keep direct engine calls on the pre-QoS behaviour
    # (one interactive anon bucket).
    tenant: str = ANON_TENANT
    lane: str = LANE_INTERACTIVE
    # Stamped by the QoSQueue at every (re-)enqueue; preemption and the
    # starved-lane trigger judge waits against THIS, not t_submit, so a
    # just-preempted victim can't instantly read as starved.
    t_enqueue: float = 0.0
    # Preemptive decode (the PR 6 export/replay path turned inward): how
    # many times this request has been preempted out of a slot
    # (PREEMPT_BUDGET bounds it), when the current preemption started
    # (monotonic; the wall from here to re-admission is credited back to
    # the deadline — preempted time is excluded from the victim's
    # clock), and how many chars of the resume prefix's TEXT the client
    # already received (the _admit_resume emission skips exactly that
    # many, the engine-side analog of the fleet relay's suppression).
    preempt_count: int = 0
    preempt_t0: Optional[float] = None
    resume_skip: int = 0
    # Goodput ledger (ISSUE 8): transcript tokens already billed as
    # delivered for this request. A fleet-migrated import starts at
    # len(resume_ids) — the donor replica decoded AND billed that
    # prefix; this engine only bills what it decodes beyond it.
    ledger_delivered: int = 0
    # Why the next _replay_slot re-splice exists: "preempt" bills the
    # re-derivation to the ledger's preempted class (QoS export/replay),
    # anything else to replayed (containment reset / fleet migration).
    # Cleared on every _replay_slot entry — early-return paths included
    # — so a later unrelated containment replay bills replayed.
    resume_cause: str = ""
    # SLO accounting (ISSUE 8): monotonic stamp of the FIRST token this
    # request ever delivered — survives preempt/resume (the slot's
    # t_first resets with the slot), so a resumed request's TTFT sample
    # reflects the client's real first byte. ttft_exempt marks fleet
    # imports: their first byte happened on the donor replica, and a
    # recipient-side sample would overstate.
    t_first0: Optional[float] = None
    ttft_exempt: bool = False
    # Grammar-constrained decoding (ISSUE 11): the resolved grammar
    # profile id (constrain/runtime.py — base profile, tenant-tier
    # readonly clamp, or an installed allowed-verbs variant). -1 =
    # unconstrained (GRAMMAR_DECODE off).
    gpid: int = -1
    # Session plane (ISSUE 20): the namespaced session id (empty =
    # sessionless) and whether admission radix-matched at least one
    # full page — the gate on the turn-N session TTFT SLO.
    session: str = ""
    radix_warm: bool = False


@dataclasses.dataclass
class _Slot:
    req: _Request
    detok: StreamDecoder
    n_prompt: int
    pos: int                      # scheduled device position (counts dispatched chunks)
    queue_ms: float
    t_admit: float
    prefill_ms: float = 0.0       # ADMISSION latency: admit → first-token
                                  # consume. Unlike the single-sequence
                                  # engine's prefill_ms (device prefill span,
                                  # jax_engine.py), this includes up to two
                                  # in-flight decode chunks of pipeline wait —
                                  # the price of stall-free admissions. The
                                  # isolated device span is unobservable
                                  # without a host sync that would stall
                                  # every slot.
    t_decode0: float = 0.0
    t_first: Optional[float] = None
    chunks_inflight: int = 0      # dispatched-but-unconsumed entries for this slot
    decode_chunks_inflight: int = 0  # the "chunk" subset of chunks_inflight
                                  # (waste accounting: a host-only finish
                                  # wastes these × chunk_len device steps)
    exhausted: bool = False       # KV capacity reached; drain pipeline, then finish
    prefix_hit: bool = False      # served from the system-prompt prefix-KV cache
    detok_ms: float = 0.0         # host detokenization time, accumulated
    # Block-paged KV pool (ISSUE 10): the pool blocks this slot's table
    # maps, in page order (None in dense mode), and the admitted
    # (possibly left-truncated) prompt ids — the basis of the radix
    # chain inserted at finish/preempt. Growth happens at dispatch
    # (_pool_ensure_coverage); release is deferred until every chunk
    # whose table snapshot could write them has retired.
    blocks: Optional[List[int]] = None
    pool_ids: Optional[List[int]] = None
    # Grammar-constrained decoding (ISSUE 11): host-truth FSM state
    # over the CONSUMED token stream (the device carries its own
    # speculative _fsm_d), and the count of in-flight chunks whose rows
    # a forced-run fast-forward spliced over — their token indexing is
    # pre-splice, so consume skips exactly that many entries (FIFO).
    gs: int = 0
    stale_chunks: int = 0
    # Speculative decoding (ISSUE 12): exact host truth of the device
    # carry at the LAST arm — absolute position ``anchor_pos`` when the
    # generated count was ``anchor_g``. The spec consume path re-syncs
    # the conservative ``pos`` bound from these (a spec chunk advances
    # by accepted-count, not a fixed width).
    anchor_pos: int = 0
    anchor_g: int = 0


class BatchedJaxEngine(JaxEngine):
    """Engine-protocol implementation with continuous batching."""

    name = "jax-batched"

    def __init__(self, *args, batch_size: int = 8, chunk_len: int = 16,
                 force_ragged: bool = False,
                 kv_pool: bool = True,
                 kv_pool_page: int = 16,
                 kv_pool_blocks: int = 0,
                 radix_cache: bool = True,
                 radix_lru_blocks: int = 0,
                 host_kv_blocks: int = 0,
                 state_snapshots: int = 0,
                 grammar_decode: bool = False,
                 grammar_profile: str = "default",
                 grammar_forced_run_min: int = 4,
                 spec_decode: bool = False,
                 spec_draft_k: int = 4,
                 spec_draft_model: str = "gemma-2b-it",
                 spec_draft_path: Optional[str] = None,
                 spec_draft_seed: Optional[int] = None,
                 watchdog_secs: float = 120.0,
                 startup_grace_secs: float = 900.0,
                 admit_scratch_mb: int = 512,
                 chunk_pipe_depth: int = 2,
                 max_queue_depth: int = 64,
                 device_termination: bool = True,
                 slot_health_check: bool = True,
                 quarantine_retry_budget: int = 1,
                 reset_max_per_min: int = 12,
                 lane_weights: Optional[dict] = None,
                 tenant_max_queue: int = 0,
                 preempt_wait_ms: float = 500.0,
                 preempt_budget: int = 2,
                 slo_interactive_ms: float = 0.0,
                 ledger_enable: bool = True,
                 slo_ttft_ms: float = 0.0,
                 slo_session_ttft_ms: float = 0.0,
                 session_token_budget: int = 0,
                 slo_windows: tuple = (300, 3600),
                 slo_objective: float = 0.99,
                 sentinel_enable: bool = True,
                 sentinel_window: int = 256,
                 sentinel_factor: float = 2.0,
                 sentinel_min_samples: int = 16,
                 perf_baselines=None,
                 faults=None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if chunk_len < 1:
            raise ValueError("chunk_len must be >= 1")
        if chunk_pipe_depth < 1:
            raise ValueError("chunk_pipe_depth must be >= 1")
        self.batch_size = batch_size
        self.chunk_len = chunk_len
        # Decode chunks kept in flight: one running, one queued behind it
        # (why 2, and when 3: config.py, chunk_pipe_depth). A prompt
        # staged after chunk N is consumed rides chunk N+depth
        # (_worker_loop), so the depth is chunk periods a request waits
        # before its first token.
        # chunk_len=16 matches the bench-proven serving default
        # (config.py CHUNK_LEN).
        self.chunk_pipe_depth = chunk_pipe_depth
        # Device-resident termination (the tentpole of ISSUE 4): the
        # decode chunk folds EOS + per-slot token budgets into its carried
        # active mask, so finished slots stop sampling/KV writes
        # mid-chunk and the packed result buffer
        # ([tokens, done_mask, live_lengths, n_alive] — protocol.py)
        # carries termination to the host in the SAME single fetch as the
        # tokens. False restores the host-side EOS scan (A/B + fallback).
        self.device_termination = device_termination
        # Block-paged KV pool (the ISSUE 10 tentpole): one shared
        # [L, n_blocks, page, KV, hd] cache per layer + per-slot block
        # tables replaces per-slot dense S_alloc regions. ``kv_pool_page``
        # must divide the 128-token kv-limit tile (config.py validates
        # the env knob; direct construction re-checks here).
        # ``kv_pool_blocks`` 0 = auto (batch_size x pages-per-slot — the
        # dense HBM envelope, which oversubscription then shares);
        # ``radix_cache`` False = pool without prefix sharing (A/B);
        # ``radix_lru_blocks`` 0 = auto (a quarter of the pool).
        self.kv_pool = bool(kv_pool)
        self.kv_pool_page = max(1, kv_pool_page)
        if 128 % self.kv_pool_page:
            raise ValueError(
                f"KV_POOL_PAGE must divide the 128-token kv-limit tile, "
                f"got {self.kv_pool_page}")
        self.kv_pool_blocks = max(0, kv_pool_blocks)
        self.radix_cache = bool(radix_cache)
        self.radix_lru_blocks = max(0, radix_lru_blocks)
        # Two-tier KV (ISSUE 20): pinned host-RAM capacity (blocks)
        # behind the radix tree; 0 keeps the single-tier world.
        self.host_kv_blocks = max(0, host_kv_blocks)
        self._host_store: Optional[HostBlockStore] = None
        # Recurrent-state cache (ISSUE 33), for a model with state-space
        # layers only: ``state_snapshots`` rows of device memory hold
        # snapshots of a sequence's state (0 = auto, 4 a decode slot);
        # the StateStore is their host truth, rebuilt with the pool.
        self.state_snapshots = max(0, state_snapshots)
        self._state: Optional[StateStore] = None
        self._snap: dict = {}
        self._use_pool = False        # resolved at start (mesh fallback)
        # True when KV_POOL was requested but the mesh forced the dense
        # ladder (data/pipe/seq axes >1 — the pool's block axis is a
        # shared structure across slots and can't shard over them).
        # Surfaced in /health's sharding section + the
        # kv_pool_mesh_fallback gauge so the fallback is never silent.
        self._kv_pool_mesh_fallback = False
        self._pool: Optional[BlockPool] = None
        self._radix: Optional[RadixCache] = None
        self._pool_prefill_fns: dict = {}   # (bucket, kv_limit) -> jitted
        self._pool_starved = 0        # slots truncated by pool exhaustion
        # Which attention serves (engine/regime.py) is worked out at
        # start from the backend, the mesh, the KV dtype and the model's
        # geometry; regime and reason ride sharding_health /
        # kv_pool_health and the decode_attention_regime gauge, so a
        # fallback (int8 KV, non-dividing tp) is observable instead of
        # inferred. ``force_ragged`` is for tests: it runs the
        # interpreted kernel where no TPU is, and no environment
        # variable reaches it.
        self.force_ragged = bool(force_ragged)
        self._use_ragged = False
        self._attention_regime = DENSE
        self._attention_regime_reason = "not started"
        self._lane_heads = 1
        # What the kinds of this configuration's cache count
        # (models/families.py; /health's family sections).
        self._counts_experts = False
        self._counts = CacheCounters(self.model_cfg)
        self._attention_steps = (None, None, None)
        self._ragged_chunk_fns: dict = {}   # (adm width, spec) -> jitted
        # slot_idx -> staged admission (ids/start/ngen0/budget/seed/
        # temp/gs): the unmatched prompt suffix rides the NEXT chunk as
        # a long-q_len slot instead of a separately compiled prefill
        # (in arrival order: the staging rule reads it, ``stage_window``).
        self._pending_adm: dict = {}
        # /health.ragged.window (ISSUE 39), the scheduler's arithmetic at
        # dispatch: chunks that carried a window, the rows their slots
        # brought (staged suffixes + one a rider), the rows the prologue
        # computed (width + batch; slot x width before the rows were
        # packed), and staged suffixes that waited a chunk for room.
        self._window_counts = dict.fromkeys(
            ("windows", "rows_valid", "rows_computed", "deferred"), 0)
        # Grammar-constrained decoding (ISSUE 11): the kubectl token
        # FSM masks sampling device-side and forced runs fast-forward
        # as suffix prefills. Requires device termination (the FSM
        # state word rides the chunk carry).
        if grammar_decode and not device_termination:
            raise ValueError("GRAMMAR_DECODE requires DEVICE_TERMINATION")
        self.grammar_decode = bool(grammar_decode)
        self.grammar_profile = grammar_profile
        self.grammar_forced_run_min = max(1, grammar_forced_run_min)
        self._grammar = None          # GrammarRuntime, built at start
        self._grammar_version = -1    # device-table upload generation
        self._gram_tc_d = self._gram_ok_d = self._gram_next_d = None
        # Cumulative grammar counters (scheduler-thread writes, scrape
        # reads — delta-mirrored like the pipeline totals).
        self._grammar_forced = 0      # tokens delivered by splices
        self._grammar_masked = 0      # tokens sampled under a mask
        self._grammar_dead_ends: dict = {}   # cause -> count
        self._grammar_ff_splices = 0  # fast-forward splice events
        # Speculative decoding (ISSUE 12): the 2B drafts k tokens per
        # slot, one 7B forward verifies all k inside the packed chunk.
        # Requires DEVICE_TERMINATION (the accept/reject fold rides the
        # chunk carry) and the KV pool (resolved at start, like the
        # pool's own mesh fallback). ``spec_draft_seed`` is the random-
        # init seed for a path-less draft (tests pin it to get a draft
        # that genuinely disagrees with the target).
        if spec_decode and not device_termination:
            raise ValueError("SPEC_DECODE requires DEVICE_TERMINATION")
        if spec_decode and spec_draft_k < 1:
            raise ValueError(
                f"SPEC_DRAFT_K must be >= 1, got {spec_draft_k}")
        self.spec_decode = bool(spec_decode)
        self.spec_draft_k = int(spec_draft_k)
        self.spec_draft_model = spec_draft_model
        self.spec_draft_path = spec_draft_path
        self.spec_draft_seed = spec_draft_seed
        self._use_spec = False        # resolved at start (pool gate)
        self._spec_live = False       # False after a draft:die drill
        self._spec_steps = 0          # verify iterations per chunk
        self._chunk_tokens = chunk_len  # max tokens one chunk can emit
        self._spec_drafted = 0        # cumulative draft proposals
        self._spec_accepted = 0       # cumulative accepted drafts
        self._spec_degraded = 0       # draft-engine-death degradations
        self._draft_sharded = False   # draft world rides the mesh
        self._draft_kv_fallback = False  # draft KV replicated (gather)
        self._draft_cfg = None
        self._draft_params = None
        self._draft_cache = None
        self._draft_prefill_fns: dict = {}   # (bucket, kv_limit) -> jit
        self._spec_chunk_fns: dict = {}      # kv bucket -> jitted spec fn
        self.watchdog_secs = watchdog_secs
        # Cold-start grace (VERDICT r5 weak #4): until the scheduler has
        # consumed its first pipeline entry — and whenever an admission is
        # mid-flight on the scheduler thread — the watchdog widens its
        # no-progress limit to this value, so a >watchdog_secs cold 7B
        # compile (observed >2 min on the real-checkpoint start) is not
        # mis-read as a hung device dispatch that degrades the engine and
        # fails every waiting slot. A genuinely hung dispatch DURING
        # serving still trips at watchdog_secs.
        self.startup_grace_secs = max(startup_grace_secs, 0.0)
        # Admission-scratch HBM budget (MB): group admissions allocate
        # kpad × suffix-depth scratch KV; kpads whose scratch would exceed
        # this are dropped per shape (admit_kpads_for). 0 = uncapped.
        self.admit_scratch_mb = max(0, admit_scratch_mb)
        # Serializes the group-admission scratch between the scheduler and
        # the background admission warm: the two must never hold kpad-row
        # scratch caches at the same time (the r5 bs=64 OOM had warm-thread
        # duplicates doubling peak scratch). Admissions never BLOCK on it —
        # a contended lock falls back to single admissions.
        self._admit_scratch_lock = threading.Lock()
        self._admit_kpad_caps: dict = {}   # scratch depth -> max kpad
        self._first_consumed = False       # first pipeline entry consumed
        # Bounded admission (overload shedding): submissions beyond this
        # queue depth raise EngineOverloaded at submit time instead of
        # waiting llm_timeout for a slot that cannot come. 0 = unbounded.
        self.max_queue_depth = max(0, max_queue_depth)
        # QoS ring (ISSUE 7): preemptive-decode policy knobs. The queue
        # itself (fair-share WDRR + tenant caps + scan-time expiry) is
        # built below as self._admissions; the brownout controller trims
        # effective batch/background slot shares when interactive queue
        # wait breaches its SLO.
        self.preempt_wait_ms = max(0.0, preempt_wait_ms)
        self.preempt_budget = max(0, preempt_budget)
        self._brownout = BrownoutController(slo_interactive_ms)
        # Telemetry plane (ISSUE 8): the goodput ledger classifies every
        # device decode step this engine burns (delivered vs the waste
        # classes — obs/ledger.py), fed at the exact sites that already
        # count those events; the SLO engine judges TTFT and queue-wait
        # samples per lane against their targets and serves multi-window
        # burn rates (obs/slo.py), which also feed the brownout
        # controller as an early-trim signal.
        self.ledger = GoodputLedger(enabled=ledger_enable)
        self._slo = SloEngine(
            {SLO_TTFT: slo_ttft_ms, SLO_QUEUE_WAIT: slo_interactive_ms,
             SLO_SESSION_TTFT: slo_session_ttft_ms},
            objective=slo_objective, windows=tuple(slo_windows))
        # Per-session token budgets (ISSUE 20): charged at delivery on
        # the scheduler thread, read at classification on the event
        # loop — same policy object type as the fake so budget
        # semantics can't diverge.
        self._session_budgets = SessionBudgets(session_token_budget)
        # Perf-regression sentinel (ISSUE 15, obs/steptime.py): one
        # sample per decode-chunk cycle (the dispatch-to-dispatch
        # interval while the pipe stays busy — it covers exactly one
        # consume, so device slowdowns, fetch stalls, AND scheduler
        # stalls all stretch it) keyed by (phase, kv bucket), plus one
        # per admission prefill. ``perf_baselines`` is a loaded table
        # or a PERF_BASELINES file path; absent an entry, each digest
        # self-calibrates from its first samples.
        self._steptime = StepTimeSentinel(
            enabled=sentinel_enable, window=sentinel_window,
            factor=sentinel_factor, min_samples=sentinel_min_samples,
            baselines=perf_baselines)
        # (t, phase, bucket, tokens) of the previous chunk dispatch +
        # whether a consume happened since — the pair that gates a
        # dispatch interval into a step-time sample. A depth-1 pipe
        # never satisfies the busy condition (no chunk in flight at
        # dispatch) and simply yields no samples.
        self._steptime_pending = None
        self._steptime_consumed = False
        self._preemptions = 0          # cumulative preempt-and-replay count
        self._preempted_tokens = 0     # generated tokens carried across them
        self._preempt_times: collections.deque = collections.deque(maxlen=512)
        self._preempt_for_lane: Optional[str] = None
        # Per-lane completion timestamps so Retry-After on a shed is
        # priced from the SHED LANE's own drain rate (a background shed
        # must not quote the interactive lane's brisk drain).
        self._lane_finish: dict = {}
        #: testing/faults.py injector (admit / chunk / decode / scheduler
        #: points); None in normal serving.
        self.faults = faults
        # Fault containment (ISSUE 5, the INNER ring): device-side slot
        # health detection + quarantine + reset-and-replay. The
        # supervisor owns policy/counters; this scheduler owns the
        # mechanism (_contain_poisoned_step / _reset_decode_state /
        # _replay_slot). SLOT_HEALTH_CHECK=false drops the in-chunk
        # detection (the step-exception containment stays).
        self.slot_health_check = slot_health_check
        self.supervisor = EngineSupervisor(
            retry_budget=quarantine_retry_budget,
            max_resets_per_min=reset_max_per_min)
        # Bisection probation (step-wide poison, culprit unknown): slots
        # parked out of the batch while the probe half replays. Each
        # entry is a _Slot with its detok (generated-so-far prefix) and
        # timings intact; unparked slots resume via _replay_slot.
        self._parked: List[_Slot] = []
        self._probation_clean = 0  # clean chunks consumed this probation
        self._rejections = 0       # EngineOverloaded sheds (stats())
        # Completion timestamps feeding the live drain-rate estimate that
        # prices Retry-After on sheds. Appended from the scheduler thread,
        # read racily from the event loop — fine for a hint.
        self._finish_times: collections.deque = collections.deque(maxlen=64)
        # (t, completion_tokens) per finish, feeding the windowed
        # engine_tokens_per_sec gauge via stats(). Scheduler-thread
        # appends, racy event-loop reads — fine for a gauge. maxlen bounds
        # memory; 4096 finishes inside one window is beyond the gauge's
        # resolution needs anyway.
        self._token_finishes: collections.deque = collections.deque(maxlen=4096)
        # Pipeline observability (ISSUE 4 satellite): cumulative decode
        # steps executed for already-terminated slots (should sit at ~0
        # with the device-resident done mask), chunk dispatch/consume/
        # prune counts, fetch-latency samples (drained by the /metrics
        # scrape into the chunk_fetch_seconds histogram), the last
        # consumed chunk's device-reported live-slot count, and a ring of
        # per-chunk dispatch/consume events (GET /debug/chunks). All
        # written by the scheduler thread, read racily by scrapes — fine
        # for gauges.
        self._wasted_steps = 0
        self._chunks_dispatched = 0
        self._chunks_consumed = 0
        self._chunks_pruned = 0
        self._fetch_samples: collections.deque = collections.deque(maxlen=4096)
        self._last_n_alive = 0
        self._chunk_log: collections.deque = collections.deque(maxlen=512)
        # Engine-side spans (obs/trace.py): cumulative per-name totals for
        # /health.spans; the scheduler thread's own intervals, which go
        # into the ring above and, as TraceAnnotations, into a
        # /debug/profile capture on the device ops' clock; and the stamp
        # the queue_wait span splits on — when the free-slot count last
        # left 0 (None while no slot is free).
        self._spans = EngineSpans(self._chunk_log,
                                  annotate=jax.profiler.TraceAnnotation)
        # Fair-share admission (the ISSUE 7 tentpole): weighted
        # deficit-round-robin over per-tenant sub-queues replaces the
        # FIFO queue.Queue — same put/get/qsize surface, plus per-tenant
        # caps, flood-preferring displacement, and scan-time expiry
        # (an expired request stops occupying MAX_QUEUE_DEPTH the moment
        # it is dead, counted as queue_expired instead of served).
        self._admissions: QoSQueue = QoSQueue(
            max_depth=self.max_queue_depth,
            tenant_cap=max(0, tenant_max_queue),
            weights=lane_weights,
            on_expire=self._expire_queued)
        self._worker: Optional[threading.Thread] = None
        self._running = False
        self._group_admitted = 0   # batched group admissions served
        self._last_progress = time.monotonic()
        self._last_admit_t = 0.0   # burst-ramp momentum (see _worker_loop)
        self._ramp_hold_t0 = None  # when the current ramp hold engaged
        self._stopping = False     # drain in progress (see stop())
        self._admitting = 0        # requests popped but not yet slotted —
                                   # drain must count them as busy (an
                                   # admission's prefill can run for
                                   # seconds on the scheduler thread)
        self._admitting_reqs: List[_Request] = []
                                   # the popped requests themselves: in
                                   # neither _slots nor the queue, so if
                                   # the scheduler thread dies mid-
                                   # admission (BaseException) only this
                                   # list lets the supervisor requeue
                                   # them instead of leaking a generate()
                                   # blocked forever

    @classmethod
    def from_config(cls, cfg, faults=None) -> "BatchedJaxEngine":
        """``faults=None`` parses FAULT_POINTS itself (standalone use);
        the factory passes its single shared injector instead so admit/
        chunk/generate points live on one object."""
        from ..models.config import get_config
        from ..testing.faults import FaultInjector

        if faults is None:
            faults = FaultInjector.from_spec(cfg.fault_points)
            if faults is not None and faults.has("generate"):
                # Standalone from_config can't install the ChaosEngine
                # wrapper the generate point needs — refuse rather than
                # run a drill that silently does less than its spec.
                raise ValueError(
                    "FAULT_POINTS 'generate' requires the ChaosEngine "
                    "wrapper; build via server.factory.build_engine"
                )
        return cls(
            get_config(cfg.model_name),
            model_path=cfg.model_path,
            tokenizer_path=cfg.tokenizer_path,
            dtype=cfg.dtype,
            quant=cfg.quant,
            kv_quant=cfg.kv_quant,
            max_seq_len=cfg.max_seq_len,
            prefill_buckets=cfg.prefill_bucket_list,
            top_k=cfg.top_k,
            top_p=cfg.top_p,
            attn_impl=cfg.attn_impl,
            moe_impl=cfg.moe_impl,
            prefix_cache=cfg.hbm_prefix_cache,
            mesh_shape=cfg.mesh_shape,
            dcn_mesh_shape=cfg.dcn_mesh_shape,
            batch_size=cfg.decode_batch_size,
            chunk_len=cfg.chunk_len,
            chunk_pipe_depth=cfg.chunk_pipe_depth,
            kv_pool=cfg.kv_pool,
            kv_pool_page=cfg.kv_pool_page,
            kv_pool_blocks=cfg.kv_pool_blocks,
            radix_cache=cfg.radix_cache,
            radix_lru_blocks=cfg.radix_lru_blocks,
            host_kv_blocks=cfg.host_kv_blocks,
            state_snapshots=cfg.state_snapshots,
            grammar_decode=cfg.grammar_decode,
            grammar_profile=cfg.grammar_profile,
            grammar_forced_run_min=cfg.grammar_forced_run_min,
            spec_decode=cfg.spec_decode,
            spec_draft_k=cfg.spec_draft_k,
            spec_draft_model=cfg.spec_draft_model,
            spec_draft_path=cfg.spec_draft_path,
            watchdog_secs=cfg.engine_watchdog_secs,
            startup_grace_secs=cfg.engine_startup_grace_secs,
            admit_scratch_mb=cfg.admit_scratch_mb,
            max_queue_depth=cfg.max_queue_depth,
            device_termination=cfg.device_termination,
            slot_health_check=cfg.slot_health_check,
            quarantine_retry_budget=cfg.quarantine_retry_budget,
            reset_max_per_min=cfg.engine_reset_max_per_min,
            lane_weights=cfg.lane_weight_map,
            tenant_max_queue=cfg.tenant_max_queue,
            preempt_wait_ms=cfg.preempt_wait_ms,
            preempt_budget=cfg.preempt_budget,
            slo_interactive_ms=cfg.slo_interactive_ms,
            ledger_enable=cfg.ledger_enable,
            slo_ttft_ms=cfg.slo_ttft_ms,
            slo_session_ttft_ms=cfg.slo_session_ttft_ms,
            session_token_budget=cfg.qos_session_token_budget,
            slo_windows=cfg.slo_window_list,
            slo_objective=cfg.slo_objective,
            sentinel_enable=cfg.sentinel_enable,
            sentinel_window=cfg.sentinel_window,
            sentinel_factor=cfg.sentinel_factor,
            sentinel_min_samples=cfg.sentinel_min_samples,
            perf_baselines=cfg.perf_baselines or None,
            faults=faults,
        )

    # ------------------------------------------------------------ startup

    def _start_blocking(self) -> None:
        t0 = time.monotonic()
        self._stopping = False       # support stop() → start() restarts
        self._first_consumed = False  # re-arm the cold-start watchdog grace
        self._setup_compile_cache()
        self._setup_mesh()
        # Speculative decoding under the mesh (ISSUE 18): the draft
        # world is mesh-native — draft params/cache shard per
        # parallel/sharding.py::draft_cache_specs and the spec chunk
        # compiles against the mesh — so spec now composes with tp/ep.
        # What stays refused is a >1 data/pipe/seq axis: the spec pool's
        # blocks are a shared cross-slot structure and the draft stack
        # rides the mesh whole (no pipeline split). Config validation
        # mirrors this jax-free; this is the belt-and-braces check for
        # direct construction.
        if (self.spec_decode and self.mesh is not None and any(
                self.mesh.shape[a] > 1 for a in ("data", "pipe", "seq"))):
            raise ValueError(
                "SPEC_DECODE does not compose with a mesh that has a "
                ">1 data/pipe/seq axis (MESH_SHAPE); use a tensor/"
                "expert-parallel mesh or disable one of them")
        self._load()
        # Which attention serves, decided once (engine/regime.py). The
        # block pool is the default layout and composes with TP/EP
        # meshes (the pool cache shards on the KV-head axis,
        # parallel/sharding.py::pool_cache_specs; block tables stay
        # per-slot host numpy); a >1 data/pipe/seq axis forces the dense
        # ladder, and that fallback is LOUD: kv_pool_mesh_fallback rides
        # /health + /metrics.
        backend = jax.default_backend()
        regime, self.kv_pool_page, reason = resolve_attention_regime(
            self.model_cfg, backend=backend,
            mesh_shape=(dict(self.mesh.shape) if self.mesh is not None
                        else None),
            kv_quant=self.kv_quant, kv_pool=self.kv_pool,
            device_termination=self.device_termination,
            pool_page=self.kv_pool_page, force_ragged=self.force_ragged)
        self._attention_regime = regime
        self._attention_regime_reason = reason
        self._use_pool = regime != DENSE
        self._use_ragged = regime == RAGGED
        # KV heads of a pool row that share a lane tile: the compiled
        # kernel reads heads of 64 two a row of 128 lanes, and the pool is
        # made so (ops/ragged_attention.py::lane_heads); 1 everywhere else.
        self._lane_heads = (
            lane_heads(self.model_cfg.head_dim,
                       self.model_cfg.kv_heads_paged)
            if self._use_ragged and backend == "tpu" else 1)
        refusal = cache_refusal(
            self.model_cfg, regime,
            dict(self.mesh.shape) if self.mesh is not None else None,
            self.kv_quant, self.spec_decode)
        if refusal:
            logger.error("%s", refusal)
            raise ValueError(refusal)
        # The grouped expert path counts the experts it reads
        # (models/transformer.py::_moe_mlp's own rule).
        self._counts_experts = self._use_pool and serves_grouped(
            self.model_cfg, self.mesh, self.moe_impl)
        # The static ``attn_impl`` of every chunk-program forward.
        self._decode_impl = RAGGED if self._use_ragged else "dense"
        self._kv_pool_mesh_fallback = self.kv_pool and not self._use_pool
        # A warning where the operator's pool is refused by the mesh or
        # a TPU reads it through the gather; information otherwise (the
        # CPU derives ``gather``).
        fell_back = self.kv_pool and (
            regime == DENSE or (backend == "tpu" and regime != RAGGED))
        logger.log(logging.WARNING if fell_back else logging.INFO,
                   "attention regime %s (pool page %d): %s",
                   regime, self.kv_pool_page, reason)
        if self.grammar_decode and self._grammar is None:
            # Grammar runtime (ISSUE 11): compile the kubectl grammar
            # against THIS tokenizer. Host numpy truth; the stacked
            # fixed-shape tables upload to device at dispatch time
            # (refreshed whenever a per-request variant installs).
            # Kept across stop() → start() restarts (weight swaps don't
            # change the tokenizer, and the compile costs seconds at a
            # real vocab).
            from ..constrain import GrammarRuntime, assert_safety_consistent

            assert_safety_consistent()
            self._grammar = GrammarRuntime(
                self.tokenizer, self.model_cfg.vocab_size,
                self.model_cfg.eos_ids, profile=self.grammar_profile,
                forced_run_min=self.grammar_forced_run_min)
            logger.info(
                "grammar-constrained decode on: profile=%s hash=%s "
                "states=%d classes=%d",
                self.grammar_profile,
                self._grammar.health()["grammar_hash"],
                self._grammar.health()["states"],
                self._grammar.health()["classes"])
        # Speculative decoding (ISSUE 12 → ISSUE 18): resolve + load
        # the draft model. Pool-only — the rejected-row discipline
        # ("last generated row unwritten", replay chains stop at
        # emitted[:-1]) is the pool contract, and the pool is the
        # default layout; the dense ladder falls back to plain decode.
        # tp/ep meshes serve sharded (data/pipe/seq were refused above,
        # which keeps _use_spec implying mesh_pool_ok).
        self._use_spec = self.spec_decode and self._use_pool
        if self.spec_decode and not self._use_pool:
            logger.warning(
                "SPEC_DECODE requires the block-paged KV pool; serving "
                "plain (non-speculative) decode")
        if self._use_spec:
            from ..models.config import get_config as _get_model_config
            from ..models.transformer import init_params
            draft_cfg = _get_model_config(self.spec_draft_model)
            if draft_cfg.vocab_size != self.model_cfg.vocab_size:
                raise ValueError(
                    f"SPEC_DRAFT_MODEL {self.spec_draft_model!r} has "
                    f"vocab {draft_cfg.vocab_size}, target "
                    f"{self.model_cfg.name!r} has "
                    f"{self.model_cfg.vocab_size} — draft and verifier "
                    f"must share one tokenizer")
            self._draft_cfg = draft_cfg
            if self._draft_params is not None:
                # Restart (weight swap / fleet rejoin): the draft's
                # PARAMS survive — a rollout swaps the target weights —
                # while its KV world rebuilds in _init_decode_state like
                # a containment reset.
                pass
            elif self.spec_draft_path:
                from ..models.convert import convert_hf_checkpoint
                logger.info("Loading draft checkpoint from %s",
                            self.spec_draft_path)
                self._draft_params = convert_hf_checkpoint(
                    draft_cfg, self.spec_draft_path, dtype=self.dtype)
            else:
                dseed = (self.spec_draft_seed
                         if self.spec_draft_seed is not None
                         else self.seed + 1)
                logger.warning(
                    "No SPEC_DRAFT_PATH; random-initializing draft %s "
                    "(toy/dev mode, seed %d)", draft_cfg.name, dseed)
                self._draft_params = init_params(
                    jax.random.PRNGKey(dseed), draft_cfg,
                    dtype=self.dtype)
            # Draft world on the mesh (ISSUE 18): the draft's params
            # shard through the SAME policy as the target's (Megatron
            # column/row splits, vocab-sharded embed/head) so the 2B's
            # forwards and its residual path ride the f≈1 layout PR 14
            # gave the 7B. Its KV cache shards on the KV-head axis
            # (draft_cache_specs) — when the draft's KV heads don't
            # divide tp (gemma-2b-it's single head under tp=8) the
            # cache replicates and draft attention runs gathered:
            # correct, slower, and LOUD (_draft_kv_fallback rides
            # /health + /metrics).
            if self.mesh is not None and self.mesh.size > 1:
                from ..parallel.sharding import (draft_kv_fallback,
                                                 shard_params)
                self._draft_params = shard_params(
                    self._draft_params, self.mesh, draft_cfg)
                self._draft_sharded = True
                self._draft_kv_fallback = draft_kv_fallback(
                    self.mesh, draft_cfg)
                if self._draft_kv_fallback:
                    logger.warning(
                        "draft %s KV heads (%d) do not divide the "
                        "mesh's model axis (%d); draft KV serves "
                        "replicated (gather fallback)",
                        draft_cfg.name, draft_cfg.n_kv_heads,
                        self.mesh.shape["model"])
            else:
                self._draft_sharded = False
                self._draft_kv_fallback = False
            self._spec_steps = max(
                1, self.chunk_len // (self.spec_draft_k + 1))
            self._chunk_tokens = self._spec_steps * (self.spec_draft_k
                                                     + 1)
            self._spec_live = True
            logger.info(
                "speculative decode on: draft=%s k=%d (%d verify "
                "iterations x %d tokens per chunk)",
                draft_cfg.name, self.spec_draft_k, self._spec_steps,
                self.spec_draft_k + 1)
        else:
            self._spec_steps = 0
            self._chunk_tokens = self.chunk_len
            self._spec_live = False
            self._draft_sharded = False
            self._draft_kv_fallback = False
        if not self._use_pool:
            self._build_prefill_fns()
            self._init_prefix_cache()
        cfg = self.model_cfg
        N, S = self.batch_size, self.max_seq_len
        # The slot caches carry one chunk of slack past max_seq so the final
        # chunk of a near-capacity slot can always run at full chunk_len —
        # one compiled chunk program, no tail-length variants to compile
        # mid-serving, and tail tokens are never cut off at chunk
        # granularity. A slot is exhausted once pos >= max_seq (sweep), so
        # writes stay < S + chunk_len by construction. (A speculative
        # chunk can emit up to _chunk_tokens — more than chunk_len when
        # chunk_len < k+1 — so the slack covers the larger of the two.)
        S_alloc = S + max(self.chunk_len, self._chunk_tokens)

        if self._use_pool:
            # Pool geometry: S_alloc page-rounds so every per-slot table
            # has a whole number of pages; kv buckets are 128-tiled, and
            # the page divides 128 by the constructor check, so every
            # gather width is a whole page count.
            S_alloc = -(-S_alloc // self.kv_pool_page) * self.kv_pool_page
            self._pool_max_pages = S_alloc // self.kv_pool_page
            self._pool_n_blocks = (self.kv_pool_blocks
                                   or N * self._pool_max_pages)
            if self._pool_n_blocks < self._pool_max_pages:
                raise ValueError(
                    f"KV_POOL_BLOCKS={self._pool_n_blocks} cannot hold "
                    f"even one full-length sequence "
                    f"({self._pool_max_pages} pages)")
            self._pool_prefill_kv_buckets = kv_bucket_ladder(S_alloc)
            if self._use_ragged:
                self._attention_steps = self._resolve_attention_steps()
                logger.info("ragged kernel: %d KV pages a grid step, %d "
                            "grid steps a decode call, its live blocks "
                            "streamed through %d buffers",
                            *self._attention_steps)
        # Attention cost under ``gather`` and ``dense`` grows with the
        # KV span read, so the chunk program is compiled per KV *bucket*
        # — a pow2 ladder topped by S_alloc — and dispatch picks the
        # smallest bucket covering every live position. All buckets are
        # warmed at startup, so bucket growth never compiles
        # mid-serving. The ragged kernel needs no ladder (cost tracks
        # live pages per slot inside one program), and prefill reads
        # through the same kernel, so its ladder collapses to one
        # kv_limit too (_pool_prefill_span): the draft model's dense
        # prefill is the only remaining ladder client.
        self._kv_buckets = ((S_alloc,) if self._use_ragged
                            else kv_bucket_ladder(S_alloc))

        eos_ids = tuple(sorted(set(cfg.eos_ids)))

        def chunk_forward_step(kv_limit):
            """The model call the shared chunk body runs per step:
            forward over cache[:, :kv_limit] with the live mask gating
            MoE capacity (token_mask) and the KV scatter (write_mask).
            Pool mode threads the per-slot block table through — every
            KV write and read then routes the [n_blocks, page] pool."""

            if self._use_pool:
                def step(params, tok, pos, cache, live, tables):
                    # mesh rides into the pool path too (ISSUE 14):
                    # KV-head-sharded pool scatter/gather, f≈1 residual
                    # constraints, and the shard_mapped pool kernel.
                    return forward(params, cfg, tok, pos, cache,
                                   kv_limit=kv_limit,
                                   attn_impl=self._decode_impl,
                                   mesh=self.mesh,
                                   moe_impl=self.moe_impl,
                                   token_mask=live[:, None],
                                   write_mask=live,
                                   block_tables=tables)

                return step

            def step(params, tok, pos, cache, live):
                return forward(params, cfg, tok, pos, cache,
                               kv_limit=kv_limit,
                               attn_impl=self._decode_impl,
                               mesh=self.mesh,
                               moe_impl=self.moe_impl,
                               token_mask=live[:, None],
                               write_mask=live)

            return step

        def batched_chunk(kv_limit):
            # The device-termination chunk body lives in
            # make_termination_chunk_fn (module level): ``force`` is the
            # host's view of live slots (excludes freed/exhausted),
            # ``active``/``ngen`` the device-resident carry, ``budget``
            # the per-slot max_tokens vector set at splice time, ``seeds`` the
            # per-request sampling seeds, ``corrupt`` the decode:nan
            # fault seam; ONE packed buffer (pinned replicated under a
            # mesh) returns tokens + termination + occupancy + per-slot
            # health in a single fetch per chunk.
            return make_termination_chunk_fn(
                chunk_forward_step(kv_limit), self.chunk_len, eos_ids,
                self.top_k, self.top_p, vocab_size=cfg.vocab_size,
                health_check=self.slot_health_check,
                finalize=self._replicated,
                pool_tables=self._use_pool,
                grammar=self._grammar is not None,
                grammar_s_max=(self._grammar.S_max
                               if self._grammar is not None else 0))

        def batched_chunk_legacy(params, tok, pos, cache, seeds, temps,
                                 force, active, ngen, budget, corrupt,
                                 tables=None, *,
                                 kv_limit):
            """DEVICE_TERMINATION=false: the pre-ISSUE-4 chunk body —
            every force-live slot decodes the full chunk (finished slots
            keep producing garbage the host discards after its EOS scan).
            Same signature and packed-buffer contract as ``batched_chunk``
            so the dispatch/consume plumbing is identical; the done mask
            is all-False (the host scan decides) and live_lengths advance
            by the full chunk. Health detection still runs (sticky over
            the chunk) — the legacy path is an A/B for termination, not
            an opt-out of corruption containment — but nothing freezes:
            the host-side quarantine pass discards the chunk."""

            def body(carry, _):
                tok, pos, cache, ngen, health = carry
                logits, cache = forward(params, cfg, tok, pos, cache,
                                        kv_limit=kv_limit,
                                        attn_impl=self._decode_impl,
                                        mesh=self.mesh,
                                        moe_impl=self.moe_impl,
                                        token_mask=force[:, None],
                                        block_tables=tables)
                step_logits = logits[:, 0]
                step_logits = jnp.where(corrupt[:, None],
                                        jnp.float32(jnp.nan), step_logits)
                nxt = sample_tokens_seeded(step_logits, seeds, ngen, temps,
                                           top_k=self.top_k,
                                           top_p=self.top_p)
                with jax.named_scope("sampling"):
                    if self.slot_health_check:
                        bad = jnp.logical_not(
                            jnp.all(jnp.isfinite(step_logits), axis=-1))
                        health = health | jnp.where(
                            jnp.logical_and(force, bad),
                            HEALTH_NONFINITE, 0)
                        bad_tok = jnp.logical_or(
                            nxt < 0, nxt >= cfg.vocab_size)
                        health = health | jnp.where(
                            jnp.logical_and(force, bad_tok),
                            HEALTH_TOKEN_RANGE, 0)
                    nxt = jnp.where(force, nxt, tok[:, 0])
                    pos = pos + force.astype(jnp.int32)[:, None]
                    ngen = ngen + force.astype(jnp.int32)
                return (nxt[:, None], pos, cache, ngen, health), nxt

            health0 = jnp.zeros_like(ngen)
            (tok, pos, cache, ngen, health), toks = jax.lax.scan(
                body, (tok, pos, cache, ngen, health0), None,
                length=self.chunk_len
            )
            toks = jnp.swapaxes(toks, 0, 1)
            packed = self._replicated(
                pack_chunk(toks, jnp.zeros_like(force), ngen,
                           jnp.sum(force), health=health, xp=jnp))
            return packed, tok, pos, cache, active, ngen

        def chunk_body(kv_limit):
            if self.device_termination:
                return batched_chunk(kv_limit)
            return partial(batched_chunk_legacy, kv_limit=kv_limit)

        # Keyed by KV bucket alone (one fixed chunk_len here) — distinct
        # from the parent's (chunk_len, kv_limit)-keyed self._chunk_fns.
        # The grammar FSM-state vector is donated like the rest of the
        # chained carry (its position depends on whether the pool table
        # argument precedes it).
        donate = (1, 2, 3, 7, 8)
        if self._grammar is not None:
            donate = donate + ((12,) if self._use_pool else (11,))
        if not getattr(self, "_batch_chunk_fns", None):
            # First start only: stop() → start() restarts (weight
            # swaps, fleet rejoins) reuse the jitted program set —
            # params are a traced argument of unchanged shape, so a
            # swapped replica's first request re-executes warm programs
            # instead of paying a multi-second re-trace + compile.
            self._batch_chunk_fns = {
                b: jax.jit(chunk_body(b), donate_argnums=donate)
                for b in self._kv_buckets
            }

        def ragged_forward_step_fn(kv_limit):
            """The prologue's model call: one forward over a [N, W]
            mixed window through the ragged kernel — per-slot q_lens
            pick each row's valid prefix, the 2-D write mask gates the
            KV scatter to exactly those columns, and logits_at keeps
            only the last valid position's row (the one the fold
            samples from). Its residual stream is the window's valid
            rows, packed (ISSUE 39): the staged suffixes' lengths sum to
            at most W (``regime.stage_window``) and every other slot rides one
            column, so W + N rows hold them, where N x W were computed."""

            def rstep(params, tok, pos, cache, wmask, tables, q_lens):
                return forward(params, cfg, tok, pos, cache,
                               kv_limit=kv_limit,
                               attn_impl="ragged",
                               mesh=self.mesh,
                               moe_impl=self.moe_impl,
                               token_mask=wmask,
                               write_mask=wmask,
                               block_tables=tables,
                               q_lens=q_lens,
                               logits_at=jnp.maximum(q_lens, 1) - 1,
                               packed_rows=sum(tok.shape))

            return rstep

        if self._use_ragged:
            # One ragged mixed-chunk program per ADMISSION WIDTH (the
            # prefill bucket the staged suffixes pad to) — this set
            # replaces the legacy (bucket, kv_limit) prefill ladder
            # (|buckets| x |kv ladder| programs) plus the per-kv-bucket
            # chunk ladder, which is the compiled-program-count drop
            # the warmup test asserts. Same donation layout as the
            # plain set (adm args trail, so the indices hold). The
            # ``if not in`` guard keeps warm-swap restarts retrace-free
            # (PR 13).
            def ragged_chunk_body(adm_w):
                kvl = self._kv_buckets[-1]
                return make_termination_chunk_fn(
                    chunk_forward_step(kvl), self.chunk_len, eos_ids,
                    self.top_k, self.top_p, vocab_size=cfg.vocab_size,
                    health_check=self.slot_health_check,
                    finalize=self._replicated,
                    pool_tables=True,
                    grammar=self._grammar is not None,
                    grammar_s_max=(self._grammar.S_max
                                   if self._grammar is not None else 0),
                    ragged_w=adm_w,
                    ragged_forward_step=ragged_forward_step_fn(kvl))

            for w in self.prefill_buckets:
                if (w, False) not in self._ragged_chunk_fns:
                    self._ragged_chunk_fns[(w, False)] = jax.jit(
                        ragged_chunk_body(w), donate_argnums=donate)

        if self._use_spec:
            # Speculative draft/verify chunk programs (ISSUE 12 →
            # ISSUE 18), one per KV bucket beside the plain set — both
            # stay compiled so a draft:die drill flips to plain decode
            # mid-stream with zero recompiles (on a mesh: both PROGRAM
            # SETS compile against the mesh at warmup, so the flip is
            # recompile-free there too). The draft runs a dense
            # per-slot cache at the SAME kv_limit (positions are
            # shared) and never a pool kernel; it DOES ride the
            # serving mesh — its forwards and residual path shard
            # through the same f≈1 policy as the target's
            # (parallel/sharding.py), with the KV-head axis replicating
            # when it doesn't divide tp (draft_kv_fallback).
            dcfg = self._draft_cfg

            def draft_forward_step(kv_limit):
                def dstep(dparams, tok, pos, dcache, live):
                    return forward(dparams, dcfg, tok, pos, dcache,
                                   kv_limit=kv_limit, attn_impl="dense",
                                   mesh=self.mesh, moe_impl="dense",
                                   token_mask=live[:, None],
                                   write_mask=live)

                return dstep

            def spec_chunk_body(kv_limit):
                return make_termination_chunk_fn(
                    chunk_forward_step(kv_limit), self.chunk_len,
                    eos_ids, self.top_k, self.top_p,
                    vocab_size=cfg.vocab_size,
                    health_check=self.slot_health_check,
                    finalize=self._replicated,
                    pool_tables=True,
                    grammar=self._grammar is not None,
                    grammar_s_max=(self._grammar.S_max
                                   if self._grammar is not None else 0),
                    spec_k=self.spec_draft_k,
                    spec_steps=self._spec_steps,
                    draft_forward_step=draft_forward_step(kv_limit))

            sdonate = (1, 2, 3, 7, 8, 13)
            if self._grammar is not None:
                sdonate = sdonate + (14,)
            if not self._spec_chunk_fns:   # restarts keep the programs
                self._spec_chunk_fns = {
                    b: jax.jit(spec_chunk_body(b), donate_argnums=sdonate)
                    for b in self._kv_buckets
                }

            if self._use_ragged:
                def spec_ragged_body(adm_w):
                    kvl = self._kv_buckets[-1]
                    return make_termination_chunk_fn(
                        chunk_forward_step(kvl), self.chunk_len,
                        eos_ids, self.top_k, self.top_p,
                        vocab_size=cfg.vocab_size,
                        health_check=self.slot_health_check,
                        finalize=self._replicated,
                        pool_tables=True,
                        grammar=self._grammar is not None,
                        grammar_s_max=(self._grammar.S_max
                                       if self._grammar is not None
                                       else 0),
                        spec_k=self.spec_draft_k,
                        spec_steps=self._spec_steps,
                        draft_forward_step=draft_forward_step(kvl),
                        ragged_w=adm_w,
                        ragged_forward_step=ragged_forward_step_fn(kvl))

                for w in self.prefill_buckets:
                    if (w, True) not in self._ragged_chunk_fns:
                        self._ragged_chunk_fns[(w, True)] = jax.jit(
                            spec_ragged_body(w), donate_argnums=sdonate)

        def splice(cache, src_k, src_v, tok, pos, temps, active, ngen,
                   budget, seeds, slot, n_prompt, first_tok, temperature,
                   max_toks, seed, ngen0):
            """Insert a prefilled request into slot ``slot``.
            ``first_tok`` is a [1] device array — admission never reads it
            back to the host; the token value travels to the client via the
            inflight pipeline. The termination state is armed here too:
            the slot's budget vector entry gets the request's max_tokens,
            its generated-count is set to ``ngen0`` (1 for a fresh
            admission — the admission-sampled first token; the
            generated-so-far count for a containment replay, which is
            what re-aligns the per-request RNG stream), its sampling
            seed lands in the seeds vector, and the device-live mask
            arms unless the budget is already spent."""
            with jax.named_scope("kv_splice"):
                k = kv_slot_update(cache.k, src_k, slot)
                v = kv_slot_update(cache.v, src_v, slot)
                lengths = cache.lengths.at[slot].set(n_prompt)
                tok = tok.at[slot, 0].set(first_tok[0])
                pos = pos.at[slot, 0].set(n_prompt)
                temps = temps.at[slot].set(temperature)
                active = active.at[slot].set(max_toks > ngen0)
                ngen = ngen.at[slot].set(ngen0)
                budget = budget.at[slot].set(max_toks)
                seeds = seeds.at[slot].set(seed)
            return (KVCache(k=k, v=v, lengths=lengths), tok, pos, temps,
                    active, ngen, budget, seeds)

        if getattr(self, "_splice_fn", None) is None:
            self._splice_fn = jax.jit(
                splice, donate_argnums=(0, 3, 4, 5, 6, 7, 8, 9))
        if not hasattr(self, "_batch_admit_fns"):
            self._batch_admit_fns = {}  # (kind, *shape) -> jitted program
            self._batch_ready = set()   # (kpad, sbucket, kv_limit) compiled
        self._S_alloc = S_alloc

        # Device-side scheduler state (slot vectors + KV cache) — built
        # by _init_decode_state so the fault-containment reset path
        # re-initializes EXACTLY what startup initialized. Under a
        # serving mesh, slots shard over ``data`` and KV heads over
        # ``model`` (parallel/sharding.py); the jitted chunk/splice
        # programs inherit these shardings, so XLA places the TP/EP
        # collectives and the donated buffers never move.
        self._init_decode_state()
        self._key_d = jax.random.PRNGKey(self.seed)
        self._slots: List[Optional[_Slot]] = [None] * N
        # Created HERE, not at worker-loop entry: a supervisor restart
        # replays survivors (which may enqueue "first" pipeline entries)
        # BEFORE the new loop thread runs — a loop-entry reset would
        # silently drop those entries and lose each replayed admission's
        # first token.
        self._inflight: List[tuple] = []

        if self._use_pool:
            self._pool_warmup()
            self._batch_warm_thread = None
        else:
            self._dense_warmup()
        self._post_warm_threads(t0)
        return

    def _dense_warmup(self) -> None:
        """Eager startup warm of the dense-ladder serving programs:
        smallest prefill bucket, every KV-bucket decode chunk, the
        splice, and the hot group-admission shape (by execution — the
        only safe time to run cache-donating programs)."""
        cfg = self.model_cfg
        N, S = self.batch_size, self.max_seq_len
        # Warm-up: smallest prefill bucket + the decode chunk + splice.
        b = self.prefill_buckets[0]
        scratch = self._new_cache(1, S)
        logits, scratch = self._prefill_fns[b](
            self.params,
            jnp.zeros((1, b), jnp.int32),
            jnp.broadcast_to(jnp.arange(b), (1, b)).astype(jnp.int32),
            scratch,
            jnp.ones((1, b), jnp.float32),
        )
        self._sample_fn(
            jnp.zeros((1, cfg.vocab_size), jnp.float32), self._key_d,
            jnp.asarray(0.0, jnp.float32),
        )
        (self._cache, self._tok_d, self._pos_d, self._temps_d,
         self._active_d, self._ngen_d, self._budget_d,
         self._seeds_d) = self._splice_fn(
            self._cache, scratch.k, scratch.v, self._tok_d, self._pos_d,
            self._temps_d, self._active_d, self._ngen_d, self._budget_d,
            self._seeds_d,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(1, jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(1, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32),
        )
        for kv_b in self._kv_buckets:
            packed = self._run_chunk(kv_b, jnp.zeros((N,), jnp.bool_),
                                     self._no_corrupt_d)
        # Warm the batched-admission programs. Group scratch is allocated
        # at SUFFIX depth now — kv_limit positions (prefix + suffix bucket,
        # tile-rounded), not S_alloc: a suffix admission only ever fills
        # prefix.n + sbucket slots, and on 7B geometry the S_alloc-deep
        # version was the controllable term in the bs=64 OOM (VERDICT r5
        # weak #3; kpad=16 × S_alloc ≈ 763 MB int8 vs ≈ 470 MB at the hot
        # depth). Two warm tiers:
        # - the hot shape (smallest suffix bucket) fully, by EXECUTION —
        #   this pre-worker moment is the only safe time to run the
        #   splice-into-slots program (it donates the live cache);
        # - other suffix buckets compile in the background warm, which
        #   AOT-primes their splice variants (different scratch depth =
        #   different program) without touching live buffers.
        if self._prefix is not None:
            from .prefix_cache import round_kv_limit

            P = self._prefix.n
            self._cap_admit_kpads(sorted({
                d for d in (round_kv_limit(P + b, self.max_seq_len)
                            for b in self.prefill_buckets)
                if d is not None
            }))
            sbucket = self.prefill_buckets[0]
            kvl = round_kv_limit(P + sbucket, self.max_seq_len)
            if kvl is not None:
                spos = jnp.broadcast_to(
                    P + jnp.arange(sbucket), (1, sbucket)).astype(jnp.int32)
                for kpad in self.admit_kpads_for(kvl):
                    scratch2 = self._new_cache(kpad, kvl)
                    scratch2 = self._get_batch_prefix_splice_fn(kpad)(
                        scratch2, self._prefix.k, self._prefix.v)
                    ft, scratch2 = self._get_batch_suffix_fn(
                        kpad, sbucket, kvl)(
                        self.params, jnp.zeros((kpad, sbucket), jnp.int32),
                        jnp.broadcast_to(spos, (kpad, sbucket)),
                        scratch2, jnp.ones((kpad, sbucket), jnp.float32),
                        jnp.ones((kpad,), jnp.int32),
                        jnp.zeros((kpad,), jnp.int32),
                        jnp.zeros((kpad,), jnp.float32),
                    )
                    # All rows out-of-bounds: exercises the program, splices
                    # nothing.
                    (self._cache, self._tok_d, self._pos_d, self._temps_d,
                     self._active_d, self._ngen_d, self._budget_d,
                     self._seeds_d) = (
                        self._get_batch_splice_fn(kpad)(
                            self._cache, scratch2.k, scratch2.v, self._tok_d,
                            self._pos_d, self._temps_d, self._active_d,
                            self._ngen_d, self._budget_d, self._seeds_d,
                            jnp.full((kpad,), N, jnp.int32),
                            jnp.zeros((kpad,), jnp.int32), ft,
                            jnp.zeros((kpad,), jnp.float32),
                            jnp.ones((kpad,), jnp.int32),
                            jnp.zeros((kpad,), jnp.int32),
                        )
                    )
                    del scratch2
                    self._batch_ready.add((kpad, sbucket, kvl))
        packed.block_until_ready()
        # Non-smallest suffix buckets compile in the background; group
        # admissions for those shapes fall back to singles until then.
        self._batch_warm_thread = threading.Thread(
            target=self._warm_batch_admit_shapes, name="batch-admit-warm",
            daemon=True,
        )
        self._batch_warm_thread.start()

    def _post_warm_threads(self, t0: float) -> None:
        """Start the scheduler/supervision threads once warm-up is done
        (shared tail of the pool and dense startup paths)."""
        cfg = self.model_cfg
        N = self.batch_size
        self._running = True
        self._worker = threading.Thread(
            target=self._worker_main, name="batch-scheduler", daemon=True
        )
        self._worker.start()
        # Scheduler-death supervision: a separate thread that notices the
        # scheduler thread dying (an uncatchable fault — scheduler:die in
        # drills, a segfaulting extension call in the wild would take the
        # process, but a raised BaseException lands here) and restarts it
        # after a reset-and-replay, dropping zero queued requests.
        threading.Thread(target=self._supervise_scheduler,
                         name="batch-supervisor", daemon=True).start()
        if self.watchdog_secs > 0:
            threading.Thread(target=self._watchdog_loop, name="batch-watchdog",
                             daemon=True).start()
        logger.info(
            "Batched engine ready: %s ×%d slots, chunk=%d, %.1fs",
            cfg.name, N, self.chunk_len, time.monotonic() - t0,
        )

    def _init_decode_state(self) -> None:
        """(Re-)initialize the device-resident scheduler state: the slot
        KV cache, token/position vectors, per-slot temperature, the
        device-termination carry (live mask / generated counts / token
        budgets), the per-request sampling-seed vector, and the all-clear
        decode:nan corruption mask. Called once at startup and again by
        the fault-containment reset path (_reset_decode_state) — one
        function so a reset can never drift from a fresh start."""
        N = self.batch_size
        if self._use_pool:
            # Pool mode: the shared block cache replaces per-slot dense
            # regions, and the HOST allocator/radix/tables are rebuilt
            # with it — a reset invalidates every cached block's KV, so
            # the whole ownership world restarts from empty (replays
            # re-allocate; the radix tree repopulates organically).
            self._cache = self._new_pool_cache()
            prev_pool, prev_radix = self._pool, self._radix
            prev_store, prev_state = self._host_store, self._state
            self._state = None
            if self.model_cfg.keeps_state:
                # One live state a slot rides the cache; the snapshot
                # store is device memory of its own, ``capacity`` rows,
                # whose host truth is rebuilt with the pool's (a reset
                # condemns every snapshot with the K/V it belongs to).
                cap = self.state_snapshots or 4 * N
                self._snap = KVCache.state_leaves_zeros(
                    self.model_cfg, cap, dtype=self.dtype,
                    ring=self.model_cfg.sliding_window)
                self._state = StateStore(
                    cap, N, self.model_cfg.state_bytes(),
                    snapshot_fn=self._state_snapshot_dev,
                    restore_fn=self._state_restore_dev,
                    zero_fn=self._state_zero_dev,
                    region=self._spans.sched.child)
                if prev_state is not None:
                    self._state.carry_counters(prev_state)
            self._pool = BlockPool(self._pool_n_blocks, self.kv_pool_page)
            # Two-tier rebuild (ISSUE 20): a reset condemns the host
            # tier too — its payloads were gathered from the poisoned
            # device world — so BOTH tiers restart empty.
            self._host_store = (
                HostBlockStore(self.host_kv_blocks)
                if self.host_kv_blocks > 0 and self.radix_cache else None)
            self._radix = (RadixCache(self._pool,
                                      max_blocks=self.radix_lru_blocks,
                                      host_store=self._host_store,
                                      offload_fn=self._pool_offload_block,
                                      onload_fn=self._pool_onload_block,
                                      faults=self.faults,
                                      state_store=self._state,
                                      region=self._spans.sched.child)
                           if self.radix_cache else None)
            # Cumulative counters survive the rebuild — the /metrics
            # delta-mirror must never see totals go backwards.
            if prev_pool is not None:
                self._pool.carry_counters(prev_pool)
            if prev_radix is not None and self._radix is not None:
                self._radix.carry_counters(prev_radix)
            if prev_store is not None and self._host_store is not None:
                self._host_store.carry_counters(prev_store)
            self._tables = np.full((N, self._pool_max_pages),
                                   self._pool_n_blocks, np.int32)
        else:
            self._cache = self._new_cache(N, self._S_alloc)
        self._tok_d = jnp.zeros((N, 1), jnp.int32)
        self._pos_d = jnp.zeros((N, 1), jnp.int32)
        self._temps_d = jnp.zeros((N,), jnp.float32)
        # Device-resident termination state: live mask, cumulative
        # completion-token counts, and per-slot token budgets. Carried
        # (donated) through every chunk so a slot that finishes inside
        # chunk N is already frozen in speculative chunks N+1.. without
        # any host involvement; splice re-arms all of these on admission.
        self._active_d = jnp.zeros((N,), jnp.bool_)
        self._ngen_d = jnp.zeros((N,), jnp.int32)
        self._budget_d = jnp.ones((N,), jnp.int32)
        # Per-request sampling seeds (set at splice time): every decode
        # step samples slot i under fold_in(PRNGKey(seeds[i]), ngen[i]),
        # the replay-parity contract (engine/sampling.py slot_keys).
        self._seeds_d = jnp.zeros((N,), jnp.int32)
        # decode:nan fault seam — all-False in normal serving; a drill
        # dispatch swaps in a mask that NaNs the target slot's logits.
        self._no_corrupt_d = jnp.zeros((N,), jnp.bool_)
        # Grammar FSM state words (ISSUE 11): global state 0 = profile
        # 0's DEAD state — harmless for empty slots (never live) and
        # re-armed by every admission/replay path.
        if self._grammar is not None:
            self._fsm_d = jnp.zeros((N,), jnp.int32)
        # Speculative decoding (ISSUE 12): the draft model's own dense
        # per-slot KV cache, rebuilt with everything else on a
        # containment reset (replays re-prefill it from host truth
        # exactly like the target's pool blocks).
        if self._use_spec:
            self._draft_cache = KVCache.zeros(
                self._draft_cfg, N, self._S_alloc, dtype=self.dtype)
            if self.mesh is not None:
                # Mesh-native draft world (ISSUE 18): KV heads over
                # ``model`` like the target's cache, slots over ``data``
                # (a no-op on pure-tp meshes); a non-dividing KV-head
                # axis sanitizes to replicated — the gather fallback.
                from ..parallel.sharding import shard_draft_cache
                self._draft_cache = shard_draft_cache(
                    self._draft_cache, self.mesh, self._draft_cfg)
        if self.mesh is not None:
            from ..parallel.sharding import shard_tokens

            self._tok_d = shard_tokens(self._tok_d, self.mesh)
            self._pos_d = shard_tokens(self._pos_d, self.mesh)
            self._temps_d = shard_tokens(self._temps_d, self.mesh)
            self._active_d = shard_tokens(self._active_d, self.mesh)
            self._ngen_d = shard_tokens(self._ngen_d, self.mesh)
            self._budget_d = shard_tokens(self._budget_d, self.mesh)
            self._seeds_d = shard_tokens(self._seeds_d, self.mesh)
            self._no_corrupt_d = shard_tokens(self._no_corrupt_d, self.mesh)
            if self._grammar is not None:
                self._fsm_d = shard_tokens(self._fsm_d, self.mesh)
        # the newest launch is one of the fills above: whatever handle of
        # the old world the spans held goes with it
        self._spans.launched(self._seeds_d)

    # ------------------------------------- block-paged KV pool (ISSUE 10)
    #
    # Ownership model: the HOST is truth — BlockPool refcounts + the
    # per-slot numpy table rows; device arrays only ever see table
    # SNAPSHOTS at dispatch. Freeing is immediate (no quiesce): every
    # device program executes in dispatch order on one stream, so a
    # stale in-flight chunk's writes to a freed block land BEFORE any
    # new owner's prefill/decode writes, and a new owner (re)writes
    # every row it will ever read — stale garbage can never surface.

    def _new_pool_cache(self) -> KVCache:
        """The shared [L, n_blocks, page, KV, hd] cache with the leaves of
        the configuration's kinds (models/transformer.py::
        KVCache.pool_zeros), a live state row a decode slot."""
        cfg = self.model_cfg
        make = functools.partial(
            KVCache.pool_zeros, cfg, n_blocks=self._pool_n_blocks,
            page=self.kv_pool_page, slots=self.batch_size,
            ring=cfg.sliding_ring(self.prefill_buckets[-1],
                                  self.kv_pool_page),
            dtype=self.dtype, kv_quant=self.kv_quant,
            counts_experts=self._counts_experts,
            lane_heads=self._lane_heads)
        if self.mesh is None:
            return make()
        # Pool-under-mesh (ISSUE 14): KV heads shard over ``model``
        # exactly like dense KV; the block axis stays whole (it is
        # shared across slots). Every jitted pool program — prefill
        # through tables, COW, the decode chunk — inherits this
        # placement, so XLA keeps TP attention local per shard until
        # the wo reduce. The pool is MADE in that placement (one
        # compiled call, each device zeroing its own heads): beside a
        # model that fills its chips, a whole pool does not fit one of
        # them on its way to the mesh (Mixtral-8x7B over model:4: 7.0
        # GiB a K leaf wanted, 4.86 free; my chip run, PR 27).
        from ..parallel.sharding import pool_cache_shardings

        return jax.jit(make, out_shardings=pool_cache_shardings(
            jax.eval_shape(make), self.mesh, self.model_cfg))()

    # ------------------------------ recurrent-state rows (ISSUE 33)
    #
    # Three small device programs move one sequence's state (12.8 MB at
    # the benchmark's cut) between a decode slot's row of the cache and
    # a row of the snapshot store; the StateStore decides when. They
    # dispatch in order with every other program, so a restore lands
    # before the prefill that reads it and a snapshot after the prefill
    # whose end it saves.

    def _live_state(self) -> dict:
        return {name: getattr(self._cache, name) for name in self._snap}

    @functools.cached_property
    def _state_copy_fn(self):
        span = self.model_cfg.sliding_window

        def row_of(a, i):
            return jax.lax.dynamic_slice_in_dim(a, i, 1, axis=1)

        def put(a, u, i):
            return jax.lax.dynamic_update_slice_in_dim(a, u, i, axis=1)

        def span_rows(live, edge):
            """The ring's rows for positions edge - span .. edge - 1 (rows
            of positions under 0 are never read)."""
            return (edge - span + jnp.arange(span)) % live.shape[2]

        def snapshot(snap, live, handle, slot, edge):
            out = {}
            with jax.named_scope("ssm/state_copy"):
                for name, a in snap.items():
                    one = row_of(live[name], slot)
                    if name in ("sk", "sv"):
                        one = one[:, :, span_rows(one, edge)]
                    out[name] = put(a, one, handle)
            return out

        def restore(live, snap, slot, handle, edge):
            out = {}
            with jax.named_scope("ssm/state_copy"):
                for name, a in live.items():
                    one = row_of(snap[name], handle)
                    if name in ("sk", "sv"):
                        one = row_of(a, slot).at[
                            :, :, span_rows(a, edge)].set(one)
                    out[name] = put(a, one, slot)
            return out

        return (jax.jit(snapshot, donate_argnums=(0,)),
                jax.jit(restore, donate_argnums=(0,)))

    def _state_snapshot_dev(self, slot: int, handle: int) -> None:
        self._snap = self._state_copy_fn[0](
            self._snap, self._live_state(), np.int32(handle), np.int32(slot),
            np.int32(self._state.edge(handle)))
        self._launched(self._snap)

    def _state_restore_dev(self, slot: int, handle: int) -> None:
        self._cache = dataclasses.replace(
            self._cache, **self._state_copy_fn[1](
                self._live_state(), self._snap, np.int32(slot),
                np.int32(handle), np.int32(self._state.edge(handle))))
        self._launched(self._live_state())

    @functools.cached_property
    def _state_zero_fn(self):
        def zero(live, slot):
            z = lambda a: jax.lax.dynamic_update_slice_in_dim(
                a, jnp.zeros_like(a[:, :1]), slot, axis=1)
            with jax.named_scope("ssm/state_copy"):
                return {name: z(a) for name, a in live.items()}

        return jax.jit(zero, donate_argnums=(0,))

    def _state_zero_dev(self, slot: int) -> None:
        self._cache = dataclasses.replace(
            self._cache, **self._state_zero_fn(self._live_state(),
                                               np.int32(slot)))
        self._launched(self._live_state())

    def _launched(self, out) -> None:
        """``EngineSpans.launched`` for a program whose outputs are all
        handed on, donated, to a later one (a state copy, a block copy,
        the draft's splice): a leaf of ``out``. The reference keeps
        nothing alive that the engine does not hold itself, and is dead
        once donated."""
        self._spans.launched(jax.tree_util.tree_leaves(out)[0])

    def _tables_d(self, tables: np.ndarray):
        """Device copy of a block-table snapshot — committed REPLICATED
        under a mesh (tables are per-slot host truth; the compiled
        chunk/prefill programs expect the replicated layout, and an
        uncommitted array would reshard per dispatch)."""
        if self.mesh is None:
            return jnp.asarray(tables)
        from ..parallel.sharding import replicate

        return replicate(np.ascontiguousarray(tables), self.mesh)

    def _pool_kv_limit(self, needed: int) -> int:
        """Smallest PREFILL KV bucket covering ``needed`` positions
        (every bucket is a whole page count: 128-tiled ladder, page
        divides 128): a gathered prefill's width must track the
        prompt, not the cache."""
        needed = min(needed, self._S_alloc)
        return next(b for b in self._pool_prefill_kv_buckets
                    if b >= needed)

    def _get_pool_prefill_fn(self, bucket: int, kv_limit: int):
        """Prefill program writing INTO the pool through a block table:
        one [1, bucket] token chunk at absolute offset positions,
        attending over the table's first kv_limit/page pages. This is
        what makes group-admission scratch obsolete — suffixes prefill
        directly into freshly allocated blocks, no staging cache and no
        splice copy."""
        key = (bucket, kv_limit)
        fn = self._pool_prefill_fns.get(key)
        if fn is None:
            cfg = self.model_cfg

            def one_row(run):
                """``run(cache) -> (logits, cache)`` on a cache whose state
                leaves are cut to decode slot ``slot``'s row: a one-
                sequence prefill continues THAT slot's recurrent state
                and leaves it there (stateless models: ``run`` as is)."""
                def with_slot(cache, slot):
                    if not cfg.keeps_state:
                        return run(cache)
                    logits, out = run(state_take_row(cache, slot))
                    return logits, state_put_row(cache, out, slot)
                return with_slot

            if self._use_ragged:
                # Ragged mode (ISSUE 19): the standalone prefill reads
                # through the SAME kernel as decode — per-row q_lens
                # pick the valid prefix, the kernel's page clamp bounds
                # the cost to live pages, and kv_limit collapses to the
                # single S_alloc rung (_pool_prefill_span), so this set
                # is one program per bucket instead of
                # |buckets| x |kv ladder|. The write mask gates padding
                # columns out of the KV scatter (legacy let them write
                # garbage at future positions; both are never attended
                # before being rewritten).
                def pool_prefill(params, tokens, positions, cache, mask,
                                 tables, slot):
                    q_lens = mask.sum(axis=1).astype(jnp.int32)
                    return one_row(lambda cache: forward(
                        params, cfg, tokens, positions,
                        cache, kv_limit=kv_limit,
                        attn_impl="ragged",
                        mesh=self.mesh,
                        moe_impl=self.moe_impl,
                        token_mask=mask,
                        write_mask=mask > 0,
                        logits_at=jnp.maximum(q_lens - 1, 0),
                        block_tables=tables,
                        q_lens=q_lens))(cache, slot)
            else:
                impl = self._prefill_impl_for(bucket, kv_limit)

                def pool_prefill(params, tokens, positions, cache, mask,
                                 tables, slot):
                    last = jnp.maximum(
                        mask.sum(axis=1).astype(jnp.int32) - 1, 0)
                    return one_row(lambda cache: forward(
                        params, cfg, tokens, positions, cache,
                        kv_limit=kv_limit, attn_impl=impl,
                        mesh=self.mesh, moe_impl=self.moe_impl,
                        token_mask=mask, logits_at=last,
                        block_tables=tables))(cache, slot)

            fn = jax.jit(pool_prefill, donate_argnums=(3,))
            self._pool_prefill_fns[key] = fn
        return fn

    @property
    def _pool_arm_fn(self):
        """Jitted slot-arming program — the splice minus the KV copy
        (prefill already wrote the pool through the table): carry token,
        position, temperature, termination carry, sampling seed."""
        fn = getattr(self, "_pool_arm_jit", None)
        if fn is None:
            def arm(tok, pos, temps, active, ngen, budget, seeds, slot,
                    n_prompt, first_tok, temperature, max_toks, seed,
                    ngen0):
                with jax.named_scope("kv_splice"):
                    tok = tok.at[slot, 0].set(first_tok[0])
                    pos = pos.at[slot, 0].set(n_prompt)
                    temps = temps.at[slot].set(temperature)
                    active = active.at[slot].set(max_toks > ngen0)
                    ngen = ngen.at[slot].set(ngen0)
                    budget = budget.at[slot].set(max_toks)
                    seeds = seeds.at[slot].set(seed)
                return tok, pos, temps, active, ngen, budget, seeds

            fn = jax.jit(arm, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
            self._pool_arm_jit = fn
        return fn

    def _run_arm(self, slot_idx: int, n_prompt: int, first_tok_d,
                 temperature: float, max_toks: int, seed: int,
                 ngen0: int) -> None:
        with self._spans.sched.child("arm", slot=slot_idx):
            (self._tok_d, self._pos_d, self._temps_d, self._active_d,
             self._ngen_d, self._budget_d,
             self._seeds_d) = self._pool_arm_fn(
                self._tok_d, self._pos_d, self._temps_d, self._active_d,
                self._ngen_d, self._budget_d, self._seeds_d,
                jnp.asarray(slot_idx, jnp.int32),
                jnp.asarray(n_prompt, jnp.int32), first_tok_d,
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(max_toks, jnp.int32),
                jnp.asarray(seed, jnp.int32),
                jnp.asarray(ngen0, jnp.int32),
            )
            # the chunk programs take the carry donated; the seeds only
            # the next arm does
            self._spans.launched(self._seeds_d)

    @property
    def _pool_cow_fn(self):
        """Jitted copy-on-write: copy the first ``rows`` KV rows of pool
        block ``src`` into block ``dst`` (rows is dynamic — one compiled
        program serves every partial-tail width; rows beyond it scatter
        out of bounds and drop)."""
        fn = getattr(self, "_pool_cow_jit", None)
        if fn is None:
            page = self.kv_pool_page

            def cp_rows(leaf, src_b, dst_b, rows):
                offs = jnp.arange(page)
                Lx, nb = leaf.shape[0], leaf.shape[1]
                f = leaf.reshape((Lx, nb * page) + leaf.shape[3:])
                src_rows = f[:, src_b * page + offs]
                dst_idx = jnp.where(offs < rows, dst_b * page + offs,
                                    nb * page)
                f = f.at[:, dst_idx].set(src_rows)
                return f.reshape(leaf.shape)

            def cp_block(leaf, src_b, dst_b, rows):
                # The same copy as one block read, one select and one
                # in-place block write. Over a mesh the partitioner
                # turns cp_rows' scatter through the flattened view
                # into a copy of the whole leaf (1.75 GiB of
                # temporaries a chip at Mixtral-8x7B's pool, which did
                # not fit: my chip run and AOT, PR 27); this form needs
                # none there or on one device. One device keeps
                # cp_rows until a one-chip cell has timed the other
                # (ROADMAP S7).
                src = jax.lax.dynamic_index_in_dim(leaf, src_b, axis=1)
                dst = jax.lax.dynamic_index_in_dim(leaf, dst_b, axis=1)
                keep = (jnp.arange(leaf.shape[2]) < rows).reshape(
                    (1, 1, leaf.shape[2]) + (1,) * (leaf.ndim - 3))
                return jax.lax.dynamic_update_index_in_dim(
                    leaf, jnp.where(keep, src, dst), dst_b, axis=1)

            cp = cp_rows if self.mesh is None else cp_block

            def cow(cache, src_b, dst_b, rows):
                def one(leaf):
                    return cp(leaf, src_b, dst_b, rows)

                def pairs(leaf):
                    # the latent leaf holds two tokens a row: the rows that
                    # cover the first ``rows`` tokens (an odd count copies
                    # its last token's partner too, which the new owner
                    # writes before any query may read it). The block
                    # form on one device too: cp_rows' scatter across the
                    # layers made the compiler lay the leaf out layer-
                    # innermost, a copy of the pool in and one out (5.0
                    # GiB of temporaries; my chip run and AOT, PR 38).
                    return cp_block(leaf, src_b, dst_b, (rows + 1) // 2)

                with jax.named_scope("kv_splice"):
                    # every paged leaf: K, V and (a selecting
                    # configuration's) index keys; or the latent leaf
                    return dataclasses.replace(
                        cache, k=jax.tree.map(one, cache.k),
                        v=jax.tree.map(one, cache.v),
                        ik=jax.tree.map(one, cache.ik),
                        lat=jax.tree.map(pairs, cache.lat))

            fn = jax.jit(cow, donate_argnums=(0,))
            self._pool_cow_jit = fn
        return fn

    def _run_cow(self, src: int, dst: int, rows: int) -> None:
        with self._spans.sched.child("cow", rows=rows):
            self._cache = self._pool_cow_fn(
                self._cache, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32), jnp.asarray(rows, jnp.int32))
            self._launched(self._cache.paged())

    # ------------------------------- host-tier block transfer (ISSUE 20)

    def _pool_offload_block(self, block: int) -> np.ndarray:
        """Gather one pool block's KV rows off the device as a flat byte
        payload (demote path). Leaf order follows the cache pytree
        (QuantKV under int8 contributes q and s leaves), so onload can
        split the bytes back by the same walk — the checksum stamped
        over this buffer covers every quantized leaf too."""
        leaves = jax.tree_util.tree_leaves(self._cache.paged())
        parts = [np.ascontiguousarray(jax.device_get(leaf[:, block]))
                 for leaf in leaves]
        return np.concatenate(
            [p.reshape(-1).view(np.uint8) for p in parts])

    def _pool_onload_block(self, block: int, data: np.ndarray) -> None:
        """Write a demoted page's verified bytes back into pool block
        ``block`` (promote path). The split mirrors _pool_offload_block's
        leaf walk; placement (mesh sharding) is preserved by the .at
        scatter on the existing leaves."""
        flat = np.ascontiguousarray(np.asarray(data, dtype=np.uint8))
        kv, treedef = jax.tree_util.tree_flatten(self._cache.paged())
        off, out = 0, []
        for leaf in kv:
            sub = (leaf.shape[0],) + tuple(leaf.shape[2:])
            dt = np.dtype(leaf.dtype)
            n = int(np.prod(sub)) * dt.itemsize
            part = np.frombuffer(
                flat[off:off + n].tobytes(), dtype=dt).reshape(sub)
            off += n
            out.append(leaf.at[:, block].set(
                jnp.asarray(part, dtype=leaf.dtype)))
        self._cache = self._cache.with_paged(
            jax.tree_util.tree_unflatten(treedef, out))
        self._launched(out)

    def _pool_alloc(self, n: int) -> Optional[List[int]]:
        """Allocate with radix-eviction backpressure (kv_pool.py helper,
        shared verbatim with the fake engine)."""
        return alloc_with_evict(self._pool, self._radix, n)

    def _pool_map_prefix(self, ids: List[int], match_all: bool = False,
                         slot_idx: int = 0) -> tuple:
        """Build a slot's block chain (kv_pool.map_prefix — THE shared
        admission path, run verbatim by the fake engine too): shared
        full blocks + tail COW (the device copy is this engine's jitted
        ``_run_cow``) + fresh blocks. Returns (blocks, m)."""
        return map_prefix(self._pool, self._radix, ids,
                          match_all=match_all, cow=self._run_cow,
                          state=self._state, slot=slot_idx,
                          region=self._spans.sched.child)

    def _pool_prefill_to_cuts(self, slot_idx: int, ids: List[int],
                              start: int, stop: int, n_prompt: int):
        """Prefill ``ids[start:stop]`` for a model that keeps a recurrent
        state, stopping at every block edge ``kv_pool.state_cuts`` names
        to save the state there (the snapshot hangs on the tree at once
        where the tree has the edge's node — a shared preamble — else on
        the slot until its chain is inserted at release). Returns the
        last valid position's logits (None when nothing was run)."""
        logits, pos = None, start
        cuts = (state_cuts(self._state, slot_idx, n_prompt,
                           self.kv_pool_page, start)
                if self._state is not None else [])
        for edge in sorted({e for e in cuts if e <= stop} | {stop}):
            if edge > pos:
                logits = self._pool_prefill_span(
                    self._tables[slot_idx], ids[:edge], pos, slot_idx)
                pos = edge
            if edge in cuts:
                take_snapshot(self._state, self._radix, slot_idx, ids, edge)
        return logits

    def _pool_prefill_span(self, table_row: np.ndarray, ids: List[int],
                           start: int, slot_idx: int = 0):
        """Prefill ``ids[start:]`` at absolute offsets through the
        slot's table, largest-bucket chunks (the unified short / suffix /
        long-prompt path — a chunk IS a suffix of everything before it).
        Returns the last valid position's logits [1, V]."""
        n = len(ids)
        big = self.prefill_buckets[-1]
        tables_d = self._tables_d(table_row[None])
        offset, logits = start, None
        while offset < n:
            L = min(big, n - offset)
            bucket = next(b for b in self.prefill_buckets if b >= L)
            # Ragged mode reads through the kernel (cost tracks live
            # pages, not the gather width): ONE kv rung per bucket,
            # collapsing the (bucket, kv_limit) program-set keys. The
            # draft prefill (_draft_prefill_slot) keeps its ladder —
            # its dense per-slot scratch really does gather kv_limit.
            # A model served with prompts past the widest bucket takes
            # the one rung under ``gather`` too (off the TPU, or with an
            # int8 pool where its cache rides one; no shipped cell): a
            # piece at any depth then runs a program the warm-up ran, at
            # the price of a gather as wide as the cache, where a rung a
            # depth compiled at a request's first piece that deep,
            # seconds on the scheduler's thread.
            kv_limit = (self._S_alloc
                        if self._use_ragged or long_prompts(self.model_cfg)
                        else self._pool_kv_limit(offset + bucket))
            # One eager piece (sched/eager_prefill): staging through the
            # program call's return; ``call_ms`` is the jitted call alone,
            # the launch and whatever it blocks on.
            with self._spans.sched.child(
                    "eager_prefill", totals=("tokens", "call_ms"),
                    slot=slot_idx, tokens=L, bucket=bucket) as piece:
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :L] = ids[offset:offset + L]
                positions = np.broadcast_to(
                    offset + np.arange(bucket), (1, bucket)).astype(np.int32)
                mask = (np.arange(bucket) < L)[None, :].astype(np.float32)
                fn = self._get_pool_prefill_fn(bucket, kv_limit)
                tokens_d, positions_d, mask_d = (
                    jnp.asarray(x) for x in (tokens, positions, mask))
                t_call = time.monotonic()
                logits, self._cache = fn(
                    self.params, tokens_d, positions_d, self._cache, mask_d,
                    tables_d, np.int32(slot_idx))
                piece["call_ms"] = (time.monotonic() - t_call) * 1000.0
                self._spans.launched(logits)
            offset += L
            self._counts.note_passes(1, eager=True)
        # One more program, the slice of the last row: a launch that waits
        # behind the pieces like any other (sched/eager_tail).
        with self._spans.sched.child("eager_tail", slot=slot_idx):
            last = logits[:, 0]
            self._spans.launched(last)
        return last

    def _pool_ensure_coverage(self, idx: int, slot: "_Slot",
                              chunk_tokens: Optional[int] = None) -> bool:
        """Grow the slot's table to cover the next chunk's writes
        (``chunk_tokens`` widens per dispatch when the speculative
        chunk can emit more than chunk_len — ISSUE 12). False = pool
        exhausted even after radix eviction: the slot is marked
        exhausted and finishes at its current length once its in-flight
        chunks drain (oversubscription's honest failure mode —
        truncation, never corruption)."""
        target = min(slot.pos + (chunk_tokens or self.chunk_len),
                     self._S_alloc)
        need = pages_for(target, self.kv_pool_page)
        while len(slot.blocks) < need:
            b = self._pool_alloc(1)
            if b is None:
                slot.exhausted = True
                self._pool_starved += 1
                if slot.req.trace is not None:
                    slot.req.trace.event(
                        f"engine: kv pool exhausted at position "
                        f"{slot.pos} — finishing at current length")
                logger.warning(
                    "kv pool exhausted: slot truncated at position %d "
                    "(%d blocks live, %d cached)", slot.pos,
                    self._pool.n_blocks - self._pool.free_count,
                    self._radix.cached_block_count()
                    if self._radix else 0)
                return False
            self._tables[idx, len(slot.blocks)] = b[0]
            slot.blocks.extend(b)
            if slot.req.export is not None:
                slot.req.export.blocks = list(slot.blocks)
        return True

    def _pool_release_slot(self, idx: Optional[int], slot: "_Slot",
                           cache_chain: bool = True) -> None:
        """Release a leaving slot's block refs. ``cache_chain`` first
        inserts the request's verified KV chain (admitted prompt +
        emitted[:-1] — rows the device has definitely written) into the
        radix tree, so completion feeds sharing: the next turn of this
        agent loop, or a preempted victim's resume, re-maps these blocks
        instead of re-prefilling."""
        if idx is not None:
            self._tables[idx, :] = self._pool_n_blocks
        if not slot.blocks:
            slot.blocks = []
            return
        if cache_chain and self._radix is not None and slot.pool_ids:
            gen = list(slot.detok.ids)
            chain = slot.pool_ids + (gen[:-1] if gen else [])
            chain = chain[:len(slot.blocks) * self.kv_pool_page]
            try:
                self._radix.insert(chain, slot.blocks)
            except Exception:  # pragma: no cover - defensive
                logger.exception("radix insert failed; chain not cached")
                cache_chain = False
            if self._state is not None and idx is not None:
                release_state(self._state, self._radix, idx, chain,
                              cache_chain)
        elif self._state is not None and idx is not None:
            release_state(self._state, None, idx, (), False)
        self._pool.decref(slot.blocks)
        slot.blocks = []

    def _admit_one_pool(self, req: _Request) -> None:
        """Pool-mode admission: radix-match the prompt, map shared
        blocks copy-on-write, prefill ONLY the unmatched suffix straight
        into freshly allocated blocks, sample the first token, arm the
        slot vectors. Turn N+1 of an agent loop (prompt extends the
        cached prompt+completion chain) becomes incremental prefill; N
        users sharing the system prompt cost one block set."""
        slot_idx = self._slots.index(None)
        t_adm = time.monotonic()
        wait_ms = (t_adm - req.t_submit) * 1000.0
        self._brownout.note_queue_wait(req.lane, wait_ms, now=t_adm)
        self._slo.note(SLO_QUEUE_WAIT, req.lane, wait_ms, now=t_adm)
        spans = self._spans.admitted(req, t_adm)

        ids = list(req.prompt_ids)
        max_prompt = self.max_seq_len - max(1, req.max_tokens)
        if len(ids) > max_prompt:
            ids = ids[-max_prompt:]
        n_prompt = len(ids)
        # Grammar admission fast-forward (ISSUE 11): with no chunks in
        # flight for a fresh slot, the forced chain from the START
        # state ("kubectl " and onward) is pure profit — it rides the
        # SAME prefill pass as the prompt, and the first sampled token
        # moves to the post-run index of the seed stream (forced tokens
        # consume indices, never randomness — byte-identical to masked
        # step-by-step decode).
        run: List[int] = []
        ends_eos = False
        gs1 = -1
        if self._grammar is not None and req.gpid >= 0:
            gs1 = self._grammar.start_state(req.gpid)
            run, ends_eos, gs_end = self._grammar.forced_run(
                gs1, req.max_tokens)
            if len(run) >= self.grammar_forced_run_min or (
                    ends_eos and run):
                gs1 = gs_end
            else:
                run, ends_eos = [], False
        full = ids + run
        blocks, m = self._pool_map_prefix(ids, slot_idx=slot_idx)
        # Session SLO gate (ISSUE 20): a seating that radix-matched at
        # least one full page is a warm re-admission — the only kind the
        # turn-N TTFT SLO judges (onload-served pages count: the match
        # promoted them before recording the hit).
        req.radix_warm = m >= self.kv_pool_page
        try:
            grow = pages_for(len(full), self.kv_pool_page) - len(blocks)
            if grow > 0:
                extra = self._pool_alloc(grow)
                if extra is None:
                    if run:          # pool pressure: decode the run
                        run, ends_eos = [], False
                        gs1 = (self._grammar.start_state(req.gpid)
                               if gs1 >= 0 else -1)
                        full = ids
                    else:
                        raise EngineUnavailable(
                            "admission failed: kv pool exhausted")
                else:
                    blocks = blocks + extra
            self._tables[slot_idx, :] = self._pool_n_blocks
            self._tables[slot_idx, :len(blocks)] = blocks
            done_at_admit = run and (len(run) >= req.max_tokens
                                     or ends_eos)
            span = full if not done_at_admit else full[:-1]
            staged = None
            first_tok_d = None
            if not done_at_admit and self._use_ragged:
                # Ragged admission (ISSUE 19): the unmatched suffix
                # does NOT run a standalone prefill+sample+arm here —
                # it stages as a long-q_len window the NEXT chunk's
                # prologue prefills, samples, and arms in ONE program
                # with everyone else's decode step (same fold_in
                # indices and grammar advance as the legacy path —
                # byte-identical transcripts).
                stage_start = len(span) - staged_suffix_len(
                    len(span) - m, self.prefill_buckets)
                if self._state is not None:
                    # the window is cut at the prompt's last block edge,
                    # so that the state there exists to be saved: what
                    # rides is the prompt's last partial block (and the
                    # forced run), the rest prefills eagerly
                    stage_start = max(m, (n_prompt - 1)
                                      // self.kv_pool_page
                                      * self.kv_pool_page)
                if stage_start > m:
                    self._pool_prefill_to_cuts(slot_idx, span, m,
                                               stage_start, n_prompt)
                staged = dict(
                    ids=list(span[stage_start:]),
                    start=stage_start,
                    ngen0=len(run),
                    budget=req.max_tokens,
                    seed=req.seed,
                    temp=req.temperature,
                    gs=gs1,
                )
                # Persist the slot's CONFIG vectors (temps/budget/seeds
                # — read-only chunk inputs, not part of the returned
                # carry) now: the adm chunk arms its own copies
                # in-trace, but every LATER chunk reads these buffers.
                # The token is a placeholder — the prologue overrides
                # tok/pos/ngen/active for staged slots and the chunk
                # returns the real carry.
                with self._spans.sched.child("placeholder", slot=slot_idx):
                    no_tok_d = jnp.zeros((1,), jnp.int32)
                    self._spans.launched(no_tok_d)
                self._run_arm(slot_idx, stage_start, no_tok_d,
                              req.temperature, req.max_tokens, req.seed,
                              len(run))
                # The draft still mirrors the FULL span now — the spec
                # prologue's first in-chunk draft forward reads rows
                # 0..pos-1 and the draft world has no ragged window.
                self._draft_prefill_slot(slot_idx, list(span))
            elif not done_at_admit:
                last_logits = self._pool_prefill_to_cuts(
                    slot_idx, span, m, len(span), n_prompt)
                first_tok_d = self._grammar_first_sample(
                    last_logits, req, gs1, len(run))
                self._run_arm(slot_idx, n_prompt + len(run), first_tok_d,
                              req.temperature, req.max_tokens, req.seed,
                              1 + len(run))
                if gs1 >= 0:
                    self._grammar_arm_after_sample(slot_idx, gs1,
                                                   first_tok_d)
                # Speculative decoding (ISSUE 12): mirror the admitted
                # span into the draft cache — the 2B must condition on
                # the same prompt(+forced run) before it drafts. The
                # draft has no radix tree, so it prefills the whole
                # span (the known spec-decode admission overhead).
                self._draft_prefill_slot(slot_idx, list(span))
            else:
                self._pool_prefill_to_cuts(slot_idx, span, m, len(span),
                                           n_prompt)
        except Exception:
            self._tables[slot_idx, :] = self._pool_n_blocks
            self._pool.decref(blocks)
            if self._state is not None:
                release_state(self._state, None, slot_idx, (), False)
            raise
        slot = _Slot(
            req=req,
            detok=StreamDecoder(self.tokenizer),
            n_prompt=n_prompt,
            pos=n_prompt + len(run),
            queue_ms=wait_ms,
            t_admit=t_adm,
            t_decode0=t_adm,
            chunks_inflight=(0 if (done_at_admit or staged is not None)
                             else 1),
            prefix_hit=m > 0,
            blocks=blocks,
            pool_ids=ids,
            gs=gs1,
            anchor_pos=n_prompt + len(run),
            anchor_g=1 + len(run),
        )
        if req.export is not None:
            req.export.blocks = list(blocks)
        if req.trace is not None:
            req.trace.event(
                f"engine: admitted to slot {slot_idx} ({n_prompt} prompt "
                f"tokens, {m} radix-matched, "
                f"{pages_for(n_prompt, self.kv_pool_page)} pool blocks)")
        self._slots[slot_idx] = slot
        self._spans.note_slots(self._slots)
        prefill_meta = dict(prompt_tokens=n_prompt, prefix_hit_tokens=m,
                            staged_w=len(staged["ids"]) if staged else 0)
        self._counts.note_admission(m, n_prompt)
        if run:
            t_dk = time.monotonic()
            piece = slot.detok.push(*run)
            slot.detok_ms += (time.monotonic() - t_dk) * 1000.0
            if req.export is not None:
                req.export.ids = list(slot.detok.ids)
            if req.t_first0 is None:
                req.t_first0 = time.monotonic()
            if piece is not None:
                self._emit(req, "token", piece)
            self._grammar_forced += len(run)
            self._grammar_ff_splices += 1
            if req.trace is not None:
                req.trace.event(
                    f"grammar: admission forced run of {len(run)} tokens "
                    f"spliced with the prompt prefill")
        if done_at_admit:
            slot.t_first = time.monotonic()
            spans.staged(slot.t_first, prefill=prefill_meta,
                         blocks=len(blocks))
            self._finish(slot_idx,
                         "stop" if ends_eos
                         and len(run) < req.max_tokens else "length")
            self._last_admit_t = time.monotonic()
            return
        if staged is not None:
            # No "first" pipeline entry: the first sampled token rides
            # the next chunk's packed buffer (row index 0) and the
            # consume path's t_first catch covers TTFT. The step-time
            # sentinel's prefill phase is noted at dispatch, keyed by
            # the ragged admission width.
            self._pending_adm[slot_idx] = staged
            self._last_admit_t = time.monotonic()
            spans.staged(self._last_admit_t, prefill=prefill_meta,
                         blocks=len(blocks))
            return
        self._to_host_async(first_tok_d)
        self._inflight.append(("first", first_tok_d, req, slot_idx))
        self._last_admit_t = time.monotonic()
        spans.staged(self._last_admit_t, prefill=prefill_meta,
                     chunks_ahead=self._chunks_in_pipe(),
                     chunks_unready=self._spans.pipe_chunks(),
                     blocks=len(blocks))

    def _pool_warmup(self) -> None:
        """Eager startup warm of the pool serving programs: the smallest
        prefill bucket (through a table), the sampler, the arm and COW
        programs, and every KV-bucket decode chunk. Warm blocks are
        freed after (their garbage is rewritten before any future owner
        reads it), then the radix tree is preloaded with the system
        prompt so the very first request prefix-shares."""
        cfg = self.model_cfg
        N = self.batch_size
        b = self.prefill_buckets[0]
        row = np.full((self._pool_max_pages,), self._pool_n_blocks,
                      np.int32)
        blocks = self._pool.alloc(
            min(pages_for(b, self.kv_pool_page), self._pool_max_pages))
        row[:len(blocks)] = blocks
        self._pool_prefill_span(row, [0] * b, 0)
        if long_prompts(cfg):
            # Served with prompts far past the widest bucket: their heads
            # are prefilled eagerly, piece by piece, and the last piece of
            # a head may be any bucket (one compiled inside a measured
            # window otherwise). Warming these for every model would add
            # compiles to every other start.
            for wb in self.prefill_buckets[1:]:
                more = self._pool.alloc(min(
                    pages_for(wb, self.kv_pool_page), self._pool_max_pages))
                wide = row.copy()
                wide[:len(more)] = more
                self._pool_prefill_span(wide, [0] * wb, 0)
                self._pool.decref(more)
        if self._state is not None:
            # the restore program (snapshot row 0, all zeros, into slot 0):
            # the warm-up answer runs the zero and snapshot programs but
            # restores nothing, and the first request seated from a
            # snapshot compiled this one inside a measured window
            self._cache = dataclasses.replace(
                self._cache, **self._state_copy_fn[1](
                    self._live_state(), self._snap, np.int32(0), np.int32(0),
                    np.int32(0)))
        self._key_d = jax.random.PRNGKey(self.seed)
        self._sample_fn(
            jnp.zeros((1, cfg.vocab_size), jnp.float32), self._key_d,
            jnp.asarray(0.0, jnp.float32),
        )
        self._run_arm(0, 1, jnp.zeros((1,), jnp.int32), 0.0, 1, 0, 1)
        self._run_cow(blocks[0], blocks[0], 0)
        tables_d = self._tables_d(self._tables)
        for kv_b in self._kv_buckets:
            packed = self._run_chunk(kv_b, jnp.zeros((N,), jnp.bool_),
                                     self._no_corrupt_d, tables_d,
                                     spec=False)
        if self._use_ragged:
            # Warm the ragged mixed-chunk program per admission width
            # (ISSUE 19) — an all-zero adm_len tuple compiles the same
            # program a real staged admission runs.
            for w in self.prefill_buckets:
                packed = self._run_chunk(
                    self._kv_buckets[-1], jnp.zeros((N,), jnp.bool_),
                    self._no_corrupt_d, tables_d, spec=False,
                    adm_w=w, adm_args=self._warm_adm_args(w))
        if self._use_spec:
            # Warm the speculative program set beside the plain one
            # (draft:die flips between them mid-serving — neither may
            # compile on the hot path), plus the draft prefill/splice
            # programs the admission path runs.
            self._draft_prefill_slot(0, [0] * b)
            for kv_b in self._kv_buckets:
                packed = self._run_chunk(kv_b,
                                         jnp.zeros((N,), jnp.bool_),
                                         self._no_corrupt_d, tables_d,
                                         spec=True)
            if self._use_ragged:
                for w in self.prefill_buckets:
                    packed = self._run_chunk(
                        self._kv_buckets[-1],
                        jnp.zeros((N,), jnp.bool_),
                        self._no_corrupt_d, tables_d, spec=True,
                        adm_w=w, adm_args=self._warm_adm_args(w))
        packed.block_until_ready()
        self._pool.decref(blocks)
        self._pool_preload_system_prompt()

    def _warm_adm_args(self, w: int) -> tuple:
        """An all-idle staged-admission tuple (adm_len zeros — every
        slot takes its plain q_len=1 prologue step) with exactly the
        shapes/dtypes _dispatch_chunk packs, so warmup compiles the
        program serving will run."""
        N = self.batch_size
        args = (jnp.zeros((N, w), jnp.int32),
                jnp.zeros((N,), jnp.int32),
                jnp.zeros((N,), jnp.int32),
                jnp.zeros((N,), jnp.int32),
                jnp.zeros((N,), jnp.int32),
                jnp.zeros((N,), jnp.int32),
                jnp.zeros((N,), jnp.float32))
        if self._grammar is not None:
            args = args + (jnp.zeros((N,), jnp.int32),)
        return args

    def _pool_preload_system_prompt(self) -> None:
        """Prefill the shared system prompt once at startup and leave
        its chain CACHED in the radix tree — the pool-mode analog of the
        dense path's resident PrefixKV (engine/prefix_cache.py), behind
        the same HBM_PREFIX_CACHE knob. Unlike the dense prefix, it
        shares under LRU like any other chain (every request touches it,
        so it stays hot) and does not survive an engine reset (the next
        admission re-prefills and re-caches it)."""
        if self._radix is None or not self.use_prefix_cache:
            return
        if self._state is not None:
            # under a page it is a partial tail, which a model that keeps
            # a recurrent state can never start from
            return
        from .prompts import SYSTEM_PROMPT

        ids = self.tokenizer.encode(SYSTEM_PROMPT)
        P = len(ids)
        if P + self.prefill_buckets[0] > self.max_seq_len:
            logger.warning(
                "Radix preload skipped: system prompt is %d tokens; no "
                "room for a suffix within max_seq %d", P, self.max_seq_len)
            return
        need = pages_for(P, self.kv_pool_page)
        if need > self._radix.max_blocks:
            logger.warning(
                "Radix preload skipped: system prompt needs %d blocks, "
                "RADIX_LRU_BLOCKS budget is %d", need,
                self._radix.max_blocks)
            return
        blocks = self._pool_alloc(need)
        if blocks is None:  # pragma: no cover - tiny pools only
            logger.warning("Radix preload skipped: pool too small")
            return
        row = np.full((self._pool_max_pages,), self._pool_n_blocks,
                      np.int32)
        row[:need] = blocks
        try:
            self._pool_prefill_span(row, list(ids), 0)
            self._radix.insert(list(ids), blocks)
        finally:
            self._pool.decref(blocks)
        logger.info(
            "Radix cache preloaded: %d-token system prompt resident in "
            "%d pool blocks", P, need)

    def _chunks_in_pipe(self) -> int:
        return sum(1 for e in self._inflight if e[0] == "chunk")

    def spans_health(self) -> dict:
        """/health.spans (obs/trace.py EngineSpans.health)."""
        return self._spans.health(self._chunks_consumed)

    def sharding_health(self) -> Optional[dict]:
        """Cheap sharding view for /health (ISSUE 14; host attributes
        only — same rule as qos_health): the active mesh shape, the
        residual TP fraction the policy achieves at the decode shape
        (1.0 = every residual-path tensor batch-sharded), whether
        the KV pool is mesh-sharded, and the kv_pool_mesh_fallback flag
        — a pool that silently fell back dense must be visible."""
        if self.mesh is None:
            return None
        from ..parallel.sharding import residual_fraction

        return {
            "mesh": {a: int(s) for a, s in self.mesh.shape.items()},
            "devices": int(self.mesh.size),
            "residual_tp_fraction": residual_fraction(
                self.mesh, self.batch_size, self.model_cfg.dim),
            "weights_shard_fraction": self._weights_shard_fraction,
            **self._weights_health(),
            "pool_sharded": bool(self._use_pool),
            "kv_pool_mesh_fallback": bool(self._kv_pool_mesh_fallback),
            # ISSUE 18: whether the draft world rides the mesh, and
            # whether its KV serves replicated because the draft's KV
            # heads don't divide tp (the gather fallback — correct but
            # off the shard-local fast path; fleets OR this flag).
            "draft_sharded": bool(self._draft_sharded),
            "draft_kv_fallback": bool(self._draft_kv_fallback),
            **self._attention_health(),
        }

    def _resolve_attention_steps(self) -> tuple:
        """(KV pages a grid step, grid steps a call, buffers its live
        blocks stream through) of the ragged kernel in the decode program,
        from the shapes a chip sees: its share of the heads, the whole
        table, the decode window (k+1 columns under speculation)."""
        from ..ops.ragged_attention import (grid_steps, pages_per_step,
                                            stream_depth)

        cfg = self.model_cfg
        tp = self.mesh.shape["model"] if self.mesh is not None else 1
        shape = (self._pool_max_pages, self.kv_pool_page,
                 *kernel_heads(cfg, tp, self._lane_heads),
                 self.spec_draft_k + 1 if self._spec_live else 1,
                 jnp.dtype(self.dtype).itemsize)
        return (pages_per_step(*shape),
                grid_steps(self.batch_size, *shape), stream_depth(*shape))

    def _attention_health(self) -> dict:
        """The regime actually serving attention (ragged | gather | dense)
        and the condition that selected it — int8 KV, non-dividing head
        counts and mesh gates fall back LOUDLY here — and, under ragged,
        what the kernel resolved at start (null otherwise)."""
        pages, steps, depth = self._attention_steps
        return {
            "attention_regime": self._attention_regime,
            "attention_regime_reason": self._attention_regime_reason,
            "attention_pages_per_step": pages,
            # KV heads of a pool row a 128-lane tile (1: a head fills its own)
            "attention_lane_heads": self._lane_heads,
            "attention_decode_grid_steps": steps,
            "attention_stream_depth": depth,
            # what a kind of the cache resolved at start
            **resolved_at_start(self.model_cfg, self._attention_regime),
        }

    def ragged_health(self) -> Optional[dict]:
        """/health.ragged: what the mixed chunks' windows carried and
        cost (``_window_counts``; cumulative). None off the ragged
        regime."""
        if not self._use_ragged:
            return None
        return {"window": dict(self._window_counts)}

    def family_health(self) -> Dict[str, Optional[dict]]:
        """/health.{moe, sparse_attention, latent_attention,
        sliding_attention, ssm}: what the kinds of the cache counted
        (engine/kv_pool.py::CacheCounters; None where the configuration
        is of no kind that gives a section) beside the engine's facts."""
        sk = getattr(getattr(self, "_cache", None), "sk", None)
        return self._counts.sections(
            batch_size=self.batch_size,
            widest_window=self.prefill_buckets[-1],
            counts_experts=self._counts_experts,
            pool_bytes_per_token=(self._pool_bytes_per_token()
                                  if self._pool is not None else None),
            ring_rows=sk.shape[2] if sk is not None else None,
            store=self._state.stats() if self._state is not None else None)

    def _pool_bytes_per_token(self) -> float:
        """What one token keeps in the pool: every paged leaf's own size
        (all layers; an int8 pool's scales too) over the tokens the pool
        holds. Read off the leaves, not the configuration: a cache that
        grew a leaf says so here."""
        leaves = jax.tree_util.tree_leaves(self._cache.paged())
        held = sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves)
        return held / (self._pool_n_blocks * self.kv_pool_page)

    def kv_pool_health(self) -> Optional[dict]:
        """Cheap pool view for /health (never stats() — same rule as
        qos_health): block-state counts, sharing/COW totals, radix
        hit-rate counters."""
        if not self._use_pool or self._pool is None:
            return None
        cached = (self._radix.cached_blocks() if self._radix is not None
                  else ())
        body = self._pool.stats(cached).as_dict()
        body["starved_slots_total"] = self._pool_starved
        body["bytes_per_token"] = self._pool_bytes_per_token()
        # Single-chip deployments read the regime here (sharding_health
        # is None without a mesh).
        body.update(self._attention_health())
        body["radix"] = (self._radix.stats() if self._radix is not None
                         else None)
        if self._host_store is not None:
            body["host_tier"] = self._host_store.stats()
        return body

    # ----------------------------------- speculative decoding (ISSUE 12)
    #
    # The 2B draft engine lives entirely inside this engine: its params
    # ride the chunk dispatch like the target's, its dense per-slot KV
    # cache rides the chunk carry, and every admission/replay/forced-run
    # path that (re)writes the target's KV mirrors the span into the
    # draft cache so the two models always condition on the same
    # transcript. Verification is EXACT MATCH against the target's own
    # seeded sample, so the transcript never depends on the draft — the
    # parity the acceptance tests pin, and why losing the draft
    # (draft:die) degrades to plain decode instead of failing anything.

    def _spec_active(self) -> bool:
        return self._use_spec and self._spec_live

    def _get_draft_prefill_fn(self, bucket: int, kv_limit: int):
        """Draft-model prefill program over a single-slot scratch cache
        ([1, bucket] tokens at absolute offsets) — the 2B twin of the
        pool prefill path, feeding ``_draft_prefill_slot``'s bucket
        loop. Dense attention: the draft is small and this is the
        admission path, not the decode hot loop. Rides the serving
        mesh (ISSUE 18) like the target's pool prefill so the sharded
        draft params never gather for an admission."""
        key = (bucket, kv_limit)
        fn = self._draft_prefill_fns.get(key)
        if fn is None:
            dcfg = self._draft_cfg

            def draft_prefill(dparams, tokens, positions, scratch,
                              mask):
                last = jnp.maximum(
                    mask.sum(axis=1).astype(jnp.int32) - 1, 0)
                return forward(dparams, dcfg, tokens, positions,
                               scratch, kv_limit=kv_limit,
                               attn_impl="dense", mesh=self.mesh,
                               moe_impl="dense", token_mask=mask,
                               logits_at=last)

            fn = jax.jit(draft_prefill, donate_argnums=(3,))
            self._draft_prefill_fns[key] = fn
        return fn

    @property
    def _draft_extract_fn(self):
        """Jitted slot→scratch extraction: copy slot ``i``'s rows of
        the batched draft cache into a [1, S_alloc] scratch, so a
        mid-stream prefill (forced-run splice) attends over the rows
        the slot already decoded."""
        fn = getattr(self, "_draft_extract_jit", None)
        if fn is None:
            def extract(cache, slot):
                def cut(leaf):
                    return jax.lax.dynamic_slice_in_dim(leaf, slot, 1,
                                                        axis=1)

                return KVCache(k=jax.tree.map(cut, cache.k),
                               v=jax.tree.map(cut, cache.v),
                               lengths=cache.lengths[:1])

            fn = jax.jit(extract)
            self._draft_extract_jit = fn
        return fn

    @property
    def _draft_splice_fn(self):
        """Jitted scratch→slot splice for the draft cache (the dense
        ``kv_slot_update`` the pre-pool target path used)."""
        fn = getattr(self, "_draft_splice_jit", None)
        if fn is None:
            def splice(cache, src_k, src_v, slot):
                with jax.named_scope("kv_splice"):
                    return KVCache(
                        k=kv_slot_update(cache.k, src_k, slot),
                        v=kv_slot_update(cache.v, src_v, slot),
                        lengths=cache.lengths)

            fn = jax.jit(splice, donate_argnums=(0,))
            self._draft_splice_jit = fn
        return fn

    def _draft_prefill_slot(self, slot_idx: int, ids: List[int],
                            start: int = 0) -> None:
        """Mirror a target KV span into the draft cache: prefill
        ``ids[start:]`` at absolute offsets through a scratch (fresh at
        admission; extracted from the slot for a mid-stream span so
        earlier rows stay attendable), then splice the scratch back
        into the slot. Runs at every site that arms the target's KV —
        admission, replay, forced-run fast-forward — so draft and
        target always condition on the same transcript, with the same
        "carry token's row unwritten" tail."""
        if not self._spec_active():
            return
        n = len(ids)
        if n <= start:
            return
        if start == 0:
            scratch = KVCache.zeros(self._draft_cfg, 1, self._S_alloc,
                                    dtype=self.dtype)
            if self.mesh is not None:
                # Sharded at every arm site (ISSUE 18): the scratch
                # carries the same KV-head sharding as the slot cache
                # (batch 1 sanitizes the data axis away), so the
                # bucketed prefill loop and the splice-back never
                # reshard mid-admission/replay/fast-forward.
                from ..parallel.sharding import shard_draft_cache
                scratch = shard_draft_cache(scratch, self.mesh,
                                            self._draft_cfg)
        else:
            scratch = self._draft_extract_fn(
                self._draft_cache, jnp.asarray(slot_idx, jnp.int32))
        big = self.prefill_buckets[-1]
        offset = start
        while offset < n:
            L = min(big, n - offset)
            bucket = next(b for b in self.prefill_buckets if b >= L)
            kv_limit = self._pool_kv_limit(offset + bucket)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :L] = ids[offset:offset + L]
            positions = np.broadcast_to(
                offset + np.arange(bucket), (1, bucket)).astype(np.int32)
            mask = (np.arange(bucket) < L)[None, :].astype(np.float32)
            _, scratch = self._get_draft_prefill_fn(bucket, kv_limit)(
                self._draft_params, jnp.asarray(tokens),
                jnp.asarray(positions), scratch, jnp.asarray(mask))
            offset += L
        self._draft_cache = self._draft_splice_fn(
            self._draft_cache, scratch.k, scratch.v,
            jnp.asarray(slot_idx, jnp.int32))
        self._launched(self._draft_cache)

    def _chunk_waste_bound(self) -> int:
        """Per-in-flight-chunk bound on counted device steps, for the
        waste caps at preempt/disconnect. A speculative chunk's width
        is ``_chunk_tokens`` (possibly > chunk_len when chunk_len <
        k+1); in-flight chunks can briefly mix widths across a
        draft:die flip, so the bound is the max of the two — the
        ``remaining``-budget cap at each billing site keeps the
        overstatement modest, same as the standing device-EOS caveat."""
        if self._use_spec:
            return max(self.chunk_len, self._chunk_tokens)
        return self.chunk_len

    def spec_health(self) -> Optional[dict]:
        """Cheap speculative-decode view for /health (host counters
        only — same rule as qos/kv_pool/grammar health)."""
        if not self.spec_decode:
            return None
        drafted = self._spec_drafted
        return {
            "enabled": self.spec_decode,
            "active": self._spec_active(),
            "draft_model": (self._draft_cfg.name if self._draft_cfg
                            is not None else self.spec_draft_model),
            "k": self.spec_draft_k,
            "verify_steps_per_chunk": self._spec_steps,
            "drafted_tokens_total": drafted,
            "accepted_tokens_total": self._spec_accepted,
            "acceptance_ratio": (round(self._spec_accepted / drafted, 4)
                                 if drafted else None),
            "degraded_total": self._spec_degraded,
            # ISSUE 18: spec under the mesh — mirrors sharding_health
            # so the acceptance table and the mesh view tell one story.
            "draft_sharded": bool(self._draft_sharded),
            "draft_kv_fallback": bool(self._draft_kv_fallback),
        }

    # ------------------------------- grammar-constrained decode (ISSUE 11)
    #
    # Host truth: the GrammarRuntime's numpy tables + each slot's ``gs``
    # field (the FSM state over CONSUMED tokens). The device carries its
    # own speculative state vector (_fsm_d) exactly like ngen/active;
    # every admission/replay path re-arms it from host truth.

    def _grammar_tables_d(self) -> tuple:
        """Device copies of the stacked grammar tables — ``tok_class``,
        the bit-packed legality ``class_ok_bits`` and ``class_next``
        (the unpacked ``class_ok`` stays on the host) — refreshed when a
        per-request variant install bumped the runtime's version (table
        shapes are fixed, so this never re-traces the chunk program).
        The refresh reads a lock-consistent snapshot and stamps ITS
        version — a racing install can neither tear the copied rows nor
        leave a post-install version on pre-install contents."""
        g = self._grammar
        if g.version != self._grammar_version:
            version, tc, ok, nxt = g.snapshot_tables()
            if self.mesh is not None:
                # Pinned REPLICATED on the mesh (ISSUE 14): the stacked
                # tables are per-profile host truth every shard's row
                # gathers read in full — a partitioner-chosen layout
                # would either reshard per dispatch or shard rows a
                # gather then has to fetch cross-device mid-scan.
                from ..parallel.sharding import replicate

                self._gram_tc_d = replicate(tc, self.mesh)
                self._gram_ok_d = replicate(ok, self.mesh)
                self._gram_next_d = replicate(nxt, self.mesh)
            else:
                self._gram_tc_d = jnp.asarray(tc)
                self._gram_ok_d = jnp.asarray(ok)
                self._gram_next_d = jnp.asarray(nxt)
            self._grammar_version = version
        return self._gram_tc_d, self._gram_ok_d, self._gram_next_d

    @property
    def _grammar_set_fn(self):
        """Jitted single-slot FSM-state write (the grammar analog of the
        arm program's per-slot scatter)."""
        fn = getattr(self, "_grammar_set_jit", None)
        if fn is None:
            def set_state(fsm, slot, gs):
                return fsm.at[slot].set(gs)

            fn = jax.jit(set_state, donate_argnums=(0,))
            self._grammar_set_jit = fn
        return fn

    def _grammar_arm(self, slot_idx: int, gs: int) -> None:
        self._fsm_d = self._grammar_set_fn(
            self._fsm_d, jnp.asarray(slot_idx, jnp.int32),
            jnp.asarray(gs, jnp.int32))
        self._spans.launched(self._fsm_d)

    @property
    def _grammar_arm_sampled_fn(self):
        """Jitted FSM arm for an admission whose first token is still a
        device value (zero host reads — the admission contract): the
        slot's device state becomes advance(gs_base, first_tok),
        computed through the stacked tables on device."""
        fn = getattr(self, "_grammar_arm_sampled_jit", None)
        if fn is None:
            s_max = self._grammar.S_max

            def arm(fsm, tc, nxt_tbl, slot, gs_base, first_tok):
                cls = tc[gs_base // s_max, first_tok[0]]
                return fsm.at[slot].set(nxt_tbl[gs_base, cls])

            fn = jax.jit(arm, donate_argnums=(0,))
            self._grammar_arm_sampled_jit = fn
        return fn

    def _grammar_arm_after_sample(self, slot_idx: int, gs_base: int,
                                  first_tok_d) -> None:
        tc, _, nx = self._grammar_tables_d()
        self._fsm_d = self._grammar_arm_sampled_fn(
            self._fsm_d, tc, nx, jnp.asarray(slot_idx, jnp.int32),
            jnp.asarray(gs_base, jnp.int32), first_tok_d)
        self._spans.launched(self._fsm_d)

    def _grammar_first_sample(self, last_logits, req: "_Request",
                              gs: int, gen_index: int):
        """Masked admission first-token sample at generation index
        ``gen_index`` of the request's seed stream (index 0 for a plain
        admission; the post-run index after an admission fast-forward —
        forced tokens consume indices but no randomness)."""
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed), gen_index)
        temp = jnp.asarray(req.temperature, jnp.float32)
        if self._grammar is None or req.gpid < 0:
            first_tok_d = self._sample_fn(last_logits, key, temp)
        else:
            mask_d = jnp.asarray(self._grammar.allowed_np(gs))
            first_tok_d = self._grammar_mask_sample_fn(last_logits, key,
                                                       temp, mask_d)
        self._spans.launched(first_tok_d)
        return first_tok_d

    @property
    def _grammar_mask_sample_fn(self):
        """Jitted masked single-logits sampler for admission first
        tokens: drop illegal logits to -inf, then the same seeded
        sampler the unmasked path runs (same key stream, renormalized
        over the masked support)."""
        fn = getattr(self, "_grammar_mask_sample_jit", None)
        if fn is None:
            def masked(logits, key, temperature, mask):
                return self._sample_fn(
                    jnp.where(mask, logits, -jnp.inf), key, temperature)

            fn = jax.jit(masked)
            self._grammar_mask_sample_jit = fn
        return fn

    def _grammar_note_dead_end(self, cause: str) -> None:
        self._grammar_dead_ends[cause] = \
            self._grammar_dead_ends.get(cause, 0) + 1

    def _grammar_consume(self, slot: "_Slot", new_ids) -> None:
        """Advance a slot's host FSM state by consumed tokens and count
        them as masked decode steps."""
        for t in new_ids:
            slot.gs = self._grammar.advance(slot.gs, int(t))
        self._grammar_masked += len(new_ids)

    def _grammar_fast_forward(self, idx: int, slot: "_Slot") -> None:
        """Forced-run fast-forward (the ISSUE 11 tentpole): when the
        slot's FSM state starts a single-successor chain, splice the
        whole run as ONE suffix prefill into its pool blocks instead of
        decoding it token-by-token.

        Net-win policy: in-flight speculative chunks would decode the
        run's prefix anyway (their compute is sunk and, under masking,
        their tokens are exactly the forced tokens), so the splice only
        fires when the chain exceeds what the pipe already covers by
        GRAMMAR_FORCED_RUN_MIN. The spliced-over in-flight chunks are
        marked stale (consumed rows skipped — their token indexing is
        pre-splice) and billed as masked waste, mirroring preemption.

        RNG discipline: forced tokens consume generation indices but no
        randomness; the next sampled token draws fold_in(seed, ngen) at
        the post-run index — byte-identical to what masked step-by-step
        decode (singleton support forces the same tokens) would have
        produced, which is the fast-forward on/off parity the tests
        pin.

        A state-keeping model splices only while none of the slot's
        decode chunks is in flight: the chunks already dispatched run
        before the splice's prefill and have fed the run's first tokens
        to the slot's recurrent state, and K/V rows are rewritten by
        position where a state would take those tokens a second time.
        The masked chunks force the same tokens, so the run is decoded."""
        if (self._grammar is None or not self._use_pool
                or slot.req.gpid < 0 or slot.exhausted):
            return
        if self.model_cfg.keeps_state and slot.decode_chunks_inflight > 0:
            return
        req = slot.req
        g = len(slot.detok.ids)
        cap = req.max_tokens - g
        if cap <= 0:
            return
        run, ends_eos, end_gs = self._grammar.forced_run(slot.gs, cap)
        covered = slot.decode_chunks_inflight * (
            self._chunk_tokens if self._spec_active() else self.chunk_len)
        net = len(run) - covered
        if net < self.grammar_forced_run_min and not (
                ends_eos and run and net > 0):
            return
        n_prompt = len(slot.pool_ids or [])
        base = n_prompt + g          # absolute position after current ids
        if base + len(run) > self._S_alloc:
            return                   # capacity end is the sweep's job
        # Grow the block table to cover the run's KV rows.
        need = pages_for(base + len(run), self.kv_pool_page)
        while len(slot.blocks) < need:
            b = self._pool_alloc(1)
            if b is None:
                return               # pool pressure: decode normally
            self._tables[idx, len(slot.blocks)] = b[0]
            slot.blocks.extend(b)
        # One forward derives the run's KV: positions base-1..base+f-2,
        # i.e. the last already-emitted token (whose row decode had not
        # written yet) plus run[:-1]; the run's last token becomes the
        # device carry and is written by the next decode step, keeping
        # the "last generated token's KV row is unwritten" invariant
        # every replay/radix path assumes.
        ids_full = list(slot.pool_ids or []) + list(slot.detok.ids) + run
        self._pool_prefill_span(self._tables[idx],
                                ids_full[:base + len(run) - 1],
                                max(0, base - 1), idx)
        # Speculative decoding (ISSUE 12): mirror the forced span into
        # the draft cache (from base-1, attending over the slot's
        # already-decoded draft rows) — forced runs bypass drafting
        # entirely, but the 2B must still hold their KV to draft what
        # comes after.
        self._draft_prefill_slot(idx, ids_full[:base + len(run) - 1],
                                 start=max(0, base - 1))
        t_dk = time.monotonic()
        piece = slot.detok.push(*run)
        slot.detok_ms += (time.monotonic() - t_dk) * 1000.0
        slot.gs = end_gs
        if req.export is not None:
            req.export.ids = list(slot.detok.ids)
            req.export.blocks = list(slot.blocks)
        if piece is not None:
            self._emit(req, "token", piece)
        self._grammar_forced += len(run)
        self._grammar_ff_splices += 1
        # Stale in-flight chunks: their rows index a pre-splice token
        # stream — skip them at consume (FIFO makes the count exact)
        # and own up to their now-redundant device steps.
        if slot.decode_chunks_inflight > 0:
            self._bill_waste(min(covered, cap), req)
            slot.stale_chunks += slot.decode_chunks_inflight
        if req.trace is not None:
            req.trace.event(
                f"grammar: forced run of {len(run)} tokens spliced as "
                f"one prefill (state {slot.gs}, "
                f"{'EOS next' if ends_eos else 'decode resumes'})")
        new_g = len(slot.detok.ids)
        if new_g >= req.max_tokens:
            slot.pos = max(slot.pos, base + len(run))
            self._finish(idx, "length")
            return
        if ends_eos:
            slot.pos = max(slot.pos, base + len(run))
            self._finish(idx, "stop")
            return
        # Re-arm the device: carry = the run's last token at its own
        # position; ngen = new_g re-aligns the per-request RNG stream
        # (fold_in(seed, generation_index) — sampling resumes at the
        # index unconstrained masked decode would have reached).
        self._run_arm(idx, base + len(run) - 1,
                      jnp.asarray([run[-1]], jnp.int32),
                      req.temperature, req.max_tokens, req.seed, new_g)
        self._grammar_arm(idx, end_gs)
        slot.anchor_pos = base + len(run) - 1
        slot.anchor_g = new_g
        slot.pos = max(slot.pos, base + len(run))

    def grammar_health(self) -> Optional[dict]:
        """Cheap grammar view for /health (host counters only — same
        rule as qos_health/kv_pool_health)."""
        if self._grammar is None:
            return None
        body = dict(self._grammar.health())
        body["forced_tokens_total"] = self._grammar_forced
        body["masked_steps_total"] = self._grammar_masked
        body["fast_forward_splices_total"] = self._grammar_ff_splices
        body["dead_ends_total"] = dict(self._grammar_dead_ends)
        return body

    def _warm_batch_admit_shapes(self) -> None:
        """Background-compile group-admission programs for the non-smallest
        suffix buckets (the smallest is warmed eagerly at startup). Runs on
        its own scratch state — never touches live scheduler buffers; each
        shape is published to _batch_ready only after its first execution,
        so the scheduler can never block on a half-compiled program."""
        try:
            # Long-prompt offset programs first (prefix-independent; the
            # batched engine never runs the single-sequence ladder warm).
            self._warm_chunked_prefill_offsets()
        except Exception:  # pragma: no cover - warm is best-effort
            logger.exception("chunked-prefill warm failed; long prompts "
                             "compile on first use")
        if self._prefix is None:
            return
        try:
            from .prefix_cache import round_kv_limit

            P = self._prefix.n
            for sbucket in self.prefill_buckets[1:]:
                kvl = round_kv_limit(P + sbucket, self.max_seq_len)
                if kvl is None:
                    continue
                spos = jnp.broadcast_to(
                    P + jnp.arange(sbucket), (1, sbucket)).astype(jnp.int32)
                for kpad in self.admit_kpads_for(kvl):
                    if self._shutdown or not self._running:
                        return
                    if jax.default_backend() != "cpu":
                        try:
                            # AOT-compile the suffix forward OUTSIDE the
                            # scratch lock: jax shares the backend
                            # executable cache across lower().compile()
                            # and the later call (verified on this
                            # toolchain), so the locked window below
                            # holds the scratch for one execution — not
                            # the minutes a cold 7B XLA compile takes,
                            # during which group admissions would all
                            # degrade to singles. Skipped on CPU: there
                            # the extra trace+lower costs more than the
                            # compile it hides. Best-effort: a
                            # mesh-sharded cache lowers with different
                            # layouts here, making this a no-op (the
                            # locked execution then compiles — the
                            # pre-AOT behaviour).
                            scratch_sds = jax.eval_shape(
                                partial(self._new_cache, kpad, kvl))
                            self._get_batch_suffix_fn(
                                kpad, sbucket, kvl).lower(
                                self.params,
                                jax.ShapeDtypeStruct((kpad, sbucket),
                                                     jnp.int32),
                                jax.ShapeDtypeStruct((kpad, sbucket),
                                                     jnp.int32),
                                scratch_sds,
                                jax.ShapeDtypeStruct((kpad, sbucket),
                                                     jnp.float32),
                                jax.ShapeDtypeStruct((kpad,), jnp.int32),
                                jax.ShapeDtypeStruct((kpad,), jnp.int32),
                                jax.ShapeDtypeStruct((kpad,), jnp.float32),
                            ).compile()
                        except Exception:  # pragma: no cover - best-effort
                            logger.debug(
                                "AOT warm compile failed; the locked "
                                "execution will compile instead",
                                exc_info=True)
                    # Scratch serialization: the warm's kpad-row scratch
                    # (suffix depth, same as a live group admission's) and
                    # the scheduler's must never be resident TOGETHER —
                    # warm used to double peak admission-scratch HBM,
                    # part of the r5 bs=64 OOM budget. While this thread
                    # holds the lock, group admissions fall back to
                    # singles instead of blocking.
                    with self._admit_scratch_lock:
                        scratch = self._new_cache(kpad, kvl)
                        scratch = self._get_batch_prefix_splice_fn(kpad)(
                            scratch, self._prefix.k, self._prefix.v)
                        ft, scratch = self._get_batch_suffix_fn(
                            kpad, sbucket, kvl)(
                            self.params,
                            jnp.zeros((kpad, sbucket), jnp.int32),
                            jnp.broadcast_to(spos, (kpad, sbucket)),
                            scratch, jnp.ones((kpad, sbucket), jnp.float32),
                            jnp.ones((kpad,), jnp.int32),
                            jnp.zeros((kpad,), jnp.int32),
                            jnp.zeros((kpad,), jnp.float32),
                        )
                        ft.block_until_ready()
                        del scratch, ft
                    self._warm_splice_aot(kpad, kvl)
                    self._batch_ready.add((kpad, sbucket, kvl))
        except Exception:  # pragma: no cover - warm is best-effort
            logger.exception("batch-admission warm failed; "
                             "single-admission fallback stays")

    def _warm_splice_aot(self, kpad: int, depth: int) -> None:
        """Prime the splice-into-slots program for a ``depth``-deep
        scratch src WITHOUT executing it: the program donates the LIVE
        cache, so only the pre-worker eager warm may run it — for the
        non-hot suffix depths the background warm AOT-compiles instead
        (lower().compile() primes the backend executable cache; the
        scheduler's first use re-traces a tiny scatter and hits it).
        Best-effort: under a mesh the unsharded ShapeDtypeStructs lower a
        different layout and the first use pays a small scatter compile —
        covered by the watchdog's admission grace."""
        try:
            cache_sds = jax.eval_shape(
                partial(self._new_cache, self.batch_size, self._S_alloc))
            scratch_sds = jax.eval_shape(partial(self._new_cache, kpad,
                                                 depth))
            N = self.batch_size
            self._get_batch_splice_fn(kpad).lower(
                cache_sds, scratch_sds.k, scratch_sds.v,
                jax.ShapeDtypeStruct((N, 1), jnp.int32),
                jax.ShapeDtypeStruct((N, 1), jnp.int32),
                jax.ShapeDtypeStruct((N,), jnp.float32),
                jax.ShapeDtypeStruct((N,), jnp.bool_),
                jax.ShapeDtypeStruct((N,), jnp.int32),
                jax.ShapeDtypeStruct((N,), jnp.int32),
                jax.ShapeDtypeStruct((N,), jnp.int32),
                jax.ShapeDtypeStruct((kpad,), jnp.int32),
                jax.ShapeDtypeStruct((kpad,), jnp.int32),
                jax.ShapeDtypeStruct((kpad,), jnp.int32),
                jax.ShapeDtypeStruct((kpad,), jnp.float32),
                jax.ShapeDtypeStruct((kpad,), jnp.int32),
                jax.ShapeDtypeStruct((kpad,), jnp.int32),
            ).compile()
        except Exception:  # pragma: no cover - best-effort
            logger.debug("splice AOT warm failed; first group admission "
                         "of this shape compiles a small scatter",
                         exc_info=True)

    async def stop(self, drain_secs: float = 0.0) -> None:
        self._ready = False          # new generate() calls now 503
        self._stopping = True        # watchdog must not re-mark ready
        if drain_secs > 0:
            # Drain: the scheduler keeps running, finishing active slots
            # and admitting anything already queued; we only tear down
            # once the system is empty or the deadline passes (remaining
            # work is then aborted by the shutdown path below). Racy reads
            # of scheduler-owned state are fine for a poll.
            deadline = time.monotonic() + drain_secs
            while time.monotonic() < deadline:
                # getattr: _slots/_inflight only exist after a successful
                # start(); cleanup after a failed startup must not mask
                # the original error with an AttributeError here.
                busy = (any(s is not None
                            for s in getattr(self, "_slots", ()))
                        or not self._admissions.empty()
                        or self._admitting > 0
                        or bool(getattr(self, "_parked", ()))
                        or bool(getattr(self, "_inflight", ())))
                # A concurrent stop(0) — the second-signal force path —
                # sets _shutdown mid-drain; stop waiting immediately.
                if not busy or self._shutdown:
                    break
                await asyncio.sleep(0.05)
        self._running = False
        self._shutdown = True
        if self._worker is not None:
            await asyncio.to_thread(self._worker.join, 10.0)
            self._worker = None
        t = getattr(self, "_batch_warm_thread", None)
        if t is not None:
            await asyncio.to_thread(t.join, 60.0)
            self._batch_warm_thread = None
        await super().stop()

    def stats(self) -> dict:
        """Live scheduler state for the /metrics gauges (scraped, not
        pushed): slot occupancy, admission queue depth, and page-granular
        KV accounting (page size = KV_POOL_PAGE in either layout)."""
        slots = list(getattr(self, "_slots", None) or [])
        if self._use_pool and self._pool is not None:
            # Pool truth: pages = pool blocks, used = everything not on
            # the free list (live slot mappings + radix-cached chains).
            used = self._pool.n_blocks - self._pool.free_count
            pages_total = self._pool.n_blocks
        else:
            page = self.kv_pool_page
            pages_per_slot = -(-self.max_seq_len // page)
            # pos can run into the S_alloc slack on a final chunk; clamp
            # so used never exceeds total (utilization ratios stay <= 1).
            used = sum(
                -(-min(s.pos, self.max_seq_len) // page)
                for s in slots if s is not None
            )
            pages_total = self.batch_size * pages_per_slot
        # Windowed decode throughput (engine_tokens_per_sec): tokens
        # completed over the trailing window, counted at the scheduler —
        # covers every finish (streams included), immune to the
        # last-writer race the old per-request gauge had.
        horizon = time.monotonic() - self.TOKEN_RATE_WINDOW_SECS
        tok_window = sum(n for t, n in list(self._token_finishes)
                         if t >= horizon)
        # Drain the fetch-latency samples accumulated since the last
        # scrape (the /metrics handler feeds them into the
        # chunk_fetch_seconds histogram). popleft-until-empty is safe
        # against the scheduler thread appending concurrently.
        fetch_samples = []
        while True:
            try:
                fetch_samples.append(self._fetch_samples.popleft())
            except IndexError:
                break
        return {
            "batch_occupancy": sum(s is not None for s in slots),
            "queue_depth": self._admissions.qsize(),
            "kv_pages_used": used,
            "kv_pages_total": pages_total,
            # Block-paged pool + radix sharing (ISSUE 10): block-state
            # counts, sharing/COW totals, radix hit/miss token counters
            # — delta-mirrored into Prometheus at scrape time
            # (Metrics.observe_kv_pool) and summarized in /health.
            "kv_pool": self.kv_pool_health(),
            # The cache kinds' sections (moe, sparse_attention,
            # latent_attention, sliding_attention, ssm): cumulative
            # counters, null where the configuration is not of the kind.
            **self.family_health(),
            "ragged": self.ragged_health(),
            "sharding": self.sharding_health(),
            "queue_rejections": self._rejections,
            "max_queue_depth": self.max_queue_depth,
            "tokens_per_sec_window": tok_window / self.TOKEN_RATE_WINDOW_SECS,
            # Decode-pipeline observability (ISSUE 4): speculative chunks
            # currently in flight vs the configured depth, the device's
            # own live-slot count from the last consumed chunk, wasted
            # decode-step and chunk dispatch/consume/prune totals, and
            # the drained fetch-latency samples.
            "pipe_depth": self.chunk_pipe_depth,
            "pipe_inflight": sum(
                1 for e in list(getattr(self, "_inflight", []))
                if e[0] == "chunk"),
            "device_active_slots": self._last_n_alive,
            "device_termination": self.device_termination,
            "wasted_decode_steps": self._wasted_steps,
            "chunks_dispatched": self._chunks_dispatched,
            "chunks_consumed": self._chunks_consumed,
            "chunks_pruned": self._chunks_pruned,
            "chunk_fetch_secs": fetch_samples,
            # Fault-containment totals (ISSUE 5): resets by cause,
            # quarantines by reason, health trips, replayed tokens —
            # delta-mirrored into Prometheus at scrape time
            # (Metrics.observe_containment) and surfaced in /health.
            "containment": dict(self.supervisor.stats(),
                                parked=len(self._parked),
                                slot_health_check=self.slot_health_check),
            # QoS ring (ISSUE 7): per-lane queue depth + occupancy,
            # expiry/displacement/preemption totals, brownout state —
            # delta-mirrored into Prometheus at scrape time
            # (Metrics.observe_qos) and summarized in /health.
            "qos": dict(self._admissions.stats(),
                        lane_occupancy=self.lane_occupancy(),
                        preemptions=self._preemptions,
                        preempted_tokens=self._preempted_tokens,
                        brownout_level=self._brownout.level,
                        brownout_transitions=self._brownout.transitions,
                        lane_shares={
                            k: round(v, 4)
                            for k, v in self._brownout.shares.items()}),
            # Telemetry plane (ISSUE 8): goodput ledger lane table and
            # SLO burn rates — delta-mirrored into Prometheus at scrape
            # time (Metrics.observe_ledger / observe_slo). Pure reads.
            "ledger": self.ledger.snapshot(),
            "slo": self._slo.snapshot(),
            # Grammar-constrained decoding (ISSUE 11): forced/masked
            # token totals + dead ends by cause — delta-mirrored at
            # scrape time (Metrics.observe_grammar) and summarized in
            # /health's grammar section.
            "grammar": self.grammar_health(),
            # Speculative decoding (ISSUE 12): drafted/accepted totals
            # + acceptance ratio — delta-mirrored at scrape time
            # (Metrics.observe_spec) and summarized in /health's spec
            # section.
            "spec": self.spec_health(),
            # Perf-regression sentinel (ISSUE 15): per-(phase, bucket)
            # step-time digests + breach verdicts — mirrored into the
            # step_time_seconds{phase,bucket,quantile} gauges at scrape
            # time (Metrics.observe_steptime) and watched by the
            # service-level incident triggers.
            "steptime": self._steptime.snapshot(),
        }

    def steptime_health(self) -> dict:
        """Cheap step-time sentinel view for /health and the incident
        watcher (a bounded-ring sort per digest, never stats())."""
        return self._steptime.snapshot()

    #: finish timestamps older than this don't feed the drain-rate
    #: estimate — after an idle hour the first shed must not price
    #: Retry-After off a rate diluted by the gap.
    DRAIN_RATE_HORIZON_SECS = 60.0

    #: averaging window for the stats() tokens_per_sec_window rate.
    TOKEN_RATE_WINDOW_SECS = 60.0

    def retry_after_hint(self, extra_depth: int = 0,
                         lane: Optional[str] = None) -> float:
        """Seconds until queued work plausibly drains, from the live
        completion rate over recent finishes (last ≤64, within the
        freshness horizon) — the Retry-After a shed response carries.
        With ``lane`` set the estimate is priced from THAT lane's own
        queue depth and drain rate (a background shed must not quote
        the interactive lane's brisk drain); it falls back to the
        engine-wide estimate when the lane has no drain history. Falls
        back to 5 s with no recent drain history at all (cold or
        just-woken engine), clamped to [1, 60]."""
        horizon = time.monotonic() - self.DRAIN_RATE_HORIZON_SECS
        if lane is not None:
            depth = self._admissions.lane_depths().get(lane, 0) + extra_depth
            ts = [t for t in list(self._lane_finish.get(lane, ()))
                  if t >= horizon]
            if len(ts) >= 2 and ts[-1] > ts[0]:
                rate = (len(ts) - 1) / (ts[-1] - ts[0])
                if rate > 0:
                    return min(max(depth / rate, 1.0), 60.0)
            return self.retry_after_hint(extra_depth)
        depth = self._admissions.qsize() + extra_depth
        ts = [t for t in list(self._finish_times) if t >= horizon]
        if len(ts) >= 2 and ts[-1] > ts[0]:
            rate = (len(ts) - 1) / (ts[-1] - ts[0])
            if rate > 0:
                return min(max(depth / rate, 1.0), 60.0)
        return 5.0

    # ---------------------------------------------------------- scheduler

    def _worker_loop(self) -> None:
        # Chunk pipeline, CHUNK_PIPE_DEPTH deep (default 2): dispatch chunk
        # N+1 (chained on device arrays) before pulling chunk N's tokens,
        # so the fetch and the host's work between chunks overlap decode
        # compute. The inflight queue carries two entry kinds, consumed
        # strictly FIFO:
        #
        # - ("chunk", toks_d, snapshot): a decode chunk for all slots, with
        #   a snapshot of slot→request at dispatch time; a row whose slot
        #   was freed or reassigned since is discarded on read.
        # - ("first", tok_d, req, slot_idx): an admission's first token,
        #   still on device — admissions never block on a host read (the
        #   round-1 bottleneck: one blocking RTT per admission serialized
        #   prefill against decode). The value is pulled when the entry
        #   reaches the queue head, by which time later-dispatched work
        #   overlaps the transfer.
        #
        # Admissions splice onto the *latest* device state, so a request
        # admitted with k chunks in flight starts decoding k chunks later
        # (at the default depth: right after a consume, behind the one
        # chunk that is running) — ordering stays linear because
        # everything chains through donated buffers. Only "chunk" entries
        # count against the pipeline depth; first-token entries are
        # transfers, not compute.
        # (self._inflight is created at startup and deliberately NOT
        # reset here: a supervisor restart may already have queued
        # replayed admissions' first-token entries.)
        while self._running:
            try:
                if self.faults is not None:
                    # scheduler:die — raises a BaseException the except
                    # below can't catch: this thread dies for real, and
                    # _supervise_scheduler's restart is what recovers.
                    self.faults.check_scheduler_die()
                self._last_progress = time.monotonic()
                self._spans.note_slots(self._slots)
                self._spans.note_pipe(self._inflight)
                # Bisection probation: the parked half is exonerated when
                # the probe group fully drains (no slots, no pipeline) —
                # or earlier, after PROBATION_CLEAN_CHUNKS clean chunks in
                # _consume_oldest, so long-generation probes don't stall
                # admissions for their whole remaining decode.
                if (self._parked and not self._inflight
                        and all(s is None for s in self._slots)):
                    self._unpark_parked()
                    continue
                # QoS ring: AIMD brownout evaluation (time-gated, cheap)
                # and preemptive decode — a higher-lane request starved
                # past PREEMPT_WAIT_MS with every slot busy exports the
                # cheapest lower-lane victim, whose freed slot the
                # _admit_pending call right below hands to that lane.
                self._brownout.maybe_eval(
                    burn_fn=lambda: self._slo.fast_burn(
                        SLO_QUEUE_WAIT, LANE_INTERACTIVE))
                self._maybe_preempt()
                self._admit_pending()
                self._sweep_finishes()
                n_active = sum(
                    s is not None and not s.exhausted for s in self._slots
                )
                chunks_in_pipe = self._chunks_in_pipe()
                # Latency mode at low occupancy: deliver a fresh admission's
                # first token before launching speculative decode chunks —
                # the transfer otherwise queues behind a full chunk's
                # compute (~TTFT + one chunk). With
                # more streams active, throughput mode: keep the pipeline
                # full and let transfers overlap.
                if (chunks_in_pipe == 0 and n_active <= 2 and self._inflight
                        and self._inflight[0][0] in ("first", "firsts")):
                    self._consume_oldest()
                    continue
                if n_active > 0 and chunks_in_pipe < self.chunk_pipe_depth:
                    # Burst ramp: slots a chunk is dispatched without can't
                    # join it — a request that misses the first
                    # CHUNK_PIPE_DEPTH chunks (~0.23 s each on 7B geometry,
                    # PERF.md section 5) starts that many chunk periods
                    # late even though the whole burst arrived within
                    # ~65 ms (round-4 probe). While admissions still
                    # show momentum (one landed within the last 30 ms) and
                    # free slots remain, nap briefly instead of dispatching
                    # chunk 1, so the rest of the burst boards it. Costs a
                    # lone request ≤ ~30 ms on its *second* token (TTFT
                    # rides the admission program, unaffected).
                    now = time.monotonic()
                    if (chunks_in_pipe == 0
                            and any(s is None for s in self._slots)
                            and now - self._last_admit_t
                                < self.ADMIT_RAMP_SECS):
                        # Every admission re-arms the momentum check, so a
                        # steady trickle could defer chunk 0 indefinitely;
                        # the hold is additionally capped from when it
                        # first engaged (ADMIT_RAMP_MAX_SECS).
                        if self._ramp_hold_t0 is None:
                            self._ramp_hold_t0 = now
                        if now - self._ramp_hold_t0 < self.ADMIT_RAMP_MAX_SECS:
                            if self._admissions.empty():
                                with self._spans.sched.region("idle"):
                                    time.sleep(0.002)
                            continue
                    self._ramp_hold_t0 = None
                    self._dispatch_chunk()
                    continue
                self._prune_dead_chunks()
                if self._inflight:
                    self._consume_oldest()
                    continue
                # Idle: block until an admission arrives. Routed through
                # _admit_popped so a failing admission (e.g. an injected
                # admit fault or a scratch-cache OOM) errors THAT request
                # instead of tripping the scheduler-error path that fails
                # every active slot.
                try:
                    with self._spans.sched.region("idle"):
                        req = self._admissions.get(timeout=0.05)
                except _queue.Empty:
                    continue
                self._admitting += 1
                self._admitting_reqs.append(req)
                try:
                    self._admit_popped([req])
                finally:
                    self._admitting -= 1
            except Exception as e:
                # The step is POISONED, not the engine: before ISSUE 5 this
                # path failed every active slot — one bad request (or one
                # flaky device step) took down the whole batch. Now the
                # containment pass quarantines the culprit (bisecting when
                # the fault names no slot) and reset-and-replays the
                # innocent survivors; only an exhausted reset budget falls
                # back to the old fail-everything behaviour.
                logger.exception("batch scheduler step poisoned; "
                                 "running containment")
                try:
                    self._contain_poisoned_step(CAUSE_SCHEDULER_ERROR,
                                                error=e)
                except Exception:  # pragma: no cover - containment itself
                    logger.exception("containment failed; failing active "
                                     "slots")
                    self._fail_all_active(
                        EngineUnavailable("scheduler error"))
        # Shutdown: fail everything still holding a coroutine — active
        # slots (their in-flight chunks are abandoned), parked probation
        # slots, and queued admissions — so no generate() call blocks
        # forever.
        self._fail_all_active(EngineUnavailable("engine stopped"))
        while True:
            try:
                req = self._admissions.get_nowait()
            except _queue.Empty:
                break
            self._emit(req, "error", EngineUnavailable("engine stopped"))

    def _worker_main(self) -> None:
        """Scheduler-thread entry: runs the loop and, when the loop dies
        of an uncatchable fault (BaseException — the poisoned-step
        containment inside the loop handles every Exception), lets the
        thread exit so _supervise_scheduler notices the corpse and
        restarts it. Never re-raises: a dead scheduler is a recoverable
        engine event, not a process event."""
        self._spans.sched.start()
        try:
            self._worker_loop()
        except BaseException:
            logger.critical(
                "batch scheduler thread died; supervisor will restart it",
                exc_info=True)
        finally:
            self._spans.sched.stop()

    # ------------------------------------------- containment (ISSUE 5)

    def set_reset_listener(self, fn) -> None:
        """Wire engine resets to the service layer (the PR 1 breaker):
        ``fn(cause)`` runs after every recorded reset, so a flapping
        engine opens the breaker even while individual requests keep
        recovering."""
        self.supervisor.on_reset = fn

    def _fail_all_active(self, error: BaseException) -> None:
        """The pre-containment blast radius — every active, parked, and
        (NOT queued — those stay) request fails. Only reached when
        containment itself is out of budget or broken."""
        self._inflight.clear()
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._finish(i, "abort", error=error)
        for slot in self._parked:
            self._emit(slot.req, "error", error)
        self._parked.clear()

    def _contain_poisoned_step(self, cause: str, named=(),
                               error: Optional[BaseException] = None) -> None:
        """The quarantine + reset-and-replay pass (scheduler thread).

        ``named`` lists slots the device health word implicated (the
        culprit is known); empty means a step-wide fault (exception in a
        scheduler step / poisoned fetch) where the culprit is unknown
        and bisection does the isolating: replay half the survivors,
        park the rest, recurse on whichever half poisons again. A slot
        solo-implicated past its retry budget is failed terminally with
        RequestQuarantined (410 — the engine is fine, THAT request is
        not); everyone else is re-spliced from prompt + generated-so-far
        prefix and replayed under its recorded sampling seed, so
        recovered transcripts are bit-identical to a fault-free run.
        Queued admissions are untouched throughout — a reset drops zero
        queued requests."""
        survivors = [s for s in self._slots if s is not None]
        if not self.supervisor.allow_reset():
            # Reset budget exhausted (ENGINE_RESET_MAX_PER_MIN): stop
            # resetting — a flapping engine must degrade, not thrash.
            # Failing the affected requests feeds the PR 1 breaker,
            # which is the designed next ring out.
            logger.critical(
                "engine reset budget exhausted (%d/min); failing %d "
                "slot(s) instead of resetting again",
                self.supervisor.max_resets_per_min, len(survivors))
            self._fail_all_active(error if isinstance(error, Exception)
                                  else EngineUnavailable(
                                      "engine reset budget exhausted"))
            return

        # Culprit isolation. Health-named suspects are implicated
        # directly; an un-named fault whose suspect pool is down to one
        # request has bisected to its culprit. Either way the retry
        # budget decides quarantine-now vs one-more-replay (a transient
        # device fault must not kill an innocent request on first trip).
        quarantined: List[_Slot] = []
        reasons: dict = {}
        pool = list(survivors)
        if named:
            for slot in named:
                if self.supervisor.implicate(slot.req):
                    quarantined.append(slot)
                    reasons[id(slot)] = REASON_HEALTH
        else:
            # Narrow to the standing suspect pool: after an early
            # exoneration the batch re-mixes cleared cohabitants (and new
            # admissions) with the still-suspect half, and only the
            # latter should keep bisecting. No flags standing (or a stale
            # pool that already drained) means everyone is suspect.
            flagged = [s for s in survivors if s.req.suspect]
            if flagged:
                pool = flagged
            if len(pool) == 1:
                slot = pool[0]
                if self.supervisor.implicate(slot.req):
                    quarantined.append(slot)
                    reasons[id(slot)] = REASON_ISOLATED

        # Tear down: slots detach, the speculative pipeline drops, and
        # the device state is rebuilt exactly as startup built it. Pool
        # mode: the rebuilt allocator/radix world starts empty, so every
        # survivor's block list is a stale previous-generation view —
        # cleared here; replays re-allocate (and must NEVER decref stale
        # ids into the fresh pool).
        self._slots = [None] * self.batch_size
        self._inflight.clear()
        self._reset_decode_state()
        if self._use_pool:
            for s in survivors:
                s.blocks = []
        self.supervisor.note_reset(cause)

        qset = {id(s) for s in quarantined}
        for slot in quarantined:
            reason = reasons[id(slot)]
            self.supervisor.note_quarantine(reason)
            # Ledger: everything this request generated is now discarded
            # — its steps were burned, never delivered (a quarantine
            # never reaches _finish, so nothing double-bills).
            burn = len(slot.detok.ids) - slot.req.ledger_delivered
            slot.req.ledger_delivered = len(slot.detok.ids)
            self.ledger.record(CLASS_QUARANTINE_BURN, burn,
                               lane=slot.req.lane, tenant=slot.req.tenant)
            if slot.req.trace is not None:
                slot.req.trace.event(
                    f"engine: quarantined ({reason}, "
                    f"suspected {slot.req.suspect_count}x, "
                    f"{len(slot.detok.ids)} tokens generated)")
            self._finish_times.append(time.monotonic())
            self._emit(slot.req, "error", RequestQuarantined(
                f"request quarantined after poisoning {cause} "
                f"{slot.req.suspect_count}x (retry budget "
                f"{self.supervisor.retry_budget})"))

        rest = [s for s in survivors
                if id(s) not in qset and not s.req.cancel.is_set()]
        if named:
            probe, parked = rest, []
        else:
            # Step-wide fault: bisect WITHIN the suspect pool only —
            # replay one half of it, park the other, and replay every
            # non-suspect (exonerated cohabitant / post-fault admission)
            # immediately alongside the probe. If the probe poisons
            # again, this pass recurses on the halved pool; if it runs
            # PROBATION_CLEAN_CHUNKS clean chunks (or drains), suspicion
            # narrows to the parked half and it unparks.
            pool_rest = [s for s in pool
                         if id(s) not in qset and not s.req.cancel.is_set()]
            pool_ids = {id(s) for s in pool_rest}
            innocents = [s for s in rest if id(s) not in pool_ids]
            if len(pool_rest) <= 1:
                probe, parked = rest, []
            else:
                probe_sus, parked = EngineSupervisor.split(pool_rest)
                probe = probe_sus + innocents
            for s in innocents:
                s.req.suspect = False
            for s in pool_rest:
                s.req.suspect = True
        logger.warning(
            "engine reset (%s): %d survivor(s) — %d quarantined, "
            "%d replaying, %d parked for bisection",
            cause, len(survivors), len(quarantined), len(probe),
            len(parked))
        self._parked.extend(parked)
        self._probation_clean = 0   # each containment pass restarts probation
        for slot in parked:
            if slot.req.trace is not None:
                slot.req.trace.event(
                    "engine: parked for culprit bisection")
        for slot in probe:
            self._guarded_replay(slot)

    def _unpark_parked(self) -> None:
        """End bisection probation: replay every parked slot (each
        resumes from its generated-so-far prefix) and let admissions
        resume on the next loop pass."""
        parked, self._parked = self._parked, []
        self._probation_clean = 0
        for slot in parked:
            self._guarded_replay(slot)

    def _reset_decode_state(self) -> None:
        """Rebuild every device-resident buffer from scratch. The old
        buffers may be donated-away or poisoned (NaN KV rows) — nothing
        is salvaged; replay re-derives per-slot state from host truth
        (prompt + emitted tokens + seed)."""
        self._init_decode_state()
        # Staged ragged admissions die with the device state they were
        # staged against; replay's fresh _admit_one re-stages them.
        self._pending_adm.clear()
        self._last_progress = time.monotonic()

    def _guarded_replay(self, slot: "_Slot") -> None:
        """Replay one surviving slot; a failing replay (OOM, fault drill
        hitting the admission path) errors THAT request only."""
        try:
            self._replay_slot(slot)
        except Exception:
            logger.exception("replay failed; failing the request")
            self._emit(slot.req, "error",
                       EngineUnavailable("replay after engine reset failed"))

    def _replay_slot(self, slot: "_Slot") -> None:
        """Re-splice one surviving request from prompt + generated-so-far
        prefix: prefill(prompt ++ emitted[:-1]), force the carry token to
        the last emitted id, and re-arm the device vectors with
        ngen = len(emitted) — the per-request seed stream then continues
        at exactly the generation index a fault-free run would be at, so
        the remaining tokens are bit-identical. The slot object (detok
        state, timings, trace) is reused: nothing already streamed to the
        client is re-emitted.

        Numerics caveat: the replay rebuilds the emitted tokens' KV via
        one batched prefill where the original run built it step-by-step
        in decode. Bit-identity therefore also rests on prefill/decode
        producing the same floats for the same positions — exact here
        (f32 CPU/TPU tests) but a last-ULP logit difference under e.g.
        bf16 matmul reduction reordering could flip a near-tie pick
        (same numerics class as the int8-KV argmax-flip xfail)."""
        req = slot.req
        # Consume the resume cause at ENTRY — the early returns below
        # must clear it too, or a preempted-then-cancelled request's
        # later containment replay would misbill as preempted.
        resume_cause, req.resume_cause = req.resume_cause, ""
        if req.cancel.is_set():
            return
        if req.deadline is not None and time.monotonic() > req.deadline:
            self._emit(req, "error",
                       GenerationTimeout("generation timeout"))
            return
        ids = list(slot.detok.ids)
        if not ids:
            # Nothing emitted yet (the admission's first token was still
            # in the dropped pipeline): a fresh admission reproduces the
            # original run exactly — the first token samples at index 0
            # of the same seed stream.
            self._admit_one(req)
            return
        g = len(ids)
        slot_idx = self._slots.index(None)
        replay_ids = list(req.prompt_ids) + ids[:-1]
        if self._use_pool:
            # Pool replay: the re-derivation is a radix match first — a
            # preempted victim's chain was cached at preemption, so its
            # resume re-maps shared blocks (plus one tail COW) and
            # prefills NOTHING instead of re-prefilling prompt+prefix;
            # after a containment reset the tree is empty and this
            # degenerates to a full prefill into fresh blocks, exactly
            # the dense path's semantics.
            max_prompt = self.max_seq_len - max(1, req.max_tokens - g)
            if len(replay_ids) > max_prompt:
                replay_ids = replay_ids[-max_prompt:]
            n_total = len(replay_ids)
            blocks, m = self._pool_map_prefix(replay_ids, match_all=True,
                                              slot_idx=slot_idx)
            try:
                self._tables[slot_idx, :] = self._pool_n_blocks
                self._tables[slot_idx, :len(blocks)] = blocks
                if m < n_total:
                    # a model with a recurrent state resumes from the
                    # nearest snapshot on the chain, or from token 0
                    self._pool_prefill_span(self._tables[slot_idx],
                                            replay_ids, m, slot_idx)
                self._run_arm(slot_idx, n_total,
                              jnp.asarray([ids[-1]], jnp.int32),
                              req.temperature, req.max_tokens, req.seed, g)
                # Speculative decoding (ISSUE 12): the draft cache was
                # reset (or belongs to another request) — re-derive the
                # 2B's view of prompt + emitted[:-1] so drafting resumes
                # conditioned on the same transcript.
                self._draft_prefill_slot(slot_idx, replay_ids)
            except Exception:
                self._tables[slot_idx, :] = self._pool_n_blocks
                self._pool.decref(blocks)
                if self._state is not None:
                    release_state(self._state, None, slot_idx, (), False)
                raise
            slot.blocks = blocks
            # The chain basis (admitted prompt part) for the eventual
            # radix insert: replay_ids minus the g-1 generated ids.
            slot.pool_ids = replay_ids[:n_total - (g - 1)] if g > 1 \
                else replay_ids
            if req.export is not None:
                req.export.blocks = list(blocks)
            if req.trace is not None and m > 0:
                req.trace.event(
                    f"engine: replay re-mapped {m}/{n_total} tokens from "
                    f"shared pool blocks (prefilled {n_total - m})")
        else:
            last_logits, scratch, n_total, _ = self._prefill_prompt(
                replay_ids, max(1, req.max_tokens - g))
            del last_logits  # the next token is sampled in-chunk, not here
            (self._cache, self._tok_d, self._pos_d, self._temps_d,
             self._active_d, self._ngen_d, self._budget_d,
             self._seeds_d) = self._splice_fn(
                self._cache, scratch.k, scratch.v, self._tok_d, self._pos_d,
                self._temps_d, self._active_d, self._ngen_d, self._budget_d,
                self._seeds_d,
                jnp.asarray(slot_idx, jnp.int32),
                jnp.asarray(n_total, jnp.int32),
                jnp.asarray([ids[-1]], jnp.int32),
                jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.max_tokens, jnp.int32),
                jnp.asarray(req.seed, jnp.int32),
                jnp.asarray(g, jnp.int32),
            )
            self._spans.launched(self._seeds_d)
        slot.pos = n_total
        slot.anchor_pos = n_total
        slot.anchor_g = g
        slot.chunks_inflight = 0
        slot.decode_chunks_inflight = 0
        slot.stale_chunks = 0
        if self._grammar is not None and req.gpid >= 0:
            # Host truth and device state both re-derive from the
            # emitted ids: the next masked step samples at the state the
            # fault-free run would be in.
            slot.gs = self._grammar.run(req.gpid, ids)
            self._grammar_arm(slot_idx, slot.gs)
        slot.exhausted = n_total >= self.max_seq_len
        self._slots[slot_idx] = slot
        self.supervisor.note_replay(g)
        # Ledger: the g already-generated tokens are re-derived by the
        # replay prefill — device work that produces no new client byte.
        # Preemption resumes bill the preempted class; containment
        # resets and fleet-migration imports bill replayed.
        cls = (CLASS_PREEMPTED if resume_cause == "preempt"
               else CLASS_REPLAYED)
        self.ledger.record(cls, g, lane=req.lane, tenant=req.tenant)
        if req.trace is not None:
            req.trace.event(
                f"engine: replayed into slot {slot_idx} from {g} "
                f"generated tokens (seed {req.seed})")
            req.trace.link("resumed", slot=slot_idx, tokens=g)
        self._last_admit_t = time.monotonic()

    def _supervise_scheduler(self) -> None:
        """Watch for scheduler-thread DEATH (the watchdog watches for
        scheduler HANG). A dead scheduler — scheduler:die in drills, an
        uncatchable error in the wild — is recovered exactly like a
        poisoned step: reset, replay survivors, restart the loop thread.
        Queued admissions live in a thread-safe queue the dead thread
        never drained, so zero queued requests are dropped."""
        while self._running:
            time.sleep(0.2)
            worker = self._worker
            if (not self._running or self._stopping or worker is None
                    or worker.is_alive()):
                continue
            survivors = [s for s in self._slots if s is not None]
            if not self.supervisor.allow_reset():
                logger.critical(
                    "scheduler dead and reset budget exhausted; "
                    "marking engine degraded")
                self._ready = False
                err = EngineUnavailable(
                    "scheduler dead; engine reset budget exhausted")
                self._fail_all_active(err)
                for req in self._admitting_reqs:
                    self._emit(req, "error", err)
                self._admitting_reqs.clear()
                while True:
                    try:
                        req = self._admissions.get_nowait()
                    except _queue.Empty:
                        break
                    self._emit(req, "error", err)
                return
            logger.critical("batch scheduler thread dead; resetting decode "
                            "state and restarting it (%d survivor(s))",
                            len(survivors))
            # Requeue requests the dead thread had popped but not yet
            # settled (mid-admission when it died): they hold no slot and
            # no generated tokens, so a fresh admission is a correct
            # replay. Skip any that DID reach a slot before the death —
            # those ride the survivor replay below.
            slotted = {id(s.req) for s in survivors}
            for req in self._admitting_reqs:
                if id(req) not in slotted:
                    # Head re-entry, never put(): an already-admitted
                    # request must not be shed by caps on its way back.
                    self._admissions.requeue_head(req)
            self._admitting_reqs.clear()
            self._slots = [None] * self.batch_size
            self._inflight.clear()
            self._reset_decode_state()
            if self._use_pool:
                for s in survivors:
                    s.blocks = []
            self.supervisor.note_reset(CAUSE_SCHEDULER_DEATH)
            for slot in survivors:
                self._guarded_replay(slot)
            self._worker = threading.Thread(
                target=self._worker_main, name="batch-scheduler",
                daemon=True)
            self._worker.start()

    #: batched-admission group sizes (pow2-padded); cap bounds the scratch
    #: KV memory (kpad × S_alloc slots) and the compile variety.
    ADMIT_KPADS = (2, 4, 8, 16)

    #: how long after an admission the scheduler keeps holding the FIRST
    #: speculative decode chunk for more of the burst to board it, and the
    #: hard cap on one continuous hold (re-armed momentum can't exceed it).
    ADMIT_RAMP_SECS = 0.03
    ADMIT_RAMP_MAX_SECS = 0.12

    def _replicated(self, arr):
        """Pin an array to fully-replicated sharding under a serving mesh
        (no-op single-device). Applied to the packed chunk buffer so the
        host fetch reads one complete, settled copy regardless of how the
        partitioner laid out the concat of data-sharded tokens and
        replicated scalars."""
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(self.mesh, PartitionSpec()))

    @property
    def admit_kpads(self) -> tuple:
        """Group sizes structurally usable: a group can never exceed the
        free slot count, so kpads beyond batch_size would only waste
        warm-up compiles and scratch HBM. Empty at batch_size==1: the
        group path is structurally unreachable there (a burst can never
        pop more than one free slot's worth). Per-shape HBM capping on
        top of this list lives in ``admit_kpads_for``. POOL mode returns
        empty: suffixes prefill directly into freshly allocated blocks
        (no staging scratch), which makes the whole group-admission
        scratch machinery — and its ADMIT_SCRATCH_MB budget — obsolete
        there (the ISSUE 10 contract)."""
        if self._use_pool:
            return ()
        return tuple(k for k in self.ADMIT_KPADS if k <= self.batch_size)

    def admit_kpads_for(self, depth: int) -> tuple:
        """Group sizes usable for a suffix-scratch ``depth`` (the shape's
        kv_limit): ``admit_kpads`` further capped so kpad × one scratch
        row's KV bytes fits the ADMIT_SCRATCH_MB budget
        (``_cap_admit_kpads``). Unknown depths (budget disabled, or no
        prefix cache) pass through uncapped."""
        kpads = self.admit_kpads
        cap = self._admit_kpad_caps.get(depth)
        if cap is not None:
            kpads = tuple(k for k in kpads if k <= cap)
        return kpads

    def _scratch_row_bytes(self, depth: int) -> int:
        """HBM bytes of ONE kpad row of admission scratch at ``depth``
        sequence positions (K + V; int8 payload + f32 per-(pos, head)
        scales when KV_QUANT=int8, else the model dtype)."""
        cfg = self.model_cfg
        per_pos_head = (cfg.head_dim + 4 if self.kv_quant == "int8"
                        else cfg.head_dim * np.dtype(self.dtype).itemsize)
        return 2 * cfg.n_layers * depth * cfg.n_kv_heads * per_pos_head

    def _cap_admit_kpads(self, depths) -> None:
        """Per-depth kpad caps from the ADMIT_SCRATCH_MB budget. On 7B
        geometry the uncapped kpad=16 × S_alloc scratch was ~763 MB of
        int8 KV — a term in the bs=64 RESOURCE_EXHAUSTED budget (VERDICT
        r5 weak #3); suffix-depth rows plus this cap bound the transient
        regardless of geometry. 0 = uncapped (operator opt-out)."""
        self._admit_kpad_caps = {}
        budget = self.admit_scratch_mb * 1_000_000
        if budget <= 0:
            return
        for depth in depths:
            row = self._scratch_row_bytes(depth)
            fits = tuple(k for k in self.ADMIT_KPADS if k * row <= budget)
            self._admit_kpad_caps[depth] = fits[-1] if fits else 0
            structural = self.admit_kpads
            if structural and (not fits or fits[-1] < structural[-1]):
                logger.info(
                    "ADMIT_SCRATCH_MB=%d caps group admissions at depth %d "
                    "to kpad<=%d (%.0f MB/row)",
                    self.admit_scratch_mb, depth,
                    self._admit_kpad_caps[depth], row / 1e6)

    # --------------------------------------------- QoS ring (ISSUE 7)

    def lane_occupancy(self) -> dict:
        """Slots held per lane (racy read — routing/brownout hint, not
        an invariant). The fleet's lane-aware router reads this to know
        that a replica full of background work is still routable for
        interactive traffic."""
        counts = {lane: 0 for lane in LANES}
        for s in list(getattr(self, "_slots", None) or []):
            if s is not None:
                lane = getattr(s.req, "lane", LANE_INTERACTIVE)
                counts[lane if lane in LANES else LANE_INTERACTIVE] += 1
        return counts

    def _capped_lanes(self, counts: dict) -> tuple:
        """Lanes at their brownout-trimmed slot cap: admission skips
        them (they stay queued) until interactive queue wait recovers.
        Caps floor at one slot, so brownout never starves a lane."""
        capped = []
        for lane in (LANE_BACKGROUND, LANE_BATCH):
            cap = self._brownout.lane_cap(lane, self.batch_size)
            if cap < self.batch_size and counts.get(lane, 0) >= cap:
                capped.append(lane)
        return tuple(capped)

    def _expire_queued(self, req: _Request) -> None:
        """QoSQueue scan-time expiry callback: a queued request whose
        deadline passed is failed NOW and stops occupying
        MAX_QUEUE_DEPTH (counted as queue_expired, not served)."""
        if req.trace is not None:
            req.trace.event("qos: deadline expired while queued — purged "
                            "at queue scan")
        self._emit(req, "error",
                   GenerationTimeout("deadline expired while queued"))

    def _credit_preempt_wait(self, req: _Request) -> None:
        """Exclude preempted-out wall time from the victim's deadline:
        the clock stopped at preemption and restarts at re-admission."""
        t0 = req.preempt_t0
        if t0 is None:
            return
        req.preempt_t0 = None
        paused = time.monotonic() - t0
        if req.deadline is not None:
            req.deadline += paused
        if req.trace is not None:
            req.trace.event(f"qos: resuming after {paused * 1000.0:.0f}ms "
                            f"preempted (deadline credited)")

    def _maybe_preempt(self) -> bool:
        """Preemptive decode: when a higher-lane request has queue-waited
        past PREEMPT_WAIT_MS and every slot is busy, export the cheapest
        strictly-lower-lane victim (fewest generated tokens, lowest
        lane) through the PR 6 RequestExport path and re-enqueue it at
        the head of its tenant queue; _admit_pending hands the freed
        slot to the starved lane. Victims over PREEMPT_BUDGET are never
        picked again — budget exhaustion leaves them running."""
        if self.preempt_wait_ms <= 0 or self._parked:
            return False
        if any(s is None for s in self._slots):
            return False
        now = time.monotonic()
        # A brownout-capped lane can't use a freed slot (admission would
        # exclude it) — preempting for it would just churn the victim.
        lane = self._admissions.starved_lane(
            now, self.preempt_wait_ms / 1000.0,
            exclude=self._capped_lanes(self.lane_occupancy()))
        if lane is None:
            return False
        rank = lane_rank(lane)
        victims = [
            (i, s) for i, s in enumerate(self._slots)
            if s is not None and not s.exhausted
            and lane_rank(getattr(s.req, "lane", LANE_INTERACTIVE)) < rank
            and s.req.preempt_count < self.preempt_budget
        ]
        if not victims:
            return False
        idx, _ = min(victims,
                     key=lambda t: (lane_rank(t[1].req.lane),
                                    len(t[1].detok.ids)))
        self._preempt_slot(idx, lane)
        self._preempt_for_lane = lane
        return True

    def _preempt_slot(self, idx: int, for_lane: str) -> None:
        """Export one running request and free its slot — the PR 5/6
        replay contract turned inward: (prompt, generated ids, seed) is
        the portable state, so the later _admit_resume re-splice
        continues the transcript bit-identically. In-flight chunks for
        this slot are discarded by snapshot mismatch exactly like a
        cancel; their already-executed steps are billed as waste."""
        slot = self._slots[idx]
        self._slots[idx] = None
        req = slot.req
        req.preempt_count += 1
        req.preempt_t0 = time.monotonic()
        self._spans.of(req).requeued(req.preempt_t0)
        ids = list(slot.detok.ids)
        req.resume_ids = ids or None
        # The client already holds detok.text; the resume emission skips
        # exactly that many chars (UTF-8 hold-back means text can trail
        # ids — same suppression the fleet relay does by length).
        req.resume_skip = len(slot.detok.text)
        req.resume_emitted = False
        if req.export is not None:
            req.export.ids = list(ids)
        if (self.device_termination and slot.decode_chunks_inflight > 0):
            remaining = max(0, req.max_tokens - len(ids))
            self._bill_waste(min(
                slot.decode_chunks_inflight * self._chunk_waste_bound(),
                remaining), req)
        self._preemptions += 1
        self._preempted_tokens += len(ids)
        # Ledger billing happens at RESUME (_replay_slot, preempted
        # class): the re-derivation prefill is the device work, and a
        # victim cancelled while queued never pays it. No cause when
        # nothing was generated — re-admission then takes the FRESH
        # path (_admit_one), which never consumes the marker, and a
        # stale one would misbill a later containment replay.
        req.resume_cause = "preempt" if ids else ""
        self._preempt_times.append(req.preempt_t0)
        if req.trace is not None:
            req.trace.event(
                f"qos: preempted out of slot {idx} after {len(ids)} tokens "
                f"(lane {req.lane} yields to starved lane {for_lane}; "
                f"preemption {req.preempt_count}/{self.preempt_budget}) — "
                f"exported for seeded replay")
            # Causal span link: the stitched /debug/requests timeline
            # joins this segment to the later resume by these links.
            req.trace.link("preempted", from_slot=idx, tokens=len(ids),
                           for_lane=for_lane, lane=req.lane)
        if self._use_pool:
            # Cache the victim's verified chain before releasing its
            # blocks: the resume (or any cohabitant sharing the prefix)
            # re-maps them from the radix tree instead of re-prefilling
            # — preemption becomes a block-table operation, not a
            # recompute.
            self._pool_release_slot(idx, slot, cache_chain=True)
        self._admissions.requeue_head(req)

    def _inject_flood(self, n: int, loop) -> None:
        """tenant:flood:<n> drill (testing/faults.py): enqueue a burst
        of real decode work under one synthetic background tenant so
        fairness and preemption are exercisable without a load
        generator. Bursts past the queue's own caps are simply dropped —
        the drill must not wedge the queue it is stressing."""
        from ..testing.faults import FLOOD_LANE, FLOOD_TENANT

        now = time.monotonic()
        max_toks = max(1, min(32, self.max_seq_len // 2))
        for i in range(n):
            prompt = f"tenant flood drill {i}"
            req = _Request(
                prompt_ids=self.tokenizer.encode(prompt),
                max_tokens=max_toks,
                temperature=0.0,
                deadline=now + 30.0,
                loop=loop,
                out_queue=asyncio.Queue(),
                cancel=threading.Event(),
                t_submit=now,
                seed=i,
                prompt=prompt,
                tenant=FLOOD_TENANT,
                lane=FLOOD_LANE,
            )
            try:
                self._admissions.put(req)
            except EngineOverloaded:
                break

    def qos_health(self) -> dict:
        """Cheap QoS view for /health (never calls stats() — that drains
        samples owed to the /metrics scrape): per-lane queue depth, the
        active brownout level/shares, and preemptions in the last
        minute."""
        now = time.monotonic()
        return {
            "lanes": self._admissions.lane_depths(),
            "brownout_level": self._brownout.level,
            "lane_shares": {k: round(v, 4)
                            for k, v in self._brownout.shares.items()},
            "preemptions_total": self._preemptions,
            "preemptions_last_60s": sum(
                1 for t in list(self._preempt_times) if t >= now - 60.0),
            "queue_expired_total": self._admissions.expired_total,
            "queue_displaced_total": self._admissions.displaced_total,
            "session_budgets": self._session_budgets.snapshot(),
        }

    # ------------------------------------------ telemetry plane (ISSUE 8)

    def _bill_waste(self, n: int, req: Optional[_Request]) -> None:
        """Bill ``n`` wasted device steps to BOTH the legacy counter
        (wasted_decode_steps_total) and the goodput ledger's
        wasted_masked class — one call site per waste event so the two
        books can never drift apart."""
        if n <= 0:
            return
        self._wasted_steps += n
        lane = getattr(req, "lane", LANE_INTERACTIVE) if req is not None \
            else LANE_INTERACTIVE
        tenant = getattr(req, "tenant", None) if req is not None else None
        self.ledger.record(CLASS_WASTED_MASKED, n, lane=lane, tenant=tenant)

    def slo_health(self) -> dict:
        """SLO burn-rate view for /health (obs/slo.py snapshot — pure
        reads, never stats(), same rule as qos_health)."""
        return self._slo.snapshot()

    def ledger_snapshot(self) -> dict:
        """Full goodput ledger for /debug/ledger: the lane table plus
        the hashed-tenant table (debug-only by the cardinality rule)
        and the conservation check."""
        snap = self.ledger.snapshot()
        snap["tenants"] = self.ledger.tenant_snapshot()
        snap["conservation"] = self.ledger.conservation()
        return snap

    def _admit_pending(self) -> None:
        """Admit every queued request that fits a free slot. Requests on
        the prefix-cache suffix path with the same (bucket, kv span) are
        prefilled TOGETHER in one batched program — one read of the weights
        for the whole burst instead of one per request, which is the
        difference between ~640 ms and ~100 ms for a 32-request burst on a
        2B model (round-3 profiling; also fixes round-2 weak #8's
        admission-burst latency spike). Everything else (full prefill,
        chunked/ring long prompts) takes the single-request path."""
        if self._parked:
            # Bisection probation: only the probe group may occupy slots
            # — a new admission joining a suspect batch would muddy the
            # culprit attribution. Queued requests simply wait (and are
            # never dropped); probation lasts at most a few chunks.
            return
        free = sum(s is None for s in self._slots)
        # QoS: lanes at their browned-out slot cap stay queued (their
        # requests are skipped, not shed); right after a preemption the
        # first pop is pinned to the starved lane so the freed slot goes
        # to the waiter the preemption was FOR, not to whatever lane the
        # WDRR round happened to be serving.
        counts = self.lane_occupancy()
        prefer, self._preempt_for_lane = self._preempt_for_lane, None
        pending = []
        while len(pending) < free:
            try:
                req = self._admissions.get_nowait(
                    exclude_lanes=self._capped_lanes(counts),
                    min_lane=prefer)
            except _queue.Empty:
                if prefer is None:
                    break
                prefer = None   # starved waiter vanished (cancel/expiry)
                continue
            prefer = None
            counts[req.lane if req.lane in LANES else LANE_INTERACTIVE] += 1
            pending.append(req)
        if not pending:
            return
        # Popped-but-not-yet-slotted requests are invisible to both the
        # slot scan and the queue — count them so a concurrent drain
        # (stop(drain_secs)) doesn't tear down under an admission whose
        # cold prefill can run for seconds on this thread.
        self._admitting += len(pending)
        self._admitting_reqs.extend(pending)
        try:
            self._admit_popped(pending)
        finally:
            self._admitting -= len(pending)

    def _admit_popped(self, pending: List[_Request]) -> None:
        with self._spans.sched.region("admit", "admit",
                                      chunk=self._chunks_dispatched + 1,
                                      requests=len(pending)):
            self._admit_popped_in_span(pending)

    def _admit_popped_in_span(self, pending: List[_Request]) -> None:
        # Every request popped off the queue MUST reach either a slot or an
        # error event — an exception mid-burst (e.g. OOM allocating the
        # group scratch) may not silently drop the rest of the burst, or
        # their generate() calls would block forever.
        for req in pending:
            # Preempted victims resume with their paused wall excluded
            # from the deadline, BEFORE any deadline check can see it.
            self._credit_preempt_wait(req)
        def guarded(admit, reqs):
            # Tick the watchdog per admission: a lazily-compiled admission
            # shape can legitimately block for tens of seconds and must
            # not read as a hung device.
            self._last_progress = time.monotonic()
            try:
                admit()
            except Exception:
                logger.exception("admission failed; failing %d request(s)",
                                 len(reqs))
                for req in reqs:
                    self._emit(req, "error",
                               EngineUnavailable("admission failed"))
            # Settled (slotted or errored) either way — drop the mid-
            # admission record. A BaseException skips this on purpose:
            # the record is what lets _supervise_scheduler recover the
            # request after the thread dies.
            for req in reqs:
                try:
                    self._admitting_reqs.remove(req)
                except ValueError:  # pragma: no cover - defensive
                    pass

        groups: dict = {}
        singles: List[_Request] = []
        for req in pending:
            try:
                key = (self._suffix_group_key(req) if self.admit_kpads
                       else None)
            except Exception:  # pragma: no cover - defensive
                key = None
            if key is None:
                singles.append(req)
            else:
                groups.setdefault(key, []).append(req)
        for (sbucket, kv_limit), reqs in groups.items():
            # Per-shape group-size cap (ADMIT_SCRATCH_MB budget); an empty
            # cap degenerates to single admissions.
            kpads = self.admit_kpads_for(kv_limit)
            while reqs:
                take = reqs[:(kpads[-1] if kpads else 1)]
                del reqs[:len(take)]
                if len(take) == 1:
                    guarded(lambda: self._admit_one(take[0]), take)
                else:
                    guarded(
                        lambda: self._admit_group(take, sbucket, kv_limit),
                        take,
                    )
        for req in singles:
            guarded(lambda: self._admit_one(req), [req])

    def _suffix_group_key(self, req: _Request):
        """(sbucket, kv_limit) when this request will take the prefix-hit
        suffix-prefill path, else None (single-request admission). Routing
        delegates to the engine's _suffix_plan so grouped and single
        admissions always agree."""
        if self._prefix is None:
            return None
        if req.resume_ids:
            # Migrated-in requests re-splice through the single replay
            # path (their KV is prompt + generated prefix, not a
            # prefix-cache suffix shape).
            return None
        if self._grammar is not None and req.gpid >= 0:
            # Grammar requests sample their first token MASKED (and may
            # admission-fast-forward); the group program samples
            # unmasked — route them through the single path.
            return None
        ids = req.prompt_ids
        max_prompt = self.max_seq_len - max(1, req.max_tokens)
        if len(ids) > max_prompt or not self._prefix.matches(ids):
            return None
        plan = self._suffix_plan(ids)
        if plan is None:
            return None
        sbucket, kv_limit, _ = plan
        return (sbucket, kv_limit)

    # ----- batched-admission programs (compiled per shape, cache-persisted)

    def _get_batch_prefix_splice_fn(self, kpad: int):
        key = ("prefix_splice", kpad)
        fn = self._batch_admit_fns.get(key)
        if fn is None:
            def splice_prefix_batch(cache, pk, pv):
                with jax.named_scope("kv_splice"):
                    k = kv_update_slice(cache.k, kv_broadcast_rows(pk, kpad))
                    v = kv_update_slice(cache.v, kv_broadcast_rows(pv, kpad))
                    lengths = jnp.full_like(cache.lengths, kv_tokens(pk))
                return KVCache(k=k, v=v, lengths=lengths)

            fn = jax.jit(splice_prefix_batch, donate_argnums=(0,))
            self._batch_admit_fns[key] = fn
        return fn

    def _get_batch_suffix_fn(self, kpad: int, sbucket: int, kv_limit: int):
        """forward over [kpad, sbucket] suffixes + per-row last-logit
        gather + per-row first-token sample, one program."""
        key = ("suffix", kpad, sbucket, kv_limit)
        fn = self._batch_admit_fns.get(key)
        if fn is None:
            cfg = self.model_cfg
            impl = self._prefill_impl_for(sbucket, kv_limit)

            def batch_suffix(params, tokens, positions, cache, mask,
                             lengths, seeds, temperatures):
                # logits_at: the LM head projects ONLY each row's last
                # valid position — a [kpad, sbucket, 256k-vocab] f32
                # activation here measured as an HBM OOM on the 7B bench
                # when the admission warm overlapped serving.
                logits, cache = forward(params, cfg, tokens, positions,
                                        cache, kv_limit=kv_limit,
                                        attn_impl=impl, mesh=self.mesh,
                                        moe_impl=self.moe_impl,
                                        token_mask=mask,
                                        logits_at=lengths - 1)
                # First tokens sample at generation index 0 of each row's
                # per-request seed stream — identical to the single
                # admission path, so group vs single admission can never
                # diverge a sampled transcript.
                first = sample_tokens_seeded(logits[:, 0], seeds,
                                             jnp.zeros_like(seeds),
                                             temperatures,
                                             top_k=self.top_k,
                                             top_p=self.top_p)
                return first, cache

            fn = jax.jit(batch_suffix, donate_argnums=(3,))
            self._batch_admit_fns[key] = fn
        return fn

    def _get_batch_splice_fn(self, kpad: int):
        """Scatter kpad prefilled rows into their slots in one program.
        Padding rows carry slot index == batch_size (out of bounds) and are
        dropped by the scatter."""
        key = ("splice", kpad)
        fn = self._batch_admit_fns.get(key)
        if fn is None:
            def splice_many(cache, src_k, src_v, tok, pos, temps, active,
                            ngen, budget, seeds, slots, n_prompts,
                            first_toks, temperatures, max_toks, req_seeds):
                with jax.named_scope("kv_splice"):
                    k = kv_set_slots(cache.k, src_k, slots)
                    v = kv_set_slots(cache.v, src_v, slots)
                    lengths = cache.lengths.at[slots].set(n_prompts,
                                                          mode="drop")
                    tok = tok.at[slots, 0].set(first_toks, mode="drop")
                    pos = pos.at[slots, 0].set(n_prompts, mode="drop")
                    temps = temps.at[slots].set(temperatures, mode="drop")
                    active = active.at[slots].set(max_toks > 1, mode="drop")
                    ngen = ngen.at[slots].set(1, mode="drop")
                    budget = budget.at[slots].set(max_toks, mode="drop")
                    seeds = seeds.at[slots].set(req_seeds, mode="drop")
                return (KVCache(k=k, v=v, lengths=lengths), tok, pos, temps,
                        active, ngen, budget, seeds)

            fn = jax.jit(splice_many,
                         donate_argnums=(0, 3, 4, 5, 6, 7, 8, 9))
            self._batch_admit_fns[key] = fn
        return fn

    def _admit_group(self, reqs: List[_Request], sbucket: int,
                     kv_limit: int) -> None:
        """Batched admission: splice the resident prefix into kpad scratch
        rows, prefill every suffix in ONE forward, sample all first tokens,
        scatter the rows into their slots — zero host reads; the first
        tokens travel as one ("firsts", vector) pipeline entry (one fetch
        for the whole group)."""
        if self.faults is not None:
            self.faults.check("admit")
        live = []
        for req in reqs:
            if req.cancel.is_set():
                continue
            if req.deadline is not None and time.monotonic() > req.deadline:
                self._emit(req, "error",
                           GenerationTimeout("timed out waiting for a slot"))
                continue
            live.append(req)
        if len(live) <= 1:
            for req in live:
                self._admit_one(req)
            return
        kpad = next(
            (k for k in self.admit_kpads_for(kv_limit) if k >= len(live)),
            None)
        # Only fully-compiled shapes run the group path; a cold shape would
        # compile a full model forward ON the scheduler thread and stall
        # every active slot mid-serving ("admission never recompiles
        # anything"). Until the background warm (_warm_batch_admit_shapes)
        # lands a shape, fall back to single admissions — no worse than the
        # pre-group-path behavior.
        if kpad is None or (kpad, sbucket, kv_limit) not in self._batch_ready:
            for req in live:
                self._admit_one(req)
            return
        # Scratch serialization (never block the scheduler): if the
        # background admission warm currently holds kpad-row scratch of
        # its own, admit singly rather than doubling peak scratch HBM or
        # waiting out a warm compile.
        if not self._admit_scratch_lock.acquire(blocking=False):
            for req in live:
                self._admit_one(req)
            return
        try:
            self._admit_group_locked(live, kpad, sbucket, kv_limit)
        finally:
            self._admit_scratch_lock.release()

    def _admit_group_locked(self, live: List[_Request], kpad: int,
                            sbucket: int, kv_limit: int) -> None:
        prefix = self._prefix
        t_adm = time.monotonic()
        for req in live:
            wait_ms = (t_adm - req.t_submit) * 1000.0
            self._brownout.note_queue_wait(req.lane, wait_ms, now=t_adm)
            self._slo.note(SLO_QUEUE_WAIT, req.lane, wait_ms, now=t_adm)
            self._spans.admitted(req, t_adm)

        # Suffix-depth scratch: kv_limit positions hold everything a
        # suffix admission writes (prefix.n + sbucket, tile-rounded); the
        # old S_alloc-deep rows were pure HBM waste (VERDICT r5 weak #3).
        scratch = self._new_cache(kpad, kv_limit)
        scratch = self._get_batch_prefix_splice_fn(kpad)(
            scratch, prefix.k, prefix.v)

        tokens = np.zeros((kpad, sbucket), np.int32)
        mask = np.zeros((kpad, sbucket), np.float32)
        suf_lens = np.ones((kpad,), np.int32)  # padding rows gather index 0
        temps = np.zeros((kpad,), np.float32)
        seeds = np.zeros((kpad,), np.int32)
        for i, req in enumerate(live):
            suf = req.prompt_ids[prefix.n:]
            tokens[i, :len(suf)] = suf
            mask[i, :len(suf)] = 1.0
            suf_lens[i] = len(suf)
            temps[i] = req.temperature
            seeds[i] = req.seed
        positions = np.broadcast_to(
            prefix.n + np.arange(sbucket), (kpad, sbucket)).astype(np.int32)

        first_toks_d, scratch = self._get_batch_suffix_fn(
            kpad, sbucket, kv_limit)(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            scratch, jnp.asarray(mask), jnp.asarray(suf_lens),
            jnp.asarray(seeds),
            jnp.asarray(temps),
        )

        slots_arr = np.full((kpad,), self.batch_size, np.int32)  # OOB = drop
        n_prompts = np.zeros((kpad,), np.int32)
        budgets = np.ones((kpad,), np.int32)
        pairs = []
        for i, req in enumerate(live):
            slot_idx = self._slots.index(None)
            n_prompt = prefix.n + int(suf_lens[i])
            slots_arr[i] = slot_idx
            n_prompts[i] = n_prompt
            budgets[i] = req.max_tokens
            self._slots[slot_idx] = _Slot(
                req=req,
                detok=StreamDecoder(self.tokenizer),
                n_prompt=n_prompt,
                pos=n_prompt,
                queue_ms=(t_adm - req.t_submit) * 1000.0,
                t_admit=t_adm,
                t_decode0=t_adm,
                chunks_inflight=1,
                prefix_hit=True,
            )
            if req.trace is not None:
                req.trace.event(
                    f"engine: group-admitted to slot {slot_idx} "
                    f"(burst of {len(live)}, suffix bucket {sbucket})")
            pairs.append((req, slot_idx))

        (self._cache, self._tok_d, self._pos_d, self._temps_d,
         self._active_d, self._ngen_d, self._budget_d, self._seeds_d) = (
            self._get_batch_splice_fn(kpad)(
                self._cache, scratch.k, scratch.v, self._tok_d, self._pos_d,
                self._temps_d, self._active_d, self._ngen_d, self._budget_d,
                self._seeds_d,
                jnp.asarray(slots_arr),
                jnp.asarray(n_prompts), first_toks_d, jnp.asarray(temps),
                jnp.asarray(budgets), jnp.asarray(seeds),
            )
        )
        self._spans.launched(self._seeds_d)
        self._to_host_async(first_toks_d)
        self._inflight.append(("firsts", first_toks_d, pairs))
        self._group_admitted += 1
        self._last_admit_t = time.monotonic()
        self._spans.note_slots(self._slots)
        for i, req in enumerate(live):
            req.spans.staged(
                self._last_admit_t, chunks_ahead=self._chunks_in_pipe(),
                chunks_unready=self._spans.pipe_chunks(),
                prefill=dict(prompt_tokens=int(n_prompts[i]),
                             prefix_hit_tokens=prefix.n, staged_w=0))

    def _admit_one(self, req: _Request) -> None:
        """Dispatch-only admission: prefill → device-side first-token
        sample → KV splice, all chained on device arrays with zero host
        reads. The first token reaches the client through the inflight
        pipeline (``_consume_first``), overlapping its transfer with decode
        chunks instead of stalling every active slot on a round trip."""
        if self.faults is not None:
            self.faults.check("admit")
        if req.cancel.is_set():
            return
        if req.deadline is not None and time.monotonic() > req.deadline:
            self._emit(req, "error",
                       GenerationTimeout("timed out waiting for a slot"))
            return
        if req.resume_ids:
            self._admit_resume(req)
            return
        if self._use_pool:
            self._admit_one_pool(req)
            return
        slot_idx = self._slots.index(None)
        t_adm = time.monotonic()
        wait_ms = (t_adm - req.t_submit) * 1000.0
        self._brownout.note_queue_wait(req.lane, wait_ms, now=t_adm)
        self._slo.note(SLO_QUEUE_WAIT, req.lane, wait_ms, now=t_adm)
        spans = self._spans.admitted(req, t_adm)

        last_logits, scratch, n_prompt, prefix_hit = self._prefill_prompt(
            req.prompt_ids, req.max_tokens
        )
        # First token = generation index 0 of the request's own seed
        # stream (same key derivation as the in-chunk sampler), so a
        # containment replay — or an offline reproduction from the seed
        # in /debug/requests/{id} — regenerates it bit-identically.
        # Under GRAMMAR_DECODE the sample is masked to the START state's
        # legal set (dense mode: masking only — fast-forward needs the
        # pool's suffix-prefill path).
        gs0 = (self._grammar.start_state(req.gpid)
               if self._grammar is not None and req.gpid >= 0 else -1)
        first_tok_d = self._grammar_first_sample(last_logits, req, gs0, 0)
        (self._cache, self._tok_d, self._pos_d, self._temps_d,
         self._active_d, self._ngen_d, self._budget_d,
         self._seeds_d) = self._splice_fn(
            self._cache, scratch.k, scratch.v, self._tok_d, self._pos_d,
            self._temps_d, self._active_d, self._ngen_d, self._budget_d,
            self._seeds_d,
            jnp.asarray(slot_idx, jnp.int32), jnp.asarray(n_prompt, jnp.int32),
            first_tok_d,
            jnp.asarray(req.temperature, jnp.float32),
            jnp.asarray(req.max_tokens, jnp.int32),
            jnp.asarray(req.seed, jnp.int32), jnp.asarray(1, jnp.int32),
        )
        self._spans.launched(self._seeds_d)

        if gs0 >= 0:
            self._grammar_arm_after_sample(slot_idx, gs0, first_tok_d)
        slot = _Slot(
            req=req,
            detok=StreamDecoder(self.tokenizer),
            n_prompt=n_prompt,
            pos=n_prompt,
            queue_ms=(t_adm - req.t_submit) * 1000.0,
            t_admit=t_adm,
            t_decode0=t_adm,
            chunks_inflight=1,
            prefix_hit=prefix_hit,
            gs=gs0,
        )
        if req.trace is not None:
            req.trace.event(
                f"engine: admitted to slot {slot_idx} "
                f"({n_prompt} prompt tokens, prefix_hit={prefix_hit})")
        self._slots[slot_idx] = slot
        # Start the device→host copy immediately: transfers overlap each
        # other and device compute, so the blocking read at consume time
        # finds the data already local: an admission burst pays one
        # overlapped transfer, not one blocking read each.
        self._to_host_async(first_tok_d)
        self._inflight.append(("first", first_tok_d, req, slot_idx))
        self._last_admit_t = time.monotonic()
        self._spans.note_slots(self._slots)
        spans.staged(
            self._last_admit_t, chunks_ahead=self._chunks_in_pipe(),
            chunks_unready=self._spans.pipe_chunks(),
            prefill=dict(
                prompt_tokens=n_prompt, staged_w=0,
                prefix_hit_tokens=self._prefix.n if prefix_hit else 0))

    def _admit_resume(self, req: _Request) -> None:
        """Cross-replica import (fleet migration): seat a request that
        already generated tokens on ANOTHER engine. The portable tuple
        (prompt, resume_ids, seed) re-splices through the SAME replay
        path containment uses — one prefill of prompt + prefix[:-1],
        carry token forced to the last generated id, ngen0 re-aligning
        the RNG stream — so the continuation is bit-identical to the
        donor's would-have-been transcript. The prefix TEXT is re-emitted
        first (one token event); the fleet relay suppresses it against
        what the client already received, which also makes an engine
        without import support (replay-from-scratch) behave identically
        from the fleet's view."""
        t_adm = time.monotonic()
        spans = self._spans.admitted(req, t_adm)
        detok = StreamDecoder(self.tokenizer)
        piece = detok.push(*req.resume_ids)
        if req.resume_emitted:
            piece = None          # requeued after a mid-admission death
        elif req.resume_skip and piece is not None:
            # Preemption resume (same engine, no fleet relay to
            # suppress): the client already received resume_skip chars
            # of this prefix — emit only what UTF-8 hold-back kept
            # unemitted at preempt time. Emitted text is monotone in the
            # ids, so the slice can never drop undelivered bytes.
            piece = piece[req.resume_skip:] or None
        req.resume_emitted = True
        req.resume_skip = 0
        slot = _Slot(
            req=req,
            detok=detok,
            n_prompt=len(req.prompt_ids),
            pos=0,                # set by _replay_slot's splice
            queue_ms=(t_adm - req.t_submit) * 1000.0,
            t_admit=t_adm,
            t_decode0=t_adm,
        )
        if piece is not None:
            self._emit(req, "token", piece)
        if req.export is not None:
            req.export.ids = list(detok.ids)
        if req.trace is not None:
            req.trace.event(
                f"engine: importing migrated request "
                f"({len(req.resume_ids)} generated tokens, seed {req.seed})")
        if len(detok.ids) >= req.max_tokens:
            # The imported prefix already spends the budget: finish
            # through the normal path (flush + done event) without ever
            # touching the device.
            slot_idx = self._slots.index(None)
            slot.t_first = t_adm
            self._slots[slot_idx] = slot
            self._finish(slot_idx, "length")
            return
        self._replay_slot(slot)
        # The replay armed the slot from the imported prefix: the next
        # chunk's first row is this segment's first token.
        self._spans.note_slots(self._slots)
        spans.staged(time.monotonic(), chunks_ahead=self._chunks_in_pipe(),
                     chunks_unready=self._spans.pipe_chunks(),
                     prefill=dict(prompt_tokens=len(req.prompt_ids),
                                  resumed_tokens=len(req.resume_ids)))

    def _consume_first(self, first_tok: int, req: _Request,
                       slot_idx: int) -> None:
        """Deliver an admission's first token (already fetched). EOS /
        single-token finishes happen here; the slot's already-dispatched
        decode chunks are then discarded via snapshot mismatch."""
        slot = self._slots[slot_idx]
        if slot is None or slot.req is not req:
            return  # finished/raced before its first token arrived
        slot.chunks_inflight -= 1
        now = time.monotonic()
        slot.t_first = now
        if req.t_first0 is None:
            req.t_first0 = now
        slot.t_decode0 = now
        slot.prefill_ms = (now - slot.t_admit) * 1000.0
        # Sentinel prefill sample: admission → first-token consume (the
        # same quantity slot.prefill_ms reports), keyed by the prefill
        # bucket covering the prompt so label cardinality stays bounded.
        self._steptime.note(
            PHASE_PREFILL,
            prefill_bucket(slot.n_prompt, self.prefill_buckets),
            now - slot.t_admit, tokens=slot.n_prompt, now=now)
        if req.trace is not None:
            req.trace.event("engine: first token")
        self._spans.of(req).first_token(now)
        if first_tok in self.model_cfg.eos_ids:
            # The device can't see a first-token EOS (the admission program
            # samples it blind) — speculative chunks already in flight
            # decoded this slot for nothing, which the wasted-steps
            # counter must own up to.
            self._finish(slot_idx, "stop", wasted_inflight=True)
            return
        t_dk = time.monotonic()
        piece = slot.detok.push(first_tok)
        slot.detok_ms += (time.monotonic() - t_dk) * 1000.0
        if req.export is not None:
            req.export.ids = list(slot.detok.ids)
        if piece is not None:
            self._emit(req, "token", piece)
        if self._grammar is not None and req.gpid >= 0:
            self._grammar_consume(slot, [first_tok])
        if req.max_tokens <= 1:
            self._finish(slot_idx, "length")
            return
        if self._grammar is not None and req.gpid >= 0:
            self._grammar_fast_forward(slot_idx, slot)

    def _sweep_finishes(self) -> None:
        """Host-only finishes before a dispatch: cancellation, deadline,
        and KV capacity (``pos`` counts *scheduled* chunks, so in-flight
        pipeline chunks can never write past the cache). A
        capacity-exhausted slot is excluded from further dispatches but
        only finished once its in-flight chunks are consumed — otherwise
        up to 2×chunk_len already-generated tokens would be dropped."""
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if slot.req.cancel.is_set():
                self._finish(i, "abort", wasted_inflight=True)
            elif (slot.req.deadline is not None
                  and time.monotonic() > slot.req.deadline):
                self._finish(i, "timeout",
                             error=GenerationTimeout("generation timeout"),
                             wasted_inflight=True)
            elif slot.exhausted or slot.pos >= self.max_seq_len:
                # Capacity end: KV span reached max_seq, or (pool mode)
                # block allocation starved even after radix eviction.
                slot.exhausted = True
                if slot.chunks_inflight == 0:
                    self._finish(i, "length")

    def _run_chunk(self, bucket: int, force_d, corrupt_d,
                   tables_d=None, spec: Optional[bool] = None,
                   adm_w: Optional[int] = None, adm_args: tuple = ()):
        """Invoke one decode-chunk program with the mode-correct
        argument tail (pool block tables, speculative draft params +
        cache, grammar state + tables, staged ragged admissions) and
        thread the chained device state back — the single call site
        the warmups and the dispatcher share, so an argument-shape
        drift between modes is structurally impossible. ``spec``
        defaults to the live speculative state (the warmups pin it
        explicitly so both program sets compile before serving).
        ``adm_w`` selects the ragged mixed-chunk program for that
        admission window width; ``adm_args`` is its trailing staged-
        admission vector tuple."""
        if spec is None:
            spec = self._spec_active()
        args = (self.params, self._tok_d, self._pos_d, self._cache,
                self._seeds_d, self._temps_d, force_d, self._active_d,
                self._ngen_d, self._budget_d, corrupt_d)
        if tables_d is not None:
            args = args + (tables_d,)
        if spec:
            args = args + (self._draft_params, self._draft_cache)
        if self._grammar is not None:
            tc, ok, nx = self._grammar_tables_d()
            args = args + (self._fsm_d, tc, ok, nx)
        if adm_w is not None:
            out = self._ragged_chunk_fns[(adm_w, spec)](
                *(args + adm_args))
        else:
            fns = (self._spec_chunk_fns if spec
                   else self._batch_chunk_fns)
            out = fns[bucket](*args)
        if spec and self._grammar is not None:
            (packed, self._tok_d, self._pos_d, self._cache,
             self._active_d, self._ngen_d, self._draft_cache,
             self._fsm_d) = out
        elif spec:
            (packed, self._tok_d, self._pos_d, self._cache,
             self._active_d, self._ngen_d, self._draft_cache) = out
        elif self._grammar is not None:
            (packed, self._tok_d, self._pos_d, self._cache,
             self._active_d, self._ngen_d, self._fsm_d) = out
        else:
            (packed, self._tok_d, self._pos_d, self._cache,
             self._active_d, self._ngen_d) = out
        return packed

    def _dispatch_chunk(self) -> None:
        """One ``sched/dispatch`` interval: array staging + the program
        call, until the call returns (the device runs on). ``slots`` 0
        marks a dispatch that found nothing left to run."""
        with self._spans.sched.region("dispatch", "dispatch",
                                      chunk=self._chunks_dispatched + 1,
                                      slots=0, pipe_empty_ms=0.0,
                                      drained_ms=0.0) as entry:
            self._dispatch_chunk_in_span(entry)

    def _dispatch_chunk_in_span(self, entry: dict) -> None:
        if self.faults is not None:
            # A "chunk" hang blocks this (scheduler) thread exactly like a
            # hung device dispatch — the watchdog's target scenario.
            self.faults.check("chunk")
            # draft:die (ISSUE 12): the draft engine is gone. Flip to
            # the plain chunk programs — requests in flight keep
            # decoding byte-identically (the transcript never depended
            # on drafts), they just stop getting the verify speed-up.
            if self._spec_active() and self.faults.draft_die():
                self._spec_live = False
                self._spec_degraded += 1
                logger.warning(
                    "draft engine died (draft:die); degrading to plain "
                    "non-speculative decode")
        spec = self._spec_active()
        ct = self._chunk_tokens if spec else self.chunk_len
        # Ragged staged admissions (ISSUE 19): pending suffix windows
        # ride THIS chunk — the prologue prefills, samples, and arms
        # them in the same program dispatch as everyone else's
        # decode/verify step. Which of them, and how wide the window
        # is, is ``stage_window``'s rule (ISSUE 39: the prologue
        # computes the window's valid rows, so their lengths add up);
        # the ones that do not fit stay staged, their slots sit this
        # chunk out (``deferred``) and they head the next one's line. A
        # spec chunk's row widens by the prologue's one token.
        adm_w: Optional[int] = None
        adm_args: tuple = ()
        staged: dict = {}
        deferred: set = set()
        if self._use_ragged and self._pending_adm:
            waiting = {i: e for i, e in self._pending_adm.items()
                       if self._slots[i] is not None
                       and not self._slots[i].exhausted}
            taken, width = stage_window(
                [len(e["ids"]) for e in waiting.values()],
                self.prefill_buckets)
            adm_w = width or None
            line = list(waiting.items())
            staged = dict(line[:taken])
            self._pending_adm = dict(line[taken:])
            deferred = set(self._pending_adm)
        t_disp = time.monotonic()
        if staged:
            if spec:
                ct = self._chunk_tokens + 1
            N = self.batch_size
            a_tok = np.zeros((N, adm_w), np.int32)
            a_len = np.zeros((N,), np.int32)
            a_start = np.zeros((N,), np.int32)
            a_ngen0 = np.zeros((N,), np.int32)
            a_budget = np.zeros((N,), np.int32)
            a_seed = np.zeros((N,), np.int32)
            a_temp = np.zeros((N,), np.float32)
            a_gs = np.zeros((N,), np.int32)
            for i, e in staged.items():
                L = len(e["ids"])
                a_tok[i, :L] = e["ids"]
                a_len[i] = L
                a_start[i] = e["start"]
                a_ngen0[i] = e["ngen0"]
                a_budget[i] = e["budget"]
                a_seed[i] = e["seed"]
                a_temp[i] = e["temp"]
                a_gs[i] = max(e["gs"], 0)
            adm_args = tuple(jnp.asarray(x) for x in (
                a_tok, a_len, a_start, a_ngen0, a_budget, a_seed,
                a_temp))
            if self._grammar is not None:
                adm_args = adm_args + (jnp.asarray(a_gs),)
        def rides(i: int) -> bool:
            s = self._slots[i]
            return s is not None and not s.exhausted and i not in deferred

        active_slots = [self._slots[i] for i in range(self.batch_size)
                        if rides(i)]
        if not active_slots:
            return
        if self._use_pool:
            # Grow block tables to cover this chunk's writes BEFORE the
            # dispatch snapshot: decode allocates pages on demand (the
            # whole point of the pool — a slot holds only the pages its
            # live span needs). A slot the pool can't serve is marked
            # exhausted and excluded from this chunk.
            for i, s in enumerate(self._slots):
                if rides(i):
                    self._pool_ensure_coverage(i, s, ct)
            active_slots = [self._slots[i] for i in range(self.batch_size)
                            if rides(i)]
            if not active_slots:
                return
        force = jnp.asarray([rides(i) for i in range(self.batch_size)],
                            jnp.bool_)
        # Smallest KV bucket covering every live position this chunk can
        # reach: decode attention cost tracks actual sequence lengths, not
        # max_seq. Buckets only grow, so recently-admitted short sequences
        # sharing a batch with a long one pay the long one's bucket — the
        # static-shape trade, same as the active-slot masking. ``s.pos``
        # counts *scheduled* chunks (an upper bound: a slot the device
        # terminated mid-chunk froze earlier), so the bucket choice and
        # the capacity sweep stay conservative.
        needed = max(s.pos for s in active_slots) + ct
        bucket = next(b for b in self._kv_buckets if b >= needed)
        # Step-time sentinel sample: the interval since the previous
        # dispatch, provided a consume happened in between AND the pipe
        # never emptied (an idle gap between requests must not read as
        # a 10-second step). One such interval covers exactly one chunk
        # cycle — ct device steps — so the stored unit is ms/step.
        now = time.monotonic()
        pend = self._steptime_pending
        if (pend is not None and self._steptime_consumed
                and any(e[0] == "chunk" for e in self._inflight)):
            t0, phase0, bucket0, toks0 = pend
            self._steptime.note(phase0, bucket0, now - t0,
                                steps=toks0[0], tokens=toks0[1], now=now)
        # A mixed admission chunk samples into the PREFILL phase keyed
        # by the ragged admission width — its prologue does real
        # prefill work, and one fat window must not pollute the decode
        # digests' anomaly baselines (ISSUE 15).
        self._steptime_pending = (
            now,
            PHASE_PREFILL if adm_w is not None
            else PHASE_SPEC_VERIFY if spec else PHASE_DECODE,
            adm_w if adm_w is not None else bucket,
            (ct, ct * len(active_slots)))
        self._steptime_consumed = False
        # decode:nan fault seam: normally the cached all-False mask; a
        # drill swaps in a mask that NaNs the target slot's logits inside
        # the jitted chunk so the REAL device-side health detection (and
        # everything downstream of it) is what gets exercised.
        corrupt_d = self._no_corrupt_d
        if self.faults is not None:
            hits = self.faults.decode_nan_slots([
                s.req.prompt if s is not None and not s.exhausted else None
                for s in self._slots
            ])
            if hits:
                mask = np.zeros((self.batch_size,), bool)
                mask[hits] = True
                corrupt_d = jnp.asarray(mask)
                if self.mesh is not None:
                    # Match _no_corrupt_d's sharding: the chunk program
                    # was compiled against the data-sharded layout, and
                    # an uncommitted single-device array would reshard
                    # per faulted dispatch.
                    from ..parallel.sharding import shard_tokens
                    corrupt_d = shard_tokens(corrupt_d, self.mesh)
        packed_d = self._run_chunk(
            bucket, force, corrupt_d,
            self._tables_d(self._tables) if self._use_pool else None,
            spec=spec, adm_w=adm_w, adm_args=adm_args)
        snapshot = [self._slots[i].req if rides(i) else None
                    for i in range(self.batch_size)]
        for s in active_slots:
            s.pos += ct
            s.chunks_inflight += 1
            s.decode_chunks_inflight += 1
        self._to_host_async(packed_d)  # overlap the transfer (see _admit_one)
        chunks_ahead = self._chunks_in_pipe()
        chunks_unready = self._spans.pipe_chunks()
        self._chunks_dispatched += 1
        # forward passes of this chunk program (a spec chunk: its verifies)
        self._counts.note_passes(self._spec_steps if spec else self.chunk_len)
        self._inflight.append(("chunk", packed_d, snapshot, ct, spec,
                               self._chunks_dispatched))
        entry.update(self._spans.dispatched(self._inflight, packed_d))
        if staged:
            wc = self._window_counts
            wc["windows"] += 1
            wc["rows_valid"] += (
                sum(len(e["ids"]) for e in staged.values())
                + sum(rides(i) and i not in staged
                      for i in range(self.batch_size)))
            wc["rows_computed"] += adm_w + self.batch_size
            wc["deferred"] += len(deferred)
        for i in staged:
            # This chunk carries slot i's prologue: its stage_wait ends
            # where this dispatch began, behind the chunks already queued.
            self._spans.of(self._slots[i].req).dispatched(
                t_disp, self._chunks_dispatched, chunks_ahead,
                chunks_unready, adm_w=adm_w,
                ctx_tokens=self._slots[i].n_prompt)
        entry.update(kv_bucket=bucket, slots=len(active_slots),
                     admissions=len(staged), adm_w=adm_w or 0,
                     pipe=chunks_ahead + 1)

    # ----------------------------------------------------------- watchdog

    def _watchdog_loop(self) -> None:
        """Detect a hung device dispatch (SURVEY.md §5 failure-detection
        row): the scheduler thread blocks in a device read that never
        completes, so every request — including ones with no client
        timeout — would wait forever and /health would stay green. Checked
        from a separate thread; fires once."""
        interval = max(1.0, self.watchdog_secs / 4.0)
        fired = False
        while self._running:
            time.sleep(interval)
            if not fired:
                fired = self._watchdog_check()
            elif time.monotonic() - self._last_progress <= 2 * interval:
                # The stall was transient (e.g. a giant one-off compile):
                # the scheduler is ticking again. Already-failed requests
                # stay failed, but new traffic can be served.
                logger.warning("engine watchdog: scheduler progress "
                               "resumed; re-marking engine ready")
                # Never re-open admissions while stop() is draining: the
                # whole point of the drain is that new traffic 503s and
                # the LB retries elsewhere.
                if not self._stopping:
                    self._ready = True
                fired = False

    def _watchdog_check(self) -> bool:
        """One watchdog evaluation; returns True when it fired."""
        busy = bool(self._inflight) or any(
            s is not None for s in self._slots
        )
        if not busy:
            self._last_progress = time.monotonic()
            return False
        # Cold-start / lazy-compile grace (VERDICT r5 weak #4): a compile
        # blocks the scheduler thread exactly like a hung dispatch, and a
        # cold 7B start measured >2 min in one compile. Until the first
        # pipeline entry has been consumed (startup + warmup window), and
        # while an admission is mid-flight on the scheduler thread (the
        # lazy-compile site), no-progress is judged against the wider
        # ENGINE_STARTUP_GRACE_SECS; a hang during steady-state decode
        # still trips at ENGINE_WATCHDOG_SECS.
        limit = self.watchdog_secs
        if not self._first_consumed or self._admitting > 0:
            limit = max(limit, self.startup_grace_secs)
        if time.monotonic() - self._last_progress <= limit:
            return False
        logger.critical(
            "engine watchdog: no scheduler progress for %.0fs with work in "
            "flight — marking engine degraded and failing %d slot(s)",
            limit,   # the limit actually in force (may be the cold-start
                     # grace, not watchdog_secs — the operator must see
                     # the real stall bound that was exceeded)
            sum(s is not None for s in self._slots),
        )
        self._ready = False
        err = EngineUnavailable("engine watchdog: device dispatch hung")
        for slot in list(self._slots):
            if slot is not None:
                # Unblock the waiting coroutine, but leave _slots to the
                # scheduler thread (it owns slot/device state). If the
                # stall was a slow one-off rather than a true hang, a
                # concurrently-resuming _admit_one could otherwise install
                # a slot for an already-errored request and decode it to
                # max_tokens into an abandoned queue (ADVICE r3).
                # cancel.set() makes the resumed scheduler drop the request
                # at its next sweep / admission check instead.
                slot.req.cancel.set()
                self._emit(slot.req, "error", err)
        while True:
            try:
                req = self._admissions.get_nowait()
            except _queue.Empty:
                break
            req.cancel.set()
            self._emit(req, "error", err)
        return True

    def _prune_dead_chunks(self) -> None:
        """Drop leading chunk entries that carry tokens for no live slot —
        e.g. the speculative chunks in flight when the last active request
        finishes. Fetching them would block the scheduler ~a chunk's
        compute + fetch each, which lands straight on the next request's
        queue time."""
        while self._inflight and self._inflight[0][0] == "chunk":
            snapshot = self._inflight[0][2]
            live = any(
                snap is not None and self._slots[i] is not None
                and self._slots[i].req is snap
                for i, snap in enumerate(snapshot)
            )
            if live:
                return
            entry = self._inflight.pop(0)
            if not self.device_termination:
                # Legacy A/B accounting: a pruned chunk still EXECUTED a
                # full chunk of garbage for every slot it was dispatched
                # with — the tail waste the done mask eliminates. (Device
                # mode prices host-only finishes at _finish time instead;
                # device-visible finishes froze inside the chunk.)
                for snap in entry[2]:
                    if snap is not None:
                        self._bill_waste(self.chunk_len, snap)
            self._chunks_pruned += 1
            self._spans.sched.mark("prune", chunk=entry[5])
            self._spans.note_pipe(self._inflight)

    def _consume_oldest(self) -> None:
        self._last_progress = time.monotonic()
        self._first_consumed = True    # cold-start watchdog grace ends
        entry = self._inflight.pop(0)
        if entry[0] in ("first", "firsts"):
            # An admission program's first token(s): one fetch for the
            # whole group, then each slot's delivery.
            with self._spans.sched.region("fetch_wait", "fetch", first=True):
                vals = self._fetch(entry[1])
            pairs = ([(entry[2], entry[3])] if entry[0] == "first"
                     else entry[2])
            with self._spans.sched.region("consume", "consume", first=True):
                for (req, slot_idx), v in zip(pairs, vals):
                    self._consume_first(int(v), req, slot_idx)
            return
        _, packed_d, snapshot, ct, is_spec, chunk_no = entry
        if self.faults is not None:
            # decode:poison_step — a step-wide fault thrown from the
            # chunk fetch (no slot named): the widened scheduler except
            # routes it into the bisecting containment pass.
            self.faults.poison_fetch(
                [r.prompt if r is not None else None for r in snapshot])
        # THE per-chunk round trip: tokens, done mask, live lengths,
        # health, n_alive — and, for a speculative chunk, the per-slot
        # drafted/accepted lanes — cross in one packed buffer / one
        # fetch (protocol.py v3). ``ct`` is the entry's own row width
        # (a draft:die mid-pipe leaves spec-width chunks in flight
        # ahead of plain-width ones).
        with self._spans.sched.region("fetch_wait", "fetch",
                                      chunk=chunk_no) as fetched:
            buf = self._fetch(packed_d)
        # the pipe holds this chunk until its buffer is here
        self._spans.note_pipe(self._inflight)
        # sched/fetch's two stamps, measured once: the same interval is
        # the chunk_fetch_seconds sample.
        self._fetch_samples.append(fetched["ms"] / 1000.0)
        with self._spans.sched.region("consume", "consume", chunk=chunk_no,
                                      fetch_ms=fetched["ms"],
                                      pipe=self._chunks_in_pipe()) as consumed:
            res = unpack_chunk(buf, self.batch_size, ct, spec=is_spec,
                               moe=self._counts_experts,
                               sel=attention_words(self.model_cfg))
            consumed["n_alive"] = res.n_alive
            # One pass a scan step (the prologue is one of them).
            self._counts.note_chunk(
                res, self._spec_steps if is_spec else self.chunk_len)
            self._consume_chunk(res, snapshot, ct, is_spec)

    def _consume_chunk(self, res, snapshot, ct: int, is_spec: bool) -> None:
        """The host work after a chunk's fetch (one ``sched/consume``):
        spec and health accounting, each slot's row → tokens → client,
        finishes, probation bookkeeping."""
        self._chunks_consumed += 1
        self._steptime_consumed = True   # arms the next dispatch's sample
        self._last_n_alive = res.n_alive
        # Speculative accounting (ISSUE 12): acceptance counters + the
        # draft_rejected ledger class, billed per snapshot request
        # BEFORE the health-trip early return — the drafting happened
        # whether or not the chunk survives quarantine, and the books
        # must balance under the decode:nan drill too. Rejected drafts
        # are the waste; accepted drafts become delivered tokens at
        # _finish like everything else.
        if is_spec and res.drafted is not None:
            for i in range(self.batch_size):
                req_i = snapshot[i]
                if req_i is None:
                    continue
                d = int(res.drafted[i])
                a = int(res.accepted[i])
                if d <= 0:
                    continue
                self._spec_drafted += d
                self._spec_accepted += a
                if d > a:
                    self.ledger.record(
                        CLASS_DRAFT_REJECTED, d - a,
                        lane=getattr(req_i, "lane", LANE_INTERACTIVE),
                        tenant=req_i.tenant)
        # Slot-health quarantine (ISSUE 5): a tripped health bit names
        # its culprit directly. NOTHING from a poisoned chunk is emitted
        # — innocents' rows are valid, but replay regenerates them
        # bit-identically (seeded sampling), and dropping the whole chunk
        # keeps "no corrupt token ever reaches a client" unconditional.
        tripped = [
            i for i in range(self.batch_size)
            if int(res.health[i]) and snapshot[i] is not None
            and self._slots[i] is not None
            and self._slots[i].req is snapshot[i]
        ]
        if tripped:
            self.supervisor.note_health_trips(len(tripped))
            for i in tripped:
                self._spans.sched.mark(
                    "health_trip", slot=i,
                    health=describe_health(int(res.health[i])))
                if int(res.health[i]) & HEALTH_GRAMMAR_DEAD:
                    # Grammar dead end (ISSUE 11): the FSM state admits
                    # no legal token — the slot froze before emitting
                    # anything and rides the normal quarantine lane.
                    self._grammar_note_dead_end("decode")
                slot = self._slots[i]
                if slot.req.trace is not None:
                    slot.req.trace.event(
                        f"engine: slot {i} health tripped "
                        f"({describe_health(int(res.health[i]))})")
            self._contain_poisoned_step(
                CAUSE_SLOT_HEALTH,
                named=[self._slots[i] for i in tripped])
            return
        cfg = self.model_cfg
        for i, slot in enumerate(self._slots):
            if slot is None or slot.req is not snapshot[i]:
                # Slot freed/reassigned since this chunk launched. Under
                # host-side termination the device decoded the full chunk
                # for it — that is the waste the done mask removes (under
                # device termination the carry mask froze the slot, and
                # host-only finishes are priced at _finish time instead).
                if snapshot[i] is not None and not self.device_termination:
                    self._bill_waste(self.chunk_len, snapshot[i])
                continue
            slot.chunks_inflight -= 1
            slot.decode_chunks_inflight -= 1
            if slot.stale_chunks > 0:
                # A forced-run fast-forward spliced over this chunk:
                # its rows index the pre-splice stream (consume FIFO
                # order makes the countdown exact). Nothing to emit —
                # the splice already delivered these tokens.
                slot.stale_chunks -= 1
                continue
            if self.device_termination:
                new_ids, finish = consume_chunk_row(
                    res.tokens[i], bool(res.done[i]), int(res.lengths[i]),
                    len(slot.detok.ids), ct, cfg.eos_ids)
            else:
                new_ids, finish, wasted = scan_chunk_row(
                    res.tokens[i], len(slot.detok.ids), cfg.eos_ids,
                    slot.req.max_tokens)
                self._bill_waste(wasted, slot.req)
            self._spans.of(slot.req).chunk_consumed()
            if new_ids:
                if slot.t_first is None:
                    # The chunk that carried this slot's prologue (ragged
                    # admission) or followed its replay: its first token.
                    slot.t_first = time.monotonic()
                    if slot.req.t_first0 is None:
                        slot.req.t_first0 = slot.t_first
                    self._spans.of(slot.req).first_token(slot.t_first)
                t_dk = time.monotonic()
                piece = slot.detok.push(*new_ids)
                slot.detok_ms += (time.monotonic() - t_dk) * 1000.0
                # Keep the portable export current: a fresh list per
                # update, so the fleet's cross-thread read always sees a
                # settled snapshot of the generated prefix.
                if slot.req.export is not None:
                    slot.req.export.ids = list(slot.detok.ids)
                if piece is not None:
                    self._emit(slot.req, "token", piece)
                if self._grammar is not None and slot.req.gpid >= 0:
                    self._grammar_consume(slot, new_ids)
                    if finish is None:
                        self._grammar_fast_forward(i, slot)
                        if self._slots[i] is not slot:
                            continue   # fast-forward finished the slot
            if is_spec:
                # Re-sync the conservative scheduled position: a spec
                # chunk advances the device by accepted-count, not a
                # fixed width, so pos drifts high by (ct - advance) per
                # chunk — left alone it would truncate long generations
                # early at the capacity sweep and break spec-off
                # parity. The anchors are exact host truth: the device
                # carry sits at anchor_pos + tokens-emitted-since-arm,
                # plus one ct bound per still-in-flight chunk.
                # Under ragged admission a chunk carrying staged slots
                # emits up to _chunk_tokens + 1 (the prologue token), so
                # the per-chunk bound widens by one to stay an upper
                # bound for every chunk shape.
                slot.pos = (slot.anchor_pos
                            + (len(slot.detok.ids) - slot.anchor_g)
                            + slot.decode_chunks_inflight
                            * (self._chunk_tokens
                               + (1 if self._use_ragged else 0)))
            if slot.req.trace is not None:
                slot.req.trace.event(
                    f"engine: chunk consumed (+{len(new_ids)} tok"
                    f"{', done' if finish else ''}, "
                    f"n_alive={res.n_alive})")
            if finish is not None:
                self._finish(i, finish)
        # Early exoneration: the probe survived another clean chunk.
        # After PROBATION_CLEAN_CHUNKS of them, suspicion narrows to the
        # parked half, which replays NOW — instead of stalling admissions
        # until the probe drains its whole remaining decode (minutes for
        # long generations; queued requests would blow their timeouts).
        # A chunk only counts as probation evidence if its snapshot held
        # a flagged suspect — chunks dispatched before an unpark carry
        # only already-cleared slots and prove nothing.
        if any(r is not None and r.suspect for r in snapshot):
            self._probation_clean += 1
            if self._probation_clean >= PROBATION_CLEAN_CHUNKS:
                self._probation_clean = 0
                for s in self._slots:
                    if s is not None:
                        s.req.suspect = False
                if self._parked:
                    self._unpark_parked()
                # else: the narrowed (re-mixed) suspects also ran clean —
                # the fault was transient; case closed, so a later
                # unrelated fault bisects from the full batch again.
        elif self._parked and not any(
                s is not None and s.req.suspect for s in self._slots
        ) and not any(
                r is not None and r.suspect
                for e in self._inflight if e[0] == "chunk" for r in e[2]):
            # Every probe suspect completed (exonerated by finishing) and
            # none remains in the pipe: the parked half inherits the
            # suspicion now rather than waiting out innocents' decode.
            self._unpark_parked()

    def _finish(self, slot_idx: int, finish: str,
                error: Optional[BaseException] = None,
                wasted_inflight: bool = False) -> None:
        slot = self._slots[slot_idx]
        self._slots[slot_idx] = None
        # A staged ragged admission finished before its chunk (cancel /
        # deadline sweeps) must not arm a later occupant of the slot.
        self._pending_adm.pop(slot_idx, None)
        if slot is None:  # pragma: no cover - defensive
            return
        if self._use_pool:
            # Release the slot's pool blocks; clean finishes insert the
            # verified chain into the radix tree first, so a finished
            # agent turn's prompt+completion KV stays shareable for
            # turn N+1 (refcount-aware: shared blocks just lose this
            # holder).
            self._pool_release_slot(
                slot_idx, slot,
                cache_chain=(error is None and finish in ("stop",
                                                          "length")))
        # Host-ONLY finishes (cancel/timeout/first-token EOS) end a slot
        # the device still believes is live: every already-dispatched
        # chunk decodes it to no purpose. Device-visible finishes (EOS /
        # budget in the chunk carry) froze the slot inside the chunk, so
        # they never land here. Legacy host-termination mode prices this
        # at consume time (snapshot mismatch / prune) instead — counting
        # both would double-bill. The bill is capped by the slot's
        # remaining token budget: the device can never execute more
        # counted steps than that (it freezes at the budget), so a
        # disconnect near natural completion doesn't read as a full
        # pipe_depth × chunk_len of waste. (A device EOS sitting in a
        # still-unconsumed chunk can still overstate modestly — the host
        # can't see it without the fetch it is skipping.)
        if (wasted_inflight and self.device_termination
                and slot.decode_chunks_inflight > 0):
            remaining = max(0, slot.req.max_tokens - len(slot.detok.ids))
            self._bill_waste(min(
                slot.decode_chunks_inflight * self._chunk_waste_bound(),
                remaining), slot.req)
        # Any finish frees a slot — errors included — so all of them feed
        # the drain-rate estimate behind retry_after_hint(); the per-lane
        # deque prices Retry-After for THAT lane's sheds.
        t_fin = time.monotonic()
        self._spans.note_slots(self._slots)
        self._finish_times.append(t_fin)
        lane = getattr(slot.req, "lane", LANE_INTERACTIVE)
        self._lane_finish.setdefault(
            lane, collections.deque(maxlen=64)).append(t_fin)
        # Ledger: the emitted transcript is what the client's stream
        # received — goodput, even when the request then errors (an
        # abort/timeout client keeps its streamed bytes; quarantine is
        # the exception and bills quarantine_burn in the containment
        # pass, which never reaches _finish). Billed incrementally past
        # ledger_delivered: a fleet-migrated request's imported prefix
        # was decoded AND billed on the donor replica — re-billing it
        # here would double-count the same device steps fleet-wide. A
        # cancelled hedge-loser branch (export.discard, set by the
        # fleet before the cancel) emitted tokens the relay never
        # forwarded: hedge_loser burn, not delivered.
        n_new = len(slot.detok.ids) - slot.req.ledger_delivered
        slot.req.ledger_delivered = len(slot.detok.ids)
        discarded = (slot.req.export is not None
                     and getattr(slot.req.export, "discard", False))
        self.ledger.record(
            CLASS_HEDGE_LOSER if discarded else CLASS_DELIVERED,
            n_new, lane=lane, tenant=slot.req.tenant)
        # Session budget (ISSUE 20): only tokens the client actually got
        # spend budget — hedge-loser burn never demotes a session.
        if not discarded:
            self._session_budgets.charge(slot.req.session, n_new)
        if error is not None:
            if slot.req.trace is not None:
                slot.req.trace.event(
                    f"engine: failed ({finish}): {error}")
            self._spans.of(slot.req).finished(t_fin, finish=finish)
            self._emit(slot.req, "error", error)
            return
        t_dk = time.monotonic()
        piece = slot.detok.flush()
        slot.detok_ms += (time.monotonic() - t_dk) * 1000.0
        if piece is not None:
            self._emit(slot.req, "token", piece)
        t_end = time.monotonic()
        self._token_finishes.append((t_end, len(slot.detok.ids)))
        if not slot.req.ttft_exempt and not discarded:
            # t_first0 survives preempt/resume; the slot's t_first is a
            # fresh slot's view and would overstate a resumed TTFT. A
            # cancelled hedge loser contributes NO sample — the winner's
            # finish already measures this logical request, and the
            # loser's latency is exactly the stall the hedge papered
            # over (the client never saw it).
            ttft_sample_ms = ((slot.req.t_first0 or slot.t_first or t_end)
                              - slot.req.t_submit) * 1000.0
            self._slo.note(SLO_TTFT, lane, ttft_sample_ms, now=t_end)
            # Turn-N session TTFT (ISSUE 20): judged ONLY for radix-warm
            # re-admissions of a declared session — the sample set the
            # two-tier cache is accountable for.
            if slot.req.session and slot.req.radix_warm:
                self._slo.note(SLO_SESSION_TTFT, lane, ttft_sample_ms,
                               now=t_end)
        if slot.req.trace is not None:
            slot.req.trace.event(
                f"engine: finished ({finish}, "
                f"{len(slot.detok.ids)} tokens)")
        self._spans.of(slot.req).finished(
            t_end, tokens=len(slot.detok.ids), finish=finish)
        # Starvation truncation is client-visible degradation (ISSUE
        # 20): the transcript stopped short of what decode would have
        # produced, and the result says so rather than passing it off
        # as a natural stop.
        degraded = bool(getattr(slot, "exhausted", False))
        if degraded and slot.req.trace is not None:
            slot.req.trace.link("degraded", cause="kv_pool_starved",
                                tokens=len(slot.detok.ids))
        result = EngineResult(
            text=slot.detok.text,
            prompt_tokens=slot.n_prompt,
            completion_tokens=len(slot.detok.ids),
            queue_ms=slot.queue_ms,
            prefill_ms=slot.prefill_ms,
            decode_ms=(t_end - slot.t_decode0) * 1000.0,
            detok_ms=slot.detok_ms,
            ttft_ms=((slot.t_first or t_end) - slot.req.t_submit) * 1000.0,
            prefix_cache_hit=slot.prefix_hit,
            finish_reason=finish,
            engine=self.name,
            weights_version=self.weights_version,
            degraded=degraded,
        )
        self._emit(slot.req, "done", result)

    def _emit(self, req: _Request, event: str, payload) -> None:
        try:
            req.loop.call_soon_threadsafe(req.out_queue.put_nowait,
                                          (event, payload))
        except RuntimeError:
            # The request's event loop already closed (client's asyncio.run
            # exited after a timeout). Drop the event — nothing is listening
            # — and keep the scheduler alive for the other slots.
            logger.warning("dropping %r event for a dead event loop", event)

    # ------------------------------------------------------------ serving

    async def stream_events(self, prompt: str, *, max_tokens: int = 128,
                            temperature: float = 0.0,
                            timeout: Optional[float] = None,
                            seed: Optional[int] = None,
                            resume_ids: Optional[List[int]] = None,
                            export: Optional[RequestExport] = None):
        """Fleet-facing event stream (engine/fleet.py): the full
        cross-replica contract — pinned seed, ``resume_ids`` import
        (re-splice a prefix generated elsewhere), live ``export`` of the
        generated ids for migration off THIS engine."""
        async for ev in self._stream_events(
                prompt, max_tokens=max_tokens, temperature=temperature,
                timeout=timeout, seed=seed, resume_ids=resume_ids,
                export=export):
            yield ev

    async def _stream_events(self, prompt: str, *, max_tokens: int,
                             temperature: float, timeout: Optional[float],
                             seed: Optional[int] = None,
                             resume_ids: Optional[List[int]] = None,
                             export: Optional[RequestExport] = None):
        if not self._ready:
            raise EngineUnavailable("engine not started")
        # Per-request sampling seed: explicit when the caller pins one,
        # else minted deterministically from the prompt — either way the
        # transcript is a pure function of (seed, prompt, settings),
        # which containment replay AND offline reproduction rely on. The
        # seed rides the trace into /debug/requests/{id}.
        if seed is None:
            seed = zlib.crc32(prompt.encode("utf-8", "surrogatepass")) \
                & 0x7FFFFFFF
        seed = int(seed) & 0x7FFFFFFF
        # QoS classification (ISSUE 7): tenant key + priority lane ride
        # a contextvar from the HTTP layer (server/app.py middleware);
        # direct engine calls default to one interactive anon bucket —
        # the pre-QoS behaviour.
        qctx = current_qos()
        tenant = (qctx.tenant if qctx is not None else "") or ANON_TENANT
        lane = (qctx.lane if qctx is not None
                and qctx.lane in LANES else LANE_INTERACTIVE)
        session = qctx.session if qctx is not None else ""
        # Over-budget sessions classify into the background lane (ISSUE
        # 20): the session keeps working — WDRR guarantees background a
        # share — but stops outranking fresh interactive traffic.
        lane = self._session_budgets.lane_for(session, lane)
        trace = current_trace()
        # Grammar resolution (ISSUE 11): base profile, clamped readonly
        # for the background tier (TENANT_TIERS floor) or an explicit
        # readonly ask, narrowed by a validated allowed-verbs set —
        # resolved HERE so the scheduler only ever sees a profile id.
        gpid = -1
        if self._grammar is not None:
            from ..constrain import current_grammar

            gctx = current_grammar()
            if gctx is not None and gctx.allowed_verbs:
                # A novel allowed-verbs set compiles a variant FSM —
                # seconds of CPU at a real vocab — so it runs off the
                # event loop (cached sets return instantly there too).
                gpid = await asyncio.to_thread(
                    self._grammar.resolve, lane=lane, ctx=gctx)
            else:
                gpid = self._grammar.resolve(lane=lane, ctx=gctx)
            if trace is not None:
                trace.event(f"grammar: profile id {gpid} "
                            f"(lane={lane})")
        loop = asyncio.get_running_loop()
        if self.faults is not None and not getattr(self, "_warming", False):
            # tenant:flood:<n> drill — a synthetic background-tenant
            # burst lands ahead of this submission, so the request that
            # armed the probe experiences the contention under test.
            # The engine's own start()-warm-up generate must not consume
            # the one-shot (hence the _warming guard).
            burst = self.faults.tenant_flood()
            if burst:
                if trace is not None:
                    trace.event(f"qos: tenant:flood drill injecting "
                                f"{burst} synthetic requests")
                self._inject_flood(burst, loop)
        t_submit = time.monotonic()
        deadline = (t_submit + timeout) if timeout else None
        max_tokens = max(1, min(max_tokens, self.max_seq_len - 1))
        req = _Request(
            prompt_ids=self.tokenizer.encode(prompt),
            max_tokens=max_tokens,
            temperature=temperature,
            deadline=deadline,
            loop=loop,
            out_queue=asyncio.Queue(),
            cancel=threading.Event(),
            t_submit=t_submit,
            trace=trace,
            seed=seed,
            prompt=prompt,
            resume_ids=list(resume_ids) if resume_ids else None,
            export=export,
            tenant=tenant,
            lane=lane,
            # Fleet import: the resume prefix was decoded and billed
            # delivered on the donor replica (see _Request.ledger_delivered),
            # and the client's first byte happened there too.
            ledger_delivered=len(resume_ids) if resume_ids else 0,
            ttft_exempt=bool(resume_ids),
            gpid=gpid,
            session=session,
        )
        if export is not None:
            # Version the portable state at submit: ids this engine
            # generates are a function of THESE weights, and the fleet's
            # version-pinned failover routes on this stamp (ISSUE 13).
            export.weights_version = self.weights_version
        # Fair-share load shedding at submit time (QoSQueue policy):
        # past the per-tenant cap → 429 to the flooding tenant; past
        # MAX_QUEUE_DEPTH → displace the dominant tenant's newest
        # request for a quiet arrival, shed the arrival itself only
        # when ITS tenant is the flood. Retry-After is priced from the
        # shed lane's own drain rate.
        try:
            displaced = self._admissions.put(req)
        except TenantOverloaded as e:
            self._rejections += 1
            e.retry_after = max(0.0, self.retry_after_hint(lane=lane))
            if trace is not None:
                trace.event(f"qos: shed at per-tenant cap — {e}")
            raise
        except EngineOverloaded as e:
            self._rejections += 1
            e.retry_after = max(0.0, self.retry_after_hint(lane=lane))
            if trace is not None:
                trace.event(f"engine: admission queue full — shed ({e})")
            raise
        for victim in displaced:
            self._rejections += 1
            if victim.trace is not None:
                victim.trace.event(
                    "qos: displaced from the full admission queue "
                    f"(tenant {victim.tenant!r} holds the largest share)")
            self._emit(victim, "error", EngineOverloaded(
                f"displaced from a full admission queue (tenant "
                f"{victim.tenant!r} holds the largest queue share)",
                retry_after=self.retry_after_hint(lane=victim.lane)))
        if trace is not None:
            trace.event(f"engine: submitted to batch scheduler "
                        f"(queue depth {self._admissions.qsize()}, "
                        f"tenant {tenant!r}, lane {lane}, "
                        f"sampling seed {seed})")
        try:
            while True:
                # Read the LIVE deadline off the request: preemption
                # credits paused wall time back onto it, and this loop
                # must honour the extension, not the submit-time value.
                if req.deadline is not None:
                    remaining = req.deadline - time.monotonic()
                    # Worker enforces the deadline too; +2s grace covers a
                    # chunk in flight before declaring it stuck.
                    try:
                        event, payload = await asyncio.wait_for(
                            req.out_queue.get(), remaining + 2.0
                        )
                    except asyncio.TimeoutError:
                        raise GenerationTimeout("generation exceeded timeout")
                else:
                    event, payload = await req.out_queue.get()
                if event == "error":
                    raise payload
                if event == "done" and req.spans is not None:
                    # Last consume → this coroutine resumed: the event-
                    # loop handoff; the host detok time rides as meta.
                    req.spans.resumed(
                        time.monotonic(),
                        detok_host_ms=round(payload.detok_ms, 3))
                yield (event, payload)
                if event == "done":
                    return
        finally:
            req.cancel.set()
