"""Token sampling: greedy, temperature, top-k, top-p.

temperature=0 → greedy argmax, matching the reference's deterministic
``temperature=0`` LLM setup (app.py:109). Temperature is a *traced* scalar
so one compiled program serves every value (no per-float jit-cache growth,
no mid-request compile stalls); top-k/top-p are static hyperparameters
(changing them recompiles, which is the right trade — they are service
config, not per-request values).

Cost structure (round-6 attribution work): with ``top_k > 0`` the sampled
path never touches the vocab axis beyond one ``lax.top_k`` — the top-p
cutoff, the softmax, and the categorical all run over the ``k`` retained
logits (k ≤ 64 in practice vs a 256k vocab), and the winner maps back
through the top-k indices. The old path sorted and gumbel-noised the full
vocab (a [batch, 256k] sort + 256k random draws per step inside the decode
chunk). ``top_k == 0`` with ``top_p < 1`` still needs the full-vocab sort
(the nucleus cutoff is defined over all logits); plain temperature
sampling (no filters) pays only the categorical. Everything here runs
under a ``jax.named_scope`` so the benchmark's trace reduction
(benchmark/xtrace.py) can bill it as a category.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _filter_top_k_p(scaled: jnp.ndarray, top_k: int,
                    top_p: float) -> jnp.ndarray:
    """Apply static top-k then nucleus (top-p) filtering to
    temperature-scaled logits [..., vocab]. Shared by the single-sequence
    and batched paths so a request samples from the SAME distribution
    whichever engine serves it (VERDICT r4 weak #7). Full-vocab reference
    semantics; the serving paths only take this when ``top_k == 0`` (see
    ``_sample_filtered`` — with a top-k the same filter runs over the
    k-subset instead)."""
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumprobs = jnp.cumsum(probs, axis=-1)
        # Keep the smallest set with cumulative prob >= top_p (always
        # keep at least one token).
        cutoff_mask = cumprobs - probs >= top_p
        cutoff_logit = jnp.min(
            jnp.where(cutoff_mask, jnp.inf, sorted_logits),
            axis=-1, keepdims=True,
        )
        scaled = jnp.where(scaled < cutoff_logit, -jnp.inf, scaled)
    return scaled


def _sample_filtered(scaled: jnp.ndarray, key: jax.Array, top_k: int,
                     top_p: float) -> jnp.ndarray:
    """Categorical draw from temperature-scaled logits under the static
    top-k/top-p filters, avoiding vocab-sized work whenever a top-k
    bounds the support:

    - ``top_k > 0``: ``lax.top_k`` returns the k logits sorted descending
      — exactly the prefix the nucleus rule needs — so the top-p cutoff
      (cumprobs over the kept set; identical to the full filter, whose
      softmax denominator is the same k survivors), the renormalizing
      softmax inside ``categorical``, and the gumbel draw all run on
      [..., k]; the sampled position maps back via the returned indices.
      Tie behaviour at the kth logit: exactly k candidates are kept
      (arbitrary tie order), where the full-vocab filter kept every value
      tied with the kth — a measure-zero difference on real logits.
    - ``top_k == 0``: full-vocab reference filter (a nucleus cutoff
      without a k bound is a property of the whole distribution).

    Same filtered distribution either way; only the RNG *stream* differs
    from the pre-round-6 implementation (the categorical consumes k draws,
    not vocab draws), which per-seed tests must not depend on.
    """
    if top_k > 0:
        vals, idx = jax.lax.top_k(scaled, top_k)
        if top_p < 1.0:
            probs = jax.nn.softmax(vals, axis=-1)
            cumprobs = jnp.cumsum(probs, axis=-1)
            vals = jnp.where(cumprobs - probs >= top_p, -jnp.inf, vals)
        choice = jax.random.categorical(key, vals, axis=-1)
        return jnp.take_along_axis(
            idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)
    scaled = _filter_top_k_p(scaled, 0, top_p)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def sample_token_traced(
    logits: jnp.ndarray,            # [batch, vocab] f32
    key: jax.Array,
    temperature: jnp.ndarray,       # traced scalar
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """Sample next token ids [batch]. ``lax.cond`` executes only the taken
    branch — the greedy path never pays gumbel-noise generation over the
    vocab, and the sampled path applies top-k then top-p filtering."""

    def _greedy(_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(_):
        t = jnp.maximum(temperature, 1e-6)
        return _sample_filtered(logits / t, key, top_k, top_p)

    with jax.named_scope("sampling"):
        return jax.lax.cond(temperature > 0.0, _sampled, _greedy, None)


def _sample_rows(logits, temperatures, active, draw, mask=None):
    """Shared per-row decode-step scaffold: greedy argmax fallback,
    per-slot ``wants_sample`` mask (temperature > 0, intersected with the
    device-resident ``active`` mask so finished slots stop paying for
    sampling), and the ``lax.cond`` that skips the categorical branch
    entirely for all-greedy batches. ``draw`` maps temperature-scaled
    logits [batch, vocab] → sampled ids [batch]; it is the ONLY thing
    that differs between the shared-key and per-request-seeded paths, so
    the distribution-parity-critical body lives here exactly once.

    ``mask`` (grammar-constrained decoding, ISSUE 11) is a [batch,
    vocab] bool of legal tokens: illegal logits drop to -inf BEFORE the
    greedy argmax and the draw, so both paths renormalize over the
    masked support under the SAME key stream. Bit-reproducibility
    contract: the gumbel trick (``categorical`` = argmax(logits +
    gumbel)) consumes a vocab-shaped draw whether or not entries are
    masked, so a masked sample equals the unmasked sample whenever the
    unmasked winner was grammar-legal — the A/B parity the
    GRAMMAR_DECODE acceptance tests assert (top_k must be 0: a top-k
    subset changes the draw shape when the mask changes membership).
    A row with an all-False mask argmaxes over all -inf (index 0); the
    engine freezes such rows via the grammar dead-end health bit before
    anything is emitted."""
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    wants_sample = temperatures > 0.0
    if active is not None:
        wants_sample = jnp.logical_and(wants_sample, active)

    def _with_sampling(_):
        t = jnp.maximum(temperatures, 1e-6)[:, None]
        return jnp.where(wants_sample, draw(logits / t), greedy)

    return jax.lax.cond(
        jnp.any(wants_sample), _with_sampling, lambda _: greedy, None,
    )


def sample_tokens_batched(
    logits: jnp.ndarray,            # [batch, vocab] f32
    key: jax.Array,
    temperatures: jnp.ndarray,      # [batch] traced — per-slot temperature
    top_k: int = 0,
    top_p: float = 1.0,
    active: jnp.ndarray | None = None,  # [batch] bool — rows still decoding
    mask: jnp.ndarray | None = None,    # [batch, vocab] grammar legality
) -> jnp.ndarray:
    """Shared-key per-row sampling: one PRNG key per step, split across
    the rows by the categorical. Since the seeded-sampling switch (ISSUE
    5) the serving decode step runs ``sample_tokens_seeded`` instead —
    this variant is kept as the reference implementation for the
    distribution-parity tests (tests/test_sampling.py), which have no
    per-request seeds to thread. Same ``_sample_rows`` scaffold and
    ``_sample_filtered`` body, so the two variants cannot diverge in
    anything but key derivation.

    Each slot carries its own temperature; top-k/top-p are static service
    config applied identically to every sampled row — the same filtering
    ``sample_token_traced`` runs, so batched and single-sequence paths
    sample from the same distribution at the same settings. ``active``
    is the device-resident done mask's view of the batch: finished slots
    stop paying for sampling, and all-greedy batches take the argmax-only
    branch. The caller still selects its own carry value for dead rows."""
    with jax.named_scope("sampling"):
        return _sample_rows(
            logits, temperatures, active,
            lambda scaled: _sample_filtered(scaled, key, top_k, top_p),
            mask=mask,
        )


def slot_keys(seeds: jnp.ndarray, ngen: jnp.ndarray) -> jnp.ndarray:
    """[batch] per-request seeds × [batch] per-slot generation indices →
    [batch] PRNG keys: ``fold_in(PRNGKey(seed_i), ngen_i)``.

    This is THE replay-parity primitive (engine/containment.py): token
    ``g`` of request ``r`` is sampled under a key that depends only on
    ``(seed_r, g)`` — never on batch composition, chunk boundaries, or
    how many times the engine reset underneath the request — so a
    reset-and-replay that re-splices the request at generation index
    ``g`` continues the exact RNG stream a fault-free run would have
    used."""
    def one(seed, n):
        return jax.random.fold_in(jax.random.PRNGKey(seed), n)

    return jax.vmap(one)(seeds, ngen)


def sample_tokens_seeded(
    logits: jnp.ndarray,            # [batch, vocab] f32
    seeds: jnp.ndarray,             # [batch] int32 per-request seeds
    ngen: jnp.ndarray,              # [batch] int32 per-slot generation index
    temperatures: jnp.ndarray,      # [batch] traced — per-slot temperature
    top_k: int = 0,
    top_p: float = 1.0,
    active: jnp.ndarray | None = None,  # [batch] bool — rows still decoding
    mask: jnp.ndarray | None = None,    # [batch, vocab] grammar legality
) -> jnp.ndarray:
    """Per-row sampling under per-request RNG streams (``slot_keys``):
    the continuous-batching decode step and the admission first-token
    sample both run this, so a request's sampled tokens are a pure
    function of (its seed, its generation index, its logits) — the
    property the fault-containment replay relies on for bit-identical
    recovered transcripts, and what makes any transcript reproducible
    offline from the seed exposed in /debug/requests/{id}.

    Same top-k/top-p filtering as ``sample_tokens_batched`` (the shared
    ``_sample_rows`` scaffold, each row through ``_sample_filtered``);
    only the key derivation differs — per-row independent streams
    instead of one shared key per step.

    Speculative decoding (ISSUE 12) runs this SAME function once per
    verify position, with ``ngen`` advanced by the accepted-count so
    far: the token at generation index ``g`` always draws
    ``fold_in(seed, g)`` from the target's own logits whether it was
    reached by plain decode, by accepting a draft, or by resampling at
    the first rejection — which is exactly why a spec-on transcript is
    byte-identical to spec-off at any draft depth (k=0 included)."""

    def _draw(scaled):
        return jax.vmap(
            lambda row, k: _sample_filtered(row, k, top_k, top_p)
        )(scaled, slot_keys(seeds, ngen))

    with jax.named_scope("sampling"):
        return _sample_rows(logits, temperatures, active, _draw, mask=mask)


def greedy_tokens(logits: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """[batch, vocab] → [batch] argmax ids, optionally restricted to a
    grammar-legality ``mask`` (illegal → -inf first).

    This is the DRAFT side of speculative decoding (ISSUE 12): draft
    proposals are verified by exact match against the target's own
    seeded sample, so the draft never needs randomness — greedy argmax
    maximizes the acceptance rate at temperature 0 (where the target is
    argmax too) and costs no PRNG stream bookkeeping at any
    temperature. Masking drafts by the same grammar tables the verifier
    uses keeps proposals legal, so a draft can never waste its verify
    lane on a token the mask would have zeroed anyway."""
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    with jax.named_scope("draft_sampling"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def eos_mask(tokens: jnp.ndarray, eos_ids) -> jnp.ndarray:
    """[batch] bool — which sampled tokens are termination ids. The EOS
    set is tiny static service config (1-2 ids per model), so a broadcast
    compare beats any vocab-sized membership structure; runs inside the
    decode chunk's scan to fold termination into the carried active mask
    (the device-resident done mask, engine/batcher.py)."""
    eos_arr = jnp.asarray(tuple(eos_ids), jnp.int32)
    return jnp.any(tokens[..., None] == eos_arr, axis=-1)
