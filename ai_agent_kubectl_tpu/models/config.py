"""Model architecture configs.

One ``ModelConfig`` parameterizes every family in BASELINE.json's eval
matrix (Gemma-2B/7B, Llama-3-8B/70B, Mixtral-8x7B) plus tiny deterministic
test models. Family differences are expressed as data, not subclasses:

- Gemma:   (1+w) RMSNorm, sqrt(dim) embedding scale, GeGLU, tied embeddings,
           head_dim 256, MHA (7B) / MQA (2B)
- Llama-3: plain RMSNorm, SiLU-GLU, GQA 8 KV heads, theta 500k, untied
- Mixtral: Llama geometry + 8-expert top-2 MoE MLP
- Keye-VL-2.0-30B-A3B's language model (registered by the benchmark from
  benchmark/configs/, not a preset here): QK-norm, 128 experts of their
  own width through the grouped expert path (parallel/moe.py), and a
  learned top-k key selector (``index_topk``) inside paged attention
  whose keys live in a pool leaf of their own
- NVIDIA-Nemotron-3-Nano-30B-A3B's language model (registered by the
  benchmark too; ``toy-hybrid-moe`` is its toy): ``layer_pattern`` gives
  every layer ONE mixer — a Mamba-2 layer (ops/ssd_scan.py, its state a
  cache leaf of its own), two-matrix relu^2 experts under a sigmoid router
  with a selection bias beside a shared expert, or GQA without a rotary
  embedding — and the weights are stacked per kind
- Mistral-Small-4-119B-2603's language model (``mistral4``; registered by
  the benchmark, ``toy-mla-moe`` is its toy): LATENT attention — queries
  and keys/values through low-rank projections, the cache ONE compressed
  row a token (``kv_lora_rank`` + ``qk_rope_head_dim`` values, no head
  axis) that every head reads through absorbed projections — YaRN
  frequencies on interleaved pairs, a position-dependent query scale,
  and a CHIP'S SHARE of the routed experts (``n_experts`` held of the
  ``router_width`` the router scores) beside a shared expert
- Laguna-S-2.1's language model (``laguna``; registered by the benchmark,
  ``toy-sliding-moe`` is its toy): attention layers of TWO kinds in one
  model, ``*`` full and ``S`` sliding (``sliding_window`` keys, the
  query's own included), each kind with its own head count over the same
  KV heads, its own rotary rule (theta, rotated share, YaRN) and a
  per-head sigmoid gate on its output; a pre-norm block is TWO one-mixer
  layers of ``layer_pattern`` (``mixers_per_layer`` 2: attention, then a
  ``D`` dense MLP or ``E`` experts), weights stacked per kind. A sliding
  layer's K/V are a bounded state a sequence (a ring of the span plus a
  window, ``KVCache.sk``/``sv``), not rows of the paged pool
- Olmo-Hybrid-7B (``olmo_hybrid``; registered by the benchmark,
  ``toy-linear-hybrid`` is its toy): LINEAR-attention layers (``L``: the
  gated delta rule, ops/gated_delta.py — a [key_dim, value_dim] matrix a
  head that every token decays, erases along its key and writes to; the
  state a cache leaf of its own beside a 4-tap convolution's tail) three
  to one full-attention layer without a rotary embedding, each followed
  by a ``D`` dense MLP (``mixers_per_layer`` 2), and the Olmo block form:
  a sublayer's OUTPUT is normed, ``x + norm(f(x))``, its input is not
  (``post_norm``), and the QK-norm spans the whole projection before the
  head split (``qk_norm_whole``)
- Ling-3.0-flash-VL's language model (registered by the benchmark,
  ``toy-kda-mla-moe`` is its toy): a matrix-state layer and a latent layer
  in ONE pattern — ``L`` is Kimi delta attention (the delta rule with a
  decay for every KEY CHANNEL, bounded below: ``lin_channel_decay``,
  ``lin_decay_floor``; a sigmoid output gate, ``lin_out_gate``), ``*``
  every sixth layer is latent attention WITHOUT a query LoRA
  (``q_lora_rank`` 0), plain half-split rope and a per-head output gate,
  its ``lat`` leaf a plane a latent layer — behind two dense layers, a
  chip's share of 512 experts under a GROUP-LIMITED sigmoid router
  (``n_group`` / ``topk_group``)
- Granite-4.0-H-Micro (``granitemoehybrid``; registered by the benchmark,
  ``toy-ssm-dense`` is its toy): Mamba-2 layers nine to one of attention
  without a rotary embedding, each followed by a ``D`` dense MLP
  (``mixers_per_layer`` 2: a period of 20 mixers, scanned), one
  state-space group, attention heads of 64 (two KV heads a lane tile of
  the pool: ops/ragged_attention.py::lane_heads), a tied head, and four
  scalars: ``embed_multiplier``, ``residual_multiplier``,
  ``attention_multiplier``, ``logits_scaling``
- Qwen3-Next-80B-A3B-Instruct (``qwen3_next``; registered by the benchmark,
  ``toy-gdn-moe`` is its toy): the gated delta rule with FEWER KEY HEADS
  THAN VALUE HEADS (``lin_key_heads`` 16 for ``lin_value_heads`` 32: value
  head h reads key head h // 2; q and k are repeated, never the state or
  ``W_in``) three layers to one of GATED attention — ``attn_gate``
  "elementwise": ``W_q`` gives every head ``[q | gate]``, 2 x ``head_dim``
  columns, and ``sigmoid(gate)`` multiplies the head's attention output
  lane by lane before ``W_o`` — on 256-wide heads normed a head by the
  ``(1 + w)`` norm (``rms_offset`` 1, on ``q_norm`` / ``k_norm`` too) and
  rotated on a quarter of their lanes; behind each an expert layer, a
  chip's share of 512 experts under a softmax router, its shared expert
  under a sigmoid SCALAR gate a token (``shared_expert_gate``)
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    # the MLP's width, or the experts'; 0 where every MLP is a ``D`` mixer
    # of ``dense_mlp_hidden``
    mlp_hidden: int = 0
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    rms_offset: float = 0.0          # 1.0 for Gemma's (1+w) norm
    activation: str = "silu"         # silu | gelu (Gemma uses gelu_tanh)
    tie_embeddings: bool = False
    embed_scale: bool = False        # Gemma multiplies embeddings by sqrt(dim)
    # MoE (0 experts = dense MLP)
    n_experts: int = 0
    experts_per_token: int = 0
    # Width of the source's dense MLP where every layer is sparse and the
    # experts' own width is ``mlp_hidden`` (the Qwen3-MoE family's
    # ``intermediate_size`` beside ``moe_intermediate_size``): recorded so
    # the source's key has a field, computed by nothing.
    dense_mlp_hidden: int = 0
    # Per-head RMSNorm on q and k before the rotary embedding (weights
    # ``q_norm``/``k_norm`` [head_dim]; the Qwen3 convention).
    qk_norm: bool = False
    # ... or ONE RMSNorm over a token's whole q projection and one over its
    # k projection, before the head split (weights ``q_norm`` [heads x
    # head_dim], ``k_norm`` [kv heads x head_dim]; the Olmo 2 convention).
    qk_norm_whole: bool = False
    # Where a sublayer's norm sits: False ``x + f(norm(x))``, True ``x +
    # norm(f(x))`` with no norm on the input (the Olmo 2 block; patterned
    # configurations only: every mixer's norm leaf is then its output's).
    post_norm: bool = False
    # Learned sparse attention (DeepSeek-V3.2-Exp's lightning indexer on a
    # GQA model): query t scores every key s <= t with ``index_heads``
    # heads of ``index_head_dim`` against ONE index key a token,
    # I[t,s] = sum_j w[t,j] relu(q'[t,j] . k'[s]), and attends only to the
    # ``index_topk`` best (ties to the lower s), one set for all heads.
    # 0 = every key (no indexer leaves, no index-key cache).
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # One mixer a layer (the nemotron_h family): character l names layer
    # l's kind, ``M`` a Mamba-2 layer, ``E`` an expert layer, ``*``
    # attention, ``S`` sliding-window attention, ``D`` a dense MLP, each
    # ``L`` a linear-attention layer (the gated delta rule), each
    # ``x + mixer(norm(x))`` with nothing behind it. A string so the
    # dataclass stays hashable; longer than the layers it names it means
    # its first characters (a configuration cut in depth keeps the
    # published string). "" = every layer is attention then MLP.
    # ``mixers_per_layer`` characters make one of the source's layers (2
    # where the source's layer is a pre-norm block, attention then an MLP,
    # written as its two mixers): ``n_layers`` counts the source's.
    layer_pattern: str = ""
    mixers_per_layer: int = 1
    # Two kinds of attention in one model. An ``S`` layer's query at
    # position t sees keys s with t - ``sliding_window`` < s <= t, has
    # ``sliding_n_heads`` query heads over the same KV heads (0 =
    # ``n_heads``) and the plain rotary embedding of ``sliding_rope_theta``
    # on the first ``sliding_rope_partial`` of its head's lanes. A ``*``
    # layer keeps ``n_heads``, ``rope_theta`` and the YaRN fields below,
    # on the first ``rope_partial`` of the lanes, cos and sin times
    # ``rope_attention_factor`` (0 = none given: 1). ``attn_gate``
    # "per-head" (or any other non-empty word but the next): sigmoid(x W_g)
    # [heads], from the layer's normed input, multiplies each head's
    # attention output before W_o (both kinds). "elementwise": the gate is a
    # vector as wide as the head and comes out of W_q itself, whose columns
    # are [q | gate] a head (2 x head_dim); no W_g leaf.
    sliding_window: int = 0
    sliding_n_heads: int = 0
    sliding_rope_theta: float = 10000.0
    sliding_rope_partial: float = 1.0
    rope_partial: float = 1.0
    rope_attention_factor: float = 0.0
    attn_gate: str = ""
    # Mamba-2 sizes: d_inner = ssm_heads x ssm_head_dim, B and C of
    # ``ssm_state`` shared by the heads of each of ``ssm_groups`` groups, a
    # causal depthwise convolution of ``ssm_conv`` taps over [x | B | C],
    # the scan's chunk ``ssm_chunk`` (tiling only: changes no result).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 0
    # Linear attention by the gated delta rule (``L`` layers; ops/
    # gated_delta.py has the recurrence): ``lin_value_heads`` heads, each a
    # float32 state [``lin_key_dim``, ``lin_value_dim``]; q and k of
    # ``lin_key_heads`` heads, value head h reading key head h // r where
    # the value heads are r times the key heads (r whole; a decay a key
    # channel needs r = 1); a causal
    # depthwise convolution of ``lin_conv`` taps over [q | k | v];
    # ``lin_neg_eigval``: the write strength beta runs over (0, 2), not
    # (0, 1).
    lin_key_heads: int = 0
    lin_value_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv: int = 0
    lin_neg_eigval: bool = False
    # Kimi delta attention (``lin_channel_decay``): the decay is a VECTOR, one
    # for every key channel of a head, and bounded below: g = ``lin_decay_
    # floor`` x sigmoid(exp(A_log_h) (x W_f + f_bias)) lies in (floor, 0), so
    # 16 tokens' decays sum to no less than 16 x floor, which float32's exp
    # must hold (ops/gated_delta.py::channel_decay_scan). The gate on the
    # normed output: ``silu`` (the gated delta rule's) or ``sigmoid`` (KDA's).
    lin_channel_decay: bool = False
    lin_decay_floor: float = 0.0
    lin_out_gate: str = "silu"
    # An expert every token takes beside the routed ones (0 = none), the
    # router's kind (``softmax``: the k largest logits, softmax over them;
    # ``sigmoid_bias``: sigmoid scores, the k largest of score + a learned
    # selection bias, the picked scores renormalised) and the factor the
    # routed sum is scaled by.
    shared_mlp_hidden: int = 0
    # The shared expert under a gate of its own: sigmoid(x w_s), ONE scalar
    # a token (leaf ``shared_expert_gate`` [d, 1]), times its output.
    shared_expert_gate: bool = False
    router: str = "softmax"
    router_scale: float = 1.0
    # A group-limited choice (DeepSeek-V3's; ``sigmoid_bias`` only): the
    # scored experts are ``n_group`` groups of equal size, a group's score
    # the sum of its two largest score + bias, only the ``topk_group`` best
    # groups' experts may be picked. 1 and 1 = no limit.
    n_group: int = 1
    topk_group: int = 1
    # An expert layer that is GIVEN a share of its experts (one chip of an
    # expert-parallel deployment): the router scores ``router_width``
    # experts (0 = ``n_experts``) and picks among all of them; the leaves
    # hold ``n_experts`` of them, from ``first_expert`` on; a pick of an
    # expert held elsewhere adds nothing here, and the partial sum goes on.
    router_width: int = 0
    first_expert: int = 0
    # Latent attention (MLA; DeepSeek-V2's, as ``mistral4`` takes it), on
    # where ``kv_lora_rank`` > 0 (in a pattern: its ``*`` layers): q =
    # RMSNorm(x W_dq) W_uq (``q_lora_rank`` 0: q = x W_q, no norm), per head
    # ``qk_nope_head_dim`` values without and ``qk_rope_head_dim`` with a
    # rotary embedding; [c | kr] = x W_dkv, c = RMSNorm(c) of
    # ``kv_lora_rank`` values and ONE rope key a token for all heads; a
    # head's key is [c W_uk_h | kr] and its value c W_uv_h (``v_head_dim``).
    # The cache is the row [c | kr] and nothing else; ``n_kv_heads`` and
    # ``head_dim`` are carried as published and read by nothing here.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Rotary scaling: pairs (2i, 2i+1) instead of (i, i + d/2); YaRN
    # frequencies (``rope_factor`` > 1 over ``rope_original_max``
    # positions, the ramp between ``rope_beta_fast`` and ``rope_beta_slow``
    # rotations; ops/rope.py); and the Llama-4 query scale 1 +
    # ``q_scale_beta`` ln(1 + floor(pos / rope_original_max)).
    rope_interleave: bool = False
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    q_scale_beta: float = 0.0
    # False: attention takes no rotary embedding (its positions come from
    # the state-space layers); ``rope_theta`` is then carried, unused.
    use_rope: bool = True
    # Four scalars of a maximal-update parametrisation (the granitemoehybrid
    # family's), each 1 (the attention's 0) where a model has none, and then
    # in no program: the embedding times ``embed_multiplier``; every
    # sublayer's output times ``residual_multiplier`` before it joins the
    # residual (patterned configurations: each mixer's); the softmax scale
    # ``attention_multiplier`` in the place of head_dim ** -0.5 (0: that);
    # the logits divided by ``logits_scaling``, before a grammar's mask and
    # the sampler see them.
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # Special tokens (tokenizer-dependent; defaults overridden per family)
    bos_id: int = 1
    eos_ids: Tuple[int, ...] = (2,)
    pad_id: int = 0
    max_seq_len: int = 8192

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def selects_keys(self) -> bool:
        return self.index_topk > 0

    @property
    def latent(self) -> bool:
        """Latent attention: the cache is one compressed row a token."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Values of a token's cached row: the normed latent and the one
        rotated rope key (256 + 64 = 320: 640 B in bf16)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def experts_scored(self) -> int:
        """Experts the router scores (those held here are ``n_experts``)."""
        return self.router_width or self.n_experts

    @property
    def gate_elementwise(self) -> bool:
        """The attention's gate is a vector a head, out of ``W_q``."""
        return self.attn_gate == "elementwise"

    @property
    def gate_per_head(self) -> bool:
        """The attention's gate is a scalar a head, from a leaf ``wg``."""
        return bool(self.attn_gate) and not self.gate_elementwise

    @property
    def counts_picks(self) -> bool:
        """A chip's share of the experts whose passes count the picks that
        land here (``KVCache.expert_picks``, /health.moe ``picks`` and
        ``picks_held``): a group-limited choice and a gated shared expert,
        the shares that came with or after the count. The two shares under a
        plain top-k router before it keep the programs they were measured
        with (models/families.py::CACHE_KINDS ``expert_share``)."""
        return (self.grouped_experts and self.router_width > 0
                and (self.n_group > 1 or self.shared_expert_gate))

    @property
    def gated_mlp(self) -> bool:
        """Three matrices (act(x Wg) * (x Wu), then Wd) or, for ``relu2``,
        two (relu(x Wu)^2, then Wd: no gate leaf anywhere in the tree)."""
        return self.activation != "relu2"

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Layer l's kind for a patterned configuration, () otherwise."""
        if not self.layer_pattern:
            return ()
        n = self.n_layers * self.mixers_per_layer
        kinds = tuple(self.layer_pattern[:n])
        if len(kinds) < n or set(kinds) - set(MIXER_KINDS):
            raise ValueError(
                f"{self.name}: layer_pattern {self.layer_pattern!r} does "
                f"not name {n} mixers of kinds {', '.join(MIXER_KINDS)}")
        if "S" in kinds and self.sliding_window < 1:
            raise ValueError(f"{self.name}: an S layer needs sliding_window")
        if "L" in kinds and (
                self.lin_key_heads < 1
                or self.lin_value_heads % self.lin_key_heads
                or (self.lin_channel_decay
                    and self.lin_value_heads != self.lin_key_heads)):
            raise ValueError(
                f"{self.name}: lin_value_heads {self.lin_value_heads} over "
                f"lin_key_heads {self.lin_key_heads}: a key head serves a "
                "whole number of value heads, and one where the decay is a "
                "key channel's; anything else is not built (ROADMAP)")
        if ("L" in kinds and self.lin_channel_decay
                and not -5.5 <= self.lin_decay_floor < 0):
            raise ValueError(
                f"{self.name}: a decay a key channel needs lin_decay_floor "
                f"in [-5.5, 0), not {self.lin_decay_floor}: 16 rows' decays "
                "are factored in float32 (ops/gated_delta.py)")
        return kinds

    def n_of(self, kind: str) -> int:
        """Layers of ``kind``; of a uniform block, every layer is each."""
        kinds = self.layer_kinds
        return kinds.count(kind) if kinds else self.n_layers

    @property
    def keeps_state(self) -> bool:
        """A bounded state a sequence beside the paged KV cache (engine/
        kv_pool.py::StateStore): some layer is a state-space layer, a
        linear-attention one, or a sliding-attention one."""
        return self.has_ssm or self.has_linear or self.slides

    @property
    def has_ssm(self) -> bool:
        return "M" in self.layer_kinds

    @property
    def has_linear(self) -> bool:
        """Some layer is linear attention (the gated delta rule)."""
        return "L" in self.layer_kinds

    @property
    def lin_conv_dim(self) -> int:
        """Channels the linear layers' convolution runs over: [q | k | v]."""
        return (2 * self.lin_key_heads * self.lin_key_dim
                + self.lin_value_heads * self.lin_value_dim)

    @property
    def slides(self) -> bool:
        """Some attention layer reads a bounded span of keys."""
        return "S" in self.layer_kinds

    def heads_of(self, kind: str) -> int:
        """Query heads of attention kind ``*`` or ``S``."""
        return (self.sliding_n_heads or self.n_heads) if kind == "S" \
            else self.n_heads

    def sliding_ring(self, widest_window: int, page: int) -> int:
        """Rows of a sequence's sliding state as it is kept live: the span
        and the widest window one call writes beside it, in whole pages. A
        row is addressed by its position modulo this."""
        return -(-(self.sliding_window + widest_window) // page) * page

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def state_bytes(self) -> int:
        """One sequence's bounded state as a snapshot holds it: float32
        [heads, head_dim, state] and a bf16 convolution tail a state-space
        layer."""
        ssm = self.n_of("M") * (
            4 * self.ssm_heads * self.ssm_head_dim * self.ssm_state
            + 2 * (self.ssm_conv - 1) * self.ssm_conv_dim
        ) if self.has_ssm else 0
        # ... and the last ``sliding_window`` K and V rows (bf16) a
        # sliding-attention layer
        sliding = self.n_of("S") * (
            2 * 2 * self.sliding_window * self.n_kv_heads * self.head_dim
        ) if self.slides else 0
        # ... and float32 [key_dim, heads x value_dim] with a bf16
        # convolution tail a linear-attention layer
        linear = self.n_of("L") * (
            4 * self.lin_key_dim * self.lin_value_heads * self.lin_value_dim
            + 2 * (self.lin_conv - 1) * self.lin_conv_dim
        ) if self.has_linear else 0
        return ssm + sliding + linear

    @property
    def index_key_width(self) -> int:
        """Row width of the index-key pool leaf: ``index_head_dim`` rounded
        up to the TPU's 128 lanes (zeros above the key). A 64-wide bf16
        minor dim has no row-major home in HBM: the compiler either pads
        it to 128 lanes or, as it chose for [L, blocks, 64, 64], lays the
        BLOCK axis minor-most, which turns every page read into a strided
        one and put a copy of the whole leaf in front of each (AOT, PR 31).
        Rows of 128 lanes are written and read like K and V."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def grouped_experts(self) -> bool:
        """The one static rule that picks the MoE path on one device
        (models/transformer.py::_moe_mlp). Each shipped family is on the
        only path that can serve it: ``dense_moe`` evaluates every expert
        for every token, n_experts / experts_per_token times the picked
        experts' arithmetic — 4x at Mixtral's 8 experts, top-2, but 16x
        and ~5 GB of [8192, 128, 768] temporaries a 512-wide window at 128
        experts, top-8; the token-grouped GEMM holds a whole expert in VMEM,
        which three 4096 x 14336 matrices (337 MB against 128 MiB) do not
        fit (AOT, PR 31: tests/test_tpu_aot.py pins the refusal). The 8x
        sits between the two; nothing between them has been timed on the
        chip, and the threshold is to be measured once an F-tiled grouped
        kernel can take wide experts (ROADMAP S2)."""
        return (self.n_experts > 0
                and self.experts_scored >= 8 * self.experts_per_token)

    @property
    def kv_heads_paged(self) -> int:
        """KV heads a row of the cache holds a token: ``n_kv_heads``, in
        whole sublane tiles of 8 where there are more than 8 (30 -> 32).
        The TPU keeps a [30, 128] bf16 row as 32 in HBM whatever the leaf's
        shape says, and the paged kernel cannot slice 30 of a tile's 32
        (Mosaic: "must be aligned to tiling (8)"; AOT, PR 45): the leaf
        says what the memory holds, the spare heads stay zero, and
        attention runs its queries padded alike (models/transformer.py::
        _layer)."""
        KV = self.n_kv_heads
        return KV if KV <= 8 else -(-KV // 8) * 8

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def softmax_scale(self) -> float:
        """What the attention scores are multiplied by."""
        return self.attention_multiplier or self.head_dim ** -0.5

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + layers)."""
        embed = self.vocab_size * self.dim
        if self.layer_kinds:
            return self._patterned_param_count()
        attn = self.n_layers * (
            self.dim * self.n_heads * self.head_dim          # wq
            + 2 * self.dim * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * self.dim         # wo
        )
        if self.latent:
            H = self.n_heads
            attn = self.n_layers * (
                self.dim * self.q_lora_rank + self.q_lora_rank * H
                * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                + self.dim * self.latent_row + self.kv_lora_rank * H
                * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * self.dim)
        mlp_units = max(self.n_experts, 1)
        mlp = self.n_layers * 3 * self.dim * (
            mlp_units * self.mlp_hidden + self.shared_mlp_hidden)
        router = self.n_layers * self.dim * self.experts_scored
        if self.selects_keys:
            attn += self.n_layers * self.dim * (
                self.index_heads * self.index_head_dim    # idx_wq
                + self.index_head_dim + self.index_heads)  # idx_wk, idx_ww
        norms = self.n_layers * 2 * self.dim + self.dim
        head = 0 if self.tie_embeddings else self.vocab_size * self.dim
        return embed + attn + mlp + router + norms + head

    def _patterned_param_count(self) -> int:
        d, mats = self.dim, 3 if self.gated_mlp else 2

        def attn_of(kind):
            H = self.heads_of(kind)
            whole = (H + self.n_kv_heads) * self.head_dim
            return (2 * d * self.head_dim * (H + self.n_kv_heads)
                    + (d * H if self.gate_per_head else 0)
                    + (d * H * self.head_dim if self.gate_elementwise else 0)
                    + (whole if self.qk_norm_whole else 0)
                    + (2 * self.head_dim if self.qk_norm else 0))

        attn = attn_of("*")
        if self.latent:
            # W_q (or W_dq, its norm, W_uq), W_dkv and its norm, W_ukv, W_o,
            # the per-head gate
            H, Qr = self.n_heads, self.q_lora_rank
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            attn = ((d * Qr + Qr + Qr * H * qk) if Qr else d * H * qk) + (
                d * self.latent_row + self.kv_lora_rank
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * d + (d * H if self.attn_gate else 0))
        moe = (self.n_experts * mats * d * self.mlp_hidden
               + mats * d * self.shared_mlp_hidden
               + d * self.experts_scored
               + (self.experts_scored if self.router == "sigmoid_bias"
                  else 0)
               + (d if self.shared_expert_gate else 0))
        ssm = (d * (2 * self.ssm_inner + 2 * self.ssm_groups * self.ssm_state
                    + self.ssm_heads)
               + self.ssm_inner * d + (self.ssm_conv + 1) * self.ssm_conv_dim
               + 3 * self.ssm_heads + self.ssm_inner)
        # W_q | W_k | W_v | W_g in (q and k of the KEY heads: lin_conv_dim),
        # W_o out, W_a and W_b, the convolution, A_log and dt_bias (a value
        # head's each), the gated norm's gain
        vals = self.lin_value_heads * self.lin_value_dim
        linear = (d * (self.lin_conv_dim + vals) + vals * d
                  + 2 * d * self.lin_value_heads
                  + self.lin_conv * self.lin_conv_dim
                  + 2 * self.lin_value_heads + self.lin_value_dim)
        if self.lin_channel_decay:
            # W_a is W_f [d, heads x key_dim] and the step bias a channel's
            keys = self.lin_key_heads * self.lin_key_dim
            linear += (d + 1) * (keys - self.lin_value_heads)
        per = {"*": attn, "E": moe, "M": ssm, "S": attn_of("S"),
               "D": mats * d * self.dense_mlp_hidden, "L": linear}
        layers = sum(per[k] + d for k in self.layer_kinds)
        head = 0 if self.tie_embeddings else self.vocab_size * d
        return self.vocab_size * d + layers + d + head


#: The kinds ``layer_pattern`` may name.
MIXER_KINDS = ("M", "E", "*", "S", "D", "L")

_CONFIGS: Dict[str, ModelConfig] = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    _CONFIGS[cfg.name] = cfg
    return cfg


# --- Test models (deterministic, CPU-fast) ---
TOY_8M = _register(ModelConfig(
    name="toy-8m", vocab_size=512, dim=256, n_layers=4, n_heads=4,
    n_kv_heads=2, head_dim=64, mlp_hidden=704, max_seq_len=2048,
))
TOY_MOE = _register(ModelConfig(
    name="toy-moe", vocab_size=512, dim=256, n_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=64, mlp_hidden=448, n_experts=4,
    experts_per_token=2, max_seq_len=2048,
))
# Many small experts through the grouped expert path, QK-norm, and a key
# selector whose top-k (48) the tests' prompts cross: the toy of the
# benchmark's keye-vl-2.0-30b-a3b-l8 configuration.
TOY_SPARSE_MOE = _register(ModelConfig(
    name="toy-sparse-moe", vocab_size=512, dim=128, n_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=64, mlp_hidden=64, n_experts=16,
    experts_per_token=2, qk_norm=True, index_topk=48, index_heads=4,
    index_head_dim=32, max_seq_len=2048,
))

# One mixer a layer, all three kinds, two periods: a Mamba-2 layer, two-
# matrix relu^2 experts (width 72: not a multiple of 128) under a sigmoid
# router with a selection bias beside a shared expert, attention without
# a rotary embedding: the toy of the benchmark's
# nemotron-3-nano-30b-a3b-l13 configuration.
TOY_HYBRID_MOE = _register(ModelConfig(
    name="toy-hybrid-moe", vocab_size=512, dim=128, n_layers=6, n_heads=4,
    n_kv_heads=2, head_dim=32, mlp_hidden=72, rms_eps=1e-5,
    activation="relu2", n_experts=16, experts_per_token=2,
    layer_pattern="ME*ME*", ssm_heads=8, ssm_head_dim=16, ssm_state=32,
    ssm_groups=2, ssm_conv=4, ssm_chunk=16, shared_mlp_hidden=144,
    router="sigmoid_bias", router_scale=2.5, use_rope=False,
    max_seq_len=2048,
))

# Latent attention over a compressed row a token (YaRN over 64 positions,
# interleaved pairs, the position-dependent query scale: the tests cross
# the boundary), and a chip's share of the experts (the router scores 16,
# this tree holds 4 of them from the first) beside a shared expert: the toy
# of the benchmark's mistral-small-4-119b-2603-l9 configuration.
TOY_MLA_MOE = _register(ModelConfig(
    name="toy-mla-moe", vocab_size=512, dim=128, n_layers=2, n_heads=4,
    n_kv_heads=4, head_dim=32, mlp_hidden=64, n_experts=4,
    experts_per_token=2, router_width=16, shared_mlp_hidden=64,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=32, rope_interleave=True,
    rope_factor=8.0, rope_original_max=64, rope_mscale=1.0,
    rope_mscale_all_dim=1.0, q_scale_beta=0.1, max_seq_len=2048,
))

# Attention of two kinds (full, sliding, full, sliding, sliding, full: a
# kind after itself and after the other; a count of each that no prefix of
# the published order has, which is how the plain reference tells the two
# orders apart) in blocks written as their two mixers: a span (24) the
# tests' prompts cross
# many times, head counts that differ by kind with groups of 3 and 2 over
# the same 2 KV heads, YaRN on half the full kind's lanes and a plain
# rotary embedding on all of the sliding kind's, a per-head gate, a leading
# dense MLP and a chip's share of the experts (the router scores 16, this
# tree holds 4): the toy of the benchmark's laguna-s-2.1-l12 configuration.
TOY_SLIDING_MOE = _register(ModelConfig(
    name="toy-sliding-moe", vocab_size=512, dim=128, n_layers=6, n_heads=4,
    n_kv_heads=2, head_dim=32, mlp_hidden=64, dense_mlp_hidden=192,
    n_experts=4, experts_per_token=2, router_width=16, router_scale=2.5,
    shared_mlp_hidden=64, layer_pattern="*DSE*ESESE*E", mixers_per_layer=2,
    sliding_window=24, sliding_n_heads=6, sliding_rope_theta=10000.0,
    rope_theta=500000.0, rope_partial=0.5, rope_factor=8.0,
    rope_original_max=64, rope_attention_factor=1.2, attn_gate="per-head",
    max_seq_len=2048,
))

# Linear attention by the gated delta rule three layers to one of full
# attention (MHA, no rotary embedding, QK-norm over the whole projection),
# each followed by a dense MLP, a sublayer's output normed and not its
# input; key and value dims that differ and are no multiple of 128 lanes,
# two periods: the toy of
# the benchmark's olmo-hybrid-7b configuration.
TOY_LINEAR_HYBRID = _register(ModelConfig(
    name="toy-linear-hybrid", vocab_size=512, dim=128, n_layers=8,
    n_heads=4, n_kv_heads=4, head_dim=32, dense_mlp_hidden=192,
    layer_pattern="LDLDLD*D" * 2, mixers_per_layer=2, lin_key_heads=4,
    lin_value_heads=4, lin_key_dim=24, lin_value_dim=40, lin_conv=4,
    lin_neg_eigval=True, post_norm=True, qk_norm_whole=True,
    use_rope=False, max_seq_len=2048,
))

# Kimi delta attention (a decay a key channel, floor -5, key and value dims
# that differ) two layers to one of latent attention without a query LoRA
# (plain half-split rope, a per-head gate), a leading dense layer, then a
# chip's share of the experts (the router scores 16 in 4 groups of which 2
# stay, this tree holds 4) under the sigmoid router with a bias, scaled 2.5,
# beside a shared expert; four KDA layers, two latent, one dense, five of
# experts: counts no prefix of the published order (five to one, two dense)
# has. The toy of the benchmark's ling-3.0-flash-vl-l12 configuration.
TOY_KDA_MLA_MOE = _register(ModelConfig(
    name="toy-kda-mla-moe", vocab_size=512, dim=128, n_layers=6, n_heads=4,
    n_kv_heads=4, head_dim=32, mlp_hidden=64, dense_mlp_hidden=192,
    n_experts=4, experts_per_token=2, router_width=16, router_scale=2.5,
    router="sigmoid_bias", n_group=4, topk_group=2, shared_mlp_hidden=64,
    layer_pattern="LDLE*ELELE*E", mixers_per_layer=2, lin_key_heads=4,
    lin_value_heads=4, lin_key_dim=24, lin_value_dim=40, lin_conv=4,
    lin_channel_decay=True, lin_decay_floor=-5.0, lin_out_gate="sigmoid",
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
    rope_theta=6000000.0, attn_gate="head_wise", max_seq_len=2048,
))

# Mamba-2 layers four to one of attention WITHOUT a rotary embedding, each
# followed by a gated dense MLP (a pre-norm block written as its two mixers),
# ONE state-space group, 64-wide attention heads four to a KV head (two KV
# heads a 128-lane tile), a tied head, and the four multipliers all away from
# 1 (a left-out one moves the logits): two periods of ten mixers, the
# attention layer in the middle of its period, so that the scanned period has
# state-space layers on both sides of it. The toy of the benchmark's
# granite-4.0-h-micro configuration.
TOY_SSM_DENSE = _register(ModelConfig(
    name="toy-ssm-dense", vocab_size=512, dim=128, n_layers=10, n_heads=8,
    n_kv_heads=2, head_dim=64, dense_mlp_hidden=192, rms_eps=1e-5,
    tie_embeddings=True, layer_pattern="MDMD*DMDMD" * 2, mixers_per_layer=2,
    ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_groups=1, ssm_conv=4,
    ssm_chunk=16, use_rope=False, embed_multiplier=6.0,
    residual_multiplier=0.35, attention_multiplier=0.03125,
    logits_scaling=4.0, max_seq_len=2048,
))

# The gated delta rule with two key heads for four value heads three layers to
# one of gated attention (the gate a vector a head, out of W_q; a (1 + w) norm
# a head on q and k; a quarter of the lanes rotated; groups of 2 over 2 KV
# heads), each followed by a chip's share of the experts (the router scores 16,
# this tree holds 4) beside a shared expert under a sigmoid scalar gate; key and
# value dims that differ, two periods. The toy of the benchmark's
# qwen3-next-80b-a3b-instruct-l12 configuration.
TOY_GDN_MOE = _register(ModelConfig(
    name="toy-gdn-moe", vocab_size=512, dim=128, n_layers=8, n_heads=4,
    n_kv_heads=2, head_dim=32, mlp_hidden=64, n_experts=4,
    experts_per_token=2, router_width=16, shared_mlp_hidden=64,
    shared_expert_gate=True, layer_pattern="LELELE*E" * 2,
    mixers_per_layer=2, lin_key_heads=2, lin_value_heads=4, lin_key_dim=24,
    lin_value_dim=40, lin_conv=4, rms_offset=1.0, qk_norm=True,
    rope_theta=10000000.0, rope_partial=0.25, attn_gate="elementwise",
    max_seq_len=2048,
))

# --- Gemma (HF: google/gemma-{2b,7b}-it) ---
GEMMA_2B = _register(ModelConfig(
    name="gemma-2b-it", vocab_size=256000, dim=2048, n_layers=18, n_heads=8,
    n_kv_heads=1, head_dim=256, mlp_hidden=16384, rms_offset=1.0,
    activation="gelu", tie_embeddings=True, embed_scale=True,
    bos_id=2, eos_ids=(1, 107), pad_id=0, max_seq_len=8192,
))
GEMMA_7B = _register(ModelConfig(
    name="gemma-7b-it", vocab_size=256000, dim=3072, n_layers=28, n_heads=16,
    n_kv_heads=16, head_dim=256, mlp_hidden=24576, rms_offset=1.0,
    activation="gelu", tie_embeddings=True, embed_scale=True,
    bos_id=2, eos_ids=(1, 107), pad_id=0, max_seq_len=8192,
))

# --- Llama 3 (HF: meta-llama/Meta-Llama-3-{8B,70B}-Instruct) ---
LLAMA3_8B = _register(ModelConfig(
    name="llama-3-8b-instruct", vocab_size=128256, dim=4096, n_layers=32,
    n_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=14336,
    rope_theta=500000.0, rms_eps=1e-5,
    bos_id=128000, eos_ids=(128001, 128009), pad_id=128001, max_seq_len=8192,
))
LLAMA3_70B = _register(ModelConfig(
    name="llama-3-70b-instruct", vocab_size=128256, dim=8192, n_layers=80,
    n_heads=64, n_kv_heads=8, head_dim=128, mlp_hidden=28672,
    rope_theta=500000.0, rms_eps=1e-5,
    bos_id=128000, eos_ids=(128001, 128009), pad_id=128001, max_seq_len=8192,
))

# --- Mixtral (HF: mistralai/Mixtral-8x7B-Instruct-v0.1) ---
MIXTRAL_8X7B = _register(ModelConfig(
    name="mixtral-8x7b-instruct", vocab_size=32000, dim=4096, n_layers=32,
    n_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=14336,
    rope_theta=1e6, rms_eps=1e-5, n_experts=8, experts_per_token=2,
    bos_id=1, eos_ids=(2,), pad_id=0, max_seq_len=32768,
))


def get_config(name: str, **overrides) -> ModelConfig:
    try:
        cfg = _CONFIGS[name]
    except KeyError:
        raise KeyError(
            f"Unknown model {name!r}; known: {sorted(_CONFIGS)}"
        ) from None
    return replace(cfg, **overrides) if overrides else cfg


def list_configs() -> Dict[str, ModelConfig]:
    return dict(_CONFIGS)
