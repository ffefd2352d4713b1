"""Architectural configs for the 7 reference model families.

Hyperparameters follow the public model cards of the checkpoints Ollama
serves in the reference experiment (experiment/RunnerConfig.py:80). ``tiny()``
derives a structure-preserving miniature (same head grouping, activation,
norm style) for CPU tests and the virtual-mesh dry run.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


class UnsupportedMechanism(ValueError):
    """A model was asked to run with a mechanism its layers do not
    support yet (a latent cache under ``kv_quantize``, say). ``mechanism``
    names it; raised at load or at session open, never mid-decode. It
    lives beside :class:`ModelConfig` so that every layer that reads a
    config (``engine/*``, ``parallel/*``) can raise it."""

    def __init__(self, mechanism: str, model: str, why: str) -> None:
        super().__init__(f"{model}: {mechanism} is not supported: {why}")
        self.mechanism = mechanism
        self.model = model


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN frequency scaling (DeepSeek-V3's form; ``ops/rope.py`` holds the
    arithmetic): rotary frequencies whose wavelength exceeds
    ``original_max_position / beta_slow`` are divided by ``factor``, those
    below ``original_max_position / beta_fast`` are kept, a linear ramp
    between; ``mscale`` / ``mscale_all_dim`` scale cos and sin by their
    ratio and the attention score by ``m(factor, mscale_all_dim)^2``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


# What the FFN of a layer is (``ModelConfig.ffn_kind``, the one place that
# reads it off the fields).
FFN_DENSE = "dense"  # every layer a dense gated FFN, ``d_ff`` wide
FFN_EXPERTS = "experts"  # every layer routed experts, ``d_ff`` wide (Mixtral)
FFN_EXPERTS_BESIDE_DENSE = "experts-beside-dense"  # dense FFN(s) ``d_ff``
# wide in every layer, the experts ``d_ff_expert`` wide on a shortcut (LongCat)
FFN_DENSE_THEN_EXPERTS = "dense-then-experts"  # the first ``n_dense_layers``
# layers a dense FFN ``d_ff`` wide; every later layer routed experts (+
# ``n_shared_experts`` that every token takes), each ``d_expert`` wide,
# INSTEAD of one (DeepSeek-V3)

# What the MIXER of a layer is (``ModelConfig.mixer_kind``, the one place
# that reads it off ``layer_types``): attention over a cache that grows a
# row a token (latent attention behind a lightning indexer where the model
# has one: ``index_topk``), or a recurrence over a state of fixed size a
# row: Mamba-2 (arXiv:2405.21060, ``models/ssm.py``) or Kimi Delta
# Attention (arXiv:2510.26692, ``models/kda.py``).
MIXER_ATTENTION = "attention"
MIXER_SSM = "ssm"
MIXER_KDA = "kda"
STATE_MIXERS = (MIXER_SSM, MIXER_KDA)
# a configuration file's names for them (``layer_types``)
_LAYER_TYPE_MIXERS = {
    "attention": MIXER_ATTENTION,
    "deepseek_sparse_attention": MIXER_ATTENTION,
    "mamba": MIXER_SSM,
    "linear_attention": MIXER_KDA,
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    activation: str = "silu"  # "silu" (SwiGLU) or "gelu" (GeGLU, gemma)
    gemma_norm: bool = False  # (1 + w) RMSNorm gain + sqrt(d_model) embed scale
    tie_embeddings: bool = False
    qkv_bias: bool = False  # qwen2 uses attention biases
    max_seq_len: int = 8192
    # Mixture-of-experts MLP (0 = dense). With n_experts > 0 the MLP weights
    # gain a leading expert axis and a per-layer router picks top_k_experts
    # per token (Mixtral-style, renormalised top-k softmax weights).
    n_experts: int = 0
    top_k_experts: int = 2
    # The expert layer as ONE CHIP'S SHARE of an expert-parallel
    # deployment: ``n_experts`` counts the routed experts HELD here, router
    # outputs ``first_expert .. first_expert + n_experts`` of a router that
    # is ``router_width`` wide (0 = n_experts: every expert is here). The
    # router's last ``n_zero_experts`` outputs are identity experts (a
    # chosen one adds ``w * h``: no weights, no matmul). A routed expert
    # that is not held adds nothing here. ``renormalize_topk`` False keeps
    # the chosen softmax weights as they are, times
    # ``routed_scaling_factor``; ``router_bias`` adds a per-output bias to
    # the scores the top-k CHOOSES by (not to the weights).
    router_width: int = 0
    n_zero_experts: int = 0
    first_expert: int = 0
    routed_scaling_factor: float = 1.0
    renormalize_topk: bool = True
    router_bias: bool = False
    # ``d_ff_expert`` > 0: the experts are that wide (0: ``d_ff`` wide).
    # Where they sit is ``ffn_kind``'s to say: with ``n_dense_layers`` 0
    # BESIDE the dense FFN(s) of width ``d_ff``, on a shortcut that leaves
    # after the layer's first attention block and joins at the layer's end;
    # with ``n_dense_layers`` > 0 they ARE the FFN of every layer after
    # the leading dense ones.
    d_ff_expert: int = 0
    # leading layers whose FFN is dense (``d_ff`` wide) before the expert
    # layers begin (``first_k_dense_replace``); needs ``n_experts``
    n_dense_layers: int = 0
    # experts every token takes beside its routed ones, fused into one
    # dense gated FFN ``n_shared_experts * d_expert`` wide
    n_shared_experts: int = 0
    # how the router scores its outputs: "softmax" over them all, or an
    # independent "sigmoid" each (DeepSeek-V3)
    router_scoring: str = "softmax"
    # Residual streams (manifold-constrained hyper-connections,
    # arXiv:2512.24880): a token's state is ``[residual_streams, d_model]``
    # and every sublayer reads a mix of the streams and writes back through
    # a Sinkhorn-projected (doubly stochastic) stream-mixing matrix
    # (models/transformer.py ``_hc_map``). 1 = the plain residual
    # ``x + F(norm(x))``. The three constants: Sinkhorn iterations, the
    # epsilon of the map's norm and of each normalisation, and the clamp
    # on the mixing logits.
    residual_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    rope_scaling: Optional[RopeScaling] = None
    # Stand-in weights: ``init_params`` alone reads these (a checkpoint
    # brings its own values). The embedding's standard deviation, and a
    # gain on the routed experts' down-projection (``we_down`` of a
    # dense-then-experts stack): how much of the stream is the token, and
    # how much of an FFN's output hangs on the router's choice.
    init_embed_std: float = 0.02
    init_routed_gain: float = 1.0
    # ... and a third, one configuration's and to go (ROADMAP D6): the final norm's
    # gain (1 by the recipe) scales every logit alike: a gap's size, never the choice
    init_final_norm_gain: float = 1.0
    # attention blocks (each followed by a dense FFN) per scanned layer
    blocks_per_layer: int = 1
    # Latent attention (``attention="latent"``): queries through a
    # ``q_lora_rank`` bottleneck, keys and values from ONE compressed row
    # of ``kv_lora_rank`` values a token plus one shared rope key of
    # ``qk_rope_head_dim``; the cache holds that row and nothing else.
    attention: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # What each layer's mixer is, one entry a layer: "attention" or "mamba"
    # (empty: every layer attention). A "mamba" layer is a Mamba-2 block:
    # ``ssm_n_heads`` heads of ``ssm_d_head`` values over a state of
    # ``ssm_d_state`` values each, ``ssm_n_groups`` groups of B and C, a
    # depthwise causal convolution ``ssm_d_conv`` wide, run over a chunk of
    # tokens in blocks of ``ssm_chunk_size`` (models/ssm.py). It keeps no
    # KV cache: its state is ``state_bytes_per_row`` bytes a row whatever
    # the row's length.
    layer_types: Tuple[str, ...] = ()
    ssm_n_heads: int = 0
    ssm_d_head: int = 0
    ssm_d_state: int = 0
    ssm_n_groups: int = 1
    ssm_d_conv: int = 4
    ssm_chunk_size: int = 256
    # Four scalars (defaults 1: not there): the embedding's gain, the gain
    # on every sublayer's result as it joins the residual stream, what an
    # attention score is multiplied by (0: one over the root of ``d_head``),
    # and what the logits are DIVIDED by.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # "rope": rotary position embedding on queries and keys; "none": no
    # position embedding at all (the recurrent layers carry the order)
    position_embedding: str = "rope"
    # A "linear_attention" layer is a Kimi Delta Attention block
    # (models/kda.py): ``kda_n_heads`` heads whose state is ``kda_d_head``
    # keys by ``kda_d_head`` values, a causal convolution ``kda_d_conv``
    # wide over q, k and v, a per-channel decay held above
    # ``kda_lower_bound`` a token.
    kda_n_heads: int = 0
    kda_d_head: int = 0
    kda_d_conv: int = 4
    kda_lower_bound: float = -5.0
    # The lightning indexer of a "deepseek_sparse_attention" layer (latent
    # attention, ``index_topk`` > 0): ``index_n_heads`` heads of
    # ``index_head_dim`` score blocks of ``index_kpool`` cached positions
    # (their index keys mean-pooled); a query attends over the
    # ``index_topk`` best complete blocks and its own block. The index key
    # rides in the cached latent row after its ``kv_lora_rank +
    # qk_rope_head_dim`` values.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_kpool: int = 1
    # > 0: every gated FFN clamps its gate from above and its up
    # projection both ways at this value before ``act(gate) * up``
    swiglu_limit: float = 0.0

    def __post_init__(self) -> None:
        if isinstance(self.layer_types, list):
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if isinstance(self.rope_scaling, dict):
            # a plain record of the fields (a configuration file's)
            object.__setattr__(self, "rope_scaling", RopeScaling(**self.rope_scaling))
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError(
                f"{self.name}: n_heads {self.n_heads} not divisible by "
                f"n_kv_heads {self.n_kv_heads}"
            )
        if self.d_head % 2 != 0:
            raise ValueError(f"{self.name}: d_head must be even for RoPE")
        if self.attention not in ("mha", "latent"):
            raise ValueError(f"{self.name}: attention {self.attention!r}")
        if self.latent:
            if min(self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                   self.v_head_dim) <= 0 or self.qk_rope_head_dim < (
                self.position_embedding == "rope"
            ):
                raise ValueError(
                    f"{self.name}: latent attention needs q_lora_rank, "
                    "kv_lora_rank, qk_nope_head_dim, v_head_dim and, with "
                    "a rotary position embedding, qk_rope_head_dim"
                )
            if self.qk_rope_head_dim % 2:
                raise ValueError(f"{self.name}: qk_rope_head_dim must be even")
            if self.d_head != self.qk_nope_head_dim + self.qk_rope_head_dim:
                raise ValueError(
                    f"{self.name}: d_head {self.d_head} is the query head, "
                    "qk_nope_head_dim + qk_rope_head_dim"
                )
        if self.blocks_per_layer < 1:
            raise ValueError(f"{self.name}: blocks_per_layer >= 1")
        if self.position_embedding not in ("rope", "none"):
            raise ValueError(
                f"{self.name}: position_embedding {self.position_embedding!r}"
            )
        if self.index_topk:
            if not self.latent or min(
                self.index_n_heads, self.index_head_dim, self.index_kpool
            ) <= 0:
                raise ValueError(
                    f"{self.name}: a lightning indexer sits in latent "
                    "attention and needs index_n_heads, index_head_dim, "
                    "index_kpool"
                )
        if self.layer_types:
            unknown = set(self.layer_types) - set(_LAYER_TYPE_MIXERS)
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"{self.name}: layer_types names each of the {self.n_layers} "
                    f"layers {sorted(_LAYER_TYPE_MIXERS)}"
                )
            attention = "deepseek_sparse_attention" if self.index_topk else "attention"
            kinds = {t for t in self.layer_types if _LAYER_TYPE_MIXERS[t] == MIXER_ATTENTION}
            if kinds - {attention}:
                raise ValueError(
                    f"{self.name}: the attention layers are {attention!r} "
                    f"(index_topk {self.index_topk}), not {sorted(kinds)}"
                )
            mixers = {self.mixer_kind(i) for i in range(self.n_layers)}
            if len(mixers & set(STATE_MIXERS)) > 1:
                raise ValueError(f"{self.name}: one kind of recurrent layer a stack")
            if MIXER_KDA in mixers:
                if min(self.kda_n_heads, self.kda_d_head) <= 0 or self.kda_d_conv < 2 or (
                    self.kda_lower_bound >= 0
                ):
                    raise ValueError(
                        f"{self.name}: a Kimi Delta Attention layer needs "
                        "kda_n_heads, kda_d_head, kda_d_conv >= 2 and a "
                        "negative kda_lower_bound"
                    )
                if self.blocks_per_layer != 1:
                    raise ValueError(
                        f"{self.name}: Kimi Delta Attention layers go with "
                        "one block a layer"
                    )
            if MIXER_SSM in mixers:
                if min(self.ssm_n_heads, self.ssm_d_head, self.ssm_d_state,
                       self.ssm_n_groups, self.ssm_chunk_size) <= 0 or self.ssm_d_conv < 2:
                    raise ValueError(
                        f"{self.name}: a state-space layer needs ssm_n_heads, "
                        "ssm_d_head, ssm_d_state, ssm_n_groups, ssm_d_conv >= 2, "
                        "ssm_chunk_size"
                    )
                if self.ssm_n_heads % self.ssm_n_groups:
                    raise ValueError(
                        f"{self.name}: ssm_n_heads {self.ssm_n_heads} not "
                        f"divisible by ssm_n_groups {self.ssm_n_groups}"
                    )
                if self.latent or self.blocks_per_layer != 1 or (
                    self.residual_streams != 1 or self.n_dense_layers
                ):
                    raise ValueError(
                        f"{self.name}: state-space layers go with plain "
                        "attention layers, one block a layer, one residual "
                        "stream and one kind of FFN"
                    )
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"{self.name}: router_scoring {self.router_scoring!r}")
        if self.residual_streams < 1:
            raise ValueError(f"{self.name}: residual_streams >= 1")
        if self.n_dense_layers:
            if not 0 < self.n_dense_layers < self.n_layers or not self.n_experts:
                raise ValueError(
                    f"{self.name}: n_dense_layers {self.n_dense_layers} leading "
                    f"dense layers need expert layers after them (n_experts, "
                    f"n_layers {self.n_layers})"
                )
            if self.blocks_per_layer != 1:
                raise ValueError(
                    f"{self.name}: leading dense layers go with one attention "
                    "block a layer"
                )
        if self.n_shared_experts and self.ffn_kind != FFN_DENSE_THEN_EXPERTS:
            raise ValueError(
                f"{self.name}: shared experts sit beside the routed experts "
                "of the layers after a dense prefix (n_dense_layers)"
            )
        if self.n_experts:
            if self.first_expert + self.n_experts > self.n_routed_experts:
                raise ValueError(
                    f"{self.name}: experts {self.first_expert}.."
                    f"{self.first_expert + self.n_experts} lie outside the "
                    f"router's {self.n_routed_experts} routed outputs"
                )
            if self.top_k_experts > self.router_outputs:
                raise ValueError(
                    f"{self.name}: top_k_experts {self.top_k_experts} exceeds "
                    f"the router's {self.router_outputs} outputs"
                )
        elif self.n_zero_experts or self.d_ff_expert:
            raise ValueError(f"{self.name}: an expert layer needs n_experts")

    @property
    def latent(self) -> bool:
        return self.attention == "latent"

    @property
    def router_outputs(self) -> int:
        """Outputs of the router: routed experts (held or not), then the
        identity experts."""
        return self.router_width or self.n_experts

    @property
    def n_routed_experts(self) -> int:
        """Routed experts the router knows, wherever they live."""
        return self.router_outputs - self.n_zero_experts

    @property
    def d_expert(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def ffn_kind(self) -> str:
        """What a layer's FFN is: the ONE place that reads it off
        ``n_experts``, ``n_dense_layers`` and ``d_ff_expert``."""
        if not self.n_experts:
            return FFN_DENSE
        if self.n_dense_layers:
            return FFN_DENSE_THEN_EXPERTS
        if self.d_ff_expert:
            return FFN_EXPERTS_BESIDE_DENSE
        return FFN_EXPERTS

    @property
    def n_expert_layers(self) -> int:
        """Layers with an expert layer in them (leading dense ones have none)."""
        return self.n_layers - self.n_dense_layers if self.n_experts else 0

    @property
    def n_dense_ffn_layers(self) -> int:
        """Layers with a dense FFN in them: every layer, the leading ones,
        or none (the experts are every layer's FFN)."""
        kind = self.ffn_kind
        if kind == FFN_EXPERTS:
            return 0
        return self.n_dense_layers if kind == FFN_DENSE_THEN_EXPERTS else self.n_layers

    def mixer_kind(self, layer: int) -> str:
        """What layer ``layer``'s mixer is: the ONE place that reads it
        off ``layer_types``."""
        if not self.layer_types:
            return MIXER_ATTENTION
        return _LAYER_TYPE_MIXERS[self.layer_types[layer]]

    def kind_index(self, layer: int) -> int:
        """Layers of ``layer``'s own mixer kind before it: its entry in
        the leaves, the cache or the state that only its kind has."""
        kind = self.mixer_kind(layer)
        return sum(1 for i in range(layer) if self.mixer_kind(i) == kind)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state and no cache."""
        return sum(
            1 for i in range(len(self.layer_types))
            if self.mixer_kind(i) in STATE_MIXERS
        )

    @property
    def state_mixer(self) -> Optional[str]:
        """The stack's one kind of recurrent layer, or None."""
        kinds = {self.mixer_kind(i) for i in range(len(self.layer_types))}
        return next((k for k in STATE_MIXERS if k in kinds), None)

    def dense_ffn_at(self, layer: int) -> bool:
        """Whether layer ``layer``'s FFN is dense and nothing else."""
        kind = self.ffn_kind
        return kind == FFN_DENSE or (
            kind == FFN_DENSE_THEN_EXPERTS and layer < self.n_dense_layers
        )

    @property
    def attention_layers(self) -> int:
        return self.n_layers - self.state_layers

    @property
    def layer_runs(self) -> Tuple[Tuple[bool, int, int], ...]:
        """The stack as runs of layers of one (mixer, FFN) kind, in order:
        ``(dense, first, count)``, ``dense`` saying that the run's FFN is
        dense and nothing else; the run's mixer is ``mixer_kind(first)``.
        One run for every model but a dense prefix before expert layers,
        which has two, and a stack of several mixers, which has a run
        wherever the mixer changes (``run_blocks`` scans each)."""
        if self.state_layers:

            def kind(i):
                return self.mixer_kind(i), self.dense_ffn_at(i)

            runs, first = [], 0
            for i in range(1, self.n_layers + 1):
                if i == self.n_layers or kind(i) != kind(first):
                    runs.append((self.dense_ffn_at(first), first, i - first))
                    first = i
            return tuple(runs)
        if self.ffn_kind != FFN_DENSE_THEN_EXPERTS:
            return ((self.ffn_kind == FFN_DENSE, 0, self.n_layers),)
        k = self.n_dense_layers
        return ((True, 0, k), (False, k, self.n_layers - k))

    @property
    def experts_held(self) -> int:
        """Experts whose weights are stored here: routed + shared."""
        return self.n_experts + self.n_shared_experts

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_head_dim if self.latent else self.d_head

    # -- the cache, as every allocation and byte count sizes it -----------------
    @property
    def cache_layers(self) -> int:
        """Leading axis of the stacked cache: one entry per attention block
        (a state-space layer has none)."""
        return self.attention_layers * self.blocks_per_layer

    # -- the recurrent state, as every allocation and byte count sizes it ------
    @property
    def state_dims(self) -> Tuple[int, int, int]:
        """``(heads, rows, columns)`` of one layer's state ``s`` a row:
        Mamba-2's ``[H, P, N]``, Kimi Delta Attention's ``[H, keys,
        values]``."""
        if self.state_mixer == MIXER_KDA:
            return self.kda_n_heads, self.kda_d_head, self.kda_d_head
        return self.ssm_n_heads, self.ssm_d_head, self.ssm_d_state

    @property
    def state_conv(self) -> Tuple[int, int]:
        """``(taps, channels)`` of the recurrent layers' causal convolution."""
        if self.state_mixer == MIXER_KDA:
            return self.kda_d_conv, 3 * self.kda_d_inner
        return self.ssm_d_conv, self.ssm_conv_width

    @property
    def kda_d_inner(self) -> int:
        return self.kda_n_heads * self.kda_d_head

    @property
    def kda_in_width(self) -> int:
        """Outputs of a KDA layer's one input projection: ``q | k | v``,
        ``beta`` a head, then the decay gate's and the output gate's
        first (rank ``kda_d_head``) stages."""
        return 3 * self.kda_d_inner + self.kda_n_heads + 2 * self.kda_d_head

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_n_heads * self.ssm_d_head

    @property
    def ssm_conv_width(self) -> int:
        """Channels of the convolution: ``x`` and one B and C a group."""
        return self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_d_state

    @property
    def ssm_in_width(self) -> int:
        """Outputs of the input projection: ``z | xBC | dt``."""
        return self.ssm_d_inner + self.ssm_conv_width + self.ssm_n_heads

    def state_bytes_per_row(self, conv_itemsize: int = 2) -> int:
        """Bytes of ONE row's recurrent state over the state-space layers,
        whatever the row's length: ``S [heads, d_head, d_state]`` float32
        and the convolution's tail, ``ssm_d_conv - 1`` inputs in the
        engine's dtype."""
        h, p, n = self.state_dims
        taps, channels = self.state_conv
        per_layer = h * p * n * 4 + (taps - 1) * channels * conv_itemsize
        return self.state_layers * per_layer

    @property
    def state_matmul_params(self) -> int:
        """Matmul weights of one recurrent mixer."""
        if self.state_mixer == MIXER_KDA:
            return (
                self.d_model * self.kda_in_width
                + 2 * self.kda_d_head * self.kda_d_inner
                + self.kda_d_inner * self.d_model
            )
        return self.ssm_matmul_params

    @property
    def state_out_channels(self) -> int:
        """Output channels of one recurrent mixer's matmuls (one quantization
        scale each)."""
        if self.state_mixer == MIXER_KDA:
            return self.kda_in_width + 2 * self.kda_d_inner + self.d_model
        return self.ssm_in_width + self.d_model

    @property
    def state_small_params(self) -> int:
        """A recurrent mixer's parameters outside its matmuls: Mamba-2's
        (``ssm_small_params``), or KDA's convolution, ``A_log`` a head,
        ``dt_bias`` a channel and the output norm's gain."""
        if self.state_mixer == MIXER_KDA:
            k, c = self.state_conv
            return k * c + self.kda_n_heads + self.kda_d_inner + self.kda_d_head
        return self.ssm_small_params

    @property
    def ssm_matmul_params(self) -> int:
        """Matmul weights of one state-space mixer: in and out projections."""
        return self.d_model * self.ssm_in_width + self.ssm_d_inner * self.d_model

    @property
    def ssm_small_params(self) -> int:
        """A state-space mixer's parameters outside its matmuls: the
        convolution and its bias, ``A_log``, ``D``, ``dt_bias`` a head, the
        gated norm's gain."""
        return (
            (self.ssm_d_conv + 1) * self.ssm_conv_width
            + 3 * self.ssm_n_heads
            + self.ssm_d_inner
        )

    @property
    def cache_heads(self) -> int:
        return 1 if self.latent else self.n_kv_heads

    @property
    def latent_row_width(self) -> int:
        """Values of a latent row that attention reads: the compressed row
        and the shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_k_width(self) -> int:
        """Values of the K leaf's row (latent: the whole compressed row, and
        the index key after it where the model has an indexer)."""
        return (
            self.latent_row_width + (self.index_head_dim if self.index_topk else 0)
            if self.latent
            else self.d_head
        )

    @property
    def index_matmul_params(self) -> int:
        """The lightning indexer's matmul weights, one layer: its queries
        off the query bottleneck, its one key and its head weights off the
        layer's input."""
        if not self.index_topk:
            return 0
        di = self.index_head_dim
        return self.q_lora_rank * self.index_n_heads * di + self.d_model * (
            di + self.index_n_heads
        )

    @property
    def cache_v_width(self) -> int:
        """Values of the V leaf's row: 0 for a latent cache, whose values
        are the first ``kv_lora_rank`` columns of the K leaf's row."""
        return 0 if self.latent else self.d_head

    @property
    def kv_values_per_token(self) -> int:
        """Cached values per token and attention block (576 for the latent
        row; ``2 * n_kv_heads * d_head`` for K and V heads)."""
        return self.cache_heads * (self.cache_k_width + self.cache_v_width)

    def layer_matmul_params(
        self, experts: float, dense: bool = False, mixer: str = MIXER_ATTENTION
    ) -> int:
        """Matmul weights of ONE layer, with ``experts`` experts counted
        (held: what is stored; expected active: what a token uses; shared
        experts count among them). ``dense`` asks for a leading dense layer
        of a model that has them (``n_dense_layers``), ``mixer`` for a
        layer of that mixer."""
        d = self.d_model
        if mixer in STATE_MIXERS:
            attn = self.state_matmul_params
        elif self.latent:
            attn = (
                d * self.q_lora_rank
                + self.q_lora_rank * self.n_heads * self.d_head
                + d * self.latent_row_width
                + self.kv_lora_rank
                * self.n_heads
                * (self.qk_nope_head_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * d
                + self.index_matmul_params
            )
        else:
            attn = (
                d * self.n_heads * self.d_head
                + 2 * d * self.n_kv_heads * self.d_head
                + self.n_heads * self.d_head * d
            )
        kind = self.ffn_kind
        ffn = 3 * d * self.d_ff
        moe = 3 * d * self.d_expert * experts + d * self.router_outputs
        if kind == FFN_DENSE or dense:
            return int(self.blocks_per_layer * (attn + ffn))
        if kind == FFN_EXPERTS_BESIDE_DENSE:
            return int(self.blocks_per_layer * (attn + ffn) + moe)
        return int(self.blocks_per_layer * attn + moe)

    def stack_matmul_params(self, experts: float) -> int:
        """Matmul weights of ALL layers: each kind's count times its layers."""
        if self.state_layers:
            return sum(
                self.layer_matmul_params(
                    experts, self.n_dense_layers > 0 and self.dense_ffn_at(i),
                    self.mixer_kind(i),
                )
                for i in range(self.n_layers)
            )
        return (
            self.n_dense_layers * self.layer_matmul_params(experts, dense=True)
            + (self.n_layers - self.n_dense_layers)
            * self.layer_matmul_params(experts)
        )

    @property
    def hc_maps(self) -> int:
        """Residual-stream maps: one a sublayer (attention, FFN) of every
        block of every layer; none with one stream."""
        if self.residual_streams == 1:
            return 0
        return 2 * self.blocks_per_layer * self.n_layers

    @property
    def hc_map_outputs(self) -> int:
        """Outputs of a map: ``n`` read weights, ``n`` write weights, the
        ``n x n`` mixing logits."""
        n = self.residual_streams
        return 2 * n + n * n

    @property
    def hc_matmul_params(self) -> int:
        """The maps' one matmul each: ``phi``, all streams against
        ``hc_map_outputs`` columns."""
        return self.hc_maps * self.residual_streams * self.d_model * self.hc_map_outputs

    @property
    def hc_params(self) -> int:
        """float32 parameters of the maps: ``phi``, three scalars and a
        bias an output."""
        return self.hc_matmul_params + self.hc_maps * (3 + self.hc_map_outputs)

    @property
    def active_experts_per_token(self) -> float:
        """Experts HELD HERE that a token is expected to use: ``top_k`` of
        the router's outputs, evenly, of which ``n_experts`` are here (all
        of top_k when every output is a held expert), and every shared one."""
        if not self.n_experts:
            return 0.0
        return (
            self.top_k_experts * self.n_experts / self.router_outputs
            + self.n_shared_experts
        )

    @property
    def params_count(self) -> int:
        """Approximate parameter count (embeddings + blocks + norms),
        with the experts held here."""
        embed = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        norms = 2 * self.d_model * self.blocks_per_layer * self.n_layers
        body = self.stack_matmul_params(self.experts_held)
        small = self.state_layers * self.state_small_params
        return embed + body + norms + small + self.hc_params + self.d_model

    def flops_per_token(self, context_len: int) -> float:
        """Approx. forward FLOPs for one decoded token at the given context:
        2 per matmul weight the token uses (the expected share of the held
        experts) + attention over the context (QK^T and PV each
        2*T*Hq*width multiply-adds; the latent form scores over the
        compressed row and sums its first ``kv_lora_rank`` columns; a
        state-space layer's recurrence costs the same at every context: 6
        a state value, decay, input and read)."""
        logits = self.d_model * self.vocab_size
        dense = 2 * (
            self.stack_matmul_params(self.active_experts_per_token)
            + logits
            + self.hc_matmul_params
        )
        if self.latent:
            per_ctx = 2 * self.n_heads * (self.latent_row_width + self.kv_lora_rank)
        else:
            per_ctx = 4 * self.n_heads * self.d_head
        attended = context_len
        index = 0.0
        if self.index_topk:
            # the indexer scores every block of the context (2 a key value
            # and head), attention reads the kept blocks and the tail
            attended = min(context_len, self.index_topk * self.index_kpool)
            index = (
                self.cache_layers * 2 * self.index_n_heads * self.index_head_dim
                * context_len / self.index_kpool
            )
        attn = self.cache_layers * attended * per_ctx + index
        h, p, n = self.state_dims
        # a state value a token: Mamba-2 decays, adds and reads it (6);
        # KDA decays it, reads it against k, adds k u and reads it against q (7)
        per_value = 7 if self.state_mixer == MIXER_KDA else 6
        ssm = per_value * self.state_layers * h * p * n
        return float(dense + attn + ssm)

    def tiny(self, vocab_size: int = 512, max_seq_len: int = 256) -> "ModelConfig":
        """Structure-preserving miniature for hermetic tests."""
        group = self.n_heads // self.n_kv_heads
        n_kv = max(1, min(2, self.n_kv_heads))
        return dataclasses.replace(
            self,
            name=f"{self.name}-tiny",
            vocab_size=vocab_size,
            d_model=64,
            n_layers=2,
            n_heads=n_kv * group if n_kv * group <= 8 else 4,
            n_kv_heads=n_kv if n_kv * group <= 8 else 2,
            d_head=16,
            d_ff=128,
            max_seq_len=max_seq_len,
        )


# The 7 Ollama models of the reference sweep (experiment/RunnerConfig.py:80),
# mapped to the checkpoints Ollama serves for those tags.
MODEL_REGISTRY: Dict[str, ModelConfig] = {
    cfg.name: cfg
    for cfg in [
        ModelConfig(
            name="qwen2:1.5b",  # Qwen2-1.5B-Instruct
            vocab_size=151_936,
            d_model=1536,
            n_layers=28,
            n_heads=12,
            n_kv_heads=2,
            d_head=128,
            d_ff=8960,
            rope_theta=1e6,
            qkv_bias=True,
            tie_embeddings=True,
        ),
        ModelConfig(
            name="gemma:2b",  # Gemma-2B-it
            vocab_size=256_000,
            d_model=2048,
            n_layers=18,
            n_heads=8,
            n_kv_heads=1,
            d_head=256,
            d_ff=16_384,
            activation="gelu",
            gemma_norm=True,
            tie_embeddings=True,
        ),
        ModelConfig(
            name="phi3:3.8b",  # Phi-3-mini-4k-instruct
            vocab_size=32_064,
            d_model=3072,
            n_layers=32,
            n_heads=32,
            n_kv_heads=32,
            d_head=96,
            d_ff=8192,
        ),
        ModelConfig(
            name="gemma:7b",  # Gemma-7B-it
            vocab_size=256_000,
            d_model=3072,
            n_layers=28,
            n_heads=16,
            n_kv_heads=16,
            d_head=256,
            d_ff=24_576,
            activation="gelu",
            gemma_norm=True,
            tie_embeddings=True,
        ),
        ModelConfig(
            name="qwen2:7b",  # Qwen2-7B-Instruct
            vocab_size=152_064,
            d_model=3584,
            n_layers=28,
            n_heads=28,
            n_kv_heads=4,
            d_head=128,
            d_ff=18_944,
            rope_theta=1e6,
            qkv_bias=True,
        ),
        ModelConfig(
            name="mistral:7b",  # Mistral-7B-Instruct-v0.3
            vocab_size=32_768,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_head=128,
            d_ff=14_336,
            rope_theta=1e6,
        ),
        ModelConfig(
            name="llama3.1:8b",  # Llama-3.1-8B-Instruct
            vocab_size=128_256,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_head=128,
            d_ff=14_336,
            rope_theta=5e5,
        ),
        # Beyond the reference's 7-model sweep: the MoE family Ollama also
        # serves, exercising the expert-parallel (ep) sharding path.
        ModelConfig(
            name="mixtral:8x7b",  # Mixtral-8x7B-Instruct-v0.1
            vocab_size=32_000,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_head=128,
            d_ff=14_336,
            rope_theta=1e6,
            n_experts=8,
            top_k_experts=2,
        ),
    ]
}


def get_model_config(name: str) -> ModelConfig:
    if name not in MODEL_REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(MODEL_REGISTRY)}"
        )
    return MODEL_REGISTRY[name]
