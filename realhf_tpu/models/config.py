"""Transformer architecture configuration.

Field-level parity with reference ``realhf/api/core/model_api.py:144``
(ReaLModelConfig): one config class describes every supported family
(llama/qwen2/mistral/gpt2/gemma/mixtral/olmoe, actor or critic). The critic
variant replaces the LM head with a scalar value head (`is_critic`).
"""

import collections
import dataclasses
from typing import Dict, Optional, Tuple

#: what a layer of a patterned model is made of: "attention" sees
#: every earlier token of its document, "window" the last
#: ``sliding_window`` of them, "latent" every earlier token through
#: keys and values expanded from ONE compressed row a token
#: (``LatentConfig``); "delta" is no attention: it has no K/V rows, a
#: head keeps ONE state that every earlier token of the document went
#: into (``DeltaConfig``); "sparse" is attention over the keys a learned
#: indexer picks for the token among the earlier ones of its document,
#: ``topk`` of them (``IndexerConfig``); "ssm" is no attention either:
#: the Mamba-2 state-space scan, a head's state [head_dim, state] under
#: ONE decay a head (``SsmConfig``). A layer is ``x + mixer(norm(x))``
#: then ``x + ff(norm(x))``; where a model's layers are a mixer OR a
#: feed-forward alone, the part a layer lacks is ``ABSENT`` (the layer
#: then has one norm, one part and one residual add)
ABSENT = "none"
OPERATORS = ("conv", "attention", "window", "latent", "delta", "sparse",
             "ssm", ABSENT)
ATTENTION_OPERATORS = ("attention", "window", "latent", "sparse")
FEED_FORWARDS = ("dense", "moe", ABSENT)
#: an operator's letter in ``TransformerConfig.pattern_string``
OPERATOR_LETTERS = {"conv": "c", "attention": "a", "window": "w",
                    "latent": "l", "delta": "d", "sparse": "s",
                    "ssm": "m", ABSENT: "-"}


@dataclasses.dataclass
class RotaryConfig:
    """A rotary embedding: the ONE description a table is built from
    (``models/transformer.py:rotary_table`` hands it to
    ``ops/rotary.py:rotary_freqs``). A model has one, its ``rotary_*``
    fields (``TransformerConfig.rotary_of`` puts them into this form),
    or one a kind of attention layer
    (``TransformerConfig.rotary_by_operator``)."""
    base: float = 10000.0
    # rotate the first ``partial_factor x head_dim`` values of a head,
    # pass the rest through
    partial_factor: float = 1.0
    # None: plain frequencies ``base^(-2j/r)``. "linear": positions
    # divided by ``factor``. "dynamic": NTK, the base grown where a
    # sequence passes ``original_max_positions``. "yarn": ``ops/
    # rotary.py:yarn_inv_freq`` over the r rotated values, cos and sin
    # times ``attention_factor``
    scaling_type: Optional[str] = None
    factor: Optional[float] = None
    # the context the frequencies were trained at ("dynamic", "yarn")
    original_max_positions: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    # pairs (2j, 2j+1) rotate together, not (j, j + r/2)
    interleaved: bool = False

    def __post_init__(self):
        if self.scaling_type not in (None, "linear", "dynamic", "yarn"):
            raise NotImplementedError(
                f"rotary scaling type {self.scaling_type!r}")
        if self.scaling_type is not None and self.factor is None:
            raise ValueError(
                f"rotary scaling type {self.scaling_type!r} needs its "
                "factor (rotary_scaling)")
        if self.scaling_type in ("dynamic", "yarn") \
                and self.original_max_positions is None:
            raise ValueError(
                f"rotary scaling type {self.scaling_type!r} needs "
                "original_max_positions (n_positions)")

    def rotated(self, head_dim: int) -> int:
        """r: how many values of a head are rotated."""
        return int(head_dim * self.partial_factor)

    def describe(self) -> str:
        """``yarn64@500000/0.5``, ``plain@50000/0.333333/interleaved``:
        for a span's attribute."""
        kind = "plain" if self.scaling_type is None \
            else f"{self.scaling_type}{self.factor:g}"
        return f"{kind}@{self.base:g}/{self.partial_factor:g}" \
            + ("/interleaved" if self.interleaved else "")


#: what a latent layer's norm of the compressed row norms at: the
#: published module builds it without an epsilon of its own, so this,
#: its class's default, and not the model's ``rms_norm_eps``
LATENT_NORM_EPS = 1e-6
#: what a delta layer's l2 norm of q and k adds to the sum of squares:
#: the published config has no key for it either
DELTA_L2_EPS = 1e-6


@dataclasses.dataclass
class LatentConfig:
    """Latent attention (operator "latent" of a layer pattern): keys
    and values come from ONE compressed row a token. With u the
    layer's normed input, ``hd = TransformerConfig.head_dim`` the
    query/key's width and ``nope = hd - rope_dim``::

        q = u wq                      [T, heads, hd]
        a = u w_kv_a                  [T, kv_rank + rope_dim]
        c = RMSNorm(a[:, :kv_rank]; kv_a_norm) at LATENT_NORM_EPS
        c w_kv_b                      [T, heads, nope + v_dim]
            -> k_nope [.., :nope], v [.., nope:]
        k = [k_nope, rotary(a[:, kv_rank:]) to EVERY head]
        q = [q[.., :nope], rotary(q[.., nope:])]

    scores over ``hd`` at ``hd ** -0.5``, values and the heads'
    outputs ``v_dim`` wide, ``wo`` [heads x v_dim, H]. The rotary
    embedding (``rotary_of("latent")``) is over the LAST ``rope_dim``
    values of a query head and over one key part all heads share.
    Every head has keys of its own (``n_kv_heads == n_q_heads``)."""
    kv_rank: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass
class DeltaConfig:
    """The gated delta rule with a decay a key channel (operator
    "delta" of a layer pattern; Kimi Delta Attention). With u the
    layer's normed input, ``n`` heads of ``head_dim`` (key and value
    alike), ``conv`` a depthwise causal convolution of ``conv_kernel``
    taps that stops at a document's first token::

        q~, k~, v = SiLU(conv(u wq)), SiLU(conv(u wk)), SiLU(conv(u wv))
        q = q~ / |q~|_2 * head_dim^-0.5,  k = k~ / |k~|_2      a head
                       (DELTA_L2_EPS beside the sum of squares)
        g = -exp(a_log[head]) * softplus((u w_fa) w_fb + dt_bias)
        beta = sigmoid(u w_b)                              [n] a token
        S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T
        o = S_t^T q             (``ops/delta_rule.py``, chunked)
        y = (RMSNorm(o; o_norm) * sigmoid((u w_ga) w_gb)) wo

    The state S [head_dim, head_dim] a head is 0 before a document's
    first token and is what decoding keeps, beside the last
    ``conv_kernel - 1`` rows of the three convolutions' inputs."""
    n_heads: int
    head_dim: int
    conv_kernel: int = 4

    @property
    def width(self) -> int:
        """All heads' keys (or values) side by side."""
        return self.n_heads * self.head_dim

    @property
    def gate_rank(self) -> int:
        """The width (u w_fa) and (u w_ga) pass through: a head's, as
        published (the config has no key for it)."""
        return self.head_dim


#: what the indexer's LayerNorm of its key norms at (its class's
#: default: the published config has no key for it)
INDEX_NORM_EPS = 1e-6


@dataclasses.dataclass
class IndexerConfig:
    """The learned indexer of a "sparse" layer (DeepSeek sparse
    attention): which keys a token's attention runs over. With u the
    layer's normed input, ``heads`` index heads of ``head_dim`` over ONE
    index key a token, ``rot`` the layer's rotary embedding over the
    whole ``head_dim``-wide head::

        qI = rot(u wq)                        [T, heads, head_dim]
        kI = rot(LayerNorm(u wk; k_norm, k_norm_bias))   [T, head_dim]
        w  = (u w_weights) * heads^-0.5 * head_dim^-0.5  [T, heads]
        I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])
        S_t = the ``topk`` visible s (same document, s <= t) of largest
              I[t, s], ties to the lower s; every visible s where there
              are no more than ``topk``

    Attention (grouped-query, every head of a token over the same
    ``S_t``) then runs over ``S_t`` alone. The selection is discrete: no
    gradient of the language-model loss reaches the indexer's leaves
    (``models/operators.py:_index_select`` stops it by name), and
    decoding keeps ``kI`` a token a layer (``cache["index_k"]``)."""
    heads: int
    head_dim: int
    topk: int


@dataclasses.dataclass
class SsmConfig:
    """The Mamba-2 state-space mixer (operator "ssm" of a layer
    pattern). With u the layer's normed input, ``n`` heads of
    ``head_dim`` (``width = n x head_dim``), ``g`` groups that share B
    and C (head h reads group ``h // (n / g)``), ``conv`` a depthwise
    causal convolution of ``conv_kernel`` taps WITH a bias that stops at
    a document's first token::

        [z | xBC | dt] = u w_in       widths width | width + 2 g state | n
        xBC = SiLU(conv(xBC) + conv_bias)
        x [n, head_dim], B [g, state], C [g, state] = split(xBC)
        Delta = softplus(dt + dt_bias)                     [n] a token
        S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T   A = -exp(a_log)
        y_t = S_t C_t + D x_t           (``ops/ssm_scan.py``, chunked)
        out = (GroupRMSNorm(y * SiLU(z); norm)) w_out

    the gate FIRST, then each of the g groups of ``width / g`` values by
    its own root mean square at the model's epsilon. The state S
    [head_dim, state] a head is 0 before a document's first token and
    is what decoding keeps (float32), beside the last ``conv_kernel -
    1`` rows of the convolution's input."""
    n_heads: int
    head_dim: int
    state: int
    n_groups: int
    conv_kernel: int = 4

    def __post_init__(self):
        if self.n_heads % self.n_groups or self.width % self.n_groups:
            raise ValueError(
                f"ssm: {self.n_heads} heads do not divide into "
                f"{self.n_groups} groups")

    @property
    def width(self) -> int:
        """All heads' values side by side (``d_inner``)."""
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: x, B and C side by side."""
        return self.width + 2 * self.n_groups * self.state

    @property
    def in_dim(self) -> int:
        """``w_in``'s columns: z, xBC and dt."""
        return self.width + self.conv_dim + self.n_heads


@dataclasses.dataclass
class MoEConfig:
    """Mixture-of-experts settings (reference ``ReaLMoEConfig``)."""
    num_experts: int = 8
    top_k: int = 2
    routing_type: str = "aux_loss"  # aux_loss | sinkhorn | none
    # Divide the top-k softmax gates by their sum (Mixtral). False
    # takes them as the softmax over ALL experts gave them, so a
    # token's gates sum to less than 1 (OLMoE, ``norm_topk_prob``).
    norm_topk_prob: bool = True
    # How the router scores an expert. "softmax": over all experts.
    # "sigmoid": each expert by itself, in float32; the k are chosen
    # by score + ``expert_bias`` (a leaf no gradient reaches, where
    # ``use_expert_bias``), the gates are the scores themselves,
    # divided by (their sum + 1e-6) under ``norm_topk_prob`` and
    # multiplied by ``routed_scaling_factor`` (LFM2-MoE).
    score_fn: str = "softmax"
    # What the router's product reads. "ffn_input": what the experts
    # read, the normed residual after the layer's operator.
    # "layer_input": the layer's INPUT, before its first norm and its
    # operator (SmallThinker's pre-attention router: ``z = x W_r``,
    # so that an expert's weights can be fetched while attention
    # runs); the experts still read the normed residual after it.
    router_input: str = "ffn_input"
    use_expert_bias: bool = False
    routed_scaling_factor: float = 1.0
    # what the sigmoid router adds to the k scores' sum before it
    # divides by it (LFM2 1e-6, Laguna 1e-20)
    norm_topk_eps: float = 1e-6
    # Width of the SHARED expert: a dense gated feed-forward beside the
    # routed ones that every token visits, added with weight 1, outside
    # the sort and the grouped products (leaves ``mlp["shared"]``).
    # Every rank of an expert-parallel deployment holds it whole. None:
    # there is none.
    shared_intermediate_dim: Optional[int] = None
    # Width of one expert where the model's dense feed-forward layers
    # have another (``TransformerConfig.intermediate_dim`` is theirs).
    intermediate_dim: Optional[int] = None
    # One expert-parallel rank's share: (first, count). The layer
    # holds the weights of experts first .. first + count - 1 only,
    # still routes over all ``num_experts`` (router and bias keep
    # that width) and computes the part of the result its own experts
    # give; pairs routed elsewhere are left out, none of its own is
    # dropped. None: every expert is held (the uncut model, the same
    # code). There is no exchange: see ``ops/moe.py``.
    experts_held: Optional[Tuple[int, int]] = None
    aux_loss_coeff: float = 1e-3
    z_loss_coeff: float = 0.0
    input_jitter_eps: Optional[float] = None
    capacity_factor: Optional[float] = None
    use_grouped_gemm: bool = True
    # Real expert parallelism (exceeds the reference, whose dispatcher
    # says "Currently does not support expert parallel",
    # token_dispatcher.py:26-27): shard the expert (E) dim of the
    # stacked expert weights over the "data" mesh axis. The GShard
    # dispatch einsums then become all-to-alls inserted by GSPMD:
    # tokens sharded by data are exchanged for experts sharded by
    # data. Requires num_experts % data_parallel_size == 0 and the
    # capacity or dense dispatch mode (ragged grouped GEMMs cannot
    # shard the group dim).
    expert_parallel: bool = False

    def __post_init__(self):
        if self.score_fn not in ("softmax", "sigmoid"):
            raise NotImplementedError(f"score_fn={self.score_fn!r}")
        if self.router_input not in ("ffn_input", "layer_input"):
            raise NotImplementedError(
                f"router_input={self.router_input!r}")
        if self.experts_held is not None:
            first, count = self.experts_held
            self.experts_held = (int(first), int(count))
            if not (0 <= first and count >= 1
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} is no range of "
                    f"the {self.num_experts} experts")

    @property
    def n_held(self) -> int:
        """Experts whose weights the layer holds."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held[1])


@dataclasses.dataclass
class TransformerConfig:
    """Architecture of one decoder-only transformer.

    Mirrors `ReaLModelConfig` (reference model_api.py:144-294) field by
    field; TPU-specific additions at the bottom control dtypes and
    rematerialization.
    """

    n_layers: int
    n_kv_heads: int
    n_q_heads: int
    hidden_dim: int
    intermediate_dim: int
    vocab_size: int
    head_dim: Optional[int] = None
    n_positions: Optional[int] = None
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    attn_pdrop: float = 0.0
    layer_norm_epsilon: float = 1e-5
    activation_function: str = "gelu"  # gelu | gelu_new | silu | relu | relu2
    scale_attn_by_inverse_layer_idx: bool = False
    scale_attn_weights: bool = True
    use_attention_bias: bool = True
    use_attn_proj_bias: bool = True
    use_mlp_bias: bool = True
    layer_norm_type: Optional[str] = None  # None (LayerNorm) | "rms" | "gemma"
    mlp_type: Optional[str] = None  # None (plain 2-mat MLP) | "llama" | "moe"
    # rotary embedding
    apply_rotary: bool = False
    rotary_base: float = 10000.0
    rotary_interleaved: bool = False
    rotary_scaling: Optional[float] = None
    rotary_scaling_type: Optional[str] = None  # "linear" | "dynamic"
    # gemma
    normalize_embed: bool = False
    # opt-style absolute position embedding offset
    abs_position_embedding_offset: int = 0
    do_layernorm_before: bool = True
    tied_embedding: bool = False
    sliding_window: Optional[int] = None
    # RMSNorm of the query and key projections, each with a scale of
    # its own. "full": over the WHOLE projected width (all heads
    # together), before the split into heads and before the rotary
    # embedding (OLMoE).
    # "head": over each HEAD's values, one scale of width head_dim
    # shared by the heads, before the rotary embedding (LFM2).
    qk_norm: Optional[str] = None
    moe: Optional[MoEConfig] = None
    # A model whose layers are not all of one kind declares them: one
    # (operator, feed-forward) a layer, operator "conv" (the gated
    # short convolution of ``conv_kernel`` taps) or "attention",
    # feed-forward "dense" (``mlp_type`` at ``intermediate_dim``) or
    # "moe" (``moe``). Its parameters are a tree a layer under
    # ``params["layers"]`` and the layer loop is unrolled
    # (``models/transformer.py``). None: ``n_layers`` of the one block
    # that ``mlp_type`` describes, stacked under ``params["blocks"]``.
    layer_pattern: Optional[Tuple[Tuple[str, str], ...]] = None
    conv_kernel: int = 3
    # What a patterned model's attention layers may differ in. Query
    # heads a layer (an entry for EVERY layer, a conv layer's ignored;
    # all share ``n_kv_heads`` and ``head_dim``, so the K/V stack keeps
    # one shape). None: ``n_q_heads`` everywhere.
    layer_q_heads: Optional[Tuple[int, ...]] = None
    # The rotary embedding by kind of layer: {"attention": ...,
    # "window": ...}; None for a kind says that its layers have NONE
    # ("latent" and "attention" layers: queries and keys go to the
    # scores as they are). None: the model-wide ``rotary_*`` fields.
    rotary_by_operator: Optional[Dict[str, Optional[RotaryConfig]]] = None
    # One output gate a head: ``g = sigmoid(u W_g)`` [.., n heads] from
    # the layer's normed input, multiplied into each head's attention
    # output before ``wo`` (leaf ``attn["w_gate"]`` [H, heads]).
    attn_output_gate: bool = False
    # What the "latent" layers of the pattern are made of; ``head_dim``
    # is then their query/key's width and ``v_head_dim`` their value's.
    latent: Optional[LatentConfig] = None
    # What the "delta" layers of the pattern are made of.
    delta: Optional[DeltaConfig] = None
    # The indexer of the pattern's "sparse" layers.
    indexer: Optional[IndexerConfig] = None
    # What the "ssm" layers of the pattern are made of.
    ssm: Optional[SsmConfig] = None
    # A LOOPED model: the whole stack of ``n_layers`` runs ``n_passes``
    # times over ONE set of weights. With x^0 the embedding rows, pass
    # t = 1..T runs every layer on x^(t-1) and then the final norm,
    # ``x^t = norm(h; ln_f)``: the final norm is INSIDE the loop, and
    # its output is what the next pass starts from and what the head
    # (and the exit gate) of pass t read. Every pass has keys and
    # values of its own (its input differs), so the caches hold
    # ``kv_layers = n_passes x`` the attention layers, pass t (from 0)
    # layer l at index ``t x n_layers + l``. Inference, generation and
    # every loss but the looped objective below read pass T alone.
    n_passes: int = 1
    # A norm AFTER each operator as well, inside the residual's add:
    # ``a = h + norm(attn(norm(h; ln1)); ln1_post)``, ``h = a +
    # norm(mlp(norm(a; ln2)); ln2_post)`` (leaves ``ln1_post`` and
    # ``ln2_post`` beside ``ln1`` and ``ln2``).
    post_norm: bool = False
    # The exit gate of a looped model: ``lambda_t = sigmoid(x^t . w +
    # b)`` a token a pass (leaves ``params["exit_gate"]``: ``w``
    # [H, 1], ``b`` [1]). A token leaves at pass t with probability
    # ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last pass
    # taking what is left (``ops/functional.py:exit_log_distribution``),
    # and supervised training weighs the passes' losses by it less
    # ``exit_entropy_coeff`` times its entropy (``interfaces/sft.py``).
    # Leaving the loop early (a threshold under 1 on the cumulative
    # p) is NOT implemented: every token runs every pass (ROADMAP).
    exit_gate: bool = False
    exit_entropy_coeff: float = 0.05
    is_critic: bool = False

    # --- TPU-native additions -----------------------------------------
    # Numerics: params kept in param_dtype; matmuls run in compute_dtype
    # (bf16 feeds the MXU); softmax/normalization accumulate in fp32.
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Rematerialize each block in backward (jax.checkpoint over the
    # layer scan) -- the reference's gradient_checkpointing flag.
    gradient_checkpointing: bool = False
    # jax.checkpoint_policies name used when gradient_checkpointing is
    # on. "nothing_saveable" = full recompute (min memory);
    # "dots_with_no_batch_dims_saveable" keeps matmul outputs (more
    # HBM, measurably faster when the model fits). WHATEVER the
    # policy, a block also keeps the flash kernel's two residuals, its
    # output and log-sum-exp (2 * head_dim + 4 bytes a (token, head);
    # models/transformer.py:_remat): cheaper at every shape the
    # kernels take than running flash_fwd again in the backward. The
    # XLA attention path keeps nothing more.
    remat_policy: str = "nothing_saveable"
    # Pipeline-parallel remat granularity when gradient_checkpointing:
    # "tick" rematerializes each whole stage-slab evaluation, making
    # resident pipeline activations depth-independent (the 1F1B-class
    # memory profile; reference TrainSchedule static_schedule.py:319);
    # "block" keeps the per-block checkpoint of the non-pipeline path.
    pipeline_remat: str = "tick"

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_dim // self.n_q_heads
        assert self.n_q_heads % self.n_kv_heads == 0, \
            (self.n_q_heads, self.n_kv_heads)
        if self.mlp_type == "moe":
            assert self.moe is not None
        if self.qk_norm not in (None, "full", "head"):
            raise NotImplementedError(f"qk_norm={self.qk_norm!r}")
        if self.layer_pattern is not None:
            self.layer_pattern = tuple(
                (str(op), str(ff)) for op, ff in self.layer_pattern)
            if len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern names {len(self.layer_pattern)} "
                    f"layers, n_layers is {self.n_layers}")
            for op, ff in self.layer_pattern:
                if op not in OPERATORS or ff not in FEED_FORWARDS \
                        or op == ff == ABSENT:
                    raise NotImplementedError(
                        f"layer ({op!r}, {ff!r}) of layer_pattern")
            if self.mlp_type == "moe":
                raise ValueError(
                    "with a layer_pattern mlp_type describes the DENSE "
                    "feed-forward; the pattern says which layers are "
                    "sparse")
            if self.n_moe_layers and self.moe is None:
                raise ValueError("layer_pattern has moe layers, moe is "
                                 "None")
            if self.sliding_window is None and any(
                    op == "window" for op, _ in self.layer_pattern):
                raise ValueError("layer_pattern has window layers, "
                                 "sliding_window is None")
            # (an ungated feed-forward, ``mlp_type`` None, has no bias)
            if not (self.layer_norm_type == "rms"
                    and (self.gated_mlp or not self.use_mlp_bias)
                    and self.apply_rotary
                    and not self.use_attention_bias
                    and not self.use_attn_proj_bias
                    and not self.scale_attn_by_inverse_layer_idx):
                raise NotImplementedError(
                    "a layer_pattern model is RMSNorm, rotary, without "
                    "biases or per-layer attention scale")
        if self.layer_pattern is None and (
                self.layer_q_heads is not None
                or self.rotary_by_operator is not None
                or self.attn_output_gate or self.latent is not None
                or self.delta is not None or self.indexer is not None
                or self.ssm is not None):
            raise NotImplementedError(
                "layer_q_heads, rotary_by_operator, attn_output_gate, "
                "latent, delta, indexer and ssm belong to a model with "
                "a layer_pattern")
        if self.moe is not None \
                and self.moe.router_input == "layer_input" \
                and self.layer_pattern is None:
            raise NotImplementedError(
                "a router that reads the layer's input (MoEConfig."
                "router_input='layer_input') belongs to a model with a "
                "layer_pattern: the pipeline's stages and the slot "
                "engine's step (engine/inflight.py) hand a "
                "feed-forward its own input alone")
        if self.n_passes < 1:
            raise ValueError(f"n_passes={self.n_passes}")
        if (self.n_passes > 1 or self.post_norm or self.exit_gate) and (
                self.layer_pattern is not None
                or self.layer_norm_type != "rms"
                or self.scale_attn_by_inverse_layer_idx
                or self.mlp_type == "moe"):
            raise NotImplementedError(
                "n_passes, post_norm and exit_gate belong to a model "
                "of one dense block with RMSNorm (no layer_pattern, no "
                "experts, no per-layer attention scale)")
        if self.activation_function == "relu2" and self.gated_mlp:
            raise NotImplementedError(
                "relu2 is an UNGATED feed-forward's activation "
                "(mlp_type None)")
        # an operator's own config and its layers come together
        for op, name in (("ssm", "ssm"), ("sparse", "indexer"),
                         ("delta", "delta"), ("latent", "latent")):
            made_of, n = getattr(self, name), len(self.layers_of(op))
            if (made_of is not None) != bool(n):
                raise ValueError(f"layer_pattern has {n} {op} layers, "
                                 f"{name} is {made_of}")
        if self.indexer is not None and (
                self.indexer.head_dim % 2 or self.indexer.topk < 1
                or self.rotary_by_operator is not None
                or self.scale_attn_by_inverse_layer_idx):
            raise NotImplementedError(
                "a sparse layer's indexer has an even head_dim, a topk "
                "of at least 1 and the model-wide rotary embedding")
        if self.latent is not None:
            if self.layers_of("latent") \
                    != self.layers_of(*ATTENTION_OPERATORS):
                raise NotImplementedError(
                    "latent layers beside attention or window layers: "
                    "the K/V stack keeps ONE shape (layer_pattern "
                    f"'{self.pattern_string}')")
            if (self.n_kv_heads != self.n_q_heads
                    or self.rotary_by_operator is None
                    or self.layer_q_heads is not None
                    or self.qk_norm is not None or self.attn_output_gate
                    or not 0 < self.latent.rope_dim < self.head_dim
                    or self.latent.rope_dim % 2
                    or "latent" not in self.rotary_by_operator):
                raise NotImplementedError(
                    "a latent layer has a key a query head, its rotary "
                    "embedding under rotary_by_operator['latent'], no "
                    "query/key norm or output gate, and an even "
                    "rope_dim under head_dim")
        if self.layer_q_heads is not None:
            self.layer_q_heads = tuple(int(n) for n in self.layer_q_heads)
            if len(self.layer_q_heads) != self.n_layers or any(
                    n % self.n_kv_heads for n in self.layer_q_heads):
                raise ValueError(
                    f"layer_q_heads={self.layer_q_heads}: one multiple "
                    f"of n_kv_heads={self.n_kv_heads} for each of the "
                    f"{self.n_layers} layers")
        if self.rotary_by_operator is not None:
            missing = {op for op, _ in self.layer_pattern
                       if op in ATTENTION_OPERATORS} \
                - set(self.rotary_by_operator)
            missing |= {op for op, rc in self.rotary_by_operator.items()
                        if rc is None and op not in ("latent",
                                                     "attention")}
            if missing or self.rotary_interleaved:
                raise ValueError(
                    f"rotary_by_operator lacks {sorted(missing)} (and "
                    "says the convention a kind of layer, RotaryConfig."
                    "interleaved, not model-wide rotary_interleaved)")
        else:
            self.rotary_of("attention")  # a RotaryConfig checks itself

    @property
    def uses_absolute_position(self) -> bool:
        return not self.apply_rotary

    @property
    def gated_mlp(self) -> bool:
        return self.mlp_type in ("llama", "moe")

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(operator, feed-forward) of every layer, patterned or not."""
        if self.layer_pattern is not None:
            return self.layer_pattern
        return (("attention", "moe" if self.mlp_type == "moe"
                 else "dense"),) * self.n_layers

    @property
    def n_moe_layers(self) -> int:
        return sum(ff == "moe" for _, ff in self.layer_kinds)

    def layers_of(self, *ops: str) -> Tuple[int, ...]:
        """The layers whose operator is one of ``ops``, in order
        (``layers_of(*ATTENTION_OPERATORS)``: those that have keys and
        values, full, window, latent and sparse attention alike)."""
        return tuple(i for i, (op, _) in enumerate(self.layer_kinds)
                     if op in ops)

    @property
    def kv_layers(self) -> int:
        """How many layers' worth of keys and values a cache holds:
        the attention layers, once a pass of a looped model (pass t
        from 0, attention layer l of n at index ``t x n + l``)."""
        return self.n_passes * len(self.layers_of(*ATTENTION_OPERATORS))

    @property
    def v_head_dim(self) -> int:
        """A value head's width, which is also an attention output
        head's: ``head_dim`` unless the layers are latent."""
        return self.head_dim if self.latent is None else self.latent.v_dim

    def layer_window(self, i: int) -> Optional[int]:
        """The window of layer ``i``'s attention, None where it sees
        its whole document. A model of one block has ONE value,
        ``sliding_window``, for every layer; a patterned model says it
        a layer ("window" against "attention")."""
        if self.layer_pattern is None:
            return self.sliding_window
        return self.sliding_window \
            if self.layer_pattern[i][0] == "window" else None

    def rotary_of(self, op: str) -> Optional[RotaryConfig]:
        """The rotary embedding of an ``op`` layer ("attention",
        "window" or "latent"): its kind's where the model declares one
        a kind (None: the kind's layers have none), else the
        model-wide ``rotary_*`` fields as a RotaryConfig."""
        if self.rotary_by_operator is not None:
            return self.rotary_by_operator[op]
        return RotaryConfig(
            base=self.rotary_base, scaling_type=self.rotary_scaling_type,
            factor=self.rotary_scaling,
            original_max_positions=self.n_positions,
            interleaved=self.rotary_interleaved)

    def rotated_dim(self, op: str) -> int:
        """How many values of a head an ``op`` layer rotates: a latent
        layer its ``rope_dim`` (the LAST of a query head), another the
        first ``partial_factor x head_dim``."""
        if op == "latent":
            return self.latent.rope_dim
        return self.rotary_of(op).rotated(self.head_dim)

    def q_heads(self, i: int) -> int:
        """Query heads of layer ``i``."""
        return self.n_q_heads if self.layer_q_heads is None \
            else self.layer_q_heads[i]

    @property
    def pattern_string(self) -> str:
        """``c a c c c``, ``a w w w a``, ``l l l``, ``d d d l d``,
        ``s s s``, ``- m - m - m a``: every layer's operator by its
        letter (``OPERATOR_LETTERS``: conv, attention, window, latent,
        delta, sparse, ssm as ``m``; ``-`` a layer that is a
        feed-forward alone)."""
        return " ".join(OPERATOR_LETTERS[op] for op, _ in self.layer_kinds)

    def require_one_block(self, what: str):
        """Refuse, by name, what only runs a model of one kind of
        block (a stack under ``params["blocks"]``)."""
        if self.layer_pattern is not None:
            kinds = collections.Counter(
                op for op, _ in self.layer_pattern if op != ABSENT)
            raise NotImplementedError(
                f"{what} is not implemented for a model with a layer "
                f"pattern (layer_pattern '{self.pattern_string}': "
                + "".join(f"{n} {op} layers, " for op, n in kinds.items())
                + f"{self.n_moe_layers} layers with experts)")
