"""Transformer architecture configuration.

Field-level parity with reference ``realhf/api/core/model_api.py:144``
(ReaLModelConfig): one config class describes every supported family
(llama/qwen2/mistral/gpt2/gemma/mixtral/olmoe, actor or critic). The critic
variant replaces the LM head with a scalar value head (`is_critic`).
"""

import dataclasses
from typing import Optional, Tuple

#: what a layer of a patterned model is made of
OPERATORS = ("conv", "attention")
FEED_FORWARDS = ("dense", "moe")


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
    use_expert_bias: bool = False
    routed_scaling_factor: float = 1.0
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
    activation_function: str = "gelu"  # gelu | gelu_new | silu
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
    # HBM, measurably faster when the model fits).
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
                if op not in OPERATORS or ff not in FEED_FORWARDS:
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
            if not (self.layer_norm_type == "rms" and self.gated_mlp
                    and self.apply_rotary
                    and not self.use_attention_bias
                    and not self.use_attn_proj_bias
                    and not self.scale_attn_by_inverse_layer_idx):
                raise NotImplementedError(
                    "a layer_pattern model is RMSNorm, rotary, gated "
                    "feed-forward, without biases or per-layer "
                    "attention scale")
        if self.rotary_scaling_type is not None:
            if self.rotary_scaling is None:
                raise ValueError(
                    "rotary_scaling must be set when rotary_scaling_type is.")
            if self.rotary_scaling_type == "dynamic" and self.n_positions is None:
                raise ValueError(
                    "dynamic NTK rotary scaling requires n_positions.")

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

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        """The layers that have keys and values, in order."""
        return tuple(i for i, (op, _) in enumerate(self.layer_kinds)
                     if op == "attention")

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, (op, _) in enumerate(self.layer_kinds)
                     if op == "conv")

    @property
    def pattern_string(self) -> str:
        """``c a c c c``: every layer's operator by its first letter."""
        return " ".join(op[0] for op, _ in self.layer_kinds)

    def require_one_block(self, what: str):
        """Refuse, by name, what only runs a model of one kind of
        block (a stack under ``params["blocks"]``)."""
        if self.layer_pattern is not None:
            raise NotImplementedError(
                f"{what} is not implemented for a model with a layer "
                f"pattern (layer_pattern '{self.pattern_string}': "
                f"{len(self.conv_layers)} conv and "
                f"{len(self.attention_layers)} attention layers, "
                f"{self.n_moe_layers} of them sparse)")

    def n_params(self) -> int:
        """Approximate parameter count (for FLOPs/memory estimates),
        layer by layer of the pattern: every matrix, the convolutions'
        taps, the router (and its selection bias) over all experts,
        the experts HELD, and the query/key norms; biases and the
        layer norms' scales are left out."""
        h, f, v = self.hidden_dim, self.intermediate_dim, self.vocab_size
        attn = h * (self.n_q_heads + 2 * self.n_kv_heads) * self.head_dim \
            + self.n_q_heads * self.head_dim * h
        if self.qk_norm == "full":
            attn += (self.n_q_heads + self.n_kv_heads) * self.head_dim
        elif self.qk_norm == "head":
            attn += 2 * self.head_dim
        conv = 4 * h * h + self.conv_kernel * h
        dense = (3 if self.gated_mlp else 2) * h * f
        moe = 0
        if self.moe is not None:
            # the experts HELD, the router (and bias) over all of them
            moe = 3 * h * (self.moe.intermediate_dim or f) \
                * self.moe.n_held + h * self.moe.num_experts
            if self.moe.use_expert_bias:
                moe += self.moe.num_experts
        embed = v * h if self.tied_embedding else 2 * v * h
        if self.is_critic:
            embed = v * h + h
        return embed + sum(
            (attn if op == "attention" else conv)
            + (moe if ff == "moe" else dense)
            for op, ff in self.layer_kinds)
