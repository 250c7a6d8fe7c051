"""The per-model execution engine: train / inference / generate.

TPU-native replacement for the reference's `PipelinableEngine` ABC
(``realhf/api/core/model_api.py:305-463``) and its implementations
(``backend/inference.py:21``, ``backend/megatron.py:702``,
``backend/pipe_runner.py:779``): one class wraps a sharded parameter
pytree on the model's mesh and exposes

  - ``train_batch(microbatches, loss_fn)``: jitted value_and_grad with
    gradient accumulation over a scanned microbatch stack, global-norm
    clipping, optax update (AdamW + schedule). Grad accumulation over
    a scan replaces Megatron's DDP no_sync loop (megatron.py:726-797);
    mixed precision is bf16 compute over fp32 master params, so the
    loss-scaler machinery disappears.
  - ``forward(fn_name, ...)``: jitted inference helpers (logprobs,
    values, scores, hidden).
  - ``generate(...)``: the jitted KV-cache decode loop.

All methods consume/produce device arrays in [S, L] stream layout;
the algorithm interfaces do SequenceSample <-> stream packing.
"""

import collections
import dataclasses
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from realhf_tpu.base import logging
from realhf_tpu.base.backend import pallas_enabled as _pallas_enabled
from realhf_tpu.engine import generation as gen_mod
from realhf_tpu.engine.optim import OptimizerConfig, make_optimizer
from realhf_tpu.models import operators
from realhf_tpu.models import sharding as shard_rules
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import ATTENTION_OPERATORS, TransformerConfig
from realhf_tpu.obs import metrics, parts, tracing
from realhf_tpu.ops import decode_attention as decode_ops
from realhf_tpu.ops import functional as F
from realhf_tpu.ops import moe as moe_ops
from realhf_tpu.ops.attention import flash_takes
from realhf_tpu.ops.decode_attention import (
    mesh_nontrivial as _mesh_nontrivial,
)
from realhf_tpu.ops.delta_rule import scan_handed, scan_kernel_calls
from realhf_tpu.ops.ssm_scan import scan_kernel_calls as ssm_kernel_calls
from realhf_tpu.ops.flash_attention import (block_counts, flash_fwd_per_bwd,
                                            flash_mask_calls, row_streams)
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel.mesh import MeshContext
from realhf_tpu.parallel.realloc import offload_to_host, tree_bytes

logger = logging.getLogger("engine")

# (params, hidden [B, L, H] after the final norm, microbatch)
#   -> (loss, statistics): a head and an objective (Engine._objective)
LossFn = Callable[[Any, jnp.ndarray, Dict[str, jnp.ndarray]],
                  Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]


def _abstract(x):
    """Shape, dtype and (for committed arrays) sharding of a jit
    argument: what ``lower`` needs, holding no buffer."""
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


#: memory kinds of gauge ``engine_program_bytes`` <- the fields of
#: the compiler's count (``obs/parts.py:MEMORY_FIELDS``)
_MEMORY_KINDS = dict(arguments="argument_size_in_bytes",
                     temporaries="temp_size_in_bytes",
                     outputs="output_size_in_bytes",
                     aliased="alias_size_in_bytes")
#: spans an engine remembers whose program's text it has not read
_UNREAD_SPANS = 1024
#: the live engines: ``tracing.stop()`` asks each for its programs
_ENGINES = weakref.WeakSet()


def _programs_of_capture(spans, profiled: bool) -> Dict[str, Dict]:
    """The ONE provider ``tracing.stop()`` calls, after the profile
    has stopped: fingerprint -> ``ProgramFacts`` as plain dicts of
    every program that ran under an ``engine:*`` span of ``spans``."""
    out = {}
    for engine in list(_ENGINES):
        out.update(engine._capture_programs(spans, profiled))
    return out


tracing.set_program_provider(_programs_of_capture)


class Engine:

    def __init__(self,
                 cfg: TransformerConfig,
                 ctx: MeshContext,
                 params: Any,
                 optimizer: Optional[OptimizerConfig] = None,
                 total_train_steps: Optional[int] = None):
        self.cfg = cfg
        self.ctx = ctx
        self.mesh = ctx.mesh
        self.version = 0
        # (an operator's record, how many of the layers are its)
        self._operators = tuple(operators.used(cfg))
        # Multi-controller operation: when the mesh spans >1 OS process
        # (one jax.distributed world across hosts, reference NCCL world
        # global_comm.py:44), every member process runs the SAME engine
        # calls. Host inputs must then be global arrays (replicated;
        # each process already holds the full batch) and array outputs
        # are jitted back to replicated so every member can read them.
        self._mesh_procs = sorted(
            {d.process_index for d in self.mesh.devices.flat})
        self._multiproc = len(self._mesh_procs) > 1
        # (read `engine.multiproc` from outside; collective-count
        # decisions in the runtime key on it)
        if self._multiproc:
            import jax as _jax
            mine = _jax.process_index()
            if mine not in self._mesh_procs:
                raise ValueError(
                    f"Engine mesh spans processes {self._mesh_procs} "
                    f"but this engine was built on process {mine}; "
                    "only group members may host the model.")

        # Pipeline parallelism: blocks layer-sharded over "pipe".
        # Training runs the schedule ParallelismConfig.pipeline_schedule
        # picks -- 1F1B by default (parallel/schedule.py: explicit
        # instruction streams, custom-VJP backward, bounded residuals)
        # with GPipe (parallel/pipeline.py) as the selectable fallback;
        # inference-only forwards always use the GPipe rotation (see
        # _forward).
        if ctx.pp_size > 1:
            cfg.require_one_block(
                "pipeline parallelism (parallel/pipeline.py, "
                "schedule.py)")
            from realhf_tpu.parallel.pipeline import PipelineContext
            from realhf_tpu.parallel.schedule import default_microbatches
            if cfg.n_layers % ctx.pp_size != 0:
                raise ValueError(
                    f"n_layers={cfg.n_layers} not divisible by "
                    f"pipeline_parallel_size={ctx.pp_size}")
            if ctx.parallel.context_parallel_size > 1:
                raise NotImplementedError(
                    "pipeline parallelism cannot be combined with "
                    "context parallelism (ring attention) yet; use "
                    "pp x tp x dp or cp x tp x dp.")
            sched = getattr(ctx.parallel, "pipeline_schedule", "") \
                or "1f1b"
            n_mb = ctx.parallel.pipeline_microbatches \
                or default_microbatches(ctx.pp_size, sched)
            self._pipeline_ctx = PipelineContext(
                mesh=self.mesh, n_stages=ctx.pp_size,
                n_microbatches=n_mb, schedule=sched)
        else:
            self._pipeline_ctx = None

        # Expert parallelism: expert weights E-sharded over the data
        # axis; this constraint turns dispatch/combine into all-to-alls
        # (models/sharding.py moe_ep_constraint). Validated BEFORE the
        # device_put below so invalid configs fail instantly with a
        # clear message instead of after a full-model transfer.
        self._moe_constraint = shard_rules.moe_ep_constraint(cfg, self.mesh)
        if self._moe_constraint is not None:
            if moe_ops.dispatch_mode(cfg) == "ragged":
                raise ValueError(
                    "MoEConfig.expert_parallel requires the capacity "
                    "or dense dispatch mode (set capacity_factor, or "
                    "use_grouped_gemm=False); ragged grouped GEMMs "
                    "cannot shard the expert group dim.")
            if cfg.moe.num_experts % ctx.dp_size != 0:
                raise ValueError(
                    f"expert_parallel needs num_experts "
                    f"({cfg.moe.num_experts}) divisible by "
                    f"data_parallel_size ({ctx.dp_size}).")
        elif moe_ops.dispatch_mode(cfg) == "ragged" and self.mesh.size > 1:
            # the experts' grouped products are Pallas kernels where
            # the stacks lie whole on the device; over a mesh a bare
            # pallas_call would gather them, so they stay
            # lax.ragged_dot, which GSPMD partitions (ops/moe.py)
            self._moe_constraint = moe_ops.SHARDED_STACKS

        self._param_shardings = shard_rules.param_shardings(cfg, self.mesh)
        # Megatron-style vocab padding so wte/head shard over tp even
        # when vocab_size is not a tp multiple (re-padded if the source
        # carried another tp's padding).
        params = shard_rules.normalize_vocab_padding(cfg, params,
                                                     ctx.tp_size)
        params = self._cast_param_dtype(params)
        with tracing.span("setup:model:shard",
                          layout=str(ctx.parallel)) as sp:
            self.params = sp.result(
                jax.device_put(params, self._param_shardings))
            if sp is not tracing.NOOP_SPAN:
                sp.set_attribute("bytes", tree_bytes(self.params))
        self._constrain = shard_rules.activation_constraint(
            self.mesh, ctx.parallel.sequence_parallel)
        # Context parallelism: attention becomes a ring over the "ctx"
        # mesh axis; the rest of the model shards L via GSPMD.
        # whether packed rows go to the flash kernel (their length
        # decides the rest, call by call): what flash_kv_blocks_total
        # counts. The ring and the pipeline's XLA path do not.
        self._flash_rows = False
        if ctx.parallel.context_parallel_size > 1:
            for rec, n in self._operators:
                if n and rec.no_context_parallel:
                    raise NotImplementedError(rec.no_context_parallel.format(
                        pattern=cfg.pattern_string))
            from realhf_tpu.ops.ring_attention import ring_attention
            mesh = self.mesh

            def _ring(q, k, v, seg, causal=True, scale=None,
                      sliding_window=None):
                return ring_attention(q, k, v, seg, mesh, "ctx",
                                      causal=causal, scale=scale,
                                      sliding_window=sliding_window)

            self._attention_fn = _ring
        elif _pallas_enabled() and _mesh_nontrivial(self.mesh):
            if ctx.pp_size > 1:
                # Inside the pipe-manual shard_map a bare pallas_call
                # would force per-stage gathers; use the XLA path,
                # which GSPMD partitions natively.
                from realhf_tpu.ops.attention import packed_attention_xla

                def _xla_attn(q, k, v, seg, causal=True, scale=None,
                              sliding_window=None):
                    return packed_attention_xla(
                        q, k, v, seg, causal=causal, scale=scale,
                        sliding_window=sliding_window)

                self._attention_fn = _xla_attn
            else:
                # Partition the Pallas flash kernel over dp x tp: a
                # bare pallas_call has no GSPMD rule and would gather
                # full Q/K/V per device
                # (ops/attention.make_sharded_attention).
                from realhf_tpu.ops.attention import (
                    make_sharded_attention,
                )
                self._attention_fn = make_sharded_attention(self.mesh)
                self._flash_rows = True
        else:
            self._attention_fn = None
            self._flash_rows = _pallas_enabled()

        # what this model's programs run, on every engine:* span: the
        # dispatch a sparse model takes (also the label of
        # moe_routed_pairs_total), a patterned model's layers
        mode = moe_ops.dispatch_mode(cfg)
        self._model_attrs: Dict[str, Any] = {} if mode is None else dict(
            moe_dispatch=mode, experts=cfg.moe.num_experts,
            top_k=cfg.moe.top_k)
        if cfg.layer_pattern is not None:
            self._model_attrs.update(
                layer_pattern=cfg.pattern_string,
                dense_layers=cfg.n_layers - cfg.n_moe_layers)
            # what each operator says of its layers (its record's)
            for rec, n in self._operators:
                if rec.attrs is not None and (n or rec.always):
                    self._model_attrs.update(rec.attrs(cfg, n))
            if mode is not None:
                self._model_attrs.update(
                    experts_held=cfg.moe.n_held,
                    router=cfg.moe.score_fn + (
                        "_bias" if cfg.moe.use_expert_bias else ""))
                if cfg.moe.shared_intermediate_dim is not None:
                    self._model_attrs.update(
                        shared_expert=cfg.moe.shared_intermediate_dim)
                if cfg.moe.router_input != "ffn_input":
                    self._model_attrs.update(
                        router_input=cfg.moe.router_input)
            if mode is not None and not cfg.gated_mlp:
                self._model_attrs.update(
                    expert_ff=f"{cfg.activation_function}/ungated")
            if cfg.layer_q_heads is not None:
                self._model_attrs.update(q_heads=" ".join(
                    str(cfg.q_heads(i))
                    for i in cfg.layers_of(*ATTENTION_OPERATORS)))
            if cfg.rotary_by_operator is not None:
                nope = [op for op, rc in cfg.rotary_by_operator.items()
                        if rc is None]
                if nope:  # layers that rotate nothing
                    self._model_attrs.update(
                        nope_layers=len(cfg.layers_of(*nope)))
                self._model_attrs.update(rotary=" ".join(
                    f"{op[0]}:{'none' if rc is None else rc.describe()}"
                    for op, rc in sorted(
                        cfg.rotary_by_operator.items())))
        if cfg.n_passes > 1 or cfg.exit_gate:
            self._model_attrs.update(
                passes=cfg.n_passes, kv_layers=cfg.kv_layers,
                post_norm=cfg.post_norm, exit_gate=cfg.exit_gate)
        if mode == "dense" and cfg.moe.num_experts > 4 \
                and cfg.moe.experts_held is None:
            logger.warning(
                "MoE model running in dense dispatch (capacity_factor "
                "unset, grouped GEMM disabled): every expert processes "
                "every token -- %dx the FLOPs of top-%d routing. Set "
                "MoEConfig.use_grouped_gemm=True (ragged_dot) or "
                "capacity_factor (e.g. 1.25).",
                cfg.moe.num_experts // cfg.moe.top_k, cfg.moe.top_k)

        self.optimizer_config = optimizer
        if (optimizer is not None and optimizer.offload
                and self._multiproc):
            raise ValueError(
                "OptimizerConfig.offload has only run on "
                "single-process meshes; disable offload or use a "
                "single-process group for this role.")
        if optimizer is not None and optimizer.type != "empty":
            # Mixed precision: non-fp32 params train against an fp32
            # master copy held INSIDE the optimizer state (reference
            # Megatron bf16 + fp32 master, megatron.py:823-940).
            master = jnp.dtype(cfg.param_dtype) != jnp.dtype(jnp.float32)
            self._tx = make_optimizer(optimizer, total_train_steps,
                                      master_weights=master)
            # ZeRO-1: Adam moments (and the fp32 master copy) shard
            # over the DATA axis on top of the params' tp/pp specs
            # (reference Megatron DistributedOptimizer,
            # backend/megatron.py:823-940; DeepSpeed ZeRO-1,
            # deepspeed.py:445). GSPMD inserts the reduce-scatter /
            # all-gather pair around the update.
            zero1 = getattr(optimizer, "zero1", True)
            with tracing.span("setup:model:optimizer", zero1=zero1) as sp:
                state_shape = jax.eval_shape(self._tx.init, self.params)
                self._opt_shardings = shard_rules.opt_state_shardings(
                    state_shape, cfg, self.mesh, zero1=zero1)
                self.opt_state = sp.result(jax.jit(
                    self._tx.init,
                    out_shardings=self._opt_shardings)(self.params))
            # ZeRO-2-flavored grad accumulation: the fp32 grad
            # accumulator shards over DP too, turning the DP grad
            # all-reduce into a reduce-scatter (Megatron
            # DistributedOptimizer grad-buffer layout).
            if zero1:
                self._grad_shardings = jax.tree.map(
                    lambda sh, p: jax.sharding.NamedSharding(
                        self.mesh, shard_rules.zero1_moment_spec(
                            sh.spec, p.shape,
                            self.mesh.shape.get("data", 1))),
                    self._param_shardings, self.params)
            else:
                self._grad_shardings = None
        else:
            self._tx = None
            self.opt_state = None
            self._opt_shardings = None
            self._grad_shardings = None

        # engine_compiles_total / engine_compile_secs_total from here on
        metrics.watch_compiles()
        self._train_step_cache: Dict[Any, Callable] = {}
        self._generate_cache: Dict[Any, Callable] = {}
        # (program name, what tells its compilations apart: loss key
        # or sampling options, batch shape) -> what its compiled text
        # says of itself, read once; see program_facts
        self._facts: Dict[Any, parts.ProgramFacts] = {}
        # span id -> (program name, key, call) of this engine's
        # engine:* spans whose program's text had not been read when
        # they ended: tracing.stop() of a profiled capture reads it
        self._unread: Dict[str, tuple] = collections.OrderedDict()
        # program name -> (jitted fn, abstract args, static kwargs) of
        # its last call; see compiled_text
        self._last_call: Dict[str, tuple] = {}
        self._last_key: Dict[str, Any] = {}
        _ENGINES.add(self)
        # Generation view on pp/ctx meshes (decode_engine): a second
        # inference-only Engine on a collapsed dp x tp mesh over the
        # SAME devices; weights reshard into it when they change.
        self._decode_view: Optional["Engine"] = None
        self._decode_view_src: Any = None
        self._jit_forward_hidden = None
        self._gather_jit = None
        self._jit_logprobs = None
        self._jit_values = None

    # ------------------------------------------------------------------
    # Compiled-program introspection
    # ------------------------------------------------------------------
    def _run(self, name: str, key, fn: Callable, attrs: Dict[str, Any],
             *args, **static):
        """Call one of this engine's jitted programs, remembering its
        abstract signature for :meth:`compiled_text`. ``attrs``
        (:meth:`_count_batch`) go on its ``engine:*`` span, beside
        ``program`` (the XLA module's name, as a device trace prints
        it) and, once the program's text has been read,
        ``program_fingerprint``: span, device operation and
        :meth:`program_facts` join on them. ``key``: what tells the
        compilations under ``name`` apart (:meth:`_program_facts`)."""
        self._last_call[name] = (fn, jax.tree.map(_abstract, args),
                                 static)
        self._last_key[name] = key
        if not tracing.enabled():
            self._last_span = tracing.NOOP_SPAN
            return fn(*args, **static)
        # engine:<name> holds the dispatch and, in a synced stretch,
        # the wait for the outputs
        with tracing.span(f"engine:{name}", **self._model_attrs,
                          program=f"jit_{fn.__name__}", **attrs) as sp:
            self._last_span = sp
            lowered = fn._cache_size()
            out = sp.result(fn(*args, **static))
            sp.set_attribute("compiled", fn._cache_size() > lowered)
            facts = self._facts.get((name, key))
            if facts is not None:
                sp.set_attribute("program_fingerprint", facts.fingerprint)
            else:
                self._unread[sp.span_id] = (name, key,
                                            self._last_call[name])
                if len(self._unread) > _UNREAD_SPANS:
                    self._unread.popitem(last=False)
            return out

    def _count_batch(self, seg_ids, decode_tokens: int = 0
                     ) -> Dict[str, Any]:
        """What the program about to run does with its batch, counted
        on the host from the segment ids: the counters, and what of
        them its ``engine:*`` span carries (:meth:`_run`)."""
        self._count_routed_pairs(seg_ids, decode_tokens)
        return {**self._count_rows(seg_ids),
                **self._count_flash_blocks(seg_ids)}

    def _count_rows(self, seg_ids) -> Dict[str, float]:
        """The counters an operator's record keeps of its own from the
        packed rows of the program about to run (``Operator.count``: a
        sparse layer's selected pairs and scoring blocks), and what of
        them the span carries. Nothing for a model without such layers
        or a batch on the device already."""
        attrs = {}
        if isinstance(seg_ids, np.ndarray):
            for rec, n in self._operators:
                if n and rec.count is not None:
                    attrs.update(rec.count(self.cfg, n, seg_ids,
                                           str(self.ctx.model_name.role)))
        return attrs

    def _count_flash_blocks(self, seg_ids) -> Dict[str, float]:
        """``flash_kv_blocks_total{role,kind}``: the (query block, key
        block) pairs the flash forward kernel visits over these packed
        rows (``visited``), the pairs under the rows' causal diagonals
        (``causal``) and those of the visited that no edge crosses, for
        which the kernels build no mask (``unmasked``), one head's,
        added up over the attention layers, EACH by its own rule
        (``ops.flash_attention.block_counts``): ``visited`` and
        ``unmasked`` under the layer's window where it has one,
        ``causal`` without, so that a stack of window and full layers
        adds up right. The span's ``flash_block_share`` is visited
        over causal, its ``flash_unmasked_share`` unmasked over
        visited. Nothing where the rows do not go to the kernel, or
        are on the device already."""
        cfg = self.cfg
        if not (self._flash_rows and isinstance(seg_ids, np.ndarray)
                and flash_takes(seg_ids.shape[-1], cfg.head_dim)
                and not cfg.scale_attn_by_inverse_layer_idx):
            return {}
        layers_of = collections.Counter(
            cfg.layer_window(i)
            for i in cfg.layers_of(*ATTENTION_OPERATORS))
        counts = dict(visited=0, causal=0, unmasked=0)
        for window, n in layers_of.items():
            for kind, pairs in zip(counts, block_counts(
                    seg_ids, sliding_window=window)):
                counts[kind] += n * pairs
        role = str(self.ctx.model_name.role)
        for kind, n in counts.items():
            metrics.inc("flash_kv_blocks_total", n, role=role, kind=kind)
        # rows past what the kernels hold whole go to those that stream
        # K and V by block (flash_*_stream): the same ranges, fetched
        stream_rows = int(np.prod(seg_ids.shape[:-1])) \
            if row_streams(seg_ids.shape[-1]) else 0
        metrics.inc("flash_stream_rows_total", stream_rows, role=role)
        return dict(
            flash_block_share=counts["visited"] / counts["causal"],
            flash_unmasked_share=counts["unmasked"] / max(
                counts["visited"], 1),
            flash_stream_rows=stream_rows)

    def _count_routed_pairs(self, seg_ids, decode_tokens: int = 0):
        """``moe_routed_pairs_total{role,dispatch}``: the (token,
        expert) pairs the program about to run routes, counted on the
        host from its batch: valid tokens (plus the tokens a decode
        loop is asked for) x top_k x sparse layers, over ALL the
        router's experts whatever share of them is held. A device
        array is not read: all its positions count (pads are routed
        like tokens). An operator's own counter likewise
        (``Operator.token_counter``, such as ``conv_tokens_total{role}``):
        tokens x its layers of a patterned model."""
        tokens = decode_tokens + (
            int(np.count_nonzero(seg_ids))
            if isinstance(seg_ids, np.ndarray) else int(seg_ids.size))
        for rec, n in self._operators:
            if n and rec.token_counter is not None:
                metrics.inc(rec.token_counter, tokens * n,
                            role=str(self.ctx.model_name.role))
        if self.cfg.n_passes > 1:
            # (token, pass) pairs: every token runs every pass
            metrics.inc("loop_token_passes_total",
                        tokens * self.cfg.n_passes,
                        role=str(self.ctx.model_name.role))
        if "moe_dispatch" not in self._model_attrs:
            return
        metrics.inc("moe_routed_pairs_total",
                    tokens * self.cfg.moe.top_k * self.cfg.n_moe_layers,
                    role=str(self.ctx.model_name.role),
                    dispatch=self._model_attrs["moe_dispatch"])

    def _report_moe_load(self, stats: Dict[str, Any]):
        """The train step's load statistics (``ops.moe.STATS``) as
        attributes of the ``engine:train*`` span that has just ended
        and as metrics: ``LOAD_STAT`` (the worst layer of the worst
        microbatch) as gauge ``moe_load_max_over_mean{role}``; of a
        model that holds a share of its experts also
        ``moe_held_load_max_over_mean{role}`` and counters
        ``moe_held_pairs_total{role}`` and
        ``moe_share_overflow_total{role}``."""
        role = str(self.ctx.model_name.role)

        def worst(name):
            load = float(np.max(stats[name]))
            self._last_span.set_attribute(name, load)
            return load

        if moe_ops.LOAD_STAT in stats:
            metrics.set_gauge("moe_load_max_over_mean",
                              worst(moe_ops.LOAD_STAT), role=role)
        if moe_ops.HELD_LOAD_STAT in stats:
            metrics.set_gauge("moe_held_load_max_over_mean",
                              worst(moe_ops.HELD_LOAD_STAT), role=role)
        if moe_ops.HELD_PAIRS_STAT in stats:
            # the pairs the held experts' products multiplied: only
            # the device knows them
            held = float(np.sum(stats[moe_ops.HELD_PAIRS_STAT]))
            metrics.inc("moe_held_pairs_total", held, role=role)
            self._last_span.set_attribute(moe_ops.HELD_PAIRS_STAT, held)
            # the (layer, microbatch) whose held pairs passed the fast
            # path's rows: each cost a whole layer's grouped products
            slow = float(np.sum(stats[moe_ops.SHARE_OVERFLOW_STAT]))
            metrics.inc("moe_share_overflow_total", slow, role=role)
            self._last_span.set_attribute(moe_ops.SHARE_OVERFLOW_STAT, slow)

    def _report_exits(self, stats: Dict[str, Any]):
        """What a looped model's objective said of its exit gate in the
        train step that has just ended (``interfaces/sft.py``), as
        attributes of its ``engine:train*`` span and as gauges:
        ``loop_expected_exit_pass{role}`` (the mean over the answer
        tokens of sum_t t p_t), ``loop_exit_entropy{role}``, and a pass
        t from 1 ``loop_exit_mass{role,pass}`` (the mean p_t) and
        ``loop_pass_nll{role,pass}`` (the mean next-token loss from
        pass t's hidden state); a sequence of minibatches reports its
        last."""
        if "expected_exit_pass" not in stats:
            return
        role = str(self.ctx.model_name.role)

        def last(name):
            value = float(np.ravel(stats[name])[-1])
            self._last_span.set_attribute(name, value)
            return value

        metrics.set_gauge("loop_expected_exit_pass",
                          last("expected_exit_pass"), role=role)
        metrics.set_gauge("loop_exit_entropy", last("exit_entropy"),
                          role=role)
        for t in range(1, self.cfg.n_passes + 1):
            metrics.set_gauge("loop_exit_mass", last(f"exit_p{t}"),
                              role=role, **{"pass": str(t)})
            metrics.set_gauge("loop_pass_nll", last(f"nll_pass{t}"),
                              role=role, **{"pass": str(t)})

    def _program_facts(self, name: str, key, call=None
                       ) -> parts.ProgramFacts:
        """What the program run under ``name`` says of itself
        (``obs/parts.py:ProgramFacts``: its operations by part, pass
        and opcode, the compiler's count of its memory), read ONCE a
        ``(name, key)`` from its compiled text; ``key`` tells the
        compilations under one name apart (loss or sampling options,
        batch shape). ``call``: the call to read (default: the last
        under ``name``). From the same read come the attributes of
        the program's spans: ``flash_fwd_per_bwd`` of a train program
        (``ops.flash_attention.flash_fwd_per_bwd``: 1.0 where the
        rematerialised blocks keep the flash kernel's residuals, 2.0
        where they run it again; None off the kernels) and its
        ``attn_proj_remat_products`` (``obs.parts.count_products``:
        the attention projections' products the backward runs a second
        time; k's and v's where the blocks keep what the two wide ones
        made, 4 a layer where they keep nothing) and
        ``head_remat_products`` (the same count under part
        ``vocab_head``: 1, the loop body's, where the backward makes a
        chunk's logits a second time, as every loss over
        ``shifted_logprobs_from_hidden`` does; 0 where the head
        computes its gradient in the chunk that has the logits,
        ``ops/functional.py:weighted_logprob_sum``: SFT), of a
        generate program :meth:`_decode_facts`, and of every program
        of a model in the ragged dispatch mode ``moe_products``,
        ``moe_gmm_calls``, ``moe_ragged_dot_calls``
        (``ops.moe.grouped_product_calls``: which grouped matmul the
        experts' products really are), and of every program of a model
        with delta layers ``delta_scan_kernel_calls``
        (``ops.delta_rule.scan_kernel_calls``: the chunked scan's
        kernels in the text: a forward and a backward one a delta
        layer of a train program, 0 where the XLA products run) and,
        of a train program, ``delta_scan_handed``
        (``ops.delta_rule.scan_handed``: the arrays a backward kernel
        takes from the forward one, 2 = every chunk's start state and
        its pairs and inverse, 0 where the XLA products run), of
        every program of a model with ssm layers
        ``ssm_scan_kernel_calls`` (``ops.ssm_scan.scan_kernel_calls``:
        the same of the Mamba-2 scan's two kernels), and
        of every program of a model with sparse layers
        ``flash_mask_calls`` (``ops.flash_attention.flash_mask_calls``:
        the flash kernels in the text that take the selection, three a
        sparse layer of a train program, 0 on the XLA path). Sets
        gauge
        ``engine_program_bytes{role,program,kind}``."""
        if (name, key) not in self._facts:
            train = name.startswith("train")
            if name == "generate":
                mine = self._decode_facts(key)
            elif train:
                def mine(text):
                    return dict(
                        flash_fwd_per_bwd=flash_fwd_per_bwd(text),
                        attn_proj_remat_products=parts.count_products(
                            text, parts.ATTN_PROJ, parts.REMAT),
                        head_remat_products=parts.count_products(
                            text, parts.VOCAB_HEAD, parts.REMAT))
            else:
                mine = None
            ragged = moe_ops.dispatch_mode(self.cfg) == "ragged"
            delta = self.cfg.delta is not None
            ssm = self.cfg.ssm is not None
            sparse = self.cfg.indexer is not None

            def derive(text):
                out = mine(text) if mine is not None else {}
                if ragged:
                    out.update(moe_ops.grouped_product_calls(text))
                if delta:
                    out.update(delta_scan_kernel_calls=scan_kernel_calls(
                        text))
                    if train:
                        out.update(delta_scan_handed=scan_handed(text))
                if ssm:
                    out.update(ssm_scan_kernel_calls=ssm_kernel_calls(
                        text))
                if sparse:
                    out.update(flash_mask_calls=flash_mask_calls(text))
                return out

            # the program lowered again where jax has not kept it (the
            # stages go to this span), and the parse of its text
            t0 = time.monotonic()
            with tracing.span("engine:facts") as sp:
                facts = parts.read_program(self._compiled(name, call),
                                           derive)
                sp.set_attribute("program", facts.module)
                sp.set_attribute("bytes", facts.text_bytes)
            metrics.inc("engine_stage_secs_total", time.monotonic() - t0,
                        stage="facts")
            self._facts[(name, key)] = facts
            role = str(self.ctx.model_name.role)
            for kind, field in _MEMORY_KINDS.items():
                metrics.set_gauge("engine_program_bytes",
                                  facts.memory[field], role=role,
                                  program=facts.module, kind=kind)
        return self._facts[(name, key)]

    def _decode_facts(self, key):
        """What a generate program's compiled text says of the KV cache
        inside its decode loop: ``decode_kernel`` (``stacked``: the
        Pallas kernel reads the stacked cache in place; ``xla``: the
        einsum path on a layer sliced out) and ``decode_layer_copies``
        (``ops.decode_attention.decode_layer_copies``: 0 where nothing
        slices or relayouts a layer's cache). ``key``: (the program's
        cache key, streams, prompt length)."""
        (gconfig, _, _), b, lp = key
        cfg = self.cfg
        shape = decode_ops.local_layer_shape(
            self.mesh, b, cfg.n_q_heads, cfg.n_kv_heads,
            T.round_cache_len(lp + gconfig.max_new_tokens), cfg.head_dim)
        return lambda text: dict(
            decode_kernel=("stacked" if decode_ops.KERNEL_NAME in text
                           else "xla"),
            decode_layer_copies=decode_ops.decode_layer_copies(
                text, shape))

    def _read_facts_now(self, name: str, key):
        """A train or generate program has just run under ``name``:
        read its facts if this is its first call (the warm-up step,
        inside set-up), and set on the ``engine:*`` span that has just
        ended its fingerprint and what else came of the text
        (``decode_kernel``, ``decode_layer_copies``,
        ``flash_fwd_per_bwd``, ``attn_proj_remat_products``,
        ``head_remat_products``), as on every later span of it."""
        facts = self._program_facts(name, key)
        self._unread.pop(getattr(self._last_span, "span_id", None), None)
        self._last_span.set_attribute("program_fingerprint",
                                      facts.fingerprint)
        for k, v in facts.attributes.items():
            if v is not None:
                self._last_span.set_attribute(k, v)

    def program_facts(self, name: str) -> parts.ProgramFacts:
        """The facts of the program LAST run under ``name`` ("train",
        "train_seq", "hidden", "logprobs", "values", "generate"); its
        text is read now where it has not been."""
        return self._program_facts(name, self._last_key[name])

    def _capture_programs(self, spans, profiled: bool
                          ) -> Dict[str, Dict[str, Any]]:
        """``tracing.stop()``'s question (``_programs_of_capture``):
        fingerprint -> facts of every program of this engine that ran
        under an ``engine:*`` span of ``spans``. A program whose text
        has not been read (``logprobs``, ``values``, ``hidden``; a
        train program off the flash kernels) is read now where the
        capture had a profile, and its spans get their
        ``program_fingerprint``; the capture's clock has stopped."""
        mine = {f.fingerprint: f for f in self._facts.values()}
        out = {}
        for sp in spans:
            unread = self._unread.pop(sp.span_id, None)
            if unread is not None:
                name, key, call = unread
                if profiled or (name, key) in self._facts:
                    facts = self._program_facts(name, key, call)
                    mine[facts.fingerprint] = facts
                    sp.set_attribute("program_fingerprint",
                                     facts.fingerprint)
            facts = mine.get(sp.attributes.get("program_fingerprint"))
            if facts is not None and facts.fingerprint not in out \
                    and sp.name.startswith("engine:"):
                out[facts.fingerprint] = facts.as_dict()
        return out

    def _compiled(self, name: str, call=None):
        """The ``jax.stages.Compiled`` of ``call`` (default: the
        program last run under ``name``). With the compile cache on
        this is a cache hit, not a second compile."""
        fn, args, static = call or self._last_call[name]
        return fn.lower(*args, **static).compile()

    def compiled_text(self, name: str) -> str:
        """Optimized HLO of the program last run under ``name``
        ("train", "train_seq", "hidden", "logprobs", "values",
        "generate"). Which kernels a run really used is read here --
        a ``tpu_custom_call`` in the text -- not from the backend gate,
        which knows nothing of the shape gates."""
        return self._compiled(name).as_text()

    # ------------------------------------------------------------------
    # Multi-process (worker-group) helpers
    # ------------------------------------------------------------------
    @property
    def multiproc(self) -> bool:
        """True when this engine's mesh spans >1 OS process; gathers /
        saves are then collectives every member must join."""
        return self._multiproc

    @property
    def _replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def _globalize(self, arr):
        """Host array -> device array usable by this engine's jits.

        Single-process: plain jnp.asarray (jit reshards under GSPMD).
        Multi-process mesh: build a REPLICATED global jax.Array from
        the process-local copy (every member fetched the same batch
        from the data plane), since jit on a cross-process mesh only
        accepts global arrays.
        """
        if not self._multiproc:
            return jnp.asarray(arr)
        a = np.asarray(arr)
        return jax.make_array_from_callback(
            a.shape, self._replicated_sharding, lambda idx: a[idx])

    def _globalize_tree(self, tree):
        """Host pytree -> device, ONE bundled transfer where possible.
        Per-leaf ``jnp.asarray`` costs a dispatch per leaf, so the
        whole tree goes up in one device_put."""
        if not self._multiproc:
            return jax.device_put(tree)
        return jax.tree.map(self._globalize, tree)

    def _out_replicated(self):
        """out_shardings making jit outputs replicated (hence fully
        addressable on every member process); None single-process to
        let XLA choose."""
        return self._replicated_sharding if self._multiproc else None

    @property
    def n_streams(self) -> int:
        """Preferred [S, L] stream-batch rows: one per dp rank, times
        the pipeline microbatch count when pp > 1 (each pipeline
        microbatch then carries dp streams)."""
        if self._pipeline_ctx is not None:
            return self.ctx.dp_size * self._pipeline_ctx.n_microbatches
        return self.ctx.dp_size

    # ------------------------------------------------------------------
    # The model's forward
    # ------------------------------------------------------------------
    def _forward(self, params, input_ids, seg_ids, *, train: bool,
                 every_pass: bool = False):
        """Final hidden states [B, L, H] and the auxiliary dict of this
        engine's model: the one call of ``T.forward`` on an engine's
        behalf (generation's prefill and decode steps are
        ``engine/generation.py``'s and ``inflight.py``'s own). Training
        and inference share the attention function and the
        expert-parallel constraint, and differ in three ways:

        - the auxiliary dict (a sparse model's router losses and load
          statistic) is computed for training only; else it is ``{}``;
        - training takes the mesh's own pipeline schedule (1F1B by
          default), inference always the GPipe rotation: with no
          backward, 1F1B's input saving and custom VJP are overhead;
        - inference constrains the residual stream's sharding,
          training does NOT. Nobody chose that: ROADMAP D14.

        A looped model's hidden states are its LAST pass's; with
        ``every_pass`` they are ``T.PassStates``, every pass's final
        hidden state and exit-gate logit (:meth:`_objective`).
        """
        with_aux = train and self.cfg.n_moe_layers > 0
        pipeline = self._pipeline_ctx
        if train:
            constrain = None
        else:
            constrain = self._constrain
            if pipeline is not None:
                pipeline = dataclasses.replace(pipeline, schedule="gpipe")
        out = T.forward(self.cfg, params, input_ids, seg_ids,
                        return_aux=with_aux,
                        activation_constraint=constrain,
                        attention_fn=self._attention_fn,
                        moe_constraint=self._moe_constraint,
                        pipeline=pipeline, mesh=self.mesh,
                        return_passes=every_pass)
        return out[0], (out[2] if with_aux else {})

    # ------------------------------------------------------------------
    # Training
    # ------------------------------------------------------------------
    def _objective(self, loss_fn: LossFn) -> Callable:
        """``(params, microbatch) -> (loss, statistics)``, what a train
        step differentiates: the forward on the microbatch's
        ``input_ids`` and ``seg_ids``, ``loss_fn``'s head and objective
        on its hidden states, and a sparse model's auxiliary losses
        added to the loss, their entries and the load statistic to the
        statistics (never by ``loss_fn``). A ``loss_fn`` that reads
        EVERY pass of a looped model says so (attribute ``every_pass``,
        ``interfaces/sft.py``) and is handed ``T.PassStates`` in the
        hidden states' place; every other one gets the last pass's."""
        every_pass = getattr(loss_fn, "every_pass", False)

        def objective(params, mb):
            h, aux = self._forward(params, mb["input_ids"],
                                   mb["seg_ids"], train=True,
                                   every_pass=every_pass)
            # what the interface does after the picked
            # log-probabilities; its head enters a scope of its own
            with jax.named_scope(parts.LOSS):
                loss, stats = loss_fn(params, h, mb)
                return loss + moe_ops.aux_loss(aux), {**stats, **aux}

        return objective

    def _train_step_body(self, loss_fn: LossFn) -> Callable:
        """The un-jitted one-optimizer-step body shared by
        ``_build_train_step`` (one minibatch per dispatch) and
        ``_build_train_seq`` (a lax.scan over minibatches inside one
        dispatch)."""
        objective = self._objective(loss_fn)

        def train_step(params, opt_state, mbs: Dict[str, jnp.ndarray],
                       mb_weights: jnp.ndarray):
            """mbs: dict of stacked arrays with leading dim n_mbs;
            mb_weights: [n_mbs] relative weight (e.g. token counts) used
            to average gradients exactly as one large batch would."""
            grad_fn = jax.value_and_grad(objective, has_aux=True)
            with jax.named_scope(parts.GRAD_ACCUM):
                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                if self._grad_shardings is not None:
                    zero = jax.tree.map(jax.lax.with_sharding_constraint,
                                        zero, self._grad_shardings)

            def accum(carry, x):
                gsum = carry
                mb, w = x
                with jax.named_scope(parts.FORWARD_BACKWARD):
                    (loss, stats), grads = grad_fn(params, mb)
                with jax.named_scope(parts.GRAD_ACCUM):
                    gsum = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32) * w,
                        gsum, grads)
                    if self._grad_shardings is not None:
                        gsum = jax.tree.map(
                            jax.lax.with_sharding_constraint,
                            gsum, self._grad_shardings)
                return gsum, (loss, stats)

            with jax.named_scope(parts.GRAD_ACCUM):
                wsum = mb_weights.sum()
                weights = mb_weights / wsum
            gsum, (losses, stats) = jax.lax.scan(
                accum, zero, (mbs, weights))
            with jax.named_scope(parts.OPTIMIZER):
                updates, new_opt = self._tx.update(gsum, opt_state,
                                                   params)
                if self._opt_shardings is not None:
                    # keep the ZeRO-1 moment shardings stable across
                    # steps (donated buffers must alias exactly)
                    new_opt = jax.tree.map(
                        lambda s, sh:
                        jax.lax.with_sharding_constraint(s, sh),
                        new_opt, self._opt_shardings)
                new_params = optax.apply_updates(params, updates)
                # grad_norm and the statistics go with it
                gnorm = optax.global_norm(gsum)
                mean_stats = jax.tree.map(
                    lambda s: (s * mb_weights / wsum).sum(), stats)
                for name, reduce in moe_ops.STATS.items():
                    if name in stats:  # the worst microbatch's, or all's
                        mean_stats[name] = reduce(stats[name])
                # Reserved stat "__skip_update__": when any microbatch sets
                # it > 0, the whole optimizer step is discarded -- params,
                # optimizer moments, and step count stay untouched (PPO
                # early stopping must SKIP the update, not step with a
                # zeroed loss: AdamW weight decay and MoE aux grads would
                # otherwise still apply).
                skip = mean_stats.pop("__skip_update__", None)
                if skip is not None:
                    keep_old = skip > 0
                    new_params = jax.tree.map(
                        lambda n, o: jnp.where(keep_old, o, n),
                        new_params, params)
                    new_opt = jax.tree.map(
                        lambda n, o: jnp.where(keep_old, o, n),
                        new_opt, opt_state)
                    mean_stats["early_stop_skipped"] = keep_old.astype(
                        jnp.float32)
                mean_loss = (losses * mb_weights / wsum).sum()
                return new_params, new_opt, mean_loss, mean_stats, gnorm

        return train_step

    def _train_out_shardings(self, extra_outs: int):
        """Pin the params/opt-state OUTPUTS of a train jit to their
        input shardings. Without this XLA picks output shardings
        freely, the second call sees donated inputs whose shardings no
        longer match the first compilation, and the step silently
        compiles twice (measured: a full second compile on step 2).
        The scalar/stat outputs stay compiler-chosen."""
        return (self._param_shardings, self._opt_shardings) + \
            (None,) * extra_outs

    def _build_train_step(self, loss_fn: LossFn) -> Callable:
        return jax.jit(self._train_step_body(loss_fn),
                       donate_argnums=(0, 1),
                       out_shardings=self._train_out_shardings(3))

    def _build_train_seq(self, loss_fn: LossFn) -> Callable:
        """N SEQUENTIAL optimizer steps (e.g. the PPO minibatch loop,
        reference ppo_interface.py train_step's minibatch iteration) in
        ONE compiled dispatch: an outer lax.scan threads params and
        optimizer state through the per-minibatch step body, so the
        whole loop is one dispatch and one host sync instead of one
        per minibatch. Semantics (update
        order, early-stop skip, gradient weighting) are identical to
        calling train_batch once per minibatch."""
        body = self._train_step_body(loss_fn)

        def train_seq(params, opt_state, all_mbs, all_weights):
            def outer(carry, x):
                p, o = carry
                mbs, w = x
                p, o, loss, stats, gnorm = body(p, o, mbs, w)
                return (p, o), (loss, stats, gnorm)

            (params, opt_state), (losses, stats, gnorms) = jax.lax.scan(
                outer, (params, opt_state), (all_mbs, all_weights))
            return params, opt_state, losses, stats, gnorms

        return jax.jit(train_seq, donate_argnums=(0, 1),
                       out_shardings=self._train_out_shardings(3))

    def train_batch(self, microbatches: List[Dict[str, np.ndarray]],
                    loss_fn: LossFn,
                    loss_weights: Optional[List[float]] = None,
                    loss_fn_key: Optional[str] = None) -> Dict[str, float]:
        """Run one optimizer step over the microbatches.

        All microbatches must share array shapes (the packer pads them
        to a common bucket); they are stacked and scanned on-device.
        Every microbatch holds ``input_ids`` and ``seg_ids`` [S, L]:
        the engine runs the model on them and hands ``loss_fn`` the
        final hidden states (:meth:`_objective`).

        ``loss_fn_key`` caches the compiled step: it MUST uniquely
        identify the loss closure INCLUDING every hyperparameter the
        closure captures (temperature, clip ranges, ...) -- use a tuple
        like ("ppo_actor", temp, eps_clip). Two closures sharing a key
        silently reuse the first compilation.

        ``loss_fn`` may return the reserved stat ``__skip_update__``
        (0/1 scalar); if any microbatch sets it, the optimizer update
        is discarded for this call (see _build_train_step).
        """
        if self._tx is None:
            raise RuntimeError("Engine has no optimizer (inference-only).")
        if getattr(self, "_opt_offloaded", False):
            # optimizer offload (reference DeepSpeed zero-offload,
            # deepspeed.py:445): state lives on host between steps
            self.opt_state = jax.device_put(self.opt_state,
                                            self._opt_shardings)
            self._opt_offloaded = False
        key = loss_fn_key or loss_fn
        if key not in self._train_step_cache:
            self._train_step_cache[key] = self._build_train_step(loss_fn)
        step = self._train_step_cache[key]

        if loss_weights is None:
            loss_weights = [1.0] * len(microbatches)
        host_batch = {
            k: np.stack([np.asarray(mb[k]) for mb in microbatches])
            for k in microbatches[0]
        }
        stacked, weights = self._globalize_tree(
            (host_batch, np.asarray(loss_weights, np.float32)))

        attrs = self._count_batch(host_batch["seg_ids"])
        key = (key, host_batch["seg_ids"].shape)
        self.params, self.opt_state, loss, stats, gnorm = self._run(
            "train", key, step, attrs, self.params, self.opt_state,
            stacked, weights)
        if "flash_block_share" in attrs:  # the rows go to the kernels
            self._read_facts_now("train", key)
        self.version += 1
        if self._decode_view is not None:
            # the view's gen-layout weight copy is now stale (params
            # identity moved) and would otherwise sit in HBM through
            # the memory-peak train phase; the next rollout reshards
            # fresh weights into the view anyway
            self._decode_view.params = None
            self._decode_view_src = None
        if (self.optimizer_config is not None
                and self.optimizer_config.offload):
            self.opt_state = offload_to_host(self.opt_state)
            jax.block_until_ready(self.opt_state)
            self._opt_offloaded = True
        # ONE batched host fetch for all scalar stats: converting each
        # scalar with float() would issue a separate blocking D2H sync.
        loss, stats, gnorm = jax.device_get((loss, stats, gnorm))
        self._report_moe_load(stats)
        self._report_exits(stats)
        out = {k: float(v) for k, v in stats.items()}
        out["loss"] = float(loss)
        out["grad_norm"] = float(gnorm)
        return out

    def train_minibatches(self,
                          minibatches: List[List[Dict[str, np.ndarray]]],
                          loss_fn: LossFn,
                          loss_weights: Optional[List[List[float]]] = None,
                          loss_fn_key: Optional[str] = None
                          ) -> List[Dict[str, float]]:
        """N sequential optimizer steps -- one per minibatch, each
        accumulating gradients over its microbatches -- in ONE jitted
        dispatch (the PPO minibatch loop fused; see _build_train_seq).
        Array shapes must match across ALL microbatches of ALL
        minibatches (``pad_stream_batches`` over the union). Returns
        one stats dict per minibatch, exactly what the same sequence
        of ``train_batch`` calls would have returned."""
        if self._tx is None:
            raise RuntimeError("Engine has no optimizer (inference-only).")
        if len(minibatches) == 1:
            return [self.train_batch(minibatches[0], loss_fn,
                                     loss_weights[0] if loss_weights
                                     else None, loss_fn_key)]
        if getattr(self, "_opt_offloaded", False):
            self.opt_state = jax.device_put(self.opt_state,
                                            self._opt_shardings)
            self._opt_offloaded = False
        key = ("__seq__", loss_fn_key or loss_fn)
        if key not in self._train_step_cache:
            self._train_step_cache[key] = self._build_train_seq(loss_fn)
        step = self._train_step_cache[key]

        if loss_weights is None:
            loss_weights = [[1.0] * len(m) for m in minibatches]
        host_batch = {
            k: np.stack([np.stack([np.asarray(mb[k]) for mb in m])
                         for m in minibatches])
            for k in minibatches[0][0]
        }
        stacked, weights = self._globalize_tree(
            (host_batch, np.asarray(loss_weights, np.float32)))

        attrs = self._count_batch(host_batch["seg_ids"])
        key = (key, host_batch["seg_ids"].shape)
        self.params, self.opt_state, losses, stats, gnorms = self._run(
            "train_seq", key, step, attrs, self.params, self.opt_state,
            stacked, weights)
        if "flash_block_share" in attrs:
            self._read_facts_now("train_seq", key)
        self.version += len(minibatches)
        if self._decode_view is not None:
            self._decode_view.params = None
            self._decode_view_src = None
        if (self.optimizer_config is not None
                and self.optimizer_config.offload):
            self.opt_state = offload_to_host(self.opt_state)
            jax.block_until_ready(self.opt_state)
            self._opt_offloaded = True
        losses, stats, gnorms = jax.device_get((losses, stats, gnorms))
        self._report_moe_load(stats)
        self._report_exits(stats)
        out = []
        for i in range(len(minibatches)):
            d = {k: float(v[i]) for k, v in stats.items()}
            d["loss"] = float(losses[i])
            d["grad_norm"] = float(gnorms[i])
            out.append(d)
        return out

    # ------------------------------------------------------------------
    # Inference
    # ------------------------------------------------------------------
    def forward_hidden(self, input_ids, seg_ids):
        if self._jit_forward_hidden is None:
            def hidden(params, ids, seg):
                h, _ = self._forward(params, ids, seg, train=False)
                return h
            self._jit_forward_hidden = jax.jit(
                hidden, out_shardings=self._out_replicated())
        attrs = self._count_batch(seg_ids)
        ids, seg = self._globalize_tree((input_ids, seg_ids))
        return self._run("hidden", ids.shape, self._jit_forward_hidden,
                         attrs, self.params, ids, seg)

    def forward_logprobs(self, input_ids, seg_ids, temperature: float = 1.0,
                         logits_mask=None):
        """Next-token logprobs [S, L] (the reference's `inference` MFC
        on actor/ref models, ppo_interface.py:255)."""
        if self._jit_logprobs is None:
            def logprobs(params, ids, seg, mask, temp, has_mask):
                h, _ = self._forward(params, ids, seg, train=False)
                return F.shifted_logprobs_from_hidden(
                    self.cfg, params, h, ids, seg, temperature=temp,
                    logits_mask=mask if has_mask else None)
            self._jit_logprobs = jax.jit(
                logprobs, static_argnames=("temp", "has_mask"),
                out_shardings=self._out_replicated())
        attrs = self._count_batch(seg_ids)
        ids, seg, mask = self._globalize_tree(
            (input_ids, seg_ids,
             logits_mask if logits_mask is not None
             else np.zeros((1,), bool)))
        return self._run("logprobs",
                         (ids.shape, temperature, logits_mask is not None),
                         self._jit_logprobs, attrs, self.params, ids, seg,
                         mask, temp=temperature,
                         has_mask=logits_mask is not None)

    def forward_values(self, input_ids, seg_ids):
        """Critic/reward scalar outputs [S, L]."""
        assert self.cfg.is_critic
        if self._jit_values is None:
            def values(params, ids, seg):
                h, _ = self._forward(params, ids, seg, train=False)
                return T.critic_values(self.cfg, params, h)
            self._jit_values = jax.jit(
                values, out_shardings=self._out_replicated())
        attrs = self._count_batch(seg_ids)
        ids, seg = self._globalize_tree((input_ids, seg_ids))
        return self._run("values", ids.shape, self._jit_values, attrs,
                         self.params, ids, seg)

    # ------------------------------------------------------------------
    # Generation
    # ------------------------------------------------------------------
    def decode_engine(self) -> "Engine":
        """The engine generation should run on.

        dp/tp meshes decode in place (returns self). On a pipeline- or
        context-parallel mesh, decoding against layer-sharded (pipe) or
        ring-attention (ctx) weights has no efficient schedule -- the
        reference streams tokens through PP stages instead
        (``pipe_runner.py:847``, ``static_schedule.py:195``
        GenerateSchedule). The TPU-first equivalent: reshard the weights
        onto a collapsed dp x tp mesh over the SAME devices (amortized
        over the whole rollout and refreshed only when the weights
        change) and run the fast dp/tp decode there. The move is one
        cross-mesh ``device_put`` (``set_params``), which jax 0.9.0
        takes leaf by leaf through the host: 0.49 GB/s on the v5e
        (PERF.md, PR 25), not the interconnect's rate (ROADMAP D13).
        ``ParallelismConfig.gen_tp_size``
        ("g" in the allocation shorthand, e.g. ``d2t2p2g4``) picks the
        decode tensor-parallel degree; default is the train tp, giving
        pp*dp-way decode data parallelism for free.
        """
        gen_tp = self.ctx.parallel.gen_tp_size or self.ctx.tp_size
        if (self._pipeline_ctx is None
                and self.ctx.parallel.context_parallel_size == 1
                and gen_tp == self.ctx.tp_size):
            return self
        if self._decode_view is None:
            from realhf_tpu.parallel.mesh import (
                MeshContext, ParallelismConfig, make_mesh,
            )
            devices = list(self.mesh.devices.flat)
            tp = gen_tp
            if len(devices) % tp != 0:
                raise ValueError(
                    f"gen_tp_size={tp} does not divide the mesh's "
                    f"{len(devices)} devices.")
            par = ParallelismConfig(
                data_parallel_size=len(devices) // tp,
                tensor_parallel_size=tp,
                sequence_parallel=self.ctx.parallel.sequence_parallel)
            view_ctx = MeshContext(self.ctx.model_name,
                                   make_mesh(par, devices), par)
            logger.info("Building decode view %s for %s mesh %s",
                        par, self.ctx.model_name, self.ctx.parallel)
            self._decode_view = Engine(self.cfg, view_ctx, self.params,
                                       optimizer=None)
            self._decode_view_src = self.params
        elif self._decode_view_src is not self.params:
            # train_batch donates + replaces self.params; set_params
            # installs a realloc'd pytree -- either way identity moved.
            # Drop the view's stale copy FIRST: holding it through the
            # reshard would transiently keep old+new gen-layout copies
            # resident (2x 2*n_params/gen_tp per chip -- an OOM at the
            # 70B scale this path exists for).
            self._decode_view.params = None
            self._decode_view.set_params(self.params)
            self._decode_view_src = self.params
        return self._decode_view

    def drop_decode_view(self):
        """Free the decode view's weight copy.

        On a pp/ctx mesh the view holds a second full copy of the
        weights (2*n_params/gen_tp bytes per chip) between rollouts;
        at the 70B scale that steady-state cost is the OOM frontier.
        Dropping returns HBM to one resident copy; the next rollout
        pays one cross-mesh reshard to rebuild the view. Policy knob:
        ``ModelSpec.drop_decode_view_after_rollout`` (applied by
        ModelHost after each generate MFC)."""
        if self._decode_view is not None:
            self._decode_view.params = None
            self._decode_view_src = None

    def decode_view_param_bytes(self) -> int:
        """MESH-WIDE bytes the decode view's weights currently hold
        (0 when absent or dropped) -- the quantity ``drop_decode_view``
        frees. One logical copy shards over the view's tp and
        REPLICATES over its dp groups, so this is
        ``n_params * itemsize * view_dp`` (per chip:
        ``n_params * itemsize / view_tp``)."""
        if self._decode_view is None or self._decode_view.params is None:
            return 0
        logical = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self._decode_view.params))
        return logical * self._decode_view.ctx.dp_size

    def set_gen_tp(self, gen_tp: int):
        """Install a decode-view TP override (the allocation
        shorthand's "g"), validating against the mesh NOW rather than
        at the first rollout mid-experiment."""
        ndev = len(self.mesh.devices.flat)
        if gen_tp and ndev % gen_tp != 0:
            raise ValueError(
                f"gen_tp_size={gen_tp} does not divide the mesh's "
                f"{ndev} devices.")
        if gen_tp == self.ctx.parallel.gen_tp_size:
            return
        self.ctx.parallel = dataclasses.replace(self.ctx.parallel,
                                                gen_tp_size=gen_tp)
        self._decode_view = None
        self._decode_view_src = None

    def generate(self, prompt_ids, prompt_seg, prompt_pos, key,
                 gconfig: GenerationHyperparameters,
                 eos_token_id: Optional[int], pad_token_id: int
                 ) -> gen_mod.GenerationOutput:
        view = self.decode_engine()
        if view is not self:
            return view.generate(prompt_ids, prompt_seg, prompt_pos,
                                 key, gconfig, eos_token_id, pad_token_id)
        cache_key = (gconfig, eos_token_id, pad_token_id)
        if cache_key not in self._generate_cache:
            self._generate_cache[cache_key] = gen_mod.build_generate_fn(
                self.cfg, gconfig, eos_token_id, pad_token_id,
                activation_constraint=self._constrain,
                moe_constraint=self._moe_constraint,
                out_sharding=self._out_replicated(),
                mesh=self.mesh, attention_fn=self._attention_fn)
        fn = self._generate_cache[cache_key]
        attrs = self._count_batch(
            prompt_seg,
            decode_tokens=prompt_seg.shape[0] * gconfig.max_new_tokens)
        ids, seg, pos, key = self._globalize_tree(
            (prompt_ids, prompt_seg, prompt_pos, key))
        program = (cache_key, *prompt_seg.shape)
        out = self._run("generate", program, fn, attrs, self.params, ids,
                        seg, pos, key)
        self._read_facts_now("generate", program)
        if self.cfg.layer_pattern is not None:
            # the kinds of state the decode loop carried
            self._last_span.set_attribute("kv_layers", self.cfg.kv_layers)
            b, lp = prompt_seg.shape
            slots = T.round_cache_len(lp + gconfig.max_new_tokens)
            for rec, n in self._operators:
                if rec.state_bytes is not None and (n or rec.always):
                    self._last_span.set_attribute(rec.state_bytes, n * sum(
                        st.nbytes(self.cfg, b, slots,
                                  self.cfg.compute_dtype)
                        for st in rec.state))
        return out

    def inflight_generator(self, gconfig: GenerationHyperparameters,
                           **kwargs):
        """A continuous-batching generator (``engine/inflight.py``) on
        this engine's mesh, weights and attention; ``kwargs`` are the
        generator's own (slots, prompt length, end and pad tokens).
        Call it on :meth:`decode_engine`."""
        from realhf_tpu.engine.inflight import InflightBatchingGenerator
        self.cfg.require_one_block(
            "the slot engine (engine/inflight.py)")
        return InflightBatchingGenerator(
            self.cfg, self.params, gconfig,
            moe_constraint=self._moe_constraint, mesh=self.mesh,
            attention_fn=self._attention_fn, **kwargs)

    # ------------------------------------------------------------------
    def _cast_param_dtype(self, params):
        """Cast leaves to cfg.param_dtype (bf16 models may be fed fp32
        checkpoints; the fp32 master then lives in the opt state)."""
        pdt = jnp.dtype(self.cfg.param_dtype)
        return jax.tree.map(
            lambda a: a if a.dtype == pdt else a.astype(pdt), params)

    def set_params(self, params, already_sharded: bool = False):
        """Install new weights (parameter reallocation landing point)."""
        if already_sharded:
            self.params = params
        else:
            params = shard_rules.normalize_vocab_padding(
                self.cfg, params, self.ctx.tp_size)
            params = self._cast_param_dtype(params)
            self.params = jax.device_put(params, self._param_shardings)

    def params_numpy(self):
        """Host copy with vocab padding stripped (checkpoint layout).

        On a multi-process mesh this is a COLLECTIVE: every member
        process must call it together. The gather runs LEAF BY LEAF
        (one replicating jit per parameter, copied to host before the
        next) so peak HBM overhead is one unsharded leaf, not the whole
        model -- the motivating case is a model sharded across hosts
        precisely because it does not fit one host's devices."""
        params = self.params
        if self._multiproc:
            if self._gather_jit is None:
                rep = jax.sharding.NamedSharding(
                    self.ctx.mesh, jax.sharding.PartitionSpec())
                self._gather_jit = jax.jit(lambda x: x, out_shardings=rep)

            def gather_leaf(x):
                return np.asarray(self._gather_jit(x))

            params = jax.tree.map(gather_leaf, params)
            return shard_rules.unpad_vocab(
                self.cfg, jax.tree.map(np.asarray, params))
        # single-process: ONE bundled D2H fetch for the whole tree
        # (leaf-by-leaf np.asarray pays a blocking sync per leaf)
        return shard_rules.unpad_vocab(self.cfg, jax.device_get(params))

    def opt_state_numpy(self) -> list:
        """Host copy of the optimizer-state leaves (tree order).
        COLLECTIVE on a multi-process mesh (same discipline as
        params_numpy: leaf-by-leaf replicating gathers)."""
        return list(self.iter_opt_state_numpy())

    def iter_opt_state_numpy(self):
        """Yield optimizer-state leaves as host arrays ONE AT A TIME
        (tree order) -- the streaming form of :meth:`opt_state_numpy`:
        peak extra host memory is one unsharded leaf, the difference
        between fitting host RAM and not when the fp32 Adam state is
        ~3x the model. COLLECTIVE per leaf on a multi-process mesh;
        every group member must drain the iterator in step."""
        assert self.opt_state is not None
        leaves = jax.tree.leaves(self.opt_state)
        if self._multiproc:
            if self._gather_jit is None:
                rep = jax.sharding.NamedSharding(
                    self.ctx.mesh, jax.sharding.PartitionSpec())
                self._gather_jit = jax.jit(lambda x: x, out_shardings=rep)
            for l in leaves:
                # per-leaf transfer IS the point: bounds host memory
                # to one unsharded leaf
                yield np.asarray(self._gather_jit(l))  # graft-lint: disable=purity-sync-in-loop
        else:
            for l in leaves:
                yield np.asarray(l)  # graft-lint: disable=purity-sync-in-loop

    def load_opt_state(self, host_leaves: list):
        """Install gathered host leaves back onto the state shardings
        (recovery path; see engine/opt_checkpoint.py)."""
        assert self.opt_state is not None
        treedef = jax.tree.structure(self.opt_state)
        shard_leaves = jax.tree.leaves(self._opt_shardings)
        self.opt_state = jax.tree.unflatten(
            treedef,
            [jax.device_put(l, s)
             for l, s in zip(host_leaves, shard_leaves)])
        self._opt_offloaded = False

    def inc_version(self):
        self.version += 1

    # ------------------------------------------------------------------
    # Offload (reference async_offload/wait_for_offload,
    # real_llm_api.py:274-308: pinned-CPU weight offload between uses)
    # ------------------------------------------------------------------
    @property
    def offloaded(self) -> bool:
        return getattr(self, "_offloaded", False)

    def offload(self):
        """Move weights to host memory, freeing HBM until the next use."""
        if self.offloaded:
            return
        # the decode view holds a second full weight copy in the gen
        # layout; drop it too (rebuilt on the next pp/ctx generate; the
        # jit cache survives via XLA's compilation cache)
        self._decode_view = None
        self._decode_view_src = None
        self.params = offload_to_host(self.params)
        jax.block_until_ready(self.params)
        self._offloaded = True

    def ensure_on_device(self):
        """Reload offloaded weights onto this engine's mesh shardings
        (the pre-use reload the reference runs in
        model_worker.handle_all_pre_hooks)."""
        if not self.offloaded:
            return
        self.params = jax.device_put(self.params, self._param_shardings)
        self._offloaded = False
