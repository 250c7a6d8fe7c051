"""Continuous (inflight) batching for generation.

TPU-native counterpart of the reference's InflightBatchingGenerator
prototype (``real_llm_generate.py:664``, shipped unwired there): a
fixed set of decode SLOTS runs a jitted chunked decode loop; whenever
a slot's sequence finishes (EOS or max_new_tokens), the host harvests
it and refills the slot by prefilling the next queued prompt into that
slot's KV-cache rows, while the other slots keep decoding. Short
sequences therefore never wait for the batch's longest one -- the
throughput property vLLM-style serving is built on -- while every
device computation keeps static shapes:

- ``decode_chunk``: `lax.scan` over ``chunk_size`` steps for all slots
  (one compiled program, reused forever),
- ``prefill_into_slot``: batch-1 prefill at a bucketed prompt length,
  scattered into the slot's cache rows (one compilation per bucket).

Host<->device sync happens once per chunk, not per token. The
logits-mask replay of PPO is intentionally unsupported here (use the
batch ``generate`` path); inflight mode targets throughput-oriented
rollout generation (GRPO / ReMax / gen experiments).
"""

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from realhf_tpu.base import logging
from realhf_tpu.engine import kv_pool as _kvp
from realhf_tpu.models import operators as O
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.obs import tracing
from realhf_tpu.ops.sampling import (
    NEG_INF,
    GenerationHyperparameters,
    top_k_top_p_logits,
)

logger = logging.getLogger("engine.inflight")


def _bucket(n: int, buckets=(64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return n


#: finer ladder for the partial-prefill (prefix-cache hit) path: the
#: donor window and the uncached suffix each get their own bucket, so
#: a coarse floor would waste most of the win -- a 95%-hit request
#: must pay a SMALL suffix bucket, not the full-prompt one
_PARTIAL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


@dataclasses.dataclass
class FinishedSequence:
    request_id: int
    tokens: np.ndarray     # [len] generated ids (incl. EOS if emitted)
    logprobs: np.ndarray   # [len]
    no_eos: bool           # True iff the sequence never emitted EOS
                           # (hit max_new_tokens), matching the batch
                           # path's seq_no_eos_mask semantics.
    #: speculative-decoding accounting for THIS sequence (0 when the
    #: drafter is off): drafts proposed / drafts accepted by verify
    spec_proposed: int = 0
    spec_accepted: int = 0
    #: host copies of the sequence's KV rows ([nl, nkv, len(prompt)+
    #: len(tokens), hd] each), present only for ``harvest(
    #: export_kv=True)`` -- the serving scheduler publishes them into
    #: the radix prefix cache (serving/prefix_cache.py)
    kv: Optional[Tuple[np.ndarray, np.ndarray]] = None
    #: paged backends (``harvest(export_blocks=True)``): the KV pool
    #: blocks holding this sequence's rows, each carrying ONE extra
    #: pool reference owned by the receiver -- publish them into the
    #: pooled prefix cache (which increfs what it keeps), then
    #: ``pool.free(blocks)``. ``n_rows`` = valid token rows covered.
    blocks: Optional[Tuple[int, ...]] = None
    n_rows: int = 0


class InflightBatchingGenerator:
    """Slot-machine generation over a queue of prompts."""

    #: the serving scheduler feature-detects the prefix-cache fill /
    #: KV-export extensions on this attribute (test fakes may lack it)
    supports_prefix_fill = True

    def __init__(self, cfg: TransformerConfig, params,
                 gconfig: GenerationHyperparameters,
                 *, n_slots: int, max_prompt_len: int,
                 eos_token_id: Optional[int], pad_token_id: int,
                 chunk_size: int = 32, moe_constraint=None,
                 mesh=None, attention_fn=None,
                 spec_decode_k: int = 0, drafter=None,
                 kv_pool=None, kv_cache_dtype: Optional[str] = None,
                 bucket_pair_cap: int = 24):
        if not gconfig.force_no_logits_mask:
            raise ValueError(
                "inflight batching does not produce the PPO logits "
                "mask; set force_no_logits_mask=True or use the batch "
                "generate path.")
        cfg.require_one_block("the slot engine (engine/inflight.py)")
        self.cfg = cfg
        self.params = params
        self.g = gconfig
        self.n_slots = n_slots
        self.eos = eos_token_id
        self.pad = pad_token_id
        self.chunk = chunk_size
        self.cache_len = T.round_cache_len(
            max_prompt_len + gconfig.max_new_tokens)
        # ---- KV substrate: dense per-slot windows (default) or the
        # block-granular paged pool (engine/kv_pool.py) --------------
        self.kv_pool = kv_pool
        if kv_cache_dtype is not None \
                and kv_cache_dtype not in _kvp.KV_CACHE_DTYPES:
            raise ValueError(
                f"kv_cache_dtype must be one of {_kvp.KV_CACHE_DTYPES}")
        if kv_cache_dtype == "int8" and kv_pool is None:
            raise ValueError(
                "kv_cache_dtype='int8' requires a paged KV pool "
                "(dequant-on-read lives in the pool gather path); "
                "pass kv_pool=KVPool(..., dtype='int8').")
        if kv_pool is not None:
            if kv_pool.cfg is None:
                raise ValueError("paged decoding needs a device-"
                                 "backed KVPool (not host_only)")
            self._blen = kv_pool.block_len
            self._max_blocks = -(-self.cache_len // self._blen)
            self._slot_blocks: List[List[int]] = [
                [] for _ in range(n_slots)]
            self._bt_host = np.zeros((n_slots, self._max_blocks),
                                     np.int32)
            self._bt_dev = None  # refreshed lazily on table changes
            #: upper bound of window rows a slot may have written
            #: (exact at fill/spec-round/harvest, +chunk per plain
            #: decode chunk) -- capacity reservation never needs a
            #: blocking device readback
            self._slot_rows_ub = [0] * n_slots
            self._slot_prompt_n = [0] * n_slots
            self._paged_fill_jit = jax.jit(functools.partial(
                _paged_prefill, cfg, moe_constraint, attention_fn,
                kv_pool.meta))
            self._paged_suffix_jit = jax.jit(functools.partial(
                _paged_prefill_suffix, cfg, moe_constraint,
                kv_pool.meta))
            self._paged_decode_jit = jax.jit(functools.partial(
                _paged_decode_chunk, cfg, gconfig, eos_token_id,
                pad_token_id, chunk_size, moe_constraint, mesh,
                kv_pool.meta))
            self._paged_verify_jit = None  # built with spec below
        #: distinct (donor, suffix) bucket pairs the partial-prefill
        #: path has compiled; capped (satellite: the (c_b, s_b)
        #: ladder product is 81 pairs -- silent unbounded jit-cache
        #: growth without this)
        self.bucket_pair_cap = int(bucket_pair_cap)
        self._bucket_pairs = set()
        self._bucket_cap_warned = False
        # jax.jit retraces per prompt-bucket shape on its own; one
        # jitted function covers every bucket.
        self._prefill = jax.jit(functools.partial(
            _prefill_into_slot, self.cfg, self.cache_len,
            moe_constraint, attention_fn))
        # partial-prefill entry for radix prefix-cache hits: donor KV
        # seeds rows [0, c_b) and only the uncached suffix runs the
        # forward (one compilation per (donor-bucket, suffix-bucket))
        self._prefill_suffix = jax.jit(functools.partial(
            _prefill_suffix_into_slot, self.cfg, self.cache_len,
            moe_constraint))

        # prompt-lookup speculative decoding (greedy-exact verify):
        # k drafts per round, all verified in ONE forward over the
        # carry. Sampling-based generation falls back to the plain
        # decode loop -- acceptance is only exact under argmax.
        self._spec_k = int(spec_decode_k or 0)
        if self._spec_k > 0 and not gconfig.greedy:
            logger.warning(
                "spec_decode_k=%d requested but gconfig.greedy is "
                "False; speculative decoding is greedy-exact only -- "
                "disabling.", self._spec_k)
            self._spec_k = 0
        self._drafter = None
        self._verify = None
        if self._spec_k > 0:
            if drafter is None:
                from realhf_tpu.engine.drafter import NGramDrafter
                drafter = NGramDrafter(self._spec_k)
            self._drafter = drafter
            self._verify = jax.jit(functools.partial(
                _verify_chunk, cfg, gconfig, eos_token_id,
                self._spec_k, moe_constraint))
            if self.kv_pool is not None:
                self._paged_verify_jit = jax.jit(functools.partial(
                    _paged_verify, cfg, gconfig, eos_token_id,
                    self._spec_k, moe_constraint, self.kv_pool.meta))

        nm = gconfig.max_new_tokens
        if self.kv_pool is not None:
            # paged: the pool owns the KV rows; per-slot state keeps
            # only the write index ("length" in window coordinates --
            # compacted, so row j holds token j and validity is just
            # j < length)
            kv_state = dict(length=jnp.zeros((n_slots,), jnp.int32))
        else:
            dense_dt = {None: None, "fp32": jnp.float32,
                        "bf16": jnp.bfloat16}[kv_cache_dtype]
            kv_state = dict(cache=T.init_kv_cache(
                cfg, n_slots, self.cache_len, dtype=dense_dt))
        self.state = dict(
            **kv_state,
            last_hidden=jnp.zeros((n_slots, cfg.hidden_dim),
                                  jnp.dtype(cfg.compute_dtype)),
            prompt_len=jnp.zeros((n_slots,), jnp.int32),
            emitted=jnp.zeros((n_slots,), jnp.int32),
            active=jnp.zeros((n_slots,), bool),
            unfinished=jnp.zeros((n_slots,), bool),
            hit_eos=jnp.zeros((n_slots,), bool),
            out_tokens=jnp.full((n_slots, nm), pad_token_id, jnp.int32),
            out_logprobs=jnp.zeros((n_slots, nm), jnp.float32),
            spec_proposed=jnp.zeros((n_slots,), jnp.int32),
            spec_accepted=jnp.zeros((n_slots,), jnp.int32),
        )
        self._slot_req = [-1] * n_slots  # host: request id per slot
        #: host copy of each slot's prompt: the n-gram drafter needs
        #: the full history, and the scheduler needs it to key KV
        #: publications
        self._slot_prompt: List[Optional[np.ndarray]] = [None] * n_slots
        #: how the last fill_slot was lowered (bucket REGRESSION
        #: surface: a 95%-cached prompt must compile/pay the SUFFIX
        #: bucket, not the full-prompt one)
        self.last_fill: Dict = {}
        self.fill_stats = dict(prefill_tokens=0, prefill_tokens_saved=0,
                               bucket_pairs=0, bucket_pairs_capped=0)
        self.spec_stats = dict(rounds=0)

        self._decode_chunk = jax.jit(functools.partial(
            _decode_chunk, cfg, gconfig, eos_token_id, pad_token_id,
            chunk_size, moe_constraint, mesh))

    # ------------------------------------------------------------------
    # Slot-level step API. The serving subsystem
    # (``realhf_tpu/serving/scheduler.py``) drives these directly to
    # interleave admission, decoding, and harvesting at iteration
    # granularity; ``generate_all`` below is the run-to-completion
    # composition of the same primitives.
    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        """Slot indices with no request bound to them."""
        return [s for s, r in enumerate(self._slot_req) if r < 0]

    @property
    def n_live(self) -> int:
        """Slots currently bound to a request (decoding or awaiting
        harvest)."""
        return sum(1 for r in self._slot_req if r >= 0)

    def decode_chunk(self, key: jax.Array):
        """Advance every live slot by up to ``chunk_size`` decode
        steps (one host<->device sync). With ``spec_decode_k > 0``
        (greedy only) the chunk runs speculative verify rounds
        instead: each round drafts k tokens per slot on the host
        (prompt lookup) and verifies them in ONE forward, emitting
        1..k+1 tokens per live slot per device call.

        Paged backends reserve pool blocks for the chunk's worst-case
        growth FIRST (host arithmetic, no device sync) and may raise
        :class:`~realhf_tpu.engine.kv_pool.KVPoolOOM` -- the serving
        scheduler relieves pool pressure (prefix-cache eviction, then
        sequence eviction) and retries."""
        if self._spec_k > 0 and self.n_live:
            self._spec_chunk()
        elif self.kv_pool is not None:
            self._paged_chunk(key)
        else:
            self.state = self._decode_chunk(self.params, self.state,
                                            key)

    # -- paged-mode internals (engine/kv_pool.py) ----------------------
    def _win_for(self, need: int) -> int:
        """Gather-window length for the paged compute path: the
        maximum live length rounded up on the cache-row multiple, so
        the chunk compiles O(cache_len / 128) window shapes -- one
        per bucket, as the dense path does -- instead of one per
        distinct length."""
        if need <= 0:
            return 0
        m = T._CACHE_LEN_MULTIPLE
        return min(self.cache_len, -(-need // m) * m)

    def _bt_device(self):
        if self._bt_dev is None:
            self._bt_dev = jax.device_put(self._bt_host)
        return self._bt_dev

    def _ensure_capacity(self, growth: int) -> int:
        """Reserve pool blocks so every live slot can append up to
        ``growth`` rows without a mid-chunk allocation (block tables
        are frozen inside jit). Raises :class:`KVPoolOOM` on
        exhaustion; earlier slots keep their new reservations (they
        are real and freed at harvest). Returns the gather-window
        length covering the post-chunk worst case."""
        nm = self.g.max_new_tokens
        need_max = 0
        for slot in range(self.n_slots):
            if self._slot_req[slot] < 0:
                continue
            n = self._slot_prompt_n[slot]
            cap_rows = min(self._slot_rows_ub[slot] + growth,
                           n + nm, self.cache_len)
            have = len(self._slot_blocks[slot])
            need = self.kv_pool.blocks_for_rows(cap_rows) - have
            if need > 0:
                new = self.kv_pool.alloc(need)  # may raise KVPoolOOM
                self._slot_blocks[slot].extend(new)
                self._bt_host[slot, have:have + len(new)] = new
                self._bt_dev = None
            self._slot_rows_ub[slot] = cap_rows
            need_max = max(need_max, cap_rows)
        return self._win_for(need_max)

    def _paged_chunk(self, key):
        win = self._ensure_capacity(self.chunk)
        if win == 0:
            return
        warange = jnp.arange(win, dtype=jnp.int32)
        arrays, self.state = self._paged_decode_jit(
            self.params, self.kv_pool.arrays(), self.state,
            self._bt_device(), warange, key)
        self.kv_pool.update(arrays)

    def kv_pool_stats(self) -> Dict:
        """Pool accounting plus this generator's own row usage; the
        serving scheduler adds the prefix cache's rows on top to get
        the pool-wide fragmentation ratio."""
        s = self.kv_pool.stats()
        s["rows_in_use"] = sum(
            self._slot_rows_ub[i] for i in range(self.n_slots)
            if self._slot_req[i] >= 0)
        return s

    def admission_blocks_needed(self, prompt_len: int,
                                cached_len: int = 0) -> int:
        """Free-list blocks a fill of this shape will consume
        (aliased prefix blocks are shared, not allocated), plus one
        headroom block for the first decode chunk. The scheduler
        admission gate compares this against the pool's free count."""
        c = max(0, min(int(cached_len), int(prompt_len) - 1))
        c -= c % self._blen
        return (self.kv_pool.blocks_for_rows(prompt_len)
                - c // self._blen + 1)

    def _spec_chunk(self):
        """ceil(chunk / (k+1)) verify rounds == the plain chunk's
        token budget when every draft is accepted. Each round pays one
        bundled D2H (the drafter consumes the history on the host) and
        one verify forward -- versus ``chunk`` sequential forwards on
        the plain path."""
        nm = self.g.max_new_tokens
        rounds = -(-self.chunk // (self._spec_k + 1))
        for _ in range(rounds):
            # host drafting needs the emitted tokens each round; this
            # is the one bundled readback the speculative loop is
            # built around (it replaces k+1 sequential forwards)
            host = self._host_view()  # graft-lint: disable=purity-sync-in-loop
            drafts = np.zeros((self.n_slots, self._spec_k), np.int32)
            n_live = 0
            for slot in range(self.n_slots):
                if (self._slot_req[slot] < 0
                        or not host["active"][slot]
                        or not host["unfinished"][slot]
                        or host["emitted"][slot] >= nm):
                    continue
                n_live += 1
                e = int(host["emitted"][slot])
                hist = np.concatenate(
                    [self._slot_prompt[slot],
                     host["out_tokens"][slot, :e].astype(np.int64)])
                drafts[slot] = self._drafter.propose(hist)
            if n_live == 0:
                break
            with tracing.span("serve:spec_verify", n_live=n_live,
                              k=self._spec_k):
                if self.kv_pool is not None:
                    # the per-round host view gives EXACT lengths --
                    # tighten the row upper bounds before reserving
                    # this round's worst-case growth (k+1 rows/slot)
                    for slot in range(self.n_slots):
                        if self._slot_req[slot] >= 0:
                            self._slot_rows_ub[slot] = (
                                self._slot_prompt_n[slot]
                                + int(host["emitted"][slot]))
                    win = self._ensure_capacity(self._spec_k + 1)
                    warange = jnp.arange(win, dtype=jnp.int32)
                    arrays, self.state = self._paged_verify_jit(
                        self.params, self.kv_pool.arrays(),
                        self.state, self._bt_device(), warange,
                        jnp.asarray(drafts))
                    self.kv_pool.update(arrays)
                else:
                    self.state = self._verify(self.params, self.state,
                                              jnp.asarray(drafts))
            self.spec_stats["rounds"] += 1

    def swap_params(self, params):
        """Hot-swap the weights used from the next decode/prefill on.
        Safe between ``decode_chunk`` calls: the jitted programs take
        params as an argument, so no recompilation happens as long as
        shapes/dtypes match."""
        self.params = params

    def release_slot(self, slot: int):
        """Abort the sequence in ``slot`` (cancellation/eviction): the
        slot immediately becomes free and the partial output is
        dropped. Paged backends return the slot's pool blocks to the
        free list (aliased prefix blocks just drop one reference)."""
        self._slot_req[slot] = -1
        self._slot_prompt[slot] = None
        if self.kv_pool is not None and self._slot_blocks[slot]:
            self.kv_pool.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._bt_host[slot, :] = 0
            self._bt_dev = None
            self._slot_rows_ub[slot] = 0
            self._slot_prompt_n[slot] = 0
        self.state["active"] = self.state["active"].at[slot].set(False)

    def _host_view(self) -> Dict[str, np.ndarray]:
        """ONE bundled D2H fetch of every per-slot output/status
        array. Per-slot ``np.asarray`` reads pay a blocking sync
        each; harvesting N finished slots that way costs 4N transfers
        per chunk. The bundle is a few
        n_slots x max_new_tokens int/float arrays, so downloading all
        of it beats per-slot slicing as soon as more than one value is
        read."""
        return jax.device_get({
            k: self.state[k]
            for k in ("active", "unfinished", "emitted", "hit_eos",
                      "out_tokens", "out_logprobs", "spec_proposed",
                      "spec_accepted")})

    def snapshot_slot(self, slot: int):
        """(tokens_so_far, logprobs_so_far) of the sequence in
        ``slot`` -- the incremental-streaming read. One device sync;
        use :meth:`snapshot_slots` to read several slots per chunk."""
        return self.snapshot_slots([slot])[slot]

    def snapshot_slots(self, slots: List[int]) -> Dict[int, tuple]:
        """slot -> (tokens_so_far, logprobs_so_far) for every
        requested slot via ONE bundled device fetch (the serving
        scheduler streams every live slot after each chunk; per-slot
        reads would pay one sync round-trip each)."""
        if not slots:
            return {}
        host = self._host_view()
        out: Dict[int, tuple] = {}
        for slot in slots:
            n = int(host["emitted"][slot])
            out[slot] = (host["out_tokens"][slot, :n],
                         host["out_logprobs"][slot, :n])
        return out

    def harvest(self, export_kv: bool = False,
                export_blocks: bool = False) -> List[FinishedSequence]:
        """Collect every finished sequence and free its slot (one
        bundled host transfer, not four per finished slot).

        ``export_kv=True`` additionally downloads each finished slot's
        KV rows (prompt + generated, in token order) in ONE bundled
        fetch and attaches them as ``FinishedSequence.kv`` so the
        serving scheduler can publish them into the radix prefix
        cache. This is a full slot-cache D2H -- only ask for it when a
        prefix cache is actually configured.

        ``export_blocks=True`` (paged backends only) attaches each
        finished slot's pool block ids instead -- ZERO device
        transfer: publication into the pooled prefix cache is pure
        refcount bookkeeping. Each listed block carries one extra
        pool reference owned by the caller, who must
        ``kv_pool.free(fs.blocks)`` once done publishing."""
        out: List[FinishedSequence] = []
        if self.n_live == 0:
            return out
        host = self._host_view()
        slots: List[int] = []
        for slot in range(self.n_slots):
            rid = self._slot_req[slot]
            if rid < 0 or (host["active"][slot]
                           and host["unfinished"][slot]):
                continue
            n = int(host["emitted"][slot])
            out.append(FinishedSequence(
                request_id=rid,
                tokens=host["out_tokens"][slot, :n],
                logprobs=host["out_logprobs"][slot, :n],
                no_eos=not bool(host["hit_eos"][slot]),
                spec_proposed=int(host["spec_proposed"][slot]),
                spec_accepted=int(host["spec_accepted"][slot])))
            slots.append(slot)
        if export_blocks and slots:
            if self.kv_pool is None:
                raise ValueError(
                    "export_blocks requires a paged (KV-pool) backend")
            for fs, slot in zip(out, slots):
                blocks = tuple(self._slot_blocks[slot])
                self.kv_pool.incref(blocks)  # receiver-owned refs
                fs.blocks = blocks
                fs.n_rows = (self._slot_prompt_n[slot]
                             + int(host["emitted"][slot]))
        if export_kv and slots:
            if self.kv_pool is not None:
                self._export_pool_kv(out, slots, host)
            else:
                idx = jnp.asarray(slots)
                cache = self.state["cache"]
                kv = jax.device_get(dict(k=cache["k"][:, idx],
                                         v=cache["v"][:, idx],
                                         valid=cache["valid"][idx]))
                for i, fs in enumerate(out):
                    # valid rows in row order ARE token order: donor
                    # prefix rows, then the left-padded suffix's real
                    # tail, then sequentially appended decode rows
                    rows = np.flatnonzero(kv["valid"][i])
                    fs.kv = (np.ascontiguousarray(
                                 kv["k"][:, i][:, :, rows, :]),
                             np.ascontiguousarray(
                                 kv["v"][:, i][:, :, rows, :]))
        for slot in slots:
            self.release_slot(slot)
        return out

    def _export_pool_kv(self, out: List[FinishedSequence],
                        slots: List[int], host):
        """Paged counterpart of the dense KV export: one bundled D2H
        of every finished slot's pool rows, dequantized on the host
        for int8 pools (the host radix cache stores values)."""
        blen = self._blen
        flats, counts = [], []
        for slot in slots:
            rows = (self._slot_prompt_n[slot]
                    + int(host["emitted"][slot]))
            w = np.arange(rows)
            flats.append(self._bt_host[slot, w // blen] * blen
                         + w % blen)
            counts.append(rows)
        all_rows = np.concatenate(flats) if flats else np.zeros(0, int)
        arrays = self.kv_pool.arrays()
        fetch = dict(k=arrays["k"][:, :, all_rows],
                     v=arrays["v"][:, :, all_rows])
        if self.kv_pool.meta.quant:
            fetch["ks"] = arrays["k_scale"][:, :, all_rows]
            fetch["vs"] = arrays["v_scale"][:, :, all_rows]
        got = jax.device_get(fetch)
        k, v = got["k"], got["v"]
        if self.kv_pool.meta.quant:
            k = k.astype(np.float32) * got["ks"][..., None]
            v = v.astype(np.float32) * got["vs"][..., None]
        off = 0
        for fs, rows in zip(out, counts):
            fs.kv = (np.ascontiguousarray(k[:, :, off:off + rows, :]),
                     np.ascontiguousarray(v[:, :, off:off + rows, :]))
            off += rows

    @property
    def max_prompt_len(self) -> int:
        """Longest admissible prompt: the cache row minus the decode
        budget. Admission layers (serving.RequestQueue) check this so
        oversized prompts are rejected before reaching a slot."""
        return self.cache_len - self.g.max_new_tokens

    # ------------------------------------------------------------------
    def fill_slot(self, slot: int, request_id: int,
                  prompt: np.ndarray, cached_len: int = 0,
                  prefix_kv=None, cached_blocks=None):
        """Prefill ``prompt`` into ``slot``. With ``cached_len > 0``
        the first ``cached_len`` positions are seeded from ``prefix_kv``
        (``(k, v)``, each ``[nl, nkv, >=cached_len, hd]`` host arrays
        from the radix prefix cache) and ONLY the uncached suffix runs
        the forward -- bucketed by SUFFIX length, so a 95%-hit request
        compiles and pays the small bucket, not the full-prompt one.

        Paged backends take ``cached_blocks`` (pool block ids from the
        POOLED prefix cache) instead of ``prefix_kv``: whole cached
        blocks are aliased into the slot's block table -- a refcount
        bump, zero KV copy -- and only the suffix runs the forward."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = len(prompt)
        max_prompt = self.max_prompt_len
        if n > max_prompt:
            raise ValueError(
                f"prompt of {n} tokens exceeds max_prompt_len "
                f"{max_prompt}")
        if self.kv_pool is not None:
            if prefix_kv is not None:
                raise ValueError(
                    "paged backends alias pool blocks; pass "
                    "cached_blocks (not prefix_kv)")
            self._fill_slot_paged(slot, request_id, prompt,
                                  int(cached_len), cached_blocks)
            return
        if cached_blocks is not None:
            raise ValueError("cached_blocks requires a paged (KV-"
                             "pool) backend")
        c = int(cached_len)
        if c > 0 and prefix_kv is None:
            raise ValueError("cached_len > 0 requires prefix_kv")
        # the hidden state feeding the first decode step is NOT in the
        # KV cache: at least one real token must always prefill
        c = min(c, n - 1)
        nm = self.g.max_new_tokens
        c_b = s_b = 0
        while c > 0:
            # donor rows are padded to their own bucket so jit sees a
            # bounded set of (donor, suffix) shapes instead of one
            # compilation per distinct cached_len
            c_b = _bucket(c, _PARTIAL_BUCKETS)
            s_b = _bucket(n - c, _PARTIAL_BUCKETS)
            if c_b + s_b + nm <= self.cache_len:
                break
            # donor rounding overflows the cache row: TRIM the donor
            # to the next-lower bucket boundary (a shorter cached
            # prefix is still a valid prefix) rather than throwing
            # the whole hit away
            smaller = [b for b in _PARTIAL_BUCKETS if b < c_b]
            c = smaller[-1] if smaller else 0
        if c > 0 and not self._pair_admit(c_b, s_b):
            c = 0  # compile-cache cap: fall back to full prefill
        if c <= 0:
            lp = min(_bucket(n), max_prompt)
            ids = np.full((1, lp), self.pad, np.int32)
            seg = np.zeros((1, lp), np.int32)
            pos = np.zeros((1, lp), np.int32)
            ids[0, lp - n:] = prompt          # left padding
            seg[0, lp - n:] = 1
            pos[0, lp - n:] = np.arange(n)
            # one bundled upload (see Engine._globalize_tree).
            # `slot` keeps its host int for the list index below --
            # indexing with a device scalar would force a blocking
            # D2H readback per fill.
            with tracing.span("serve:prefill", slot=slot,
                              prompt_len=n, bucket=lp):
                dev_slot, ids, seg, pos = jax.device_put(
                    (slot, ids, seg, pos))
                self.state = self._prefill(self.params, self.state,
                                           dev_slot, ids, seg, pos)
            self.last_fill = dict(bucket=lp, prompt_len=n,
                                  cached_len=0, prefilled=n)
            self.fill_stats["prefill_tokens"] += n
        else:
            s = n - c
            kdtype = self.state["cache"]["k"].dtype
            dk = np.zeros((self.cfg.kv_layers, self.cfg.n_kv_heads,
                           c_b, self.cfg.head_dim), kdtype)
            dv = np.zeros_like(dk)
            dk[:, :, :c] = np.asarray(prefix_kv[0])[:, :, :c]
            dv[:, :, :c] = np.asarray(prefix_kv[1])[:, :, :c]
            dvalid = np.arange(c_b) < c
            ids = np.full((1, s_b), self.pad, np.int32)
            seg = np.zeros((1, s_b), np.int32)
            pos = np.zeros((1, s_b), np.int32)
            ids[0, s_b - s:] = prompt[c:]        # left padding within
            seg[0, s_b - s:] = 1                 # the suffix window
            pos[0, s_b - s:] = c + np.arange(s)
            with tracing.span("serve:prefill", slot=slot,
                              prompt_len=n, bucket=s_b, cached_len=c):
                dev = jax.device_put((slot, dk, dv, dvalid, ids, seg,
                                      pos))
                self.state = self._prefill_suffix(self.params,
                                                  self.state, *dev)
            self.last_fill = dict(bucket=s_b, prompt_len=n,
                                  cached_len=c, prefilled=s)
            self.fill_stats["prefill_tokens"] += s
            self.fill_stats["prefill_tokens_saved"] += c
        self._slot_req[slot] = request_id
        self._slot_prompt[slot] = prompt

    def _pair_admit(self, c_b: int, s_b: int) -> bool:
        """Admission to the partial-prefill compile cache (satellite:
        the ``(c_b, s_b)`` ladder product is 81 shapes -- each one a
        full jit compile -- and nothing bounded it). Known pairs pass;
        new pairs past ``bucket_pair_cap`` fall back to full prefill
        with one explicit warning, counted in ``fill_stats``."""
        pair = (c_b, s_b)
        if pair in self._bucket_pairs:
            return True
        if len(self._bucket_pairs) >= self.bucket_pair_cap:
            if not self._bucket_cap_warned:
                logger.warning(
                    "partial-prefill compile cache hit its cap (%d "
                    "distinct (donor, suffix) bucket pairs); further "
                    "new shapes fall back to full prefill instead of "
                    "growing the jit cache unboundedly. Raise "
                    "bucket_pair_cap if the traffic mix really needs "
                    "more shapes.", self.bucket_pair_cap)
                self._bucket_cap_warned = True
            self.fill_stats["bucket_pairs_capped"] += 1
            return False
        self._bucket_pairs.add(pair)
        self.fill_stats["bucket_pairs"] = len(self._bucket_pairs)
        return True

    def _fill_slot_paged(self, slot: int, request_id: int,
                         prompt: np.ndarray, cached_len: int,
                         cached_blocks):
        """Paged fill: alias whole cached blocks (refcount bump, zero
        copy), allocate own blocks for the rest of the window, then
        run either the full prefill or the suffix forward, scattering
        the computed rows into the pool. May raise
        :class:`~realhf_tpu.engine.kv_pool.KVPoolOOM`."""
        n = len(prompt)
        blen = self._blen
        # whole-block aliasing only: a partial tail block would be
        # appended into by this sequence and corrupt the shared copy,
        # so the hit is trimmed to the block boundary (< blen tokens
        # of re-prefill, by construction)
        c = max(0, min(int(cached_len), n - 1))
        c -= c % blen
        c_b = s_b = 0
        if c > 0 and cached_blocks is None:
            raise ValueError(
                "cached_len > 0 requires cached_blocks on a paged "
                "backend")
        if c > 0:
            c_b = _bucket(c, _PARTIAL_BUCKETS)
            s_b = _bucket(n - c, _PARTIAL_BUCKETS)
            if not self._pair_admit(c_b, s_b):
                c = 0
        n_alias = c // blen
        if c > 0 and len(cached_blocks) < n_alias:
            raise ValueError(
                f"cached_blocks covers {len(cached_blocks)} block(s) "
                f"but cached_len {c} spans {n_alias}")
        own = self.kv_pool.alloc(
            self.kv_pool.blocks_for_rows(n) - n_alias)
        try:
            alias = [int(b) for b in cached_blocks[:n_alias]] \
                if c > 0 else []
            if alias:
                self.kv_pool.incref(alias)
        except BaseException:
            # a bad alias chain (stale cached block id) must not leak
            # the freshly-allocated blocks: nothing references them
            # yet, so release_slot could never reclaim them
            self.kv_pool.free(own)
            raise
        blocks = alias + own
        self._slot_blocks[slot] = blocks
        self._bt_host[slot, :] = 0
        self._bt_host[slot, :len(blocks)] = blocks
        self._bt_dev = None
        self._slot_rows_ub[slot] = n
        self._slot_prompt_n[slot] = n
        # bind BEFORE the forward so a failure below leaves a state
        # release_slot() fully cleans up (blocks included)
        self._slot_req[slot] = request_id
        self._slot_prompt[slot] = prompt
        bt_row = self._bt_host[slot]
        if c <= 0:
            lp = min(_bucket(n), self.max_prompt_len)
            ids = np.full((1, lp), self.pad, np.int32)
            seg = np.zeros((1, lp), np.int32)
            pos = np.zeros((1, lp), np.int32)
            ids[0, lp - n:] = prompt          # left padding
            seg[0, lp - n:] = 1
            pos[0, lp - n:] = np.arange(n)
            warange = np.arange(lp, dtype=np.int32)
            with tracing.span("serve:prefill", slot=slot,
                              prompt_len=n, bucket=lp, paged=True):
                dev = jax.device_put((ids, seg, pos, bt_row, warange))
                arrays, self.state = self._paged_fill_jit(
                    self.params, self.kv_pool.arrays(), self.state,
                    jnp.int32(slot), *dev)
            self.kv_pool.update(arrays)
            self.last_fill = dict(bucket=lp, prompt_len=n,
                                  cached_len=0, prefilled=n)
            self.fill_stats["prefill_tokens"] += n
        else:
            s = n - c
            ids = np.full((1, s_b), self.pad, np.int32)
            seg = np.zeros((1, s_b), np.int32)
            pos = np.zeros((1, s_b), np.int32)
            ids[0, s_b - s:] = prompt[c:]        # left padding within
            seg[0, s_b - s:] = 1                 # the suffix window
            pos[0, s_b - s:] = c + np.arange(s)
            warange_c = np.arange(c_b, dtype=np.int32)
            with tracing.span("serve:prefill", slot=slot,
                              prompt_len=n, bucket=s_b, cached_len=c,
                              paged=True):
                dev = jax.device_put(
                    (ids, seg, pos, bt_row, warange_c, np.int32(c)))
                ids_d, seg_d, pos_d, bt_d, wc_d, c_d = dev
                arrays, self.state = self._paged_suffix_jit(
                    self.params, self.kv_pool.arrays(), self.state,
                    jnp.int32(slot), bt_d, wc_d, c_d, ids_d, seg_d,
                    pos_d)
            self.kv_pool.update(arrays)
            self.last_fill = dict(bucket=s_b, prompt_len=n,
                                  cached_len=c, prefilled=s)
            self.fill_stats["prefill_tokens"] += s
            self.fill_stats["prefill_tokens_saved"] += c

    # ------------------------------------------------------------------
    def generate_all(self, prompts: List[np.ndarray], key: jax.Array
                     ) -> List[FinishedSequence]:
        """Run the queue to completion; results in request order."""
        queue = list(enumerate(prompts))[::-1]  # pop() takes req 0 first
        results: Dict[int, FinishedSequence] = {}

        while queue or self.n_live:
            for slot in self.free_slots():
                if not queue:
                    break
                rid, p = queue.pop()
                self.fill_slot(slot, rid, p)
            key, sub = jax.random.split(key)
            self.decode_chunk(sub)
            # host sync once per chunk: harvest finished slots
            for fs in self.harvest():
                results[fs.request_id] = fs
        return [results[i] for i in range(len(prompts))]


# ----------------------------------------------------------------------
# jitted pieces
# ----------------------------------------------------------------------
def _prefill_into_slot(cfg, cache_len, moe_constraint, attention_fn,
                       params, state, slot, ids, seg, pos):
    """Batch-1 prefill scattered into `slot`'s cache rows + state."""
    # total_len=cache_len: the prefill cache comes back already padded
    # to the slot's row length (cache_len is round_cache_len-aligned by
    # the constructor, so prefill's own rounding is a no-op).
    hidden, pcache = T.prefill(cfg, params, ids, seg, pos,
                               total_len=cache_len,
                               attention_fn=attention_fn,
                               moe_constraint=moe_constraint)
    lp = ids.shape[1]
    pad_s = cache_len - lp

    cache = dict(state["cache"])
    cache["k"] = cache["k"].at[:, slot].set(pcache["k"][:, 0])
    cache["v"] = cache["v"].at[:, slot].set(pcache["v"][:, 0])
    cache["valid"] = cache["valid"].at[slot].set(
        jnp.pad(seg[0] != 0, (0, pad_s)))
    plen = (seg[0] != 0).sum().astype(jnp.int32)
    cache["length"] = cache["length"].at[slot].set(lp)  # write index
    new = dict(state)
    new["cache"] = cache
    new["last_hidden"] = state["last_hidden"].at[slot].set(hidden[0, -1])
    new["prompt_len"] = state["prompt_len"].at[slot].set(plen)
    new["emitted"] = state["emitted"].at[slot].set(0)
    new["active"] = state["active"].at[slot].set(True)
    new["unfinished"] = state["unfinished"].at[slot].set(True)
    new["hit_eos"] = state["hit_eos"].at[slot].set(False)
    new["out_tokens"] = state["out_tokens"].at[slot].set(
        jnp.full((state["out_tokens"].shape[1],), 0, jnp.int32))
    new["out_logprobs"] = state["out_logprobs"].at[slot].set(0.0)
    new["spec_proposed"] = state["spec_proposed"].at[slot].set(0)
    new["spec_accepted"] = state["spec_accepted"].at[slot].set(0)
    return new


def _extend_rows(cfg, moe_constraint, params, k_all, v_all, valid0,
                 tokens, positions, rows, tok_mask):
    """Multi-token carry extension: run ``m`` new tokens per stream
    through the transformer IN ONE forward against the existing KV
    rows -- the shared primitive under partial prefill (suffix after a
    radix-cache donor) and speculative verify (k drafts + 1 committed
    token). ``decode_step`` is the ``m == 1`` special case of this.

    k_all/v_all: [nl, B, nkv, S, hd] rows (the full slot batch for
    verify; a batch-1 local window for suffix prefill).
    valid0: [B, S] validity BEFORE the new tokens.
    tokens/positions/rows: [B, m]; ``rows`` are the cache rows the new
    tokens write (pre-clamped to S-1 by the caller).
    tok_mask: [B, m] -- False lanes (padding / capped lanes) neither
    write KV nor count; their hidden outputs are garbage and must not
    be read.

    Returns (hidden [B, m, H] after the final norm, k_all, v_all).
    Attention is the plain XLA einsum path (scores masked per query:
    old valid rows plus new rows i <= j); on TPU meshes GSPMD
    partitions it like any other einsum -- the Pallas single-query
    decode kernels stay on the one-token hot path."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, m = tokens.shape
    s_len = valid0.shape[1]

    x = params["embed"]["wte"].astype(cdt)[tokens]  # [B, m, H]
    if cfg.uses_absolute_position:
        x = x + params["embed"]["wpe"].astype(cdt)[
            positions + cfg.abs_position_embedding_offset]
    if cfg.normalize_embed:
        x = x * jnp.asarray(cfg.hidden_dim ** 0.5, dtype=cdt)

    if cfg.apply_rotary:
        cos, sin = T.rotary_table(cfg, positions)
    else:
        half = cfg.head_dim // 2
        cos = jnp.ones((b, m, half), jnp.float32)
        sin = jnp.zeros((b, m, half), jnp.float32)

    # per-query attendable rows: everything valid before this call,
    # plus new rows written at lane i <= the query's lane j
    written = ((rows[:, :, None] == jnp.arange(s_len)[None, None, :])
               & tok_mask[:, :, None])                     # [B, m, S]
    upto = jnp.cumsum(written.astype(jnp.int32), axis=1) > 0
    qmask = valid0[:, None, :] | upto
    if cfg.sliding_window is not None:
        idx = jnp.arange(s_len, dtype=jnp.int32)[None, None, :]
        qmask = qmask & ((rows[:, :, None] - idx) < cfg.sliding_window)

    barr = jnp.arange(b)[:, None]
    group = cfg.n_q_heads // cfg.n_kv_heads

    def layer_body(x, k_all, v_all, lp, layer_idx, static_l=None):
        ln1 = O._norm(cfg, x, lp["ln1"]["scale"], lp["ln1"].get("bias"))
        q, k, v = O._qkv(cfg, lp, ln1)  # q [B,m,nq,hd]; k/v [B,m,nkv,hd]
        if cfg.apply_rotary:
            q = O.apply_rotary(q, cos, sin, cfg.rotary_interleaved)
            k = O.apply_rotary(k, cos, sin, cfg.rotary_interleaved)
        l = layer_idx if static_l is None else static_l
        k_l = k_all[l]  # [B, nkv, S, hd]
        v_l = v_all[l]
        # masked scatter of the new rows: padded lanes share clamped
        # row indices, so their writes must keep the existing values
        kw = k.astype(k_l.dtype)
        vw = v.astype(v_l.dtype)
        keep = tok_mask[:, :, None, None]
        cur_k = k_l[barr, :, rows]      # [B, m, nkv, hd]
        cur_v = v_l[barr, :, rows]
        k_l = k_l.at[barr, :, rows].set(jnp.where(keep, kw, cur_k))
        v_l = v_l.at[barr, :, rows].set(jnp.where(keep, vw, cur_v))
        k_all = k_all.at[l].set(k_l)
        v_all = v_all.at[l].set(v_l)
        base = cfg.head_dim ** -0.5 if cfg.scale_attn_weights else 1.0
        if not cfg.scale_attn_by_inverse_layer_idx:
            scale = base
        elif static_l is not None:
            scale = base / (static_l + 1)
        else:
            scale = O._attn_scale(cfg, layer_idx)
        qg = q.reshape(b, m, cfg.n_kv_heads, group, cfg.head_dim)
        scores = jnp.einsum("bmhgd,bhsd->bmhgs", qg, k_l,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(qmask[:, :, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bmhgs,bhsd->bmhgd",
                          probs.astype(v_l.dtype), v_l)
        proj = attn.reshape(b, m, -1) @ lp["attn"]["wo"].astype(x.dtype)
        if "bo" in lp["attn"]:
            proj = proj + lp["attn"]["bo"].astype(x.dtype)
        x = x + T._post_norm(cfg, lp, "ln1_post", proj)
        ln2 = O._norm(cfg, x, lp["ln2"]["scale"], lp["ln2"].get("bias"))
        x = x + T._post_norm(cfg, lp, "ln2_post",
                             T._mlp(cfg, lp, ln2, moe_constraint))
        return x, k_all, v_all

    # a looped model walks the stack once a pass over the same
    # weights, pass t layer l on row t x n_layers + l of the K/V stack,
    # the final norm after every pass (models/transformer.py:_passes)
    for first in range(0, cfg.kv_layers, cfg.n_layers):
        if first:
            x = O._norm(cfg, x, params["ln_f"]["scale"],
                        params["ln_f"].get("bias"))
        if cfg.kv_layers <= T._DECODE_UNROLL_MAX_LAYERS:
            for li in range(cfg.n_layers):
                lp = jax.tree_util.tree_map(lambda a: a[li],
                                            params["blocks"])
                x, k_all, v_all = layer_body(x, k_all, v_all, lp,
                                             first + li,
                                             static_l=first + li)
        else:
            def body(carry, layer):
                xc, kc, vc = carry
                lp, layer_idx = layer
                xc, kc, vc = layer_body(xc, kc, vc, lp, layer_idx)
                return (xc, kc, vc), None

            layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            if first:
                layer_ids = layer_ids + first
            (x, k_all, v_all), _ = jax.lax.scan(
                body, (x, k_all, v_all), (params["blocks"], layer_ids))
    x = O._norm(cfg, x, params["ln_f"]["scale"],
                params["ln_f"].get("bias"))
    return x, k_all, v_all


def _prefill_suffix_into_slot(cfg, cache_len, moe_constraint, params,
                              state, slot, donor_k, donor_v,
                              donor_valid, ids, seg, pos):
    """Partial prefill for a radix prefix-cache hit: donor KV seeds a
    local window's rows [0, c_b); the left-padded suffix runs
    :func:`_extend_rows` against it (rows [c_b, c_b + s_b)); the
    finished window then scatters into ``slot``'s cache rows. One
    compilation per (c_b, s_b) bucket pair."""
    nl, nkv, c_b, hd = donor_k.shape
    s_b = ids.shape[1]
    win = c_b + s_b
    kdt = state["cache"]["k"].dtype
    local_k = jnp.concatenate(
        [donor_k[:, None].astype(kdt),
         jnp.zeros((nl, 1, nkv, s_b, hd), kdt)], axis=3)
    local_v = jnp.concatenate(
        [donor_v[:, None].astype(kdt),
         jnp.zeros((nl, 1, nkv, s_b, hd), kdt)], axis=3)
    valid0 = jnp.concatenate(
        [donor_valid[None, :], jnp.zeros((1, s_b), bool)], axis=1)
    rows = (c_b + jnp.arange(s_b, dtype=jnp.int32))[None, :]
    tok_mask = seg != 0
    hidden, local_k, local_v = _extend_rows(
        cfg, moe_constraint, params, local_k, local_v, valid0, ids,
        pos, rows, tok_mask)

    full_valid = jnp.zeros((cache_len,), bool)
    full_valid = full_valid.at[:c_b].set(donor_valid)
    full_valid = full_valid.at[c_b:win].set(seg[0] != 0)
    plen = (donor_valid.sum() + (seg[0] != 0).sum()).astype(jnp.int32)

    cache = dict(state["cache"])
    cache["k"] = cache["k"].at[:, slot, :, :win].set(local_k[:, 0])
    cache["v"] = cache["v"].at[:, slot, :, :win].set(local_v[:, 0])
    cache["valid"] = cache["valid"].at[slot].set(full_valid)
    cache["length"] = cache["length"].at[slot].set(win)  # write index
    new = dict(state)
    new["cache"] = cache
    new["last_hidden"] = state["last_hidden"].at[slot].set(
        hidden[0, -1])
    new["prompt_len"] = state["prompt_len"].at[slot].set(plen)
    new["emitted"] = state["emitted"].at[slot].set(0)
    new["active"] = state["active"].at[slot].set(True)
    new["unfinished"] = state["unfinished"].at[slot].set(True)
    new["hit_eos"] = state["hit_eos"].at[slot].set(False)
    new["out_tokens"] = state["out_tokens"].at[slot].set(
        jnp.full((state["out_tokens"].shape[1],), 0, jnp.int32))
    new["out_logprobs"] = state["out_logprobs"].at[slot].set(0.0)
    new["spec_proposed"] = state["spec_proposed"].at[slot].set(0)
    new["spec_accepted"] = state["spec_accepted"].at[slot].set(0)
    return new


# ----------------------------------------------------------------------
# paged (KV-pool) jitted pieces: gather the live window from the pool,
# run the SAME dense compute above on it, scatter written rows back.
# The compute path is therefore byte-identical math to the dense one
# (the fp32 bit-exactness guarantee); the pool only changes where rows
# LIVE, not how they are used. One gather/scatter pair per device call
# (chunk / verify round / fill), amortized over the chunk's steps.
# ----------------------------------------------------------------------
def _paged_window(meta, pool, bt, warange, length, cdt):
    """(flat_rows [B, win], dense cache dict) for the pool-backed
    window: row ``j < length[b]`` of sequence ``b`` is valid (windows
    are compacted -- token ``j`` lives at window row ``j``)."""
    rows = _kvp.window_rows(bt, warange, meta.block_len)
    k, v = _kvp.pool_gather(meta, pool, rows, cdt)
    valid = warange[None, :] < length[:, None]
    return rows, dict(k=k, v=v, valid=valid, length=length)


def _scatter_written(meta, pool, rows, cache, len0, m, mask_extra=None):
    """Write back the rows a chunk appended: window rows
    ``[len0, len0 + m)`` per sequence, masked to the actually-written
    count. Rolled-back (spec-rejected) rows scatter too -- they are
    invalid by ``length`` and will be overwritten, but their block is
    already owned, so this is harmless and keeps the mask simple."""
    win = rows.shape[1]
    j = jnp.arange(m, dtype=jnp.int32)[None, :]
    wrows = jnp.clip(len0[:, None] + j, 0, win - 1)
    mask = (len0[:, None] + j) < win
    if mask_extra is not None:
        mask = mask & mask_extra
    idx = wrows[None, :, None, :, None]
    kw = jnp.take_along_axis(cache["k"], idx, axis=3)
    vw = jnp.take_along_axis(cache["v"], idx, axis=3)
    flat = jnp.take_along_axis(rows, wrows, axis=1)
    return _kvp.pool_scatter(meta, pool, flat, kw, vw, mask)


def _paged_decode_chunk(cfg, g, eos, pad, chunk, moe_constraint, mesh,
                        meta, params, pool, state, bt, warange, key):
    """Paged decode chunk: gather -> dense ``_decode_chunk`` -> scatter
    the <= ``chunk`` new rows per slot back into the pool."""
    cdt = jnp.dtype(cfg.compute_dtype)
    rows, cache = _paged_window(meta, pool, bt, warange,
                                state["length"], cdt)
    st = {k2: v2 for k2, v2 in state.items() if k2 != "length"}
    st["cache"] = cache
    st = _decode_chunk(cfg, g, eos, pad, chunk, moe_constraint, mesh,
                       params, st, key)
    cache = st.pop("cache")
    len0 = state["length"]
    len1 = cache["length"]
    j = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    written = j < (len1 - len0)[:, None]
    pool = _scatter_written(meta, pool, rows, cache, len0, chunk,
                            mask_extra=written)
    st["length"] = len1
    return pool, st


def _paged_verify(cfg, g, eos, k_spec, moe_constraint, meta, params,
                  pool, state, bt, warange, drafts):
    """Paged speculative round: gather -> dense ``_verify_chunk`` ->
    scatter the round's <= k+1 rows per live slot back."""
    cdt = jnp.dtype(cfg.compute_dtype)
    rows, cache = _paged_window(meta, pool, bt, warange,
                                state["length"], cdt)
    st = {k2: v2 for k2, v2 in state.items() if k2 != "length"}
    st["cache"] = cache
    st = _verify_chunk(cfg, g, eos, k_spec, moe_constraint, params,
                       st, drafts)
    cache = st.pop("cache")
    live = (state["active"] & state["unfinished"]
            & (state["emitted"] < g.max_new_tokens))
    pool = _scatter_written(meta, pool, rows, cache, state["length"],
                            1 + k_spec, mask_extra=live[:, None])
    st["length"] = cache["length"]
    return pool, st


def _paged_prefill(cfg, moe_constraint, attention_fn, meta, params,
                   pool, state, slot, ids, seg, pos, bt_row, warange):
    """Full prefill into pool blocks. The batch-1 forward is the SAME
    left-padded bucketed ``T.prefill`` the dense path runs; its rows
    are then COMPACTED on scatter (window row ``p`` holds token ``p``)
    so every sequence shares the position->block-offset invariant the
    radix cache's whole-block aliasing depends on."""
    hidden, pcache = T.prefill(cfg, params, ids, seg, pos,
                               attention_fn=attention_fn,
                               moe_constraint=moe_constraint)
    lp = ids.shape[1]
    blen = meta.block_len
    n = (seg[0] != 0).sum().astype(jnp.int32)
    # prefill put token p at row lp - n + p (left padding); strip it
    src = jnp.clip(warange + (lp - n), 0, pcache["k"].shape[3] - 1)
    kc = pcache["k"][:, 0][:, :, src]            # [nl, nkv, lp, hd]
    vc = pcache["v"][:, 0][:, :, src]
    rows = (bt_row[warange // blen] * blen + warange % blen)[None, :]
    mask = (warange < n)[None, :]
    pool = _kvp.pool_scatter(meta, pool, rows, kc[:, None],
                             vc[:, None], mask)
    new = dict(state)
    new["length"] = state["length"].at[slot].set(n)
    new["last_hidden"] = state["last_hidden"].at[slot].set(hidden[0, -1])
    new["prompt_len"] = state["prompt_len"].at[slot].set(n)
    new["emitted"] = state["emitted"].at[slot].set(0)
    new["active"] = state["active"].at[slot].set(True)
    new["unfinished"] = state["unfinished"].at[slot].set(True)
    new["hit_eos"] = state["hit_eos"].at[slot].set(False)
    new["out_tokens"] = state["out_tokens"].at[slot].set(
        jnp.full((state["out_tokens"].shape[1],), 0, jnp.int32))
    new["out_logprobs"] = state["out_logprobs"].at[slot].set(0.0)
    new["spec_proposed"] = state["spec_proposed"].at[slot].set(0)
    new["spec_accepted"] = state["spec_accepted"].at[slot].set(0)
    return pool, new


def _paged_prefill_suffix(cfg, moe_constraint, meta, params, pool,
                          state, slot, bt_row, warange_c, c, ids, seg,
                          pos):
    """Partial prefill after whole-block aliasing: the donor rows are
    ALREADY in the slot's table (rows [0, c) -- a refcount bump put
    them there, no copy); gather them into a local window, run the
    suffix through :func:`_extend_rows` at window rows [c, c + s),
    and scatter only the suffix rows back. One compile per
    (donor-bucket, suffix-bucket) pair, same ladder as dense."""
    blen = meta.block_len
    c_b = warange_c.shape[0]
    s_b = ids.shape[1]
    cdt = jnp.dtype(cfg.compute_dtype)
    drows = (bt_row[warange_c // blen] * blen
             + warange_c % blen)[None, :]
    dk, dv = _kvp.pool_gather(meta, pool, drows, cdt)
    nl, _, nkv, _, hd = dk.shape
    local_k = jnp.concatenate(
        [dk, jnp.zeros((nl, 1, nkv, s_b, hd), cdt)], axis=3)
    local_v = jnp.concatenate(
        [dv, jnp.zeros((nl, 1, nkv, s_b, hd), cdt)], axis=3)
    valid0 = jnp.concatenate(
        [(warange_c < c)[None, :], jnp.zeros((1, s_b), bool)], axis=1)
    s = (seg[0] != 0).sum().astype(jnp.int32)
    lane = jnp.arange(s_b, dtype=jnp.int32)
    wrow = jnp.clip(c + lane - (s_b - s), 0,
                    c_b + s_b - 1)[None, :]       # suffix target rows
    tok_mask = seg != 0
    hidden, lk, lv = _extend_rows(cfg, moe_constraint, params,
                                  local_k, local_v, valid0, ids, pos,
                                  wrow, tok_mask)
    # window coords == local coords for the suffix (donor is [0, c)
    # in both): read the written lanes back out and scatter them into
    # the slot's own (freshly allocated, block-aligned) pool rows
    idx = wrow[None, :, None, :, None]
    kw = jnp.take_along_axis(lk, idx, axis=3)
    vw = jnp.take_along_axis(lv, idx, axis=3)
    flat = bt_row[wrow[0] // blen] * blen + wrow[0] % blen
    pool = _kvp.pool_scatter(meta, pool, flat[None, :], kw, vw,
                             tok_mask)
    plen = (c + s).astype(jnp.int32)
    new = dict(state)
    new["length"] = state["length"].at[slot].set(plen)
    new["last_hidden"] = state["last_hidden"].at[slot].set(
        hidden[0, -1])
    new["prompt_len"] = state["prompt_len"].at[slot].set(plen)
    new["emitted"] = state["emitted"].at[slot].set(0)
    new["active"] = state["active"].at[slot].set(True)
    new["unfinished"] = state["unfinished"].at[slot].set(True)
    new["hit_eos"] = state["hit_eos"].at[slot].set(False)
    new["out_tokens"] = state["out_tokens"].at[slot].set(
        jnp.full((state["out_tokens"].shape[1],), 0, jnp.int32))
    new["out_logprobs"] = state["out_logprobs"].at[slot].set(0.0)
    new["spec_proposed"] = state["spec_proposed"].at[slot].set(0)
    new["spec_accepted"] = state["spec_accepted"].at[slot].set(0)
    return pool, new


def _verify_chunk(cfg, g, eos, k_spec, moe_constraint, params, state,
                  drafts):
    """One speculative round: commit the greedy token from
    ``last_hidden`` (free -- no forward needed), then verify the k
    host-drafted tokens behind it in ONE :func:`_extend_rows` forward.
    Greedy-exact: a draft is accepted iff it equals the argmax the
    plain decode loop would have produced at that position, so the
    emitted stream is token-for-token identical to non-speculative
    greedy decoding; rejected tails are rolled back (rows invalidated,
    ``length`` rewound)."""
    nm = g.max_new_tokens
    m = 1 + k_spec
    st = state
    cache = st["cache"]
    s_len = cache["valid"].shape[1]
    b = drafts.shape[0]
    barr = jnp.arange(b)

    live = st["active"] & st["unfinished"] & (st["emitted"] < nm)

    # the committed token: identical math to _decode_chunk's body()
    logits0 = T.lm_logits(cfg, params, st["last_hidden"]) \
        .astype(jnp.float32)
    if eos is not None and g.min_new_tokens > 0:
        suppress = ((st["emitted"] < g.min_new_tokens)[:, None]
                    & (jnp.arange(logits0.shape[-1])[None, :] == eos))
        logits0 = jnp.where(suppress, NEG_INF, logits0)
    f0 = jnp.argmax(logits0, -1).astype(jnp.int32)
    logp0 = jnp.take_along_axis(jax.nn.log_softmax(logits0, -1),
                                f0[:, None], -1)[:, 0]

    tokens_seq = jnp.concatenate(
        [f0[:, None], drafts.astype(jnp.int32)], axis=1)  # [B, m]
    j = jnp.arange(m, dtype=jnp.int32)[None, :]
    allowed = jnp.clip(nm - st["emitted"], 0, m)           # [B]
    write_mask = live[:, None] & (j < allowed[:, None])
    rows = jnp.minimum(st["cache"]["length"][:, None] + j, s_len - 1)
    positions = st["prompt_len"][:, None] + st["emitted"][:, None] + j

    hidden, k_all, v_all = _extend_rows(
        cfg, moe_constraint, params, cache["k"], cache["v"],
        cache["valid"], tokens_seq, positions, rows, write_mask)

    logits = T.lm_logits(cfg, params, hidden).astype(jnp.float32)
    if eos is not None and g.min_new_tokens > 0:
        # position j's candidate is sampled with emitted0 + j + 1
        # tokens already out -- same suppression rule as the loop
        sup = ((st["emitted"][:, None] + j + 1 < g.min_new_tokens)
               [:, :, None]
               & (jnp.arange(logits.shape[-1])[None, None, :] == eos))
        logits = jnp.where(sup, NEG_INF, logits)
    cand = jnp.argmax(logits, -1).astype(jnp.int32)        # [B, m]
    # draft i (tokens_seq[:, i+1]) was sampled from position i's
    # logits (the state after consuming tokens_seq[0..i])
    logp_steps = jnp.take_along_axis(
        jax.nn.log_softmax(logits[:, :-1], -1),
        tokens_seq[:, 1:, None], -1)[:, :, 0]              # [B, k]
    # shift: draft i must equal the model's choice AFTER consuming
    # tokens_seq[0..i] (cand[:, i]); acceptance is prefix-closed
    draft_ok = tokens_seq[:, 1:] == cand[:, :-1]
    acc = jnp.cumprod(draft_ok.astype(jnp.int32), axis=1)
    n_emit = jnp.minimum(acc.sum(1) + 1, allowed)
    n_emit = jnp.where(live, n_emit, 0)
    hit_now = jnp.zeros((b,), bool)
    if eos is not None:
        is_eos = (tokens_seq == eos) & (j < n_emit[:, None])
        hit_now = is_eos.any(axis=1)
        first_eos = jnp.argmax(is_eos, axis=1)
        n_emit = jnp.where(hit_now,
                           jnp.minimum(n_emit, first_eos + 1), n_emit)

    emit_mask = j < n_emit[:, None]
    lps = jnp.concatenate([logp0[:, None], logp_steps], axis=1)
    # write emitted lanes into out[emitted0 : emitted0 + n_emit]
    # as a gather + where over the whole output row -- a scatter
    # would clamp out-of-range lanes onto live indices and the
    # duplicate-index write order is unspecified
    p = jnp.arange(st["out_tokens"].shape[1], dtype=jnp.int32)[None, :]
    rel = p - st["emitted"][:, None]                       # [B, nm]
    take = (rel >= 0) & (rel < n_emit[:, None])
    gidx = jnp.clip(rel, 0, m - 1)
    out_tokens = jnp.where(
        take, jnp.take_along_axis(tokens_seq, gidx, axis=1),
        st["out_tokens"])
    out_logprobs = jnp.where(
        take, jnp.take_along_axis(lps, gidx, axis=1),
        st["out_logprobs"])

    emitted = st["emitted"] + n_emit
    unfinished = st["unfinished"] & ~hit_now & (emitted < nm)
    hit_eos = st["hit_eos"] | hit_now

    # cache rollback: only the emitted lanes' rows stay valid; the
    # rejected tail's rows are overwritten by the next rounds anyway
    kept = ((rows[:, :, None] == jnp.arange(s_len)[None, None, :])
            & emit_mask[:, :, None]).any(axis=1)
    valid = cache["valid"] | kept
    length = cache["length"] + n_emit
    last_hidden = jnp.where(
        live[:, None],
        hidden[barr, jnp.maximum(n_emit - 1, 0)], st["last_hidden"])

    new_cache = dict(cache, k=k_all, v=v_all, valid=valid,
                     length=length)
    return dict(
        st, cache=new_cache, last_hidden=last_hidden, emitted=emitted,
        unfinished=unfinished, hit_eos=hit_eos, out_tokens=out_tokens,
        out_logprobs=out_logprobs,
        spec_proposed=st["spec_proposed"]
        + jnp.where(live, k_spec, 0).astype(jnp.int32),
        spec_accepted=st["spec_accepted"]
        + jnp.maximum(n_emit - 1, 0).astype(jnp.int32))


def _decode_chunk(cfg, g, eos, pad, chunk, moe_constraint, mesh, params,
                  state, key):
    """`chunk` decode steps over every slot (inactive/finished slots
    keep stepping on pad tokens but write nothing)."""
    nm = g.max_new_tokens

    def body(st, k):
        live = st["active"] & st["unfinished"] \
            & (st["emitted"] < nm)
        logits = T.lm_logits(cfg, params, st["last_hidden"]) \
            .astype(jnp.float32)
        if eos is not None and g.min_new_tokens > 0:
            suppress = ((st["emitted"] < g.min_new_tokens)[:, None]
                        & (jnp.arange(logits.shape[-1])[None, :] == eos))
            logits = jnp.where(suppress, NEG_INF, logits)
        if g.greedy:
            warped = logits
            tokens = jnp.argmax(warped, -1).astype(jnp.int32)
        else:
            warped = top_k_top_p_logits(logits / g.temperature,
                                        g.top_k, g.top_p)
            tokens = jax.random.categorical(k, warped, -1) \
                .astype(jnp.int32)
        logp = jax.nn.log_softmax(warped, -1)
        logprob = jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]
        tokens = jnp.where(live, tokens, pad)

        idx = jnp.minimum(st["emitted"], nm - 1)
        rows = jnp.arange(tokens.shape[0])
        out_tokens = jnp.where(
            live[:, None],
            st["out_tokens"].at[rows, idx].set(tokens),
            st["out_tokens"])
        out_logprobs = jnp.where(
            live[:, None],
            st["out_logprobs"].at[rows, idx].set(logprob),
            st["out_logprobs"])
        emitted = st["emitted"] + live.astype(jnp.int32)
        unfinished = st["unfinished"]
        hit_eos = st["hit_eos"]
        if eos is not None:
            hit_eos = hit_eos | (live & (tokens == eos))
            unfinished = unfinished & (~live | (tokens != eos))
        unfinished = unfinished & (emitted < nm)

        pos = st["prompt_len"] + st["emitted"]
        new_hidden, cache = T.decode_step(cfg, params, st["cache"],
                                          tokens, pos, moe_constraint,
                                          mesh=mesh)
        st = dict(st, cache=cache, last_hidden=new_hidden,
                  emitted=emitted, unfinished=unfinished,
                  hit_eos=hit_eos, out_tokens=out_tokens,
                  out_logprobs=out_logprobs)
        return st, None

    keys = jax.random.split(key, chunk)

    # Early exit within the chunk: when every slot has finished (EOS
    # or max tokens), the remaining steps would decode pads and write
    # nothing -- stop instead of burning them (mirrors the batch
    # path's EOS early-exit while_loop, engine/generation.py).
    def w_cond(c):
        i, st = c
        live_any = jnp.any(st["active"] & st["unfinished"]
                           & (st["emitted"] < nm))
        return (i < chunk) & live_any

    def w_body(c):
        i, st = c
        st, _ = body(st, keys[i])
        return (i + 1, st)

    _, state = jax.lax.while_loop(w_cond, w_body,
                                  (jnp.int32(0), state))
    return state
