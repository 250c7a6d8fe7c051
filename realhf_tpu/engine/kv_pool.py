"""Block-granular paged KV pool: the shared KV substrate for decode
slots AND the radix prefix cache (ISSUE 14, ROADMAP item 4).

The dense design it replaces gives every decode slot a private
``[cache_len]`` KV window sized for the WORST case (max prompt + max
new tokens), so a replica's concurrency is fixed at construction and
short sequences strand most of their reservation. This module is the
vLLM-style fix: one device-resident arena of fixed-size **blocks**
(``block_len`` token rows each), a host-side free list with per-block
refcounts, and per-sequence **block tables** mapping a sequence's
window row ``j`` to pool row ``table[j // block_len] * block_len +
j % block_len``. Sequences allocate blocks lazily as they grow and
free them at harvest, so live-KV bytes track actual tokens, not
worst-case windows -- concurrency is bounded by *blocks*, not slots.

Layout mirrors the Pallas paged-attention convention
(``k_pages [n_kv_heads, n_pages, page_size, head_dim]``) collapsed to
row-flat head-major arrays ``[n_layers, n_kv_heads, n_rows, head_dim]``
(``n_rows = (n_blocks + 1) * block_len``) so a block is simply a
contiguous row span and gathers/scatters are plain row indexing --
the same head-major streaming layout the dense cache and decode
kernels already use. **Block 0 is reserved** as a write-off scratch
block: unset block-table entries and masked scatter lanes all route
to its rows, so duplicate clamped indices can never corrupt live data
(the duplicate-scatter ordering lesson of the spec-decode path).

Because every sequence fills its window compacted from row 0, token
position ``p`` always lives at offset ``p % block_len`` of its
covering block, for every sequence. Any shared token *prefix*
therefore has an identical block-internal layout in every sequence
that carries it -- the invariant that lets the radix prefix cache
alias whole blocks into a new sequence's table (zero KV copy) instead
of keeping private host copies.

Quantization (``dtype="int8"``): values are stored as int8 with a
float32 scale per (layer, kv-head, row) -- i.e. per token row, the
append-friendly refinement of the per-page scales quantized paged
attention uses. A whole-block scale would have to be frozen at the
block's first write, long before its later rows exist; per-row amax
scales keep the round-trip error bound local (|x - dq(q(x))| <=
amax/254 per row) at a 4/head_dim relative byte overhead.
Quantize-on-write / dequantize-on-read both live inside the jitted
gather/scatter helpers, so the compute path never sees int8.

Host-side accounting (``alloc``/``free``/``incref``) is plain Python
on purpose: it runs between device calls, never inside traced code.
:meth:`KVPool.host_only` builds a pool with no device arrays at all --
the same allocator arithmetic for scheduler/chaos tests and fakes.
"""

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from realhf_tpu.base import logging

logger = logging.getLogger("engine.kv_pool")

#: accepted ``dtype`` spellings -> storage description
KV_CACHE_DTYPES = ("fp32", "bf16", "int8")


class KVPoolOOM(RuntimeError):
    """Raised when an allocation cannot be satisfied. Carries the
    shortfall so the scheduler can relieve exactly that much pressure
    (prefix-cache eviction first, sequence eviction as last resort)."""

    def __init__(self, requested: int, free: int):
        super().__init__(
            f"KV pool exhausted: requested {requested} block(s), "
            f"{free} free")
        self.requested = requested
        self.free = free

    @property
    def shortfall(self) -> int:
        return self.requested - self.free


@dataclasses.dataclass(frozen=True)
class PoolMeta:
    """Static (hashable) pool description closed over by the jitted
    gather/scatter helpers -- dynamic arrays travel separately."""
    block_len: int
    quant: bool              # int8 storage + per-row scales
    store_dtype: str         # "float32" | "bfloat16" | "int8"


class KVPool:
    """Device-resident block arena + host-side block allocator."""

    def __init__(self, cfg, n_blocks: int, block_len: int,
                 dtype: str = "fp32", compute_dtype=None):
        if dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"kv_cache_dtype must be one of {KV_CACHE_DTYPES}, "
                f"got {dtype!r}")
        if n_blocks < 1 or block_len < 1:
            raise ValueError("n_blocks and block_len must be >= 1")
        self.cfg = cfg
        self.n_blocks = int(n_blocks)
        self.block_len = int(block_len)
        self.dtype = dtype
        self.meta = PoolMeta(
            block_len=self.block_len, quant=(dtype == "int8"),
            store_dtype={"fp32": "float32", "bf16": "bfloat16",
                         "int8": "int8"}[dtype])
        # host allocator state: ids 1..n_blocks; 0 reserved (scratch)
        self._free: List[int] = list(range(self.n_blocks, 0, -1))
        self._ref = np.zeros(self.n_blocks + 1, np.int32)
        self._ref[0] = 1  # the scratch block is never allocatable
        self.stats_counters = dict(allocs=0, frees=0, oom=0)

        self._arrays: Optional[Dict] = None
        if cfg is not None:
            import jax.numpy as jnp
            cfg.require_one_block("the paged KV pool (engine/kv_pool.py)")
            # (a looped model: a layer's rows once a pass)
            nl, nkv, hd = cfg.kv_layers, cfg.n_kv_heads, cfg.head_dim
            rows = (self.n_blocks + 1) * self.block_len
            sdt = jnp.dtype(self.meta.store_dtype)
            self._arrays = dict(
                k=jnp.zeros((nl, nkv, rows, hd), sdt),
                v=jnp.zeros((nl, nkv, rows, hd), sdt))
            if self.meta.quant:
                self._arrays["k_scale"] = jnp.zeros((nl, nkv, rows),
                                                    jnp.float32)
                self._arrays["v_scale"] = jnp.zeros((nl, nkv, rows),
                                                    jnp.float32)
            self._bytes_per_row = 2 * nl * nkv * (
                hd * sdt.itemsize + (4 if self.meta.quant else 0))
        else:
            self._bytes_per_row = 0

    @classmethod
    def host_only(cls, n_blocks: int, block_len: int,
                  bytes_per_row: int = 0) -> "KVPool":
        """Allocator arithmetic without device arrays -- for test
        fakes and scheduler/chaos suites (base/testing.py)."""
        pool = cls(None, n_blocks, block_len, dtype="fp32")
        pool._bytes_per_row = int(bytes_per_row)
        return pool

    # -- device arrays (functional style: jitted callers take the
    # dict, return an updated one, and hand it back via update) ------
    def arrays(self) -> Dict:
        if self._arrays is None:
            raise RuntimeError("host_only pool has no device arrays")
        return self._arrays

    def update(self, arrays: Dict):
        self._arrays = arrays

    # -- allocator ----------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_in_use(self) -> int:
        return self.n_blocks - len(self._free)

    @property
    def block_bytes(self) -> int:
        return self._bytes_per_row * self.block_len

    @property
    def bytes_per_row(self) -> int:
        return self._bytes_per_row

    def blocks_for_rows(self, rows: int) -> int:
        """Blocks covering ``rows`` token rows."""
        return -(-max(0, int(rows)) // self.block_len)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks (each at refcount 1). All-or-nothing:
        raises :class:`KVPoolOOM` without side effects when fewer
        than ``n`` are free."""
        n = int(n)
        if n <= 0:
            return []
        if len(self._free) < n:
            self.stats_counters["oom"] += 1
            raise KVPoolOOM(n, len(self._free))
        out = [self._free.pop() for _ in range(n)]
        self._ref[out] = 1
        self.stats_counters["allocs"] += n
        return out

    def incref(self, blocks: Iterable[int]):
        for b in blocks:
            if self._ref[b] <= 0 or b == 0:
                raise ValueError(f"incref on unallocated block {b}")
            self._ref[b] += 1

    def free(self, blocks: Iterable[int]):
        """Drop one reference per listed block; blocks reaching zero
        return to the free list."""
        for b in blocks:
            if b == 0:
                continue
            if self._ref[b] <= 0:
                raise ValueError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(int(b))
                self.stats_counters["frees"] += 1

    def ref(self, block: int) -> int:
        return int(self._ref[block])

    def stats(self) -> Dict:
        in_use = self.n_in_use
        return dict(
            blocks_total=self.n_blocks, blocks_free=self.n_free,
            blocks_in_use=in_use, block_len=self.block_len,
            block_bytes=self.block_bytes,
            bytes_per_row=self._bytes_per_row,
            bytes_in_use=in_use * self.block_bytes,
            bytes_total=self.n_blocks * self.block_bytes,
            dtype=self.dtype, **self.stats_counters)


# ----------------------------------------------------------------------
# jit-safe gather/scatter (pure functions over the arrays dict)
# ----------------------------------------------------------------------
def window_rows(bt, warange, block_len: int):
    """Flat pool rows for window positions ``warange`` (``[S]``) of
    each sequence in block table ``bt`` (``[B, max_blocks]``): row j
    of sequence b lives at ``bt[b, j // blen] * blen + j % blen``.
    Unset table entries (0) resolve into the reserved scratch block,
    whose rows are only ever read masked."""
    cols = warange // block_len                       # [S]
    return bt[:, cols] * block_len + (warange % block_len)[None, :]


def pool_gather(meta: PoolMeta, arrays, rows, compute_dtype):
    """Dequantized ``(k, v)`` -- each ``[nl, B, nkv, S, hd]`` in the
    compute dtype -- for flat pool rows ``rows`` (``[B, S]``)."""
    import jax.numpy as jnp
    k = arrays["k"][:, :, rows]          # [nl, nkv, B, S, hd]
    v = arrays["v"][:, :, rows]
    if meta.quant:
        k = k.astype(jnp.float32) * arrays["k_scale"][:, :, rows][..., None]
        v = v.astype(jnp.float32) * arrays["v_scale"][:, :, rows][..., None]
    cdt = jnp.dtype(compute_dtype)
    return (k.transpose(0, 2, 1, 3, 4).astype(cdt),
            v.transpose(0, 2, 1, 3, 4).astype(cdt))


def _quantize_rows(x):
    """Per-row symmetric int8: ``x`` [..., hd] -> (int8 values,
    float32 scales [...])."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = (amax / 127.0).astype(jnp.float32)
    q = jnp.where(scale[..., None] > 0,
                  x.astype(jnp.float32) / jnp.maximum(scale[..., None],
                                                      1e-30), 0.0)
    q = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)
    return q, scale


def pool_scatter(meta: PoolMeta, arrays, rows, k_new, v_new, mask):
    """Write ``k_new``/``v_new`` (``[nl, B, nkv, m, hd]``) at flat
    pool rows ``rows`` (``[B, m]``). Masked-off lanes are routed into
    the reserved scratch block (row span of block 0), so clamped
    duplicate indices never land on live rows -- scatter write order
    for duplicates is unspecified and has bitten this codebase before
    (see ``_verify_chunk``). Returns the updated arrays dict."""
    import jax.numpy as jnp
    safe = jnp.where(mask, rows, 0)      # 0 = scratch block, row 0
    out = dict(arrays)
    if meta.quant:
        kq, ks = _quantize_rows(k_new)
        vq, vs = _quantize_rows(v_new)
        out["k"] = arrays["k"].at[:, :, safe].set(
            kq.transpose(0, 2, 1, 3, 4))
        out["v"] = arrays["v"].at[:, :, safe].set(
            vq.transpose(0, 2, 1, 3, 4))
        out["k_scale"] = arrays["k_scale"].at[:, :, safe].set(
            ks.transpose(0, 2, 1, 3))
        out["v_scale"] = arrays["v_scale"].at[:, :, safe].set(
            vs.transpose(0, 2, 1, 3))
    else:
        sdt = arrays["k"].dtype
        out["k"] = arrays["k"].at[:, :, safe].set(
            k_new.transpose(0, 2, 1, 3, 4).astype(sdt))
        out["v"] = arrays["v"].at[:, :, safe].set(
            v_new.transpose(0, 2, 1, 3, 4).astype(sdt))
    return out


def int8_roundtrip_error_bound(x: np.ndarray) -> float:
    """The per-row bound the int8 path guarantees: half a quantization
    step, ``amax / 254`` per row (tests assert against this)."""
    amax = np.max(np.abs(x), axis=-1, keepdims=True)
    return float(np.max(amax) / 254.0 + 1e-12)
