"""Everything the benchmark knows about the ``smallthinker`` architecture
(SmallThinker-21BA3B-Instruct): the checkpoint's tensors, the plain
float32 reference (forward, training loss and its gradient) with its
tolerance, and what a step needs in parameters, FLOPs, bytes, routed
pairs, flash-kernel block products and the bytes the kernels that
stream K and V must move, all from the PUBLISHED configuration dict and
the checkpoint's tensors and nothing of the program's.

The model. Layer ``i`` on ``x`` [T, H], RMSNorm with ``rms_norm_eps``::

    z = x W_r                   [T, E] in float32: the router reads the
                                layer's INPUT, before attention and
                                before any norm
    u = RMSNorm(x; input_layernorm)
    q = u Wq [T, nq, hd]; k = u Wk, v = u Wv [T, nkv, hd]   no biases
    rope_layout[i] = 1: q, k rotated at the token's position IN ITS
          DOCUMENT, halves convention over the whole head (x1 =
          x[:hd/2], x2 = x[hd/2:]), inv_freq_j = rope_theta^(-2j/hd);
          0: q and k go to the scores as they are (NoPE)
    scores q k^T / sqrt(hd) in float32; key s visible to query t iff
          same document, s <= t, and where sliding_window_layout[i] = 1
          t - s < sliding_window_size (the window counts the query)
    a = x + (heads' outputs, concatenated) Wo
    h = RMSNorm(a; post_attention_layernorm)
    the moe_num_active_primary_experts largest z of a token; gates g =
          softmax over THOSE logits (they sum to 1)
    y = a + sum_j g_j (relu(h Wgate[e_j]) * (h Wup[e_j])) Wdown[e_j]
    model: embed_tokens -> layers -> RMSNorm(model.norm) -> lm_head

``transformers`` 4.57.6 has no ``smallthinker`` and there is no network
here. The catalog row's config is followed to the letter; what it does
not state is ASSUMED, listed in the configuration file under
``assumed`` and NOT confirmed against the published modelling code: (1)
that the router reads ``x`` itself (the paper's pre-attention router;
the public GGUF graph multiplies the router by the layer's input); (2)
top-k on the logits, then the softmax over the chosen
(``moe_primary_router_apply_softmax``); (3) the gates on the experts'
OUTPUT; (4) the halves convention of the rotary embedding; (5) a window
that counts the query itself (``row - col < 4096``); (6) the tensor
names below. What is claimed is the architecture's shapes and named
mechanisms, not that the published checkpoint loads. The "secondary
experts" of the model's description have no key in the config and are
no part of this file.

The reference takes ``ids`` [B, L] and optionally ``seg`` [B, L]
(document ids of a packed row, 0 = padding; None: a row is one
document): it builds the explicit [rows, L] visibility mask from
documents and positions, ``QUERY_BLOCK`` query rows at a time so that a
document of 16,384 tokens at 28 heads fits, loops over the layers and
over the HELD experts, adding each one's output for every token
weighted by a gate that is 0 where the expert is not among the token's
k. No kernel, no cache, no sort, no ragged product. Weights are the
checkpoint's values cast up exactly; every product is taken at
``default_matmul_precision("highest")``.

**An expert-parallel rank's share** (``expert_share: {of, first}``
beside ``moe_num_primary_experts`` = how many the files hold, as
``realhf_tpu/models/hf/smallthinker.py`` reads it): the router keeps
its published width, the k are chosen among ALL experts, only the HELD
experts' terms are added. A sliced vocabulary is a smaller vocabulary.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (1.00 to 1.03 nat at the cell's widths).
#: Sized on the chip at those widths (4 layers, 8 of 64 experts held,
#: vocabulary 18,992) by ``scripts/chip_check.py smallthinker`` and the
#: cell's own runs (my chip runs, PR 62), shares of the spread:
#:
#:   engine, bf16, the fixed batch (19 seeds)              0.00385-0.00469
#:   ONE packed row of 16,384: documents of 4096 .. 2048   0.00352-0.00466
#:   prefill of 8,192, then 127 decode steps               0.00376-0.00390
#:   this forward at default matmul precision              0.00307-0.00378
#:   HELD experts rounded to int8 by row                   0.00110-0.00173
#:   held experts rounded to float8 e4m3                   0.00464-0.00642
#:   every matrix rounded to int8 by row                   0.0204-0.0236
#:   every matrix rounded to float8 e4m3                   0.0782-0.0892
#:   every matrix rounded to float8 e5m2                   0.123-0.137
#:   WRONG: silu for relu                                  0.0225-0.0311 (0.0513)
#:   WRONG: the router after the norm (reads u)            0.0229-0.0306 (0.0266)
#:   WRONG: the gates on the experts' input                0.0591-0.0761 (0.124)
#:   WRONG: the router after attention (reads h)           0.0891-0.117  (0.116)
#:   WRONG: window layers do not rotate                    0.108-0.119   (0.0800)
#:   WRONG: the full layer rotates                         0.131-0.155   (0.0757)
#:   WRONG: a window of 4095 / of 4097                     0 / 0 (0.000243-0.000288)
#:   softmax over all 64, renormalised (NOT wrong: equal:
#:     it is how the PROGRAM computes the gates)           0 (0.0000003-0.0000012)
#:   engine, FLOAT32 at highest precision, that document   (0.00000054-0.0000017)
#:
#: (in brackets: on ONE document of 16,384 tokens, reference against
#: reference; the engine's row there through the COMPILED kernels that
#: stream K and V by block; eleven of the engine's seeds with a ragged
#: share, seven with a scanned dense one, the last with the dense mode
#: over the held stacks that the cell's file asks, ``expert_dispatch``:
#: that tree also read the float32 row's least, and seven runs of the
#: cell passed inside the tolerance.) 0.01 is 2.1
#: times the most bf16 shows on
#: the fixed batch or on any document of a packed row, half of the
#: least that int8 on the whole model reads, and under half of the
#: mildest wrong equation but one, so a forward computed below bf16,
#: or by any wrong equation of the list but the window's, fails.
#: WHAT IT CANNOT TELL: a window off by one (the fixed batch's 256
#: tokens never reach a window of 4096, and on a document that does one
#: key more or less of 4096 moves the log-probabilities by 0.0003 of
#: their spread, a fourteenth of bf16's own noise), nor anything else
#: of the kernels that stream K and V (the fixed batch is a row of 256:
#: the whole-row kernels). Both are held elsewhere:
#: ``tests/model/test_smallthinker.py`` (float32 against this file, a
#: window that bites), ``tests/ops/test_flash_attention.py`` (the
#: stream kernels against the XLA mask), the float32 engine on the chip
#: (the table's last row: 140 times or more under what a window off by one
#: reads) and the block counter (60.8% of the causal blocks, to the
#: digit). Nor a lower precision ON THE HELD EXPERTS ALONE (inside
#: bf16's noise, as in ``lfm2_moe`` and ``laguna``).
TOLERANCE = 0.01

_PRE = "model.layers.{}."
_MOE = "block_sparse_moe."
_FFN = ("gate", "up", "down")
#: rows of queries whose scores are held at once (28 heads x 256 x
#: 16,384 float32 scores are 470 MB)
QUERY_BLOCK = 256
#: the flash kernels' blocks (``realhf_tpu/ops/flash_attention.py``:
#: DEFAULT_BQ, DEFAULT_BK), which ``flash_flops`` counts products of
FLASH_BQ, FLASH_BK = 256, 512
#: the longest row whose K and V the kernels hold whole; a longer one
#: goes to the kernels that stream them (``FLASH_MAX_LEN``), which
#: serve at most this many query heads of a key/value head from one
#: fetched block (``STREAM_HEADS``)
STREAM_ABOVE, STREAM_HEADS = 4096, 8


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    if hf.get("rope_scaling") is not None \
            or not hf.get("moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "the reference has no rotary scaling and takes the softmax "
            "over the chosen logits")
    n = hf["num_hidden_layers"]
    window = hf.get("sliding_window_layout") or [0] * n
    rope = hf.get("rope_layout") or [1] * n
    if not (len(window) == len(rope) == n
            and set(window) | set(rope) <= {0, 1}):
        raise NotImplementedError(
            f"sliding_window_layout {window}, rope_layout {rope} for "
            f"{n} layers")
    nq = hf["num_attention_heads"]
    held = hf["moe_num_primary_experts"]
    share = hf.get("expert_share") or dict(of=held, first=0)
    return dict(
        layers=n, windowed=window, rotates=rope,
        hidden=hf["hidden_size"], heads=nq,
        nkv=hf.get("num_key_value_heads", nq),
        head=hf.get("head_dim") or hf["hidden_size"] // nq,
        moe_inter=hf["moe_ffn_hidden_size"], vocab=hf["vocab_size"],
        window=hf.get("sliding_window_size"),
        theta=float(hf.get("rope_theta", 10000.0)),
        experts=share["of"], top_k=hf["moe_num_active_primary_experts"],
        held=range(share["first"], share["first"] + held),
        eps=hf.get("rms_norm_eps", 1e-6),
        tied=bool(hf.get("tie_word_embeddings", False)))


def _window(d, i):
    return d["window"] if d["windowed"][i] else None


def _attention_params(d):
    h, q = d["hidden"], d["heads"] * d["head"]
    return h * (q + 2 * d["nkv"] * d["head"]) + q * h


def n_params(hf):
    """Parameters the checkpoint HOLDS: embedding and head, for every
    layer its four projections, the router over all experts, the HELD
    experts' three matrices each, two norm scales, and the final
    norm."""
    d = dims(hf)
    h = d["hidden"]
    layer = _attention_params(d) + h * d["experts"] \
        + len(d["held"]) * 3 * h * d["moe_inter"] + 2 * h
    embed = d["vocab"] * h * (1 if d["tied"] else 2)
    return d["layers"] * layer + embed + h


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes over ALL the router's
    experts: ``moe_num_active_primary_experts`` a token a layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * d["layers"]


def held_pairs(hf, seqlens):
    """The EXPECTED share of those that land on held experts, at even
    routing. What a run really multiplies is the program's counter
    ``moe_held_pairs_total``."""
    d = dims(hf)
    return routed_pairs(hf, seqlens) * len(d["held"]) / d["experts"]


def visible_pairs(n, window=None):
    """(query, key) pairs of ONE document of ``n`` tokens that the mask
    lets through: causal, and within ``window`` where there is one."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def forward_flops(hf, seqlens):
    """FLOPs of one forward over documents of these lengths, at 2 FLOPs
    a multiply-add. A layer: its four projections, scores and values
    over the pairs its mask lets through (``visible_pairs``: causal in a
    full layer, causal and window in a window one), the router over all
    experts, and the HELD experts only, at even routing: ``k x held /
    experts`` experts a token (6 x 8/64 = 0.75 in the benchmark's cell).
    The vocabulary head on every token. Norms, rotary, elementwise
    products, softmax, the sort and the scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    h = d["hidden"]
    total = 2 * tokens * h * d["vocab"]
    for i in range(d["layers"]):
        pairs = sum(visible_pairs(n, _window(d, i)) for n in seqlens)
        total += 2 * tokens * _attention_params(d) \
            + 4 * pairs * d["heads"] * d["head"] \
            + 2 * tokens * (h * d["experts"] + 3 * h * d["moe_inter"]
                            * d["top_k"] * len(d["held"]) / d["experts"])
    return total


def flash_blocks(n, window=None, bq=FLASH_BQ, bk=FLASH_BK):
    """(query block, key block) pairs that hold a visible pair, in a
    row that is ONE document of ``n`` tokens (a multiple of both
    blocks): what a flash kernel that skips every other block visits.
    Counted from the mask's own definition, pair by pair of blocks."""
    bq, bk = min(bq, n), min(bk, n)
    if n % bq or n % bk:
        raise ValueError(f"a row of {n} is no multiple of {bq}, {bk}")
    count = 0
    for i in range(n // bq):
        for j in range(n // bk):
            first_q, last_q = i * bq, (i + 1) * bq - 1
            first_k, last_k = j * bk, (j + 1) * bk - 1
            if first_k > last_q:
                continue  # every key after every query
            if window is not None and first_q - last_k >= window:
                continue  # every key too old for every query
            count += 1
    return count, bq, bk


def flash_flops(hf, seqlens):
    """FLOPs of the matrix products the flash kernels run for ONE
    forward and ONE backward over rows that are one document each of
    these lengths, as they run them: every VISITED block pair whole (a
    block on the diagonal or on the window's edge is multiplied whole
    and masked after), ``2 x rows x columns x head_dim`` FLOPs a
    product; the forward kernel takes 2 products a block pair (scores,
    values), the dq pass 3 (scores, dP, dQ), the dkv pass 4 (scores,
    dV, dP, dK); times the query heads, a layer at its own window,
    summed over layers. The kernels that stream K and V (rows past
    4096) run the same products over the same pairs.
    ``dict(fwd=, dq=, dkv=)``: a step under rematerialisation runs the
    forward kernel more than once, and the reader counts its calls."""
    d = dims(hf)
    out = dict(fwd=0, dq=0, dkv=0)
    for i in range(d["layers"]):
        for n in seqlens:
            blocks, bq, bk = flash_blocks(n, _window(d, i))
            product = 2 * bq * bk * d["head"] * blocks * d["heads"]
            out["fwd"] += 2 * product
            out["dq"] += 3 * product
            out["dkv"] += 4 * product
    return out


def stream_heads(group):
    """Query heads of one key/value head that a grid step of a stream
    kernel serves from ONE fetched block of K and V: the largest
    divisor of the group up to ``STREAM_HEADS`` (7 of 28 over 4)."""
    return max(n for n in range(1, min(group, STREAM_HEADS) + 1)
               if group % n == 0)


def flash_stream_bytes(hf, seqlens, bytes_per_el=2):
    """Bytes the kernels that stream their blocks must move between HBM
    and the chip for ONE forward and ONE backward over rows that are
    one document each of these lengths (only rows past
    ``STREAM_ABOVE`` go to them), by the mathematics' count of the
    kernels AS BUILT, ``dict(fwd=, dq=, dkv=)``:

    - forward: Q read and O written once, the log-sum-exp written once
      as the kernel keeps it, LANE-BROADCAST (``[B, heads, L, 128]``
      float32: 512 bytes a (row, head)); K and V ONE block each a visit
      of a key block by a query block's GROUP of heads
      (``stream_heads``: the 7 query heads of a key/value head share
      the fetch, so a visited pair moves ``2 x bk x head_dim`` values
      ``heads / 7`` times, not ``heads`` times);
    - dq pass: Q and dO read once, the lane-broadcast log-sum-exp and
      delta once (512 bytes a (row, head) each), dQ written once in
      float32; K and V a visit as in the forward;
    - dkv pass: K and V read once and dK and dV written once in
      float32, a key/value head; Q and dO of the group's heads ONE
      block each a visit, their log-sum-exp and delta as ROWS (4 bytes
      a (row, head) a visit).

    Left out: what the program moves around the kernels (the
    transposes to head-major, delta's product, XLA's writing of the
    two lane-broadcast copies the dq pass reads), and the segment
    ids."""
    d = dims(hf)
    hd, nq, nkv = d["head"], d["heads"], d["nkv"]
    groups = nq // stream_heads(nq // nkv)  # grid steps a visited pair
    per_group = nq // groups
    out = dict(fwd=0, dq=0, dkv=0)
    for i in range(d["layers"]):
        for n in seqlens:
            if n <= STREAM_ABOVE:
                continue
            blocks, bq, bk = flash_blocks(n, _window(d, i))
            q_side = n * nq * hd * bytes_per_el   # Q, O or dO, once
            kv_side = 2 * n * nkv * hd * bytes_per_el  # K and V, once
            # lse or delta, once, over the kernels' 128 lanes
            row_stats = n * nq * 4 * 128
            kv_visits = blocks * groups * 2 * bk * hd * bytes_per_el
            q_visits = blocks * groups * per_group * bq * (
                2 * hd * bytes_per_el + 2 * 4)
            out["fwd"] += 2 * q_side + row_stats + kv_visits
            out["dq"] += 2 * q_side + 2 * row_stats + n * nq * hd * 4 \
                + kv_visits
            out["dkv"] += kv_side + 2 * n * groups * hd * 4 + q_visits
    return out


def kv_bytes_per_token(hf, bytes_per_el=2):
    """K and V of every layer: window layers keep every row too (the
    program's cache does; a window layer NEEDS only
    ``sliding_window_size`` rows)."""
    d = dims(hf)
    return 2 * d["layers"] * d["nkv"] * d["head"] * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, and every live sequence
    reads its key/value prefix, in a window layer the last
    ``sliding_window_size`` rows of it. Prefill is left out."""
    d = dims(hf)
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    rows = 0
    for i in range(d["layers"]):
        w = _window(d, i)
        rows += sum(min(prompt_len + t, w or prompt_len + t)
                    for t in range(new_tokens))
    return weights + n_seqs * rows * 2 * d["nkv"] * d["head"] * bytes_per_el


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor. ``kind`` is
    ``matrix`` or ``norm``."""
    d = dims(hf)
    h, kv, q = d["hidden"], d["nkv"] * d["head"], d["heads"] * d["head"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
    }
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        out[pre + "input_layernorm.weight"] = ((h,), "norm")
        out[pre + "post_attention_layernorm.weight"] = ((h,), "norm")
        out[pre + "self_attn.q_proj.weight"] = ((q, h), "matrix")
        out[pre + "self_attn.k_proj.weight"] = ((kv, h), "matrix")
        out[pre + "self_attn.v_proj.weight"] = ((kv, h), "matrix")
        out[pre + "self_attn.o_proj.weight"] = ((h, q), "matrix")
        moe = pre + _MOE
        out[moe + "primary_router.weight"] = ((d["experts"], h), "matrix")
        for e in d["held"]:
            f = d["moe_inter"]
            out[f"{moe}experts.{e}.gate.weight"] = ((f, h), "matrix")
            out[f"{moe}experts.{e}.up.weight"] = ((f, h), "matrix")
            out[f"{moe}experts.{e}.down.weight"] = ((h, f), "matrix")
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: only to show that the tolerance tells each from the model
#: (``scripts/chip_check.py``, the tests).
#: ``softmax_over_all_renormalised`` is NOT wrong: the softmax over all
#: experts, the k largest, divided by their sum, is the softmax over
#: the k logits in exact arithmetic; it is there to show that.
WRONG = ("router_after_attention", "router_after_norm", "silu_for_relu",
         "softmax_over_all_renormalised", "full_layers_rotate",
         "window_layers_do_not_rotate", "window_minus_1", "window_plus_1",
         "gates_on_the_experts_input")


def _rms(x, w, eps):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * w.astype(jnp.float32)


def positions(seg):
    """Each token's position in its document, from the document ids of
    packed rows [B, L] (numpy; a document is one contiguous run)."""
    seg = np.asarray(seg)
    idx = np.broadcast_to(np.arange(seg.shape[1]), seg.shape)
    first = np.ones(seg.shape, bool)
    first[:, 1:] = seg[:, 1:] != seg[:, :-1]
    start = np.maximum.accumulate(np.where(first, idx, 0), axis=1)
    return (idx - start).astype(np.int32)


def _rope(x, pos, theta):
    """x [B, L, heads, D] rotated at positions [B, L]: the whole head
    in the halves convention."""
    import jax.numpy as jnp
    r = x.shape[-1]
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(freq)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(d, i, u, w, pos, seg, wrong=()):
    """Layer i's attention on u [B, L, H]: grouped-query, the explicit
    mask a block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    nq, nkv, hd = d["heads"], d["nkv"], d["head"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, n, _ = u.shape
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(b, n, nq, hd)
    k = (u @ w["self_attn.k_proj.weight"].T).reshape(b, n, nkv, hd)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(b, n, nkv, hd)
    window = _window(d, i)
    rotates = bool(d["rotates"][i])
    if window is None and "full_layers_rotate" in wrong:
        rotates = True
    if window is not None and "window_layers_do_not_rotate" in wrong:
        rotates = False
    if rotates:
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
    if window is not None:
        window += ("window_plus_1" in wrong) - ("window_minus_1" in wrong)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)

    def block(q_rows, seg_rows, pos_rows):
        same = (seg_rows[:, :, None] == seg[:, None, :]) \
            & (seg_rows[:, :, None] != 0)
        apart = pos_rows[:, :, None] - pos[:, None, :]
        seen = same & (apart >= 0)
        if window is not None:
            seen = seen & (apart < window)
        score = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(hd)
        # a padding row sees nothing: a large finite value, not -inf,
        # so that its (unused) softmax is no NaN
        a = jax.nn.softmax(jnp.where(seen[:, None], score, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a, v)

    if n <= QUERY_BLOCK:
        o = block(q, seg, pos)
    else:
        # blocks of query rows, one after the other (``lax.map``: a
        # document of 16,384 tokens is 64 of them, traced once); the
        # last block filled up with padding rows, which see nothing
        nb = -(-n // QUERY_BLOCK)

        def rows(x):  # [B, L, ...] -> [nb, B, QUERY_BLOCK, ...]
            x = jnp.pad(x, [(0, 0), (0, nb * QUERY_BLOCK - n)]
                        + [(0, 0)] * (x.ndim - 2))
            return jnp.moveaxis(
                x.reshape(b, nb, QUERY_BLOCK, *x.shape[2:]), 1, 0)

        o = jax.lax.map(lambda t: block(*t), (rows(q), rows(seg), rows(pos)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, nb * QUERY_BLOCK, nq, hd)[:, :n]
    return o.reshape(b, n, nq * hd) @ w["self_attn.o_proj.weight"].T


def _reglu(h, gate, up, down, wrong=()):
    import jax
    import jax.numpy as jnp
    gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
    act = jax.nn.silu if "silu_for_relu" in wrong else jax.nn.relu
    return (act(h @ gate.T) * (h @ up.T)) @ down.T


def _route(d, r, router_w, wrong=()):
    """The gates [B, L, E] over ALL experts from what the router reads,
    ``r``: the softmax over the k largest logits where the expert is
    among them, else 0."""
    import jax
    import jax.numpy as jnp
    z = r @ router_w.astype(jnp.float32).T
    kth = jax.lax.top_k(z, d["top_k"])[0][..., -1:]
    chosen = z >= kth
    if "softmax_over_all_renormalised" in wrong:
        p = jnp.where(chosen, jax.nn.softmax(z, axis=-1), 0.0)
        return p / p.sum(-1, keepdims=True)
    return jnp.where(chosen, jax.nn.softmax(
        jnp.where(chosen, z, -jnp.inf), axis=-1), 0.0)


def _blocks(hf, get, ids, seg=None, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per layer its gates [B, L, E]). Layer by layer and expert by
    expert, each cast up on the way in."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    seg = np.ones(ids.shape, np.int32) if seg is None else np.asarray(seg)
    pos, seg = jnp.asarray(positions(seg)), jnp.asarray(seg)
    norm = jax.jit(lambda x, w: _rms(x, w, d["eps"]))
    attention = {
        i: jax.jit(lambda x, u, w, i=i: x + _attention(
            d, i, u, w, pos, seg, wrong))
        for i in range(d["layers"])}
    route = jax.jit(lambda r, g: _route(d, r, g, wrong))
    on_input = "gates_on_the_experts_input" in wrong
    expert = jax.jit(lambda h, g, *ws: _reglu(g * h, *ws, wrong=wrong)
                     if on_input else g * _reglu(h, *ws, wrong=wrong))
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        u = norm(x, get(pre + "input_layernorm.weight"))
        a = attention[i](x, u, {
            n: get(pre + n) for n in (
                f"self_attn.{p}_proj.weight" for p in "qkvo")})
        h = norm(a, get(pre + "post_attention_layernorm.weight"))
        # the router reads the layer's INPUT
        reads = h if "router_after_attention" in wrong \
            else u if "router_after_norm" in wrong else x
        moe = pre + _MOE
        gates = route(reads, get(moe + "primary_router.weight"))
        x = a
        for e in d["held"]:  # what the absent experts add is left out
            x = x + expert(h, gates[..., e:e + 1], *(
                get(f"{moe}experts.{e}.{m}.weight") for m in _FFN))
        routed.append(gates)
    return x, routed


def _getter(tensors, cast):
    import jax.numpy as jnp

    def get(name):
        x = jnp.asarray(tensors[name])
        return x if cast is None or x.ndim < 2 else cast(x)
    return get


def _final(hf, x, get):
    import jax.numpy as jnp
    x = _rms(x, get("model.norm.weight"), dims(hf)["eps"])
    head = get("model.embed_tokens.weight"
               if hf.get("tie_word_embeddings", False) else "lm_head.weight")
    return x @ head.astype(jnp.float32).T


def logits(hf, tensors, ids, seg=None, cast=None, wrong=()):
    """Float32 logits [B, L, V] of the full forward: what prefill and
    decoding through the cache must agree with. ``seg``: the document
    ids of packed rows (None: a row is one document)."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids, seg, wrong)
        return np.asarray(_final(hf, x, get), np.float32)


def _token_logprobs(logits_, ids):
    import jax
    import jax.numpy as jnp
    lp = jax.nn.log_softmax(logits_, axis=-1)
    return jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None], -1)[..., 0]


def logprobs(hf, tensors, ids, cast=None, wrong=()):
    """log p(ids[:, t+1] | ids[:, :t+1]) as float32 [B, L-1], a row a
    document.

    ``tensors`` maps HF names to arrays (bf16 as written). ``cast``
    rounds every matrix on the way and ``wrong`` names equations to get
    wrong (``WRONG``): both only to size TOLERANCE. The head runs a
    block of rows at a time, so that a document of 16,384 tokens'
    logits are never whole."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids, None, wrong)
        n = ids.shape[1]
        step = n if n <= 4 * QUERY_BLOCK else 4 * QUERY_BLOCK
        score = jax.jit(lambda x, nxt: jnp.take_along_axis(
            jax.nn.log_softmax(_final(hf, x, get), axis=-1),
            nxt[..., None], -1)[..., 0])
        out = [score(x[:, s:min(s + step, n - 1)],
                     ids[:, s + 1:min(s + step, n - 1) + 1])
               for s in range(0, n - 1, step)]
    return np.asarray(jnp.concatenate(out, axis=1), np.float32)


def top_k_sets(hf, tensors, ids, layer, wrong=()):
    """Which of ALL the experts the reference routes every token of
    ``ids`` to in ``layer``: bool [B, L, E]."""
    import jax
    import jax.numpy as jnp
    hf1 = dict(hf, num_hidden_layers=layer + 1, **{
        key: hf[key][:layer + 1] for key in (
            "rope_layout", "sliding_window_layout")})
    with jax.default_matmul_precision("highest"):
        _, routed = _blocks(hf1, _getter(tensors, None),
                            jnp.asarray(ids, jnp.int32), wrong=wrong)
    return np.asarray(routed[-1] > 0)


# ----------------------------------------------------------------------
# The training loss and its gradient
# ----------------------------------------------------------------------
def sft_loss(hf, tensors, ids, prompt_len):
    """The SFT loss of ONE microbatch whose documents are the rows of
    ``ids`` [n, L] (equal lengths, the first ``prompt_len`` tokens of
    each the prompt): the mean over the answer tokens of -log p(token |
    before). No auxiliary term (``routing_type`` none in the program's
    reading of this family). Returns (loss, dict(nll=, aux=)). A
    function of ``tensors`` that ``jax.grad`` differentiates."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, None)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids)
        lp = _token_logprobs(_final(hf, x, get), ids)
        # lp[:, t] scores token t+1: answer tokens are t+1 >= prompt_len
        answer = jnp.arange(1, ids.shape[1]) >= prompt_len
        nll = -(lp * answer).sum() / (answer.sum() * ids.shape[0])
    return nll, dict(nll=nll, aux=jnp.zeros(()))


def sft_loss_and_grad(hf, tensors, ids, prompt_len):
    """(loss, parts, gradient by HF tensor name), all float32, of
    ``sft_loss`` at ``tensors`` cast up to float32."""
    import jax
    import jax.numpy as jnp

    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in tensors.items()}
    (loss, parts), grads = jax.value_and_grad(
        lambda t: sft_loss(hf, t, ids, prompt_len), has_aux=True)(f32)
    return float(loss), {k: float(v) for k, v in parts.items()}, \
        {k: np.asarray(v) for k, v in grads.items()}
