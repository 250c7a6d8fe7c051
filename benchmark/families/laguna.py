"""Everything the benchmark knows about the ``laguna`` architecture
(Laguna-XS.2): the checkpoint's tensors, the plain float32 reference
(forward, training loss and its gradient) with its tolerance, and what
a step needs in parameters, FLOPs, bytes, routed pairs and flash-kernel
block products, all from the PUBLISHED configuration dict and the
checkpoint's tensors and nothing of the program's.

The model. Layer ``i`` on ``x`` [T, H], RMSNorm with ``rms_norm_eps``::

    u = RMSNorm(x; input_layernorm)
    q = u Wq [T, n_i, hd]; k = u Wk, v = u Wv [T, nkv, hd]   no biases
          n_i = num_attention_heads_per_layer[i]
    q, k rotated by the layer's TYPE (rope_parameters[layer_types[i]]),
          at the token's position IN ITS DOCUMENT, halves convention
          over the first r = partial_rotary_factor x hd values of a
          head (x1 = x[:r/2], x2 = x[r/2:r]; the rest passes through):
       sliding_attention: inv_freq_j = theta^(-2j/r)
       full_attention:    YaRN (transformers' _compute_yarn_parameters
                          with d = r): low = floor(d ln(orig / (beta_fast
                          2 pi)) / (2 ln theta)), high = ceil(same with
                          beta_slow), clipped to [0, d - 1]; ramp_j =
                          clip((j - low) / (high - low), 0, 1);
                          inv_freq_j = (1 - ramp_j) theta^(-2j/d)
                          + ramp_j theta^(-2j/d) / factor; cos and sin
                          times attention_factor
    scores q k^T / sqrt(hd) in float32; key s visible to query t iff
          same document, s <= t, and in a sliding layer t - s <
          sliding_window (positions in the document)
    gating: g = sigmoid(u Wg) [T, n_i]; head h's output times g_h
    a = x + (heads' outputs, concatenated) Wo
    v = RMSNorm(a; post_attention_layernorm)
    dense  (mlp_layer_types[i]): y = a + down(silu(gate v) * up v)
    sparse: s = sigmoid(v W_gate) [T, E] in float32 over ALL experts;
          the num_experts_per_tok largest s; gates g_e = s_e / (their
          sum + 1e-20) x moe_routed_scaling_factor on each chosen
          expert's OUTPUT; y = a + sum_e g_e Expert_e(v) + Shared(v),
          every expert and the shared one a SwiGLU
    model: embed_tokens -> layers -> RMSNorm(model.norm) -> lm_head

``transformers`` 4.57.6 has no ``laguna`` and there is no network here.
The catalog row's config is followed to the letter; six things it does
not state are ASSUMED, the plainest reading each, listed in the
configuration file under ``assumed`` and NOT confirmed against the
published modelling code: (1) pre-norm, two norms a layer, a final
norm, no query/key norm; (2) the halves convention and that the FIRST r
values rotate; (3) ``gating: true`` is one sigmoid gate a head from the
layer's normed input; (4) the router: sigmoid scores, no selection
bias, renormalised gates, the scaling factor; (5) the shared expert is
added with weight 1, ungated; (6) the tensor names below. So what is
claimed is the architecture's shapes and named mechanisms, not that the
published checkpoint loads.

The reference takes ``ids`` [B, L] and optionally ``seg`` [B, L]
(document ids of a packed row, 0 = padding; None: a row is one
document): it builds the explicit [L, L] visibility mask from
documents and positions, a block of query rows at a time so that a row
of 4096 at 64 heads fits, loops over the layers and, in a sparse layer,
over the HELD experts, adding each one's output for every token
weighted by a gate that is 0 where the expert is not among the token's
k. No kernel, no cache, no sort, no ragged product. Weights are the
checkpoint's values cast up exactly; every product is taken at
``default_matmul_precision("highest")``.

**An expert-parallel rank's share** (``expert_share: {of, first}``
beside ``num_experts`` = how many the files hold, as
``realhf_tpu/models/hf/laguna.py`` reads it): the router keeps its
published width, the k are chosen among ALL experts, only the HELD
experts' terms are added, and the shared expert, which every rank
holds, is added whole. A sliced vocabulary is a smaller vocabulary.
"""

import math

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (0.89 to 0.93 nat at the cell's widths).
#: Sized on the chip at those widths (5 layers, 16 of 256 experts
#: held, vocabulary 12,544) by ``scripts/chip_check.py laguna`` and the
#: cell's own runs (my chip runs, PR 33), shares of the spread:
#:
#:   engine, bf16, the fixed batch (14 seeds)              0.0107-0.0125
#:   ONE packed row of 4096: documents of 1536 .. 512      0.0103-0.0135
#:   prefill of 640, then 127 decode steps, rows of 768    0.0109-0.0141
#:   this forward at default matmul precision              0.0080-0.0084
#:   HELD experts rounded to int8 by row                   0.0010-0.0013
#:   held experts rounded to float8 e4m3                   0.0042-0.0044
#:   every matrix rounded to int8 by row                   0.0371-0.0384
#:   every matrix rounded to float8 e4m3                   0.133-0.142
#:   every matrix rounded to float8 e5m2                   0.201-0.215
#:   WRONG: the shared expert left out                     0.287-0.324 (0.333-0.350)
#:   WRONG: gates not renormalised                         0.342-0.356 (0.388-0.421)
#:   WRONG: YaRN's attention factor left out               0.485-0.530 (0.567-0.596)
#:   WRONG: the gate left out                              0.600-0.626 (0.648-0.669)
#:   WRONG: full layers rotate the whole head              0.834-0.894 (0.984-0.993)
#:   WRONG: a window of 511 / of 513                       0 / 0 (0.0032-0.0034 / 0.0030)
#:   engine, FLOAT32 at highest precision, that document   (0.0000085)
#:
#: (in brackets: on ONE document of 1,536 tokens, reference against
#: reference). 0.02 is 1.6 times the most bf16 shows on the fixed
#: batch, just over half of int8 on the whole model and a fifteenth of
#: the mildest wrong equation but one, so a forward computed below
#: bf16, or by any wrong equation of the list but the last, fails.
#: WHAT IT CANNOT TELL: a window off by one. The fixed batch's
#: documents (256 tokens) never reach a window of 512, and on a
#: document that does, one key more or less of 512 that random weights
#: attend to almost evenly moves the log-probabilities by 0.003 of
#: their spread, a quarter of bf16's own noise: no tolerance on the
#: bf16 engine's log-probabilities can hold it. The window is held
#: exactly elsewhere: ``tests/model/test_laguna.py`` (float32 against
#: this file: a window off by one is 50 times over its tolerance),
#: ``tests/ops/test_flash_attention.py`` (the kernels' mask against
#: the XLA mask at windows below, at and above the blocks; the ranges
#: against brute force), the float32 engine on the chip (the table's
#: last row: the compiled windowed kernels, 350 times under what a
#: window off by one reads) and the block counter (65.0% of the causal
#: blocks, to the digit). Nor a lower precision
#: ON THE HELD EXPERTS ALONE (inside bf16's noise, as in ``lfm2_moe``).
TOLERANCE = 0.02

_PRE = "model.layers.{}."
_FFN = ("gate_proj", "up_proj", "down_proj")
#: rows of queries whose scores are held at once
QUERY_BLOCK = 512
#: the flash kernels' blocks (``realhf_tpu/ops/flash_attention.py``:
#: DEFAULT_BQ, DEFAULT_BK), which ``flash_flops`` counts products of
FLASH_BQ, FLASH_BK = 256, 512


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    if hf.get("attention_bias", False) \
            or hf.get("moe_apply_router_weight_on_input", False):
        raise NotImplementedError(
            "the reference has no attention bias and weights the "
            "experts' outputs")
    n = hf["num_hidden_layers"]
    types = hf.get("layer_types") or ["full_attention"] * n
    ffs = hf.get("mlp_layer_types") or ["sparse"] * n
    nq = hf["num_attention_heads"]
    heads = hf.get("num_attention_heads_per_layer") or [nq] * n
    if not (len(types) == len(ffs) == len(heads) == n
            and set(types) <= {"full_attention", "sliding_attention"}
            and set(ffs) <= {"dense", "sparse"}):
        raise NotImplementedError(
            f"layer_types {types}, mlp_layer_types {ffs}, heads {heads} "
            f"for {n} layers")
    share = hf.get("expert_share") or dict(of=hf["num_experts"], first=0)
    return dict(
        layers=n, types=types, ffs=ffs, heads=heads,
        hidden=hf["hidden_size"], nkv=hf.get("num_key_value_heads", nq),
        head=hf.get("head_dim") or hf["hidden_size"] // nq,
        inter=hf["intermediate_size"], moe_inter=hf["moe_intermediate_size"],
        shared=hf.get("shared_expert_intermediate_size"),
        vocab=hf["vocab_size"], window=hf.get("sliding_window"),
        experts=share["of"], top_k=hf["num_experts_per_tok"],
        held=range(share["first"], share["first"] + hf["num_experts"]),
        renorm=bool(hf.get("norm_topk_prob", True)),
        scaling=float(hf.get("moe_routed_scaling_factor", 1.0)),
        gating=bool(hf.get("gating", False)),
        eps=hf.get("rms_norm_eps", 1e-6), rope=hf["rope_parameters"],
        tied=bool(hf.get("tie_word_embeddings", False)))


def _window(d, i):
    return d["window"] if d["types"][i] == "sliding_attention" else None


def _attention_params(d, i):
    h, q = d["hidden"], d["heads"][i] * d["head"]
    return h * (q + 2 * d["nkv"] * d["head"]) + q * h \
        + (h * d["heads"][i] if d["gating"] else 0)


def _ffn_params(d, i):
    h = d["hidden"]
    if d["ffs"][i] == "dense":
        return 3 * h * d["inter"]
    return h * d["experts"] + len(d["held"]) * 3 * h * d["moe_inter"] \
        + 3 * h * (d["shared"] or 0)


def n_params(hf):
    """Parameters the checkpoint HOLDS: embedding and head, for every
    layer its four projections and its gate at the layer's own count of
    query heads, its feed-forward (dense: three matrices; sparse: the
    router over all experts, the HELD experts' three matrices each and
    the shared expert's three), two norm scales, and the final norm."""
    d = dims(hf)
    layers = sum(_attention_params(d, i) + _ffn_params(d, i)
                 + 2 * d["hidden"] for i in range(d["layers"]))
    embed = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)
    return layers + embed + d["hidden"]


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes over ALL the router's
    experts: ``num_experts_per_tok`` a token in every sparse layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * d["ffs"].count("sparse")


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
    a multiply-add. An attention layer: its projections and gate at its
    own count of heads, and scores and values over the pairs its mask
    lets through (``visible_pairs``: causal in a full layer, causal and
    window in a sliding one). A dense feed-forward: three matrices of
    ``intermediate_size``. A sparse one: the router over all experts and
    the shared expert on every token, and the HELD experts only, at even
    routing: ``num_experts_per_tok x held / experts`` experts a token (8
    x 16/256 = 0.5 in the benchmark's cell). The vocabulary head on
    every token. Norms, rotary, elementwise products, softmax, sigmoid,
    the sort and the scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    h = d["hidden"]
    total = 2 * tokens * h * d["vocab"]
    for i in range(d["layers"]):
        pairs = sum(visible_pairs(n, _window(d, i)) for n in seqlens)
        total += 2 * tokens * _attention_params(d, i) \
            + 4 * pairs * d["heads"][i] * d["head"]
        if d["ffs"][i] == "dense":
            total += 2 * tokens * 3 * h * d["inter"]
        else:
            total += 2 * tokens * (
                h * d["experts"] + 3 * h * (d["shared"] or 0)
                + 3 * h * d["moe_inter"] * d["top_k"]
                * len(d["held"]) / d["experts"])
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
            # the block pair's nearest (query, key): the block's last
            # query against its first key, or the closest causal pair
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
    dV, dP, dK); times the layer's query heads, summed over layers.
    ``dict(fwd=, dq=, dkv=)``: a step under rematerialisation runs the
    forward kernel more than once, and the reader counts its calls."""
    d = dims(hf)
    out = dict(fwd=0, dq=0, dkv=0)
    for i in range(d["layers"]):
        for n in seqlens:
            blocks, bq, bk = flash_blocks(n, _window(d, i))
            product = 2 * bq * bk * d["head"] * blocks * d["heads"][i]
            out["fwd"] += 2 * product
            out["dq"] += 3 * product
            out["dkv"] += 4 * product
    return out


def kv_bytes_per_token(hf, bytes_per_el=2):
    """K and V of every layer: window layers keep every row too (the
    program's cache does; a window layer NEEDS only ``sliding_window``
    rows)."""
    d = dims(hf)
    return 2 * d["layers"] * d["nkv"] * d["head"] * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, and every live sequence
    reads its key/value prefix, in a sliding layer the last
    ``sliding_window`` rows of it. Prefill is left out."""
    d = dims(hf)
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    rows = 0
    for i in range(d["layers"]):
        w = _window(d, i)
        rows += sum(min(prompt_len + t, w or prompt_len + t)
                    for t in range(new_tokens))
    return weights + n_seqs * rows * 2 * d["nkv"] * d["head"] * bytes_per_el


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor (layers differ in
    their tensors and in their widths, so no name stands for every
    layer). ``kind`` is ``matrix`` or ``norm``."""
    d = dims(hf)
    h, kv = d["hidden"], d["nkv"] * d["head"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
    }
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        q = d["heads"][i] * d["head"]
        out[pre + "input_layernorm.weight"] = ((h,), "norm")
        out[pre + "post_attention_layernorm.weight"] = ((h,), "norm")
        out[pre + "self_attn.q_proj.weight"] = ((q, h), "matrix")
        out[pre + "self_attn.k_proj.weight"] = ((kv, h), "matrix")
        out[pre + "self_attn.v_proj.weight"] = ((kv, h), "matrix")
        out[pre + "self_attn.o_proj.weight"] = ((h, q), "matrix")
        if d["gating"]:
            out[pre + "self_attn.g_proj.weight"] = (
                (d["heads"][i], h), "matrix")
        mlp = pre + "mlp."
        if d["ffs"][i] == "dense":
            out.update(_ffn_shapes(mlp, h, d["inter"]))
            continue
        out[mlp + "gate.weight"] = ((d["experts"], h), "matrix")
        for e in d["held"]:
            out.update(_ffn_shapes(f"{mlp}experts.{e}.", h, d["moe_inter"]))
        if d["shared"]:
            out.update(_ffn_shapes(mlp + "shared_expert.", h, d["shared"]))
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


def _ffn_shapes(pre, h, f):
    return {pre + "gate_proj.weight": ((f, h), "matrix"),
            pre + "up_proj.weight": ((f, h), "matrix"),
            pre + "down_proj.weight": ((h, f), "matrix")}


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: only to show that the tolerance tells each from the model
#: (``scripts/chip_check.py``, the tests)
WRONG = ("window_511", "window_513", "full_layers_rotate_whole_head",
         "attention_factor_left_out", "gate_left_out",
         "gates_not_renormalised", "shared_expert_left_out")


def _rms(x, w, eps):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * w.astype(jnp.float32)


def positions(seg):
    """Each token's position in its document, from the document ids of
    packed rows [B, L] (numpy; a document is one contiguous run)."""
    seg = np.asarray(seg)
    pos = np.zeros(seg.shape, np.int32)
    for b in range(seg.shape[0]):
        for t in range(1, seg.shape[1]):
            if seg[b, t] == seg[b, t - 1]:
                pos[b, t] = pos[b, t - 1] + 1
    return pos


def inv_freq(rp, head_dim, wrong=()):
    """(frequencies [r / 2] float32, r, what cos and sin are multiplied
    by) of one layer type's ``rope_parameters`` entry."""
    factor = float(rp.get("partial_rotary_factor", 1.0))
    if "full_layers_rotate_whole_head" in wrong:
        factor = 1.0
    r = int(head_dim * factor)
    theta = float(rp["rope_theta"])
    plain = theta ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    if rp.get("rope_type", "default") == "default":
        return plain.astype(np.float32), r, 1.0
    orig = rp["original_max_position_embeddings"]

    def turns_to_dim(turns):
        return r * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_to_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(rp["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    freq = (1.0 - ramp) * plain + ramp * plain / float(rp["factor"])
    scale = 1.0 if "attention_factor_left_out" in wrong \
        else float(rp.get("attention_factor", 1.0))
    return freq.astype(np.float32), r, scale


def _rope(x, pos, rp, wrong=()):
    """x [B, L, heads, D] rotated at positions [B, L]: the first r
    values of a head in the halves convention, the rest as they are."""
    import jax.numpy as jnp
    freq, r, scale = inv_freq(rp, x.shape[-1], wrong)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(freq)
    cos = (jnp.cos(ang) * scale)[:, :, None, :]
    sin = (jnp.sin(ang) * scale)[:, :, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(d, i, u, w, pos, seg, wrong=()):
    """Layer i's attention on u [B, L, H]: grouped-query, the explicit
    mask a block of query rows at a time, the gate a head."""
    import jax
    import jax.numpy as jnp
    nq, nkv, hd = d["heads"][i], d["nkv"], d["head"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, n, _ = u.shape
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(b, n, nq, hd)
    k = (u @ w["self_attn.k_proj.weight"].T).reshape(b, n, nkv, hd)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(b, n, nkv, hd)
    rp = d["rope"][d["types"][i]]
    q, k = _rope(q, pos, rp, wrong), _rope(k, pos, rp, wrong)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    window = _window(d, i)
    if window is not None:
        window += {"window_511": -1, "window_513": 1}.get(
            next((x for x in wrong if x.startswith("window_")), None), 0)
    out = []
    for s in range(0, n, QUERY_BLOCK):
        e = min(s + QUERY_BLOCK, n)
        same = (seg[:, s:e, None] == seg[:, None, :]) \
            & (seg[:, s:e, None] != 0)
        apart = pos[:, s:e, None] - pos[:, None, :]
        seen = same & (apart >= 0)
        if window is not None:
            seen = seen & (apart < window)
        score = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k) / np.sqrt(hd)
        # a padding row sees nothing: a large finite value, not -inf,
        # so that its (unused) softmax is no NaN
        a = jax.nn.softmax(jnp.where(seen[:, None], score, -1e30), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", a, v))
    o = jnp.concatenate(out, axis=1)
    if d["gating"] and "gate_left_out" not in wrong:
        g = jax.nn.sigmoid(u @ w["self_attn.g_proj.weight"].T)
        o = o * g[..., None]
    return o.reshape(b, n, nq * hd) @ w["self_attn.o_proj.weight"].T


def _swiglu(v, gate, up, down):
    import jax
    import jax.numpy as jnp
    gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
    return (jax.nn.silu(v @ gate.T) * (v @ up.T)) @ down.T


def _route(d, v, gate_w, wrong=()):
    """The gates [B, L, E] over ALL experts: the sigmoid score where
    the expert is among the token's k largest, else 0; divided by (the
    k's sum + 1e-20) under ``norm_topk_prob``; scaled."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(v @ gate_w.astype(jnp.float32).T)
    kth = jax.lax.top_k(s, d["top_k"])[0][..., -1:]
    gates = jnp.where(s >= kth, s, 0.0)
    if d["renorm"] and "gates_not_renormalised" not in wrong:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * d["scaling"]


def _blocks(hf, get, ids, seg=None, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per sparse layer its gates [B, L, E]). Layer by layer and expert by
    expert, each cast up on the way in."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    seg = np.ones(ids.shape, np.int32) if seg is None else np.asarray(seg)
    pos, seg = jnp.asarray(positions(seg)), jnp.asarray(seg)
    attention = {
        i: jax.jit(lambda x, w, i=i: x + _attention(
            d, i, _rms(x, w["input_layernorm.weight"], d["eps"]), w, pos,
            seg, wrong))
        for i in range(d["layers"])}
    ffn_in = jax.jit(lambda x, w: _rms(x, w, d["eps"]))
    swiglu = jax.jit(_swiglu)
    route = jax.jit(lambda v, g: _route(d, v, g, wrong))
    expert = jax.jit(lambda v, g, *ws: g * _swiglu(v, *ws))
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        names = ["input_layernorm.weight"] + [
            f"self_attn.{n}_proj.weight"
            for n in ("q", "k", "v", "o") + (("g",) if d["gating"] else ())]
        x = attention[i](x, {n: get(pre + n) for n in names})
        v = ffn_in(x, get(pre + "post_attention_layernorm.weight"))
        mlp = pre + "mlp."
        if d["ffs"][i] == "dense":
            x = x + swiglu(v, *(get(f"{mlp}{m}.weight") for m in _FFN))
            continue
        gates = route(v, get(mlp + "gate.weight"))
        for e in d["held"]:  # what the absent experts add is left out
            x = x + expert(v, gates[..., e:e + 1], *(
                get(f"{mlp}experts.{e}.{m}.weight") for m in _FFN))
        if d["shared"] and "shared_expert_left_out" not in wrong:
            x = x + swiglu(v, *(get(f"{mlp}shared_expert.{m}.weight")
                                for m in _FFN))
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
    wrong (``WRONG``): both only to size TOLERANCE."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids, None, wrong)
        out = jax.jit(lambda x: _token_logprobs(_final(hf, x, get), ids))(x)
    return np.asarray(out, np.float32)


def top_k_sets(hf, tensors, ids, layer):
    """Which of ALL the experts the reference routes every token of
    ``ids`` to in sparse ``layer`` (its index in the model): bool
    [B, L, E]."""
    import jax
    import jax.numpy as jnp
    hf1 = dict(hf, num_hidden_layers=layer + 1, **{
        key: hf[key][:layer + 1] for key in (
            "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer")})
    with jax.default_matmul_precision("highest"):
        _, routed = _blocks(hf1, _getter(tensors, None),
                            jnp.asarray(ids, jnp.int32))
    return np.asarray(routed[-1] > 0)


# ----------------------------------------------------------------------
# The training loss and its gradient
# ----------------------------------------------------------------------
def sft_loss(hf, tensors, ids, prompt_len):
    """The SFT loss of ONE microbatch whose documents are the rows of
    ``ids`` [n, L] (equal lengths, the first ``prompt_len`` tokens of
    each the prompt): the mean over the answer tokens of -log p(token |
    before). No auxiliary term (``routing_type`` none in the program's
    reading of this family, as the benchmark's step runs it). Returns
    (loss, dict(nll=, aux=)). A function of ``tensors`` that
    ``jax.grad`` differentiates."""
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
