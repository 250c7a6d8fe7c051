"""Everything the benchmark knows about the ``deepseek_v3`` architecture
(Moonlight-16B-A3B): the checkpoint's tensors, the plain float32
reference (forward, training loss and its gradient) with its tolerance,
and what a step needs in parameters, FLOPs, bytes, routed pairs and
flash-kernel block products, all from the PUBLISHED configuration dict
and the checkpoint's tensors and nothing of the program's.

The model. Layer ``i`` on ``x`` [T, H], RMSNorm with ``rms_norm_eps``,
``nope = qk_nope_head_dim``, ``rope = qk_rope_head_dim``, n heads::

    u = RMSNorm(x; input_layernorm)
    q = u Wq [T, n, nope + rope]            no bias anywhere
    a = u Wkva [T, kv_lora_rank + rope]     (kv_a_proj_with_mqa)
    c = RMSNorm(a[:, :kv_lora_rank]; kv_a_layernorm) AT EPS 1e-6
    c Wkvb [T, n, nope + v_head_dim]: k_nope = [.., :nope], v = [.., nope:]
    q_rope = q[.., nope:], k_rope = a[:, kv_lora_rank:] [T, 1, rope]: ONE
          for all heads; both de-interleaved, x -> [x[0::2], x[1::2]],
          then rotated in halves at the token's position IN ITS
          DOCUMENT, inv_freq_j = rope_theta^(-2j/rope), no scaling
    q = [q_nope, q_rope], k = [k_nope, k_rope to every head]
    scores q k^T (nope + rope)^-0.5 in float32; key s visible to query
          t iff same document and s <= t
    a = x + (heads' outputs [T, n, v_head_dim], concatenated) Wo
    v = RMSNorm(a; post_attention_layernorm)
    dense  (i < first_k_dense_replace): y = a + down(silu(gate v) * up v)
    sparse: s = sigmoid(v Wr) [T, E] in float32 over ALL experts; the
          num_experts_per_tok largest of s + e_score_correction_bias
          (n_group 1: one group, no limit); gates g_e = s_e / (their
          sum + 1e-20) x routed_scaling_factor on each chosen expert's
          OUTPUT; y = a + sum_e g_e Expert_e(v) + Shared(v), Shared ONE
          SwiGLU of n_shared_experts x moe_intermediate_size
    model: embed_tokens -> layers -> RMSNorm(model.norm) -> lm_head

This is ``transformers``' ``modeling_deepseek_v3.py`` (4.57.6 is
installed here: ``tests/model/test_deepseek_v3.py`` holds this file to
THAT module's logits at toy widths), the epsilon of ``kv_a_layernorm``
included: the module builds that norm without one, so it norms at 1e-6
whatever ``rms_norm_eps`` says. What the catalog row's config does not
state is listed in the configuration file under ``assumed``
(``rope_interleave`` true, the class's default). ``q_lora_rank`` other
than null, ``rope_scaling``, ``n_group`` or ``topk_group`` over 1,
multi-token prediction and ``moe_layer_freq`` other than 1 are refused.
No auxiliary loss: ``seq_aux`` belongs to the training recipe.

The reference takes ``ids`` [B, L] and optionally ``seg`` [B, L]
(document ids of a packed row, 0 = padding; None: a row is one
document): it builds the explicit [L, L] visibility mask from
documents and positions, a block of query rows at a time so that a row
of 4096 fits, loops over the layers and, in a sparse layer, over the
HELD experts, adding each one's output for every token weighted by a
gate that is 0 where the expert is not among the token's k. No kernel,
no cache, no sort, no ragged product. Weights are the checkpoint's
values cast up exactly; every product is taken at
``default_matmul_precision("highest")``.

**An expert-parallel rank's share** (``expert_share: {of, first}``
beside ``n_routed_experts`` = how many the files hold, as
``realhf_tpu/models/hf/deepseek_v3.py`` reads it): the router and its
bias keep their published width, the k are chosen among ALL experts,
only the HELD experts' terms are added, and the shared experts, which
every rank holds, are added whole. A sliced vocabulary is a smaller
vocabulary.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (0.87 to 0.89 nat at the cell's widths).
#: Sized on the chip at those widths (5 layers, 8 of 64 experts held,
#: vocabulary 20,480) by ``scripts/chip_check.py deepseek_v3`` and the
#: cell's own runs (my chip runs, PR 37), shares of the spread at two
#: seeds:
#:
#:   engine, bf16, the fixed batch (11 seeds)              0.0128-0.0166
#:   ONE packed row of 4096: documents of 1536 .. 512      0.0118-0.0146
#:   prefill of 640, then 127 decode steps, rows of 768    0.0137 (decoded 0.0105)
#:   engine, bf16, the share's SLOW path forced            0.0166 (= the fast path's)
#:   this forward at default matmul precision              0.0117 0.0128
#:   HELD experts rounded to int8 by row                   0.0030 0.0036
#:   held experts rounded to float8 e4m3                   0.0118 0.0132
#:   every matrix rounded to int8 by row                   0.0458 0.0515
#:   every matrix rounded to float8 e4m3                   0.149  0.165
#:   every matrix rounded to float8 e5m2                   0.226  0.248
#:   WRONG: selection without the bias                     0.0505 0.0602 (0.0513)
#:   WRONG: gates not times 2.446                          0.0968 0.1101 (0.1094)
#:   WRONG: no kv_a_layernorm                              0.1227 0.1344 (0.1397)
#:   WRONG: scale 128^-0.5                                 0.1840 0.2009 (0.1654)
#:   WRONG: rotary on the first 64 values                  0.5123 0.5738 (0.5345)
#:   WRONG: the shared experts left out                    0.6076 0.6454 (0.6103)
#:   WRONG: a rotary key a head                            0.6981 0.7454 (0.4939)
#:   engine, FLOAT32 at highest precision, 4096 tokens     (0.0000011)
#:
#: (in brackets: on ONE document of 4,096 tokens, the cell's row,
#: reference against reference; the last row the program with float32
#: weights through the COMPILED flash kernels at a key of 192 and a
#: value of 128.) 0.03 is 1.8 times the most bf16 shows on the fixed
#: batch, two thirds of int8 on the whole model at its mildest seed and
#: three fifths of the mildest wrong equation, so a forward computed
#: below bf16, or by any wrong equation of the list, fails. bf16 reads
#: higher here than in the other families (0.011 to 0.013 of it is the
#: products' own precision: the reference at default precision): the
#: scores sum 192 products a pair and the latent passes through two
#: more products and a norm than keys made in one. WHAT IT CANNOT
#: TELL: a lower precision ON THE HELD EXPERTS ALONE (inside bf16's
#: noise, as in ``lfm2_moe`` and ``laguna``), and the latent norm at
#: ``rms_norm_eps`` 1e-5 in place of the module's 1e-6 (1.5e-4 of the
#: latent at toy widths: ``tests/model/test_deepseek_v3.py`` holds the
#: program and this file to ``transformers``' own module in float32).
TOLERANCE = 0.03

_PRE = "model.layers.{}."
_FFN = ("gate_proj", "up_proj", "down_proj")
_ATTN = ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
#: rows of queries whose scores are held at once
QUERY_BLOCK = 512
#: the flash kernels' blocks (``realhf_tpu/ops/flash_attention.py``:
#: DEFAULT_BQ, DEFAULT_BK), which ``flash_flops`` counts products of
FLASH_BQ, FLASH_BK = 256, 512
#: what ``kv_a_layernorm`` norms at (the published module's default)
LATENT_NORM_EPS = 1e-6
#: published key -> the one value of it this reference computes
_ONLY = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc",
         "scoring_func": "sigmoid", "num_nextn_predict_layers": 0,
         "moe_layer_freq": 1, "rope_interleave": True,
         "attention_bias": False, "hidden_act": "silu"}


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    for key, only in _ONLY.items():
        if hf.get(key, only) != only:
            raise NotImplementedError(
                f"the reference computes {key}={only!r} only, not "
                f"{hf[key]!r}")
    n, nq = hf["num_hidden_layers"], hf["num_attention_heads"]
    if hf.get("num_key_value_heads", nq) != nq:
        raise NotImplementedError("latent attention: a key a query head")
    share = hf.get("expert_share") or dict(of=hf["n_routed_experts"],
                                           first=0)
    lead = min(hf.get("first_k_dense_replace", 0), n)
    return dict(
        layers=n, sparse=[i >= lead for i in range(n)], heads=nq,
        hidden=hf["hidden_size"], rank=hf["kv_lora_rank"],
        nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
        qk=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        v=hf["v_head_dim"], inter=hf["intermediate_size"],
        moe_inter=hf["moe_intermediate_size"],
        shared=hf["moe_intermediate_size"] * hf.get("n_shared_experts", 0),
        vocab=hf["vocab_size"], experts=share["of"],
        top_k=hf["num_experts_per_tok"],
        held=range(share["first"],
                   share["first"] + hf["n_routed_experts"]),
        renorm=bool(hf.get("norm_topk_prob", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        eps=hf.get("rms_norm_eps", 1e-6),
        theta=float(hf.get("rope_theta", 10000.0)),
        tied=bool(hf.get("tie_word_embeddings", False)))


def _attention_params(d):
    """q, the compression (to latent + rotary key), the expansion (a
    head's nope + v from the latent), the output: matrices only."""
    h, n = d["hidden"], d["heads"]
    return h * n * d["qk"] + h * (d["rank"] + d["rope"]) \
        + d["rank"] * n * (d["nope"] + d["v"]) + n * d["v"] * h


def _ffn_params(d, i):
    h = d["hidden"]
    if not d["sparse"][i]:
        return 3 * h * d["inter"]
    return h * d["experts"] + len(d["held"]) * 3 * h * d["moe_inter"] \
        + 3 * h * d["shared"]


def n_matrix_params(hf):
    """The matrices alone: what the issue's arithmetic counts
    (568,459,264 in the benchmark's cell)."""
    d = dims(hf)
    return sum(_attention_params(d) + _ffn_params(d, i)
               for i in range(d["layers"])) \
        + d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)


def n_params(hf):
    """Parameters the checkpoint HOLDS: the matrices, and for every
    layer its two norms and the latent's, in a sparse layer the
    router's selection bias over all experts, and the final norm."""
    d = dims(hf)
    small = sum(2 * d["hidden"] + d["rank"]
                + (d["experts"] if d["sparse"][i] else 0)
                for i in range(d["layers"]))
    return n_matrix_params(hf) + small + d["hidden"]


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes over ALL the router's
    experts: ``num_experts_per_tok`` a token in every sparse layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * sum(d["sparse"])


def held_pairs(hf, seqlens):
    """The EXPECTED share of those that land on held experts, at even
    routing. What a run really multiplies is the program's counter
    ``moe_held_pairs_total``."""
    d = dims(hf)
    return routed_pairs(hf, seqlens) * len(d["held"]) / d["experts"]


def visible_pairs(n):
    """(query, key) pairs of ONE document of ``n`` tokens under the
    causal mask."""
    return n * (n + 1) // 2


def forward_flops(hf, seqlens):
    """FLOPs of one forward over documents of these lengths, at 2 FLOPs
    a multiply-add, OF THE MATHEMATICS: a latent layer's four
    projections, its scores over the key's width (nope + rope) and its
    values over the value's (v_head_dim) for the pairs the causal mask
    lets through; a dense feed-forward's three matrices; in a sparse
    one the router over all experts and the shared experts on every
    token, and the HELD experts only, at even routing
    (``num_experts_per_tok x held / experts`` experts a token: 6 x 8/64
    = 0.75 in the benchmark's cell); the vocabulary head on every
    token. Norms, rotary, elementwise products, softmax, sigmoid, the
    sort and the scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    h = d["hidden"]
    pairs = sum(visible_pairs(n) for n in seqlens)
    total = 2 * tokens * h * d["vocab"]
    for i in range(d["layers"]):
        total += 2 * tokens * _attention_params(d) \
            + 2 * pairs * d["heads"] * (d["qk"] + d["v"])
        if not d["sparse"][i]:
            total += 2 * tokens * 3 * h * d["inter"]
        else:
            total += 2 * tokens * (
                h * d["experts"] + 3 * h * d["shared"]
                + 3 * h * d["moe_inter"] * d["top_k"]
                * len(d["held"]) / d["experts"])
    return total


def flash_blocks(n, bq=FLASH_BQ, bk=FLASH_BK):
    """(query block, key block) pairs that hold a visible pair, in a
    row that is ONE document of ``n`` tokens (a multiple of both
    blocks): what a flash kernel that skips every other block visits.
    Counted from the causal mask's own definition, pair by pair of
    blocks."""
    bq, bk = min(bq, n), min(bk, n)
    if n % bq or n % bk:
        raise ValueError(f"a row of {n} is no multiple of {bq}, {bk}")
    count = sum(1 for i in range(n // bq) for j in range(n // bk)
                if j * bk <= (i + 1) * bq - 1)  # a key at or before a query
    return count, bq, bk


def flash_flops(hf, seqlens):
    """FLOPs of the matrix products the flash kernels run for ONE
    forward and ONE backward over rows that are one document each of
    these lengths, as the MATHEMATICS has them over every VISITED block
    pair whole (a block on the diagonal is multiplied whole and masked
    after): ``2 x rows x columns x width`` a product, the width the
    key's (nope + rope = 192) for a score-shaped product and the
    value's (128) for a value-shaped one. Forward: scores + values; dq
    pass: scores, dP (value), dQ (key); dkv pass: scores, dV (value),
    dP (value), dK (key); times the heads, summed over layers. (What
    the 128-wide MXU spends on a contraction over 192 is no part of
    this count.) ``dict(fwd=, dq=, dkv=)``: a step under
    rematerialisation runs the forward kernel more than once, and the
    reader counts its calls."""
    d = dims(hf)
    out = dict(fwd=0, dq=0, dkv=0)
    qk, v = d["qk"], d["v"]
    for n in seqlens:
        blocks, bq, bk = flash_blocks(n)
        pair = 2 * bq * bk * blocks * d["heads"] * d["layers"]
        out["fwd"] += pair * (qk + v)
        out["dq"] += pair * (qk + v + qk)
        out["dkv"] += pair * (qk + v + v + qk)
    return out


def kv_bytes_per_token(hf, bytes_per_el=2, latent=False):
    """What a token adds to the cache in every layer. The program
    caches the EXPANDED keys (nope + rope wide) and values a head;
    ``latent=True``: the one compressed row (kv_lora_rank + rope) that
    is all the architecture needs kept, which the program does not
    cache yet (ROADMAP R3c)."""
    d = dims(hf)
    row = d["rank"] + d["rope"] if latent \
        else d["heads"] * (d["qk"] + d["v"])
    return d["layers"] * row * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2, latent=False):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, and every live sequence
    reads its cached prefix (``kv_bytes_per_token``). Prefill is left
    out."""
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    rows = sum(prompt_len + t for t in range(new_tokens))
    return weights + n_seqs * rows * kv_bytes_per_token(
        hf, bytes_per_el, latent)


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor. ``kind`` is
    ``matrix`` or ``norm``; ``e_score_correction_bias`` is drawn like a
    matrix, N(0, initializer_range), so that it moves the choice of
    some tokens' experts."""
    d = dims(hf)
    h, n = d["hidden"], d["heads"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
    }
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        a = pre + "self_attn."
        out[pre + "input_layernorm.weight"] = ((h,), "norm")
        out[pre + "post_attention_layernorm.weight"] = ((h,), "norm")
        out[a + "q_proj.weight"] = ((n * d["qk"], h), "matrix")
        out[a + "kv_a_proj_with_mqa.weight"] = (
            (d["rank"] + d["rope"], h), "matrix")
        out[a + "kv_a_layernorm.weight"] = ((d["rank"],), "norm")
        out[a + "kv_b_proj.weight"] = (
            (n * (d["nope"] + d["v"]), d["rank"]), "matrix")
        out[a + "o_proj.weight"] = ((h, n * d["v"]), "matrix")
        mlp = pre + "mlp."
        if not d["sparse"][i]:
            out.update(_ffn_shapes(mlp, h, d["inter"]))
            continue
        out[mlp + "gate.weight"] = ((d["experts"], h), "matrix")
        out[mlp + "gate.e_score_correction_bias"] = (
            (d["experts"],), "matrix")
        for e in d["held"]:
            out.update(_ffn_shapes(f"{mlp}experts.{e}.", h, d["moe_inter"]))
        if d["shared"]:
            out.update(_ffn_shapes(mlp + "shared_experts.", h, d["shared"]))
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
#: (``scripts/chip_check.py``, the tests). ``rotary_key_a_head``: head
#: h takes the shared rotary key rolled by 2h values, so the heads no
#: longer see ONE key part.
WRONG = ("scale_of_the_nope_width", "kv_a_layernorm_left_out",
         "rotary_on_the_first_values", "rotary_key_a_head",
         "selection_without_the_bias", "gates_not_scaled",
         "shared_experts_left_out")


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


def _rope(x, pos, theta):
    """x [B, L, heads, r] rotated at positions [B, L] as the published
    module does: pairs (2j, 2j+1) de-interleaved to [evens, odds], then
    rotated in halves. The result stays de-interleaved, queries and
    keys alike, which no score can tell."""
    import jax.numpy as jnp
    r = x.shape[-1]
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(freq)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(d, u, w, pos, seg, wrong=()):
    """A layer's latent attention on u [B, L, H]: the explicit mask a
    block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    n, nope, rope, rank = d["heads"], d["nope"], d["rope"], d["rank"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, _ = u.shape
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(b, t, n, d["qk"])
    a = u @ w["self_attn.kv_a_proj_with_mqa.weight"].T
    c = a[..., :rank]
    if "kv_a_layernorm_left_out" not in wrong:
        c = _rms(c, w["self_attn.kv_a_layernorm.weight"], LATENT_NORM_EPS)
    kv = (c @ w["self_attn.kv_b_proj.weight"].T).reshape(
        b, t, n, nope + d["v"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = a[..., None, rank:]  # [B, L, 1, rope]: one for all heads
    if "rotary_on_the_first_values" in wrong:
        # the first `rope` values of every query AND key head rotate,
        # the shared part goes to the heads as it is
        q = jnp.concatenate([_rope(q[..., :rope], pos, d["theta"]),
                             q[..., rope:]], axis=-1)
        k = jnp.concatenate(
            [_rope(k_nope[..., :rope], pos, d["theta"]),
             k_nope[..., rope:], jnp.broadcast_to(k_rope, (b, t, n, rope))],
            axis=-1)
    else:
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], pos, d["theta"])], axis=-1)
        k_rope = jnp.broadcast_to(_rope(k_rope, pos, d["theta"]),
                                  (b, t, n, rope))
        if "rotary_key_a_head" in wrong:
            k_rope = jnp.stack([jnp.roll(k_rope[:, :, h], 2 * h, axis=-1)
                                for h in range(n)], axis=2)
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
    width = nope if "scale_of_the_nope_width" in wrong else d["qk"]
    out = []
    for s in range(0, t, QUERY_BLOCK):
        e = min(s + QUERY_BLOCK, t)
        seen = (seg[:, s:e, None] == seg[:, None, :]) \
            & (seg[:, s:e, None] != 0) \
            & (pos[:, s:e, None] - pos[:, None, :] >= 0)
        score = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k) / np.sqrt(width)
        # a padding row sees nothing: a large finite value, not -inf,
        # so that its (unused) softmax is no NaN
        p = jax.nn.softmax(jnp.where(seen[:, None], score, -1e30), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    o = jnp.concatenate(out, axis=1)
    return o.reshape(b, t, n * d["v"]) @ w["self_attn.o_proj.weight"].T


def _swiglu(v, gate, up, down):
    import jax
    import jax.numpy as jnp
    gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
    return (jax.nn.silu(v @ gate.T) * (v @ up.T)) @ down.T


def _route(d, v, gate_w, bias, wrong=()):
    """The gates [B, L, E] over ALL experts: the sigmoid score where
    the expert is among the token's k largest of score + bias, else 0;
    divided by (the k's sum + 1e-20) under ``norm_topk_prob``;
    scaled."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(v @ gate_w.astype(jnp.float32).T)
    choice = s if "selection_without_the_bias" in wrong \
        else s + bias.astype(jnp.float32)
    kth = jax.lax.top_k(choice, d["top_k"])[0][..., -1:]
    gates = jnp.where(choice >= kth, s, 0.0)
    if d["renorm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates if "gates_not_scaled" in wrong else gates * d["scaling"]


def _blocks(hf, get, ids, seg=None, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per sparse layer its gates [B, L, E]). Layer by layer and expert by
    expert, each cast up on the way in."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    seg = np.ones(ids.shape, np.int32) if seg is None else np.asarray(seg)
    pos, seg = jnp.asarray(positions(seg)), jnp.asarray(seg)
    attention = jax.jit(lambda x, w: x + _attention(
        d, _rms(x, w["input_layernorm.weight"], d["eps"]), w, pos, seg,
        wrong))
    ffn_in = jax.jit(lambda x, w: _rms(x, w, d["eps"]))
    swiglu = jax.jit(_swiglu)
    route = jax.jit(lambda v, g, b: _route(d, v, g, b, wrong))
    expert = jax.jit(lambda v, g, *ws: g * _swiglu(v, *ws))
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    names = ["input_layernorm.weight", "self_attn.kv_a_layernorm.weight"] \
        + [f"self_attn.{n}.weight" for n in _ATTN]
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        x = attention(x, {n: get(pre + n) for n in names})
        v = ffn_in(x, get(pre + "post_attention_layernorm.weight"))
        mlp = pre + "mlp."
        if not d["sparse"][i]:
            x = x + swiglu(v, *(get(f"{mlp}{m}.weight") for m in _FFN))
            continue
        gates = route(v, get(mlp + "gate.weight"),
                      get(mlp + "gate.e_score_correction_bias"))
        for e in d["held"]:  # what the absent experts add is left out
            x = x + expert(v, gates[..., e:e + 1], *(
                get(f"{mlp}experts.{e}.{m}.weight") for m in _FFN))
        if d["shared"] and "shared_experts_left_out" not in wrong:
            x = x + swiglu(v, *(get(f"{mlp}shared_experts.{m}.weight")
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
    with jax.default_matmul_precision("highest"):
        _, routed = _blocks(dict(hf, num_hidden_layers=layer + 1),
                            _getter(tensors, None),
                            jnp.asarray(ids, jnp.int32))
    return np.asarray(routed[-1] > 0)


# ----------------------------------------------------------------------
# The training loss and its gradient
# ----------------------------------------------------------------------
def sft_loss(hf, tensors, ids, prompt_len):
    """The SFT loss of ONE microbatch whose documents are the rows of
    ``ids`` [n, L] (equal lengths, the first ``prompt_len`` tokens of
    each the prompt): the mean over the answer tokens of -log p(token |
    before). No auxiliary term (``seq_aux`` is the recipe's; the
    benchmark's step runs none). Returns (loss, dict(nll=, aux=)). A
    function of ``tensors`` that ``jax.grad`` differentiates; nothing
    reaches ``e_score_correction_bias``, which moves a choice and no
    gate."""
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
