"""Everything the benchmark knows about the ``keye_vl2`` architecture
(Keye-VL-2.0-30B-A3B's LANGUAGE MODEL): the checkpoint's tensors, the
plain float32 reference (forward, training loss and its gradient) with
its tolerance, and what a step needs in parameters, FLOPs, bytes, routed
pairs, selected pairs and flash-kernel products, all from the PUBLISHED
configuration dict and the checkpoint's tensors and nothing of the
program's.

The model. Layer ``i`` on ``x`` [T, H], RMSNorm at ``rms_norm_eps``,
n query heads and m key/value heads of d = ``head_dim``::

    u = RMSNorm(x; input_layernorm)
    q = RMSNorm_head(u Wq; q_norm) [T, n, d], k = RMSNorm_head(u Wk;
          k_norm) [T, m, d], v = u Wv [T, m, d]: no bias anywhere, the
          norms over each HEAD's d values with one scale of width d
    q, k rotated over the whole head in halves (rotate-half) at the
          token's position IN ITS DOCUMENT, inv_freq_j =
          rope_theta^(-2j/d). (``mrope_section`` [16, 24, 24] splits
          the 64 rotary pairs over three position axes of the vision
          tower's inputs; on TEXT all three hold the same position and
          the sections are ONE plain rotary embedding: what is computed
          here. The vision tower is no part of this configuration.)
    the indexer (``sa_config``: J = indexer_num_heads heads of
          e = indexer_head_dim over ONE index key, DeepSeek-V3.2's as
          published at this config's sizes):
       qI[t, j] = rot_e(u_t W_iq)[j]          j = 1..J, e wide
       kI[s]    = rot_e(LayerNorm(u_s W_ik; k_norm weight AND bias,
                   eps 1e-6))                  e wide, one head
       w[t]     = (u_t W_iw) J^-1/2 e^-1/2     J values
       I[t, s]  = sum_j w[t, j] ReLU(qI[t, j] . kI[s])
          rot_e: the layer's rotary embedding over the whole e-wide
          head, inv_freq_j = rope_theta^(-2j/e), rotate-half
       S_t = the ``topk`` visible s (same document, s <= t) of largest
          I[t, s], ties to the lower s; every visible s where there are
          no more than ``topk``
    o_t = sum_{s in S_t} softmax_s(q_t . k_s / sqrt(d)) v_s a head;
          query head h reads key/value head h // (n / m); EVERY head of
          a token shares S_t (indexer_num_kv_heads 1)
    a = x + (heads' outputs, concatenated) Wo
    v = RMSNorm(a; post_attention_layernorm)
    p = softmax(v Wr) [T, E] in float32 over ALL experts; the
          num_experts_per_tok largest; gates g_e = p_e / (their sum)
          under norm_topk_prob; y = a + sum_e g_e Expert_e(v), an
          expert a SwiGLU of moe_intermediate_size; no shared expert
    model: embed_tokens -> layers -> RMSNorm(model.norm) -> lm_head

Attention, norms, router and experts are ``transformers``' ``modeling_
qwen3_moe.py`` (4.57.6 is installed here: ``tests/model/test_keye_vl2
.py`` holds this file, with ``topk`` >= the row, to THAT module's
logits at toy widths, the indexer's tensors ignored). ``transformers``
has no ``KeyeVL2`` and there is no network here: what the catalog row's
config does not state is listed in the configuration file under
``assumed`` (the per-head query/key norm, the indexer's query from u,
its LayerNorm with a bias, its rotary embedding, the tensor names, that
``q_chunk_size`` / ``kv_chunk_size`` are tiles that change no result).
The published indexer also rotates q and k by a Hadamard matrix and
rounds them to float8 before the scores; an orthogonal rotation of both
changes no dot product and the rounding is an implementation's
precision, so neither is part of these equations.

TRAINING. The selection is discrete: the language-model loss has NO
gradient with respect to the indexer's four tensors (``jax.grad`` of
``sft_loss`` returns exact zeros for them; ``stop_gradient`` on S_t
changes nothing). DeepSeek's alignment loss (a KL term that pulls the
indexer's scores to the attention's own distribution) is NOT added: it
needs the probabilities the flash kernels never materialise. The
indexer's tensors therefore take no update in the benchmark's step: the
one departure from how the model was pre-trained.

The reference takes ``ids`` [B, L] and optionally ``seg`` [B, L]
(document ids of a packed row, 0 = padding; None: a row is one
document): it builds the explicit [L, L] visibility mask and the dense
[L, L] index scores a block of query rows at a time, takes the top-k by
``jax.lax.top_k`` (a sort; ties to the lower index) into a boolean
mask, loops over the layers and, in a sparse feed-forward, over the
HELD experts. No kernel, no cache, no bisection, no ragged product.
Weights are the checkpoint's values cast up exactly; every product is
taken at ``default_matmul_precision("highest")``.

THE HARNESS'S WEIGHTS, and the one tensor a layer that is drawn unlike
the other families'. ``benchmark/generate.py`` draws a tensor either
N(0, initializer_range) (kind ``matrix``) or 1 + that (kind ``norm``).
This architecture has NO dense lead and NO shared expert, and the chip
holds 16 of its 128 experts: under such weights with every norm near 1
nothing a layer adds to a token's row is the token's own but a gate's
worth of ONE held expert (0.03 an entry), while attention, which
averages 2,048 values, adds the SAME vector to every token at a gain of
1.15 a layer (v_proj 0.02 x sqrt(2048) times o_proj 0.02 x sqrt(4096)).
By the second layer the tokens' rows are one direction (mean cosine
0.88 at the last), every token of a row picks the same 8 experts
(``moe_load_max_over_mean`` 16.0 of a possible 16.0), and whether those
8 are among the 16 held is a coin a layer a SEED: the held pairs a step
read 0.46 to 0.97 M where even routing brings 0.66 M, 0 to 40 of a
step's 160 (layer, microbatch)s overflowed the share's fast path, and
two processes of the cell read 31,710 and 32,768 tokens/s (my chip
runs, PR 45): no deployment's router, and no steady cell. So
``input_layernorm.weight``, the norm before attention and the indexer,
is drawn as kind ``matrix``: a scale near 0.02 a channel. Attention's
gain falls to 0.023, a token's row stays its own (mean cosine 0.004),
the router's loads are even (max over mean 1.4 to 1.7, held pairs 0.94
to 1.08 of even: CPU, the cell's widths, rows of 1,024, three seeds),
and every FLOP and byte of the step is what it was. What it costs: the
attention branch is a seventh of the token's own row, not seven times
it, so a wrong attention is a smaller share of the logits than in the
other families (``chip_check.py keye_vl2`` sizes ``TOLERANCE`` on that
lens). A generator that could draw a router or an output projection at
a trained model's scale would not need this: PERF.md section 7.

**An expert-parallel rank's share** (``expert_share: {of, first}``
beside ``num_experts`` = how many the files hold, as ``realhf_tpu/
models/hf/keye_vl2.py`` reads it): the router keeps its published
width, the k are chosen among ALL experts, only the HELD experts' terms
are added. A sliced vocabulary is a smaller vocabulary.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (0.43 to 0.46 nat at the cell's widths and
#: its ``initializer_range`` of 0.01). Sized on the chip at those
#: widths (5 layers, 16 of 128 experts held, vocabulary 18,992) by
#: ``scripts/chip_check.py keye_vl2`` and the cell's own runs (my chip
#: runs, PR 45), shares of the spread:
#:
#:   engine, bf16, the fixed batch (11 seeds)               0.0147-0.0269
#:   ONE packed row of 4096: documents of 2560, 1024, 512   0.0175-0.0234
#:   prefill of 2176, then 127 decode steps, rows of 2304   0.0178 (decoded 0.0176)
#:   engine, bf16, ONE document of 4096 (selection live)    0.0164 (past 2048: 0.0145)
#:   this forward at default matmul precision               0.0082 0.0141
#:   HELD experts rounded to int8 by row                    0.0219 0.0233
#:   held experts rounded to float8 e4m3                    0.1146 0.1335
#:   every matrix rounded to int8 by row                    0.0498 0.0581
#:   every matrix rounded to float8 e4m3                    0.2345 0.2504
#:   every matrix rounded to float8 e5m2                    0.2182 0.2455
#:   WRONG: rotary base 10,000                              0.1614 0.1702 (0.0788)
#:   WRONG: no norm a head on q and k                       0.1714 0.1796 (0.0743)
#:   WRONG: gates not renormalised                          0.4807 0.4689 (0.4784)
#:   WRONG: scores without ReLU                             0 0           (0.0108)
#:   WRONG: no selection                                    0 0           (0.0168)
#:   WRONG: a selection a head                              0 0           (0.0232)
#:   WRONG: the weights w left out                          0 0           (0.0234)
#:   WRONG: topk 1024                                       0 0           (0.0445)
#:   the indexer's terms rounded to bfloat16 ALONE          -             (0.0011; past 2048: 0.0021)
#:   engine, FLOAT32 at highest precision, 4096 tokens      (0.00000066)
#:
#: (in brackets: on ONE document of 4,096 tokens, the cell's row,
#: reference against reference; the last row the program with float32
#: weights through the COMPILED flash kernels with the selection their
#: operand.) 0.037 is 1.3 to 1.4 times the most bf16 shows and three
#: quarters of int8 on the whole model at its mildest seed, so a forward
#: computed below bf16, or by any wrong equation the fixed batch can
#: see (four to thirteen tolerances out), fails. Under the class's
#: default ``initializer_range`` of 0.02 the same rows read 0.046 to
#: 0.089 (bf16, eleven seeds) and 0.157 to 0.170 (int8): nothing
#: between them is under the 0.1 that ``tests/benchmark/
#: test_benchmark_manifest.py`` holds every family's tolerance to, which
#: is why the configuration draws at 0.01 (its ``assumed``). WHAT THE
#: FIXED BATCH CANNOT TELL: anything about the selection (its rows of
#: 256 never reach ``topk`` = 2048 keys: the five rows of zeros), and on
#: a 4,096-token document four of those five read INSIDE 0.037 even so
#: (attention is a small part of a token's row under this family's
#: draw of its input norm), as does a lower precision on the held
#: experts alone (0.022): the float32 tests and ``chip_check.py
#: keye_vl2``'s row ``exact`` (the float32 engine through the compiled
#: kernels on that document, 0.00000066, 16,000 times under the
#: mildest of them) hold those. Rounding the indexer's terms to
#: bfloat16 moves 4,117 of a layer's 6,292,480 selected pairs and costs
#: 0.0011: a fifteenth of what bf16 costs the rest of the model.
TOLERANCE = 0.037

_PRE = "model.layers.{}."
_FFN = ("gate_proj", "up_proj", "down_proj")
_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
#: the indexer's tensors under ``self_attn.indexer.``
_INDEX = ("wq.weight", "wk.weight", "k_norm.weight", "k_norm.bias",
          "weights_proj.weight")
#: rows of queries whose scores are held at once
QUERY_BLOCK = 512
#: what the indexer's LayerNorm norms at (its class's default)
INDEX_NORM_EPS = 1e-6
#: published key -> the one value of it this reference computes
_ONLY = {"attention_bias": False, "hidden_act": "silu",
         "use_sliding_window": False, "decoder_sparse_step": 1,
         "mlp_only_layers": []}


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    for key, only in _ONLY.items():
        if hf.get(key, only) != only:
            raise NotImplementedError(
                f"the reference computes {key}={only!r} only, not "
                f"{hf[key]!r}")
    sa = hf["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise NotImplementedError("the indexer has ONE key head")
    nq = hf["num_attention_heads"]
    share = hf.get("expert_share") or dict(of=hf["num_experts"], first=0)
    return dict(
        layers=hf["num_hidden_layers"], heads=nq,
        kv_heads=hf.get("num_key_value_heads", nq),
        hidden=hf["hidden_size"],
        head=hf.get("head_dim") or hf["hidden_size"] // nq,
        moe_inter=hf["moe_intermediate_size"], vocab=hf["vocab_size"],
        experts=share["of"], top_k=hf["num_experts_per_tok"],
        held=range(share["first"], share["first"] + hf["num_experts"]),
        renorm=bool(hf.get("norm_topk_prob", False)),
        eps=hf.get("rms_norm_eps", 1e-6),
        theta=float(hf.get("rope_theta", 10000.0)),
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], topk=sa["topk"],
        tied=bool(hf.get("tie_word_embeddings", False)))


def _attention_params(d):
    """q, k, v and the output: matrices only (18,874,368 as published)."""
    return d["hidden"] * (d["heads"] + 2 * d["kv_heads"]) * d["head"] \
        + d["heads"] * d["head"] * d["hidden"]


def _index_params(d):
    """The indexer's three matrices (2,260,992 as published)."""
    return d["hidden"] * (d["index_heads"] * d["index_dim"]
                          + d["index_dim"] + d["index_heads"])


def _ffn_params(d):
    return d["hidden"] * d["experts"] \
        + len(d["held"]) * 3 * d["hidden"] * d["moe_inter"]


def n_matrix_params(hf):
    """The matrices alone: what the issue's arithmetic counts
    (562,266,112 in the benchmark's cell)."""
    d = dims(hf)
    return d["layers"] * (_attention_params(d) + _index_params(d)
                          + _ffn_params(d)) \
        + d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)


def n_params(hf):
    """Parameters the checkpoint HOLDS: the matrices, and for every
    layer its two norms, the two norms a head of q and k, the indexer's
    LayerNorm (weight and bias), and the final norm."""
    d = dims(hf)
    small = 2 * d["hidden"] + 2 * d["head"] + 2 * d["index_dim"]
    return n_matrix_params(hf) + d["layers"] * small + d["hidden"]


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes over ALL the router's
    experts: ``num_experts_per_tok`` a token in every layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * d["layers"]


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


def selected_pairs(n, topk):
    """(query, key) pairs of ONE document of ``n`` tokens that sparse
    attention runs over: the token at position p sees p + 1 keys and
    attends ``min(p + 1, topk)`` of them, whatever the indexer's
    weights (6,292,480 of 8,390,656 at n = 4096, topk = 2048: 75.0%)."""
    full = min(n, topk)
    return visible_pairs(full) + (n - full) * topk


def index_flops(hf, seqlens):
    """FLOPs of the indexer's score products AS WRITTEN, one forward
    over documents of these lengths: ``heads x dim x 2`` a CAUSAL pair
    (the scores of every visible key are needed before any is chosen),
    once a layer. The same yardstick whatever implements it."""
    d = dims(hf)
    return 2 * d["index_heads"] * d["index_dim"] * d["layers"] \
        * sum(visible_pairs(n) for n in seqlens)


def forward_flops(hf, seqlens):
    """FLOPs of one forward over documents of these lengths, at 2 FLOPs
    a multiply-add, OF THE MATHEMATICS: a layer's four attention
    projections, its scores and values over the SELECTED pairs, the
    indexer's three projections and its scores over the CAUSAL pairs;
    the router over all experts and the HELD experts only, at even
    routing (``num_experts_per_tok x held / experts`` experts a token:
    8 x 16/128 = 1 in the benchmark's cell); the vocabulary head on
    every token. Norms, rotary, elementwise products, softmax, ReLU,
    the choice of the top k and the scatter-add are left out.
    :func:`flop_shares` says where they go."""
    return sum(_forward_flops(hf, seqlens).values())


def _forward_flops(hf, seqlens):
    d = dims(hf)
    tokens, h, n = sum(seqlens), d["hidden"], d["layers"]
    picked = sum(selected_pairs(s, d["topk"]) for s in seqlens)
    return dict(
        attn_proj=n * 2 * tokens * _attention_params(d),
        attn=n * 2 * picked * d["heads"] * 2 * d["head"],
        index_proj=n * 2 * tokens * _index_params(d),
        index_scores=index_flops(hf, seqlens),
        experts=n * 2 * tokens * (
            h * d["experts"] + 3 * h * d["moe_inter"] * d["top_k"]
            * len(d["held"]) / d["experts"]),
        head=2 * tokens * h * d["vocab"])


def flop_shares(hf, seqlens):
    """``forward_flops`` by where it goes, as shares of 1:
    ``attn_proj``, ``attn`` (scores and values over the selected
    pairs), ``index_proj``, ``index_scores``, ``experts`` (router and
    held experts), ``head``."""
    parts = _forward_flops(hf, seqlens)
    total = sum(parts.values())
    return {k: v / total for k, v in parts.items()}


def flash_flops(hf, seqlens):
    """FLOPs of the matrix products that attention over the SELECTED
    pairs needs for ONE forward and ONE backward over rows that are one
    document each of these lengths, OF THE MATHEMATICS: ``2 x width`` a
    selected (query, key) pair a head a product. Forward: scores +
    values; dq pass: scores, dP, dQ; dkv pass: scores, dV, dP, dK;
    times the heads, summed over layers. The kernels visit every block
    pair under the causal diagonal whole and mask what the selection
    leaves out, so their share of the matrix peak by THIS count
    (``sparse.flash_mxu_share``) says what visiting unselected pairs
    costs, and cannot pass 100%. ``dict(fwd=, dq=, dkv=)``: a step
    under rematerialisation may run the forward kernel more than once,
    and the reader counts its calls."""
    d = dims(hf)
    pair = 2 * d["head"] * d["heads"] * d["layers"] \
        * sum(selected_pairs(n, d["topk"]) for n in seqlens)
    return dict(fwd=pair * 2, dq=pair * 3, dkv=pair * 4)


def kv_bytes_per_token(hf, bytes_per_el=2):
    """What a token adds to the three attention caches in every layer:
    K and V a key/value head and the indexer's ONE key."""
    d = dims(hf)
    return d["layers"] * bytes_per_el * (
        2 * d["kv_heads"] * d["head"] + d["index_dim"])


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, and every live sequence
    reads the indexer's keys of its whole prefix and K and V of the
    ``topk`` rows it selects (of all while there are no more). Prefill
    is left out."""
    d = dims(hf)
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    rows = [prompt_len + t for t in range(new_tokens)]
    cache = d["layers"] * bytes_per_el * sum(
        r * d["index_dim"] + min(r, d["topk"]) * 2 * d["kv_heads"]
        * d["head"] for r in rows)
    return weights + n_seqs * cache


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor. ``kind`` is
    ``matrix`` or ``norm``; the indexer's LayerNorm's bias is drawn
    like a matrix, N(0, initializer_range), so that a forward that
    drops it disagrees."""
    d = dims(hf)
    h, n, m, hd = d["hidden"], d["heads"], d["kv_heads"], d["head"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
    }
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        a = pre + "self_attn."
        # drawn like a MATRIX, N(0, initializer_range), not 1 + that:
        # see "The harness's weights" in the module's docstring
        out[pre + "input_layernorm.weight"] = ((h,), "matrix")
        out[pre + "post_attention_layernorm.weight"] = ((h,), "norm")
        out[a + "q_proj.weight"] = ((n * hd, h), "matrix")
        out[a + "k_proj.weight"] = ((m * hd, h), "matrix")
        out[a + "v_proj.weight"] = ((m * hd, h), "matrix")
        out[a + "o_proj.weight"] = ((h, n * hd), "matrix")
        out[a + "q_norm.weight"] = ((hd,), "norm")
        out[a + "k_norm.weight"] = ((hd,), "norm")
        ix = a + "indexer."
        out[ix + "wq.weight"] = (
            (d["index_heads"] * d["index_dim"], h), "matrix")
        out[ix + "wk.weight"] = ((d["index_dim"], h), "matrix")
        out[ix + "k_norm.weight"] = ((d["index_dim"],), "norm")
        out[ix + "k_norm.bias"] = ((d["index_dim"],), "matrix")
        out[ix + "weights_proj.weight"] = ((d["index_heads"], h), "matrix")
        mlp = pre + "mlp."
        out[mlp + "gate.weight"] = ((d["experts"], h), "matrix")
        for e in d["held"]:
            p = f"{mlp}experts.{e}."
            out[p + "gate_proj.weight"] = ((d["moe_inter"], h), "matrix")
            out[p + "up_proj.weight"] = ((d["moe_inter"], h), "matrix")
            out[p + "down_proj.weight"] = ((h, d["moe_inter"]), "matrix")
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: only to show that the tolerance, or a float32 test, tells each from
#: the model (``scripts/chip_check.py``, the tests). ``selection_a_
#: head``: attention head h attends the top k of index head
#: ``h mod J``'s term alone, so the heads of a token no longer share
#: one S_t.
WRONG = ("no_selection", "topk_halved", "scores_without_relu",
         "weights_left_out", "selection_a_head",
         "gates_not_renormalised", "qk_norm_left_out",
         "rotary_base_of_10000")
#: NOT a wrong equation, a lower precision of ONE part, that ``wrong=``
#: takes too: the indexer's input, its queries, key and weights rounded
#: to bfloat16 before the scores, as a bf16 program holds them. Keys
#: near the ``topk``-th score then fall in or out of S_t: what the
#: selection's precision ALONE costs (``chip_check.py keye_vl2``).
INDEX_ROUNDED = "index_rounded_to_bf16"


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
    """x [B, L, heads, r] rotated over its whole width at positions
    [B, L], rotate-half: ``x cos + rotate_half(x) sin`` with the r/2
    frequencies ``theta^(-2j/r)`` repeated over both halves."""
    import jax.numpy as jnp
    r = x.shape[-1]
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float32) / r)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(freq)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], axis=-1)
    return x * cos + turned * sin


def _index_terms(d, u, w, pos, wrong=()):
    """The indexer's queries [B, L, J, e], key [B, L, e] and weights
    [B, L, J] on u [B, L, H]."""
    import jax.numpy as jnp
    j, e = d["index_heads"], d["index_dim"]
    b, t, _ = u.shape
    rounded = (lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)) \
        if INDEX_ROUNDED in wrong else (lambda x: x)
    u = rounded(u)
    q = _rope((u @ w["wq.weight"].T).reshape(b, t, j, e), pos, d["theta"])
    k = u @ w["wk.weight"].T
    k = k - k.mean(-1, keepdims=True)
    k = k / jnp.sqrt(jnp.mean(jnp.square(k), -1, keepdims=True)
                     + INDEX_NORM_EPS) * w["k_norm.weight"] \
        + w["k_norm.bias"]
    k = _rope(k[:, :, None, :], pos, d["theta"])[:, :, 0]
    weights = (u @ w["weights_proj.weight"].T) * j ** -0.5 * e ** -0.5
    if "weights_left_out" in wrong:
        weights = jnp.ones_like(weights)
    return rounded(q), rounded(k), rounded(weights)


def _top_mask(score, seen, topk):
    """bool like ``score`` [..., L]: the ``topk`` SEEN entries of
    largest score a row, ties to the lower index, by a sort."""
    import jax
    import jax.numpy as jnp
    n = score.shape[-1]
    if topk >= n:
        return seen
    seen = jnp.broadcast_to(seen, score.shape)
    _, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), topk)
    picked = jnp.put_along_axis(jnp.zeros(score.shape, bool), idx, True,
                                axis=-1, inplace=False)
    return picked & seen


def _picked(d, qi, ki, wi, pos, seg, s, e, wrong=()):
    """bool [B, 1 or heads, e - s, L]: which keys the queries of rows
    ``s .. e - 1`` attend, from the indexer's terms of those rows
    (``qi`` [B, e - s, J, d], ``wi`` [B, e - s, J]) and every row's
    index key ``ki`` [B, L, d]."""
    import jax
    import jax.numpy as jnp
    seen = (seg[:, s:e, None] == seg[:, None, :]) \
        & (seg[:, s:e, None] != 0) \
        & (pos[:, s:e, None] - pos[:, None, :] >= 0)
    topk = d["topk"] // 2 if "topk_halved" in wrong else d["topk"]
    dots = jnp.einsum("bqjd,bkd->bjqk", qi, ki)
    if "scores_without_relu" not in wrong:
        dots = jax.nn.relu(dots)
    terms = dots * wi.transpose(0, 2, 1)[..., None]
    if "no_selection" in wrong:
        return seen[:, None]
    if "selection_a_head" in wrong:
        a_head = _top_mask(terms, seen[:, None], topk)  # [B, J, q, k]
        return a_head[:, jnp.arange(d["heads"]) % d["index_heads"]]
    return _top_mask(terms.sum(1), seen, topk)[:, None]


def _attention(d, u, w, pos, seg, wrong=()):
    """A layer's sparse attention on u [B, L, H]: the explicit masks a
    block of query rows at a time. Returns the layer's output before
    the residual."""
    import jax
    import jax.numpy as jnp
    n, m, hd = d["heads"], d["kv_heads"], d["head"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, _ = u.shape
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(b, t, n, hd)
    k = (u @ w["self_attn.k_proj.weight"].T).reshape(b, t, m, hd)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(b, t, m, hd)
    theta = 1e4 if "rotary_base_of_10000" in wrong else d["theta"]
    if "qk_norm_left_out" not in wrong:
        q = _rms(q, w["self_attn.q_norm.weight"], d["eps"])
        k = _rms(k, w["self_attn.k_norm.weight"], d["eps"])
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k, v = (jnp.repeat(x, n // m, axis=2) for x in (k, v))
    qi, ki, wi = _index_terms(
        d, u, {x: w["self_attn.indexer." + x] for x in _INDEX}, pos, wrong)
    out = []
    for s in range(0, t, QUERY_BLOCK):
        e = min(s + QUERY_BLOCK, t)
        picked = _picked(d, qi[:, s:e], ki, wi[:, s:e], pos, seg, s, e,
                         wrong)
        score = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k) / np.sqrt(hd)
        # a padding row sees nothing: a large finite value, not -inf,
        # so that its (unused) softmax is no NaN
        p = jax.nn.softmax(jnp.where(picked, score, -1e30), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    o = jnp.concatenate(out, axis=1)
    return o.reshape(b, t, n * hd) @ w["self_attn.o_proj.weight"].T


def selection(hf, tensors, ids, layer, seg=None, wrong=()):
    """bool [B, L, L]: S_t of every token of ``ids`` in ``layer`` as the
    reference computes it (the layers before it included)."""
    import jax
    import jax.numpy as jnp
    d = dims(hf)
    get = _getter(tensors, None)
    ids = jnp.asarray(ids, jnp.int32)
    seg = np.ones(ids.shape, np.int32) if seg is None else np.asarray(seg)
    pos, segj = jnp.asarray(positions(seg)), jnp.asarray(seg)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(dict(hf, num_hidden_layers=layer), get, ids, seg,
                       wrong)
        pre = _PRE.format(layer)
        u = _rms(x, get(pre + "input_layernorm.weight"), d["eps"])
        qi, ki, wi = _index_terms(
            d, u, {n: get(f"{pre}self_attn.indexer.{n}").astype(
                jnp.float32) for n in _INDEX}, pos, wrong)
        t = ids.shape[1]
        return np.concatenate([np.asarray(_picked(
            d, qi[:, s:s + QUERY_BLOCK], ki, wi[:, s:s + QUERY_BLOCK],
            pos, segj, s, min(s + QUERY_BLOCK, t), wrong)[:, 0])
            for s in range(0, t, QUERY_BLOCK)], axis=1)


def _swiglu(v, gate, up, down):
    import jax
    import jax.numpy as jnp
    gate, up, down = (m.astype(jnp.float32) for m in (gate, up, down))
    return (jax.nn.silu(v @ gate.T) * (v @ up.T)) @ down.T


def _route(d, v, gate_w, wrong=()):
    """The gates [B, L, E] over ALL experts: the softmax's value where
    the expert is among the token's k largest, else 0; divided by the
    k's sum under ``norm_topk_prob``."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(v @ gate_w.astype(jnp.float32).T, axis=-1)
    kth = jax.lax.top_k(p, d["top_k"])[0][..., -1:]
    gates = jnp.where(p >= kth, p, 0.0)
    if d["renorm"] and "gates_not_renormalised" not in wrong:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates


def _blocks(hf, get, ids, seg=None, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per layer its gates [B, L, E]). Layer by layer and expert by
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
    route = jax.jit(lambda v, g: _route(d, v, g, wrong))
    expert = jax.jit(lambda v, g, *ws: g * _swiglu(v, *ws))
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    names = ["input_layernorm.weight", "self_attn.q_norm.weight",
             "self_attn.k_norm.weight"] \
        + [f"self_attn.{n}.weight" for n in _ATTN] \
        + ["self_attn.indexer." + n for n in _INDEX]
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        x = attention(x, {n: get(pre + n) for n in names})
        v = ffn_in(x, get(pre + "post_attention_layernorm.weight"))
        mlp = pre + "mlp."
        gates = route(v, get(mlp + "gate.weight"))
        for e in d["held"]:  # what the absent experts add is left out
            x = x + expert(v, gates[..., e:e + 1], *(
                get(f"{mlp}experts.{e}.{m}.weight") for m in _FFN))
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
    decoding through the three caches must agree with. ``seg``: the
    document ids of packed rows (None: a row is one document)."""
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


# ----------------------------------------------------------------------
# The training loss and its gradient
# ----------------------------------------------------------------------
def sft_loss(hf, tensors, ids, prompt_len):
    """The SFT loss of ONE microbatch whose documents are the rows of
    ``ids`` [n, L] (equal lengths, the first ``prompt_len`` tokens of
    each the prompt): the mean over the answer tokens of -log p(token |
    before). No auxiliary term (``output_router_logits`` is false as
    published; the indexer's alignment loss is not part of the
    benchmark's step). Returns (loss, dict(nll=, aux=)). A function of
    ``tensors`` that ``jax.grad`` differentiates; NOTHING reaches the
    indexer's tensors, which move a choice and no number."""
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
