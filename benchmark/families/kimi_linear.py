"""Everything the benchmark knows about the ``kimi_linear`` architecture
(Kimi-Linear-48B-A3B-Instruct): the checkpoint's tensors, the plain
float32 reference (forward, training loss and its gradient) with its
tolerance, and what a step needs in parameters, FLOPs, bytes, routed
pairs and delta-rule products, all from the PUBLISHED configuration
dict and the checkpoint's tensors and nothing of the program's.

The model. Layer ``i`` (0-based here; ``linear_attn_config``'s two lists
count from 1) on ``x`` [T, H], RMSNorm at ``rms_norm_eps``::

    u = RMSNorm(x; input_layernorm)
    a = x + Mixer_i(u)
    v = RMSNorm(a; post_attention_layernorm)
    dense  (i < first_k_dense_replace): y = a + down(silu(gate v) * up v)
    sparse: s = sigmoid(v Wr) [T, E] in float32 over ALL experts; the
          num_experts_per_token largest of s + e_score_correction_bias
          (one expert group: plain top-k); gates g_e = s_e / (their sum
          + 1e-20) x routed_scaling_factor on each chosen expert's
          OUTPUT; y = a + sum_e g_e Expert_e(v) + Shared(v), Shared ONE
          SwiGLU of num_shared_experts x moe_intermediate_size
    model: embed_tokens -> layers -> RMSNorm(model.norm) -> lm_head

A KDA layer (Kimi Delta Attention; i + 1 in ``kda_layers``), n heads of
d (``linear_attn_config.num_heads``, ``head_dim``; key and value
alike), ``conv`` a depthwise causal convolution of
``short_conv_kernel_size`` taps without a bias over a document's own
tokens::

    q~, k~, v = SiLU(conv(u Wq)), SiLU(conv(u Wk)), SiLU(conv(u Wv))
    q = q~ / sqrt(|q~|^2 + 1e-6) * d^-0.5,  k = k~ / sqrt(|k~|^2 + 1e-6)
    g = -exp(A_log[head]) * softplus((u Wfa) Wfb + dt_bias)   [d] a head
    beta = sigmoid(u Wb)                                one a head a token
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t          S [d, d] a head, 0 before a document's first token
    Mixer = concat_heads(RMSNorm_d(o_t; o_norm) * sigmoid((u Wga) Wgb)) Wo

The reference takes that recurrence TOKEN BY TOKEN, a ``lax.scan`` over
the row that carries S: no chunks, no triangular solve, nothing of
``realhf_tpu/ops/delta_rule.py``.

A latent layer (i + 1 in ``full_attn_layers``) is DeepSeek-V3's WITHOUT
a rotary embedding (``mla_use_nope``): ``nope = qk_nope_head_dim``,
``rope = qk_rope_head_dim``::

    q = u Wq [T, n, nope + rope]
    c = u Wkva [T, kv_lora_rank + rope]
    kv = RMSNorm(c[:, :kv_lora_rank]; kv_a_layernorm) AT EPS 1e-6
    kv Wkvb [T, n, nope + v_head_dim]: k_nope = [.., :nope], v = [.., nope:]
    k = [k_nope, c[:, kv_lora_rank:] to EVERY head]: one shared part, as it is
    scores q k^T (nope + rope)^-0.5 in float32; key s visible to query
          t iff same document and s <= t
    Mixer = (heads' outputs [T, n, v_head_dim], concatenated) Wo

What the catalog row's config does not state is listed in the
configuration file under ``assumed`` (tensor names, epsilons, the
1-based lists, no convolution bias). ``mla_use_nope: false``,
``q_lora_rank`` other than null, ``rope_scaling``, more than one expert
group, multi-token prediction and ``moe_layer_freq`` other than 1 are
refused.

The reference takes ``ids`` [B, L] and optionally ``seg`` [B, L]
(document ids of a packed row, 0 = padding; None: a row is one
document): the delta state is set to 0 at a document's first token and
left as it is by padding, a convolution's window stops at the
document's first token, the latent layer's mask is built from
documents and positions a block of query rows at a time. In a sparse
layer it loops over the HELD experts, adding each one's output for
every token weighted by a gate that is 0 where the expert is not among
the token's k. Weights are the checkpoint's values cast up exactly;
every product is taken at ``default_matmul_precision("highest")``.

**An expert-parallel rank's share** (``expert_share: {of, first}``
beside ``num_experts`` = how many the files hold, as
``realhf_tpu/models/hf/kimi_linear.py`` reads it): the router and its
bias keep their published width, the k are chosen among ALL experts,
only the HELD experts' terms are added, and the shared expert, which
every rank holds, is added whole. A sliced vocabulary is a smaller
vocabulary.

**The harness's weights** (``benchmark/generate.py``: every tensor
N(0, ``initializer_range``), norm scales 1 + that) put ``A_log`` and
``dt_bias`` near 0: ``g`` is about -0.69 a token a channel, a state
halves every token. That is the hard regime for a chunk's exponents
(-44 over 64 tokens) and the weak one for memory: what a token wrote
64 tokens back weighs 2^-64, so whether a state is carried ACROSS
chunks shows only in its youngest entries. ``published_decay``
overwrites the two tensors with the published initialisation (``A_log =
log U(1, 16)``, ``dt_bias`` the inverse softplus of a step log-uniform
in [1e-3, 1e-1]: g from -1.6 to -0.001 a token), under which a state
lives hundreds of tokens; ``scripts/chip_check.py kimi_linear`` and
the tests run both.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (0.94 to 0.95 nat at the cell's widths).
#: Sized on the chip at those widths (5 layers, 8 of 256 experts held,
#: vocabulary 20,480) by ``scripts/chip_check.py kimi_linear`` and the
#: cell's own runs (my chip runs, PR 39), shares of the spread; left
#: the harness's weights (the decay near a half a token), right the
#: PUBLISHED decay (``published_decay``); in brackets on ONE document
#: of 2,048 tokens, reference against reference:
#:
#:   engine, bf16, the fixed batch (10 seeds)         0.0091-0.0107  0.0121
#:   ONE packed row of 2048: documents of 700 .. 248  0.0100-0.0103
#:   prefill of 640, then 127 decode steps, rows 768  0.0107 (decoded 0.0105)
#:   this forward at default matmul precision         0.0059
#:   HELD experts rounded to int8 by row              0.0010
#:   held experts rounded to float8 e4m3              0.0048
#:   every matrix rounded to int8 by row              0.0334
#:   every matrix rounded to float8 e4m3              0.124
#:   every matrix rounded to float8 e5m2              0.191
#:   WRONG: the state dropped at chunk boundaries     0.0149 (0.0155)  0.550 (0.691)
#:   WRONG: one decay a head                          0.0642 (0.0648)  0.787 (0.824)
#:   WRONG: the decay applied after the update        0.429  (0.432)   0.110 (0.0997)
#:   WRONG: the output gate left out                  0.453  (0.457)   0.299 (0.276)
#:   WRONG: no l2 norm                                0.809  (0.811)   1.015 (1.004)
#:   WRONG: the state carried over a boundary         0.0044*          0.155*
#:   WRONG: a convolution that runs over documents    0.0099*          0.0499*
#:   engine, FLOAT32 at highest precision, 2048 tok.  (0.00000099)     (0.00027)
#:   the same on the packed row, a document at a time 8.9e-7-1.1e-6    4e-5-2.5e-4
#:   (* on the packed row of 2048, reference against reference: the
#:   fixed batch is a row a document and reads 0 there)
#:
#: 0.02 is 1.65 to 2.2 times what bf16 shows, three fifths of int8 on the
#: whole model and under a third of the mildest wrong equation the
#: fixed batch can see under the harness's weights BUT ONE: a forward
#: computed below bf16, or by any other wrong equation of the list,
#: fails. WHAT IT CANNOT TELL under the harness's weights: the state
#: dropped at chunk boundaries (0.0149: a state halves every token
#: there, so a boundary costs its youngest entries alone; under the
#: published decay the same entry reads 0.55) and the two entries that
#: are about documents (the fixed batch is a row a document, and on a
#: packed row three boundaries touch a few of 2,048 tokens: 0.0044 and
#: 0.0099; 0.155 and 0.050 under the published decay); a lower
#: precision ON THE HELD EXPERTS ALONE (inside bf16's noise, as in the
#: other sparse families). Those are held by float32: the tests on the
#: CPU (``tests/model/test_kimi_linear.py``: every entry 50 tolerances
#: away under both initialisations) and ``chip_check.py``'s float32
#: rows through the COMPILED program, which read float32's noise under
#: the harness's weights and 0.00004 to 0.00027 under the published
#: decay. THAT float32 is THIS reference's, not the program's
#: (``chip_check.py``'s row ``scan_accuracy``, against the recurrence
#: in float64 on the host, published decay, 2,048 tokens: the chunked
#: scan 0.0000018, the recurrence token by token in float32 ON THE
#: CHIP 0.000057, on the CPU 0.00000028): a state that lives hundreds
#: of tokens is multiplied by the chip's ``exp(g)`` once a token, and
#: its rounding there does not average out.
TOLERANCE = 0.02

_PRE = "model.layers.{}."
_FFN = ("gate_proj", "up_proj", "down_proj")
#: one routed expert's matrices in the order gate, up, down
_EXPERT = ("w1", "w3", "w2")
_LATENT = ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
_DELTA = ("q_proj", "k_proj", "v_proj", "f_a_proj", "f_b_proj", "b_proj",
          "g_a_proj", "g_b_proj", "o_proj")
_CONVS = ("q_conv1d", "k_conv1d", "v_conv1d")
#: rows of queries whose scores are held at once
QUERY_BLOCK = 512
#: what ``kv_a_layernorm`` norms at, and what the l2 norm of q and k
#: adds to the sum of squares (both ``assumed``)
LATENT_NORM_EPS = 1e-6
L2_EPS = 1e-6
#: published key -> the one value of it this reference computes
_ONLY = {"mla_use_nope": True, "q_lora_rank": None, "rope_scaling": None,
         "num_expert_group": 1, "topk_group": 1,
         "moe_router_activation_func": "sigmoid",
         "num_nextn_predict_layers": 0, "moe_layer_freq": 1,
         "hidden_act": "silu"}


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
    lin = hf["linear_attn_config"]
    delta, latent = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if delta & latent or delta | latent != set(range(1, n + 1)):
        raise NotImplementedError(
            f"kda_layers {sorted(delta)} and full_attn_layers "
            f"{sorted(latent)} do not name each of the layers 1..{n} once")
    share = hf.get("expert_share") or dict(of=hf["num_experts"], first=0)
    lead = min(hf.get("first_k_dense_replace", 0), n)
    return dict(
        layers=n, delta=[i + 1 in delta for i in range(n)],
        sparse=[i >= lead for i in range(n)], heads=nq,
        hidden=hf["hidden_size"], rank=hf["kv_lora_rank"],
        nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
        qk=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        v=hf["v_head_dim"], dheads=lin["num_heads"], dhead=lin["head_dim"],
        width=lin["num_heads"] * lin["head_dim"],
        taps=lin["short_conv_kernel_size"], inter=hf["intermediate_size"],
        moe_inter=hf["moe_intermediate_size"],
        shared=hf["moe_intermediate_size"] * hf.get("num_shared_experts", 0),
        vocab=hf["vocab_size"], experts=share["of"],
        top_k=hf["num_experts_per_token"],
        held=range(share["first"], share["first"] + hf["num_experts"]),
        renorm=bool(hf.get("moe_renormalize", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        eps=hf.get("rms_norm_eps", 1e-5),
        tied=bool(hf.get("tie_word_embeddings", False)))


def _latent_matrices(d):
    h, n = d["hidden"], d["heads"]
    return h * n * d["qk"] + h * (d["rank"] + d["rope"]) \
        + d["rank"] * n * (d["nope"] + d["v"]) + n * d["v"] * h


def _delta_matrices(d):
    """q, k, v, o; the decay's and the output gate's two-step
    projections through ``head_dim``; beta's."""
    h, w = d["hidden"], d["width"]
    return 4 * h * w + 2 * (h + w) * d["dhead"] + h * d["dheads"]


def _ffn_matrices(d, i):
    h = d["hidden"]
    if not d["sparse"][i]:
        return 3 * h * d["inter"]
    return h * d["experts"] + len(d["held"]) * 3 * h * d["moe_inter"] \
        + 3 * h * d["shared"]


def n_params(hf):
    """Parameters the checkpoint HOLDS (602,434,432 in the benchmark's
    cell): the matrices; a delta layer's taps, ``A_log``, ``dt_bias``
    and ``o_norm``; a latent layer's norm; every layer's two norms; in
    a sparse layer the router's selection bias; the final norm."""
    d = dims(hf)
    total = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2) + d["hidden"]
    for i in range(d["layers"]):
        total += 2 * d["hidden"] + _ffn_matrices(d, i) \
            + (d["experts"] if d["sparse"][i] else 0)
        if d["delta"][i]:
            total += _delta_matrices(d) + 3 * d["taps"] * d["width"] \
                + d["dheads"] + d["width"] + d["dhead"]
        else:
            total += _latent_matrices(d) + d["rank"]
    return total


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes over ALL the router's
    experts: ``num_experts_per_token`` a token in every sparse layer."""
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


def delta_flops(hf, seqlens):
    """FLOPs of the delta-rule RECURRENCE as written, for one forward
    over these documents: three products of 2 x d x d a head a token a
    KDA layer (the state's read ``S^T k``, the rank-one update ``k
    u^T``, the output ``S^T q``; the decay's elementwise pass is left
    out), 3.15 MFLOP a token a layer at 32 heads of 128. WHATEVER
    implements it (the chunked form runs more, in products of another
    shape): ``delta.scan_mxu_share`` reads every implementation by this
    yardstick, so it can never pass 100%."""
    d = dims(hf)
    return sum(seqlens) * sum(d["delta"]) * d["dheads"] \
        * 3 * 2 * d["dhead"] * d["dhead"]


def forward_flops(hf, seqlens):
    """FLOPs of one forward over documents of these lengths, at 2 FLOPs
    a multiply-add, OF THE MATHEMATICS: a KDA layer's projections and
    its recurrence as written (``delta_flops``); a latent layer's four
    projections, its scores over the key's width and its values over
    the value's for the pairs the causal mask lets through; a dense
    feed-forward's three matrices; in a sparse one the router over all
    experts and the shared expert on every token, and the HELD experts
    only, at even routing (``num_experts_per_token x held / experts``
    experts a token: 8 x 8/256 = 0.25 in the benchmark's cell); the
    vocabulary head on every token. Norms, convolutions' taps,
    elementwise products, softmax, sigmoid, the sort and the
    scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    h = d["hidden"]
    pairs = sum(visible_pairs(n) for n in seqlens)
    total = 2 * tokens * h * d["vocab"] + delta_flops(hf, seqlens)
    for i in range(d["layers"]):
        if d["delta"][i]:
            total += 2 * tokens * _delta_matrices(d)
        else:
            total += 2 * tokens * _latent_matrices(d) \
                + 2 * pairs * d["heads"] * (d["qk"] + d["v"])
        if not d["sparse"][i]:
            total += 2 * tokens * 3 * h * d["inter"]
        else:
            total += 2 * tokens * (
                h * d["experts"] + 3 * h * d["shared"]
                + 3 * h * d["moe_inter"] * d["top_k"]
                * len(d["held"]) / d["experts"])
    return total


def delta_state_bytes(hf, n_seqs, bytes_per_el=2):
    """The KDA layers' decode state: a float32 [d, d] a head, and
    ``short_conv_kernel_size - 1`` rows of the three convolutions'
    inputs, for each KDA layer and stream."""
    d = dims(hf)
    return sum(d["delta"]) * n_seqs * (
        4 * d["dheads"] * d["dhead"] * d["dhead"]
        + bytes_per_el * (d["taps"] - 1) * 3 * d["width"])


def kv_bytes_per_token(hf, bytes_per_el=2):
    """What a token adds to the cache in every LATENT layer: the
    expanded keys (nope + rope wide) and values a head. A KDA layer
    adds nothing a token."""
    d = dims(hf)
    return (d["layers"] - sum(d["delta"])) * d["heads"] \
        * (d["qk"] + d["v"]) * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, every live sequence reads
    its cached prefix in the latent layers and reads and writes its
    delta state. Prefill is left out."""
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    rows = sum(prompt_len + t for t in range(new_tokens))
    return weights + n_seqs * rows * kv_bytes_per_token(hf, bytes_per_el) \
        + 2 * new_tokens * delta_state_bytes(hf, n_seqs, bytes_per_el)


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor. ``kind`` is
    ``matrix`` or ``norm``; the taps, ``A_log``, ``dt_bias`` and
    ``e_score_correction_bias`` are drawn like a matrix, N(0,
    initializer_range) (the module's docstring says what that does to
    the decay)."""
    d = dims(hf)
    h, n, w = d["hidden"], d["heads"], d["width"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
    }
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        a = pre + "self_attn."
        out[pre + "input_layernorm.weight"] = ((h,), "norm")
        out[pre + "post_attention_layernorm.weight"] = ((h,), "norm")
        if d["delta"][i]:
            for m in ("q_proj", "k_proj", "v_proj"):
                out[f"{a}{m}.weight"] = ((w, h), "matrix")
            for m in _CONVS:
                out[f"{a}{m}.weight"] = ((w, 1, d["taps"]), "matrix")
            out[a + "A_log"] = ((1, 1, d["dheads"], 1), "matrix")
            out[a + "dt_bias"] = ((w,), "matrix")
            for m in ("f_a_proj", "g_a_proj"):
                out[f"{a}{m}.weight"] = ((d["dhead"], h), "matrix")
            for m in ("f_b_proj", "g_b_proj"):
                out[f"{a}{m}.weight"] = ((w, d["dhead"]), "matrix")
            out[a + "b_proj.weight"] = ((d["dheads"], h), "matrix")
            out[a + "o_norm.weight"] = ((d["dhead"],), "norm")
            out[a + "o_proj.weight"] = ((h, w), "matrix")
        else:
            out[a + "q_proj.weight"] = ((n * d["qk"], h), "matrix")
            out[a + "kv_a_proj_with_mqa.weight"] = (
                (d["rank"] + d["rope"], h), "matrix")
            out[a + "kv_a_layernorm.weight"] = ((d["rank"],), "norm")
            out[a + "kv_b_proj.weight"] = (
                (n * (d["nope"] + d["v"]), d["rank"]), "matrix")
            out[a + "o_proj.weight"] = ((h, n * d["v"]), "matrix")
        if not d["sparse"][i]:
            out.update(_ffn_shapes(pre + "mlp.", h, d["inter"], _FFN))
            continue
        moe = pre + "block_sparse_moe."
        out[moe + "gate.weight"] = ((d["experts"], h), "matrix")
        out[moe + "gate.e_score_correction_bias"] = (
            (d["experts"],), "matrix")
        for e in d["held"]:
            out.update(_ffn_shapes(f"{moe}experts.{e}.", h, d["moe_inter"],
                                   _EXPERT))
        if d["shared"]:
            out.update(_ffn_shapes(moe + "shared_experts.", h, d["shared"],
                                   _FFN))
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


def _ffn_shapes(pre, h, f, names):
    gate, up, down = names
    return {f"{pre}{gate}.weight": ((f, h), "matrix"),
            f"{pre}{up}.weight": ((f, h), "matrix"),
            f"{pre}{down}.weight": ((h, f), "matrix")}


def published_decay(hf, tensors, seed):
    """``tensors`` with every KDA layer's ``A_log`` and ``dt_bias`` as
    the published initialisation draws them, from the seed: ``A_log =
    log U(1, 16)`` a head, ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] a channel; in the tensors' own dtype."""
    d = dims(hf)
    rng = np.random.default_rng(seed)
    out = dict(tensors)
    for i in range(d["layers"]):
        if not d["delta"][i]:
            continue
        a = _PRE.format(i) + "self_attn."
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), d["width"]))
        for name, value in (
                (a + "A_log", np.log(rng.uniform(1, 16, (1, 1, d["dheads"],
                                                         1)))),
                (a + "dt_bias", dt + np.log(-np.expm1(-dt)))):
            out[name] = np.asarray(value, np.float32).astype(
                tensors[name].dtype)
    return out


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: only to show that the tolerance tells each from the model
#: (``scripts/chip_check.py``, the tests). ``state_dropped_at_chunks``:
#: the state is set to 0 before every 64th token of a row, what a
#: chunked scan that lost its carry would compute.
WRONG = ("decay_a_head", "decay_after_the_update",
         "state_over_documents", "state_dropped_at_chunks",
         "l2_norm_left_out", "conv_over_documents",
         "output_gate_left_out")


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


def _conv(x, taps, pos, wrong):
    """The depthwise causal convolution of x [B, L, C] by taps
    [C, 1, K] (Conv1d's layout: tap K-1 on the token itself): the token
    d before counts only where it is of the same document, that is
    where the token's position in its document is at least d."""
    import jax.numpy as jnp
    k = taps.shape[-1]
    t = x.shape[1]
    out = x * taps[:, 0, k - 1]
    for back in range(1, k):
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        if "conv_over_documents" not in wrong:
            earlier = jnp.where((pos >= back)[..., None], earlier, 0.0)
        out = out + earlier * taps[:, 0, k - 1 - back]
    return out


def _delta(d, u, w, pos, seg, wrong=()):
    """A KDA layer's mixer on u [B, L, H], the recurrence a token at a
    time."""
    import jax
    import jax.numpy as jnp
    n, hd = d["dheads"], d["dhead"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, _ = u.shape

    def branch(name):
        x = _conv(u @ w[f"self_attn.{name}_proj.weight"].T,
                  w[f"self_attn.{name}_conv1d.weight"], pos, wrong)
        return jax.nn.silu(x).reshape(b, t, n, hd)

    def unit(x):
        return x / jnp.sqrt(jnp.square(x).sum(-1, keepdims=True) + L2_EPS)

    q, k, v = branch("q"), branch("k"), branch("v")
    if "l2_norm_left_out" not in wrong:
        q, k = unit(q), unit(k)
    q = q * hd ** -0.5
    f = (u @ w["self_attn.f_a_proj.weight"].T) \
        @ w["self_attn.f_b_proj.weight"].T
    g = -jnp.exp(w["self_attn.A_log"].reshape(n, 1)) * jax.nn.softplus(
        f + w["self_attn.dt_bias"]).reshape(b, t, n, hd)
    if "decay_a_head" in wrong:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ w["self_attn.b_proj.weight"].T)
    first = (pos == 0) & (seg != 0)
    if "state_over_documents" in wrong:
        first = jnp.zeros_like(first)
    if "state_dropped_at_chunks" in wrong:
        first = first | (jnp.arange(t)[None, :] % 64 == 0)

    def token(s, x):
        qt, kt, vt, gt, bt, new, live = x
        s = jnp.where(new[:, None, None, None], 0.0, s)
        if "decay_after_the_update" in wrong:
            nxt = s + bt[..., None, None] * kt[..., None] * (
                vt - jnp.einsum("bnkv,bnk->bnv", s, kt))[..., None, :]
            nxt = nxt * jnp.exp(gt)[..., None]
        else:
            nxt = s * jnp.exp(gt)[..., None]
            nxt = nxt + bt[..., None, None] * kt[..., None] * (
                vt - jnp.einsum("bnkv,bnk->bnv", nxt, kt))[..., None, :]
        # padding leaves the state as it is
        nxt = jnp.where(live[:, None, None, None], nxt, s)
        return nxt, jnp.einsum("bnkv,bnk->bnv", nxt, qt)

    by_token = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(
        token, jnp.zeros((b, n, hd, hd), jnp.float32),
        tuple(map(by_token, (q, k, v, g, beta, first, seg != 0))))
    o = _rms(by_token(o), w["self_attn.o_norm.weight"], d["eps"])
    if "output_gate_left_out" not in wrong:
        gate = (u @ w["self_attn.g_a_proj.weight"].T) \
            @ w["self_attn.g_b_proj.weight"].T
        o = o * jax.nn.sigmoid(gate).reshape(b, t, n, hd)
    return o.reshape(b, t, n * hd) @ w["self_attn.o_proj.weight"].T


def _attention(d, u, w, pos, seg):
    """A latent layer's attention on u [B, L, H], no rotary embedding:
    the explicit mask a block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    n, nope, rope, rank = d["heads"], d["nope"], d["rope"], d["rank"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, _ = u.shape
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(b, t, n, d["qk"])
    a = u @ w["self_attn.kv_a_proj_with_mqa.weight"].T
    c = _rms(a[..., :rank], w["self_attn.kv_a_layernorm.weight"],
             LATENT_NORM_EPS)
    kv = (c @ w["self_attn.kv_b_proj.weight"].T).reshape(
        b, t, n, nope + d["v"])
    # the shared part goes to every head as it is: one for all heads
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(a[..., None, rank:], (b, t, n, rope))], axis=-1)
    v = kv[..., nope:]
    out = []
    for s in range(0, t, QUERY_BLOCK):
        e = min(s + QUERY_BLOCK, t)
        seen = (seg[:, s:e, None] == seg[:, None, :]) \
            & (seg[:, s:e, None] != 0) \
            & (pos[:, s:e, None] - pos[:, None, :] >= 0)
        score = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k) \
            / np.sqrt(d["qk"])
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


def _route(d, v, gate_w, bias):
    """The gates [B, L, E] over ALL experts: the sigmoid score where
    the expert is among the token's k largest of score + bias, else 0;
    divided by (the k's sum + 1e-20) under ``moe_renormalize``;
    scaled."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(v @ gate_w.astype(jnp.float32).T)
    choice = s + bias.astype(jnp.float32)
    kth = jax.lax.top_k(choice, d["top_k"])[0][..., -1:]
    gates = jnp.where(choice >= kth, s, 0.0)
    if d["renorm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * d["scaling"]


def _mixer_names(d, i):
    if d["delta"][i]:
        return ["input_layernorm.weight", "self_attn.A_log",
                "self_attn.dt_bias", "self_attn.o_norm.weight"] \
            + [f"self_attn.{m}.weight" for m in _DELTA + _CONVS]
    return ["input_layernorm.weight", "self_attn.kv_a_layernorm.weight"] \
        + [f"self_attn.{m}.weight" for m in _LATENT]


def _blocks(hf, get, ids, seg=None, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per sparse layer its gates [B, L, E]). Layer by layer and expert by
    expert, each cast up on the way in."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    seg = np.ones(ids.shape, np.int32) if seg is None else np.asarray(seg)
    pos, seg = jnp.asarray(positions(seg)), jnp.asarray(seg)

    def mixer(delta):
        op = (lambda u, w: _delta(d, u, w, pos, seg, wrong)) if delta \
            else (lambda u, w: _attention(d, u, w, pos, seg))
        return jax.jit(lambda x, w: x + op(
            _rms(x, w["input_layernorm.weight"], d["eps"]), w))

    mixers = {True: mixer(True), False: mixer(False)}
    ffn_in = jax.jit(lambda x, w: _rms(x, w, d["eps"]))
    swiglu = jax.jit(_swiglu)
    route = jax.jit(lambda v, g, b: _route(d, v, g, b))
    expert = jax.jit(lambda v, g, *ws: g * _swiglu(v, *ws))
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    for i in range(d["layers"]):
        pre = _PRE.format(i)
        x = mixers[d["delta"][i]](
            x, {n: get(pre + n) for n in _mixer_names(d, i)})
        v = ffn_in(x, get(pre + "post_attention_layernorm.weight"))
        if not d["sparse"][i]:
            x = x + swiglu(v, *(get(f"{pre}mlp.{m}.weight") for m in _FFN))
            continue
        moe = pre + "block_sparse_moe."
        gates = route(v, get(moe + "gate.weight"),
                      get(moe + "gate.e_score_correction_bias"))
        for e in d["held"]:  # what the absent experts add is left out
            x = x + expert(v, gates[..., e:e + 1], *(
                get(f"{moe}experts.{e}.{m}.weight") for m in _EXPERT))
        if d["shared"]:
            x = x + swiglu(v, *(get(f"{moe}shared_experts.{m}.weight")
                                for m in _FFN))
        routed.append(gates)
    return x, routed


def _getter(tensors, cast):
    import jax.numpy as jnp

    def get(name):
        x = jnp.asarray(tensors[name])
        # a matrix is what a product takes: the taps, A_log and the
        # biases are no matrices and are never rounded
        return x if cast is None or x.ndim != 2 else cast(x)
    return get


def _final(hf, x, get):
    import jax.numpy as jnp
    x = _rms(x, get("model.norm.weight"), dims(hf)["eps"])
    head = get("model.embed_tokens.weight"
               if hf.get("tie_word_embeddings", False) else "lm_head.weight")
    return x @ head.astype(jnp.float32).T


def logits(hf, tensors, ids, seg=None, cast=None, wrong=()):
    """Float32 logits [B, L, V] of the full forward: what prefill and
    decoding through the states must agree with. ``seg``: the document
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
    lin = hf["linear_attn_config"]
    cut = dict(hf, num_hidden_layers=layer + 1, linear_attn_config=dict(
        lin, kda_layers=[i for i in lin["kda_layers"] if i <= layer + 1],
        full_attn_layers=[i for i in lin["full_attn_layers"]
                          if i <= layer + 1]))
    with jax.default_matmul_precision("highest"):
        _, routed = _blocks(cut, _getter(tensors, None),
                            jnp.asarray(ids, jnp.int32))
    return np.asarray(routed[-1] > 0)


# ----------------------------------------------------------------------
# The training loss and its gradient
# ----------------------------------------------------------------------
def sft_loss(hf, tensors, ids, prompt_len):
    """The SFT loss of ONE microbatch whose documents are the rows of
    ``ids`` [n, L] (equal lengths, the first ``prompt_len`` tokens of
    each the prompt): the mean over the answer tokens of -log p(token |
    before). No auxiliary term. Returns (loss, dict(nll=, aux=)). A
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
