"""Everything the benchmark knows about the ``nemotron_h`` architecture
(NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): the checkpoint's tensors, the
plain float32 reference (forward, training loss and its gradient) with
its tolerance, and what a step needs in parameters, FLOPs, routed pairs
and state-space products, all from the PUBLISHED configuration dict and
the checkpoint's tensors and nothing of the program's.

The model. Every layer is ONE part, ``x <- x + part(RMSNorm(x; norm))``
at ``layer_norm_epsilon``, the part said by the layer's letter in
``hybrid_override_pattern``; ``backbone.embeddings -> layers ->
RMSNorm(backbone.norm_f) -> lm_head``.

``M``, the Mamba-2 mixer on u [T, H]. n = ``mamba_num_heads`` heads of
P = ``mamba_head_dim`` (``d_inner = n P``, NOT ``expand x hidden_size``),
G = ``n_groups`` groups of N = ``ssm_state_size``, head h reads group
``h // (n / G)``, ``conv`` a depthwise causal convolution of
``conv_kernel`` taps WITH a bias over a document's own tokens::

    [z | xBC | dt] = u W_in          widths n P | n P + 2 G N | n
    xBC = SiLU(conv(xBC) + bias)  -> x [n, P], B [G, N], C [G, N]
    Delta = softplus(dt + dt_bias)   one a head a token, no clamp
    A = -exp(A_log)                  one a head
    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T    S [P, N] a head,
                                     0 before a document's first token
    y_t = S_t C_t + D x_t
    Mixer = (GroupRMSNorm(y * SiLU(z)) * norm) W_out

the gate FIRST, then each of the G groups of ``n P / G`` values by its
own root mean square. The reference takes that recurrence TOKEN BY
TOKEN, a ``lax.scan`` over the row that carries S: no chunks, nothing of
``realhf_tpu/ops/ssm_scan.py``. (``transformers`` 4.57.6 has no
``nemotron_h``; it has ``zamba2.Zamba2MambaMixer``, the same mixer, and
``tests/model/test_nemotron_h.py`` holds this function and the program
to ITS float32 output.)

``E``, a sparse feed-forward on v [T, H]::

    s = sigmoid(v Wr^T) [T, E] in float32 over ALL experts; the
    num_experts_per_tok largest of s + e_score_correction_bias (one
    expert group: plain top-k); gates g_e = s_e / (their sum + 1e-20)
    x routed_scaling_factor; Expert(v) = W_down relu(W_up v)^2, TWO
    matrices and no gate (``mlp_hidden_act: relu2``)
    part = sum_e g_e Expert_e(v) + Shared(v), Shared ONE such expert at
    moe_shared_expert_intermediate_size, weight 1

(the router is ``transformers``' ``deepseek_v3`` module's, to which the
tests hold it.)

``*``, grouped-query attention: ``num_attention_heads`` query and
``num_key_value_heads`` key/value heads of ``head_dim``, no bias, scores
at ``head_dim ** -0.5`` in float32, key s visible to query t iff same
document and s <= t, NO rotary embedding and no other positional term
(``assumed``: the config's ``rope_theta`` and ``partial_rotary_factor``
are read by nothing).

What the catalog row's config does not state is listed in the
configuration file under ``assumed``. Biases other than the
convolution's, ``-`` in the pattern, more than one expert group or
shared expert, another activation and a ``time_step_limit`` are refused.

The reference takes ``ids`` [B, L] and optionally ``seg`` [B, L]
(document ids of a packed row, 0 = padding; None: a row is one
document): the state is set to 0 at a document's first token and left as
it is by padding, the convolution's window stops at the document's first
token, the attention layer's mask is built from documents and positions
a block of query rows at a time. In a sparse layer it loops over the
HELD experts, adding each one's output for every token weighted by a
gate that is 0 where the expert is not among the token's k. Weights are
the checkpoint's values cast up exactly; every product is taken at
``default_matmul_precision("highest")``.

**An expert-parallel rank's share** (``expert_share: {of, first}``
beside ``n_routed_experts`` = how many the files hold, as
``realhf_tpu/models/hf/nemotron_h.py`` reads it): the router and its
bias keep their published width, the k are chosen among ALL experts,
only the HELD experts' terms are added, and the shared expert, which
every rank holds, is added whole. A sliced vocabulary is a smaller
vocabulary.

**The harness's weights** (``benchmark/generate.py``: every tensor
N(0, ``initializer_range``), kind ``norm`` 1 + that; ``D`` is drawn as
kind ``norm``, near its published 1) put ``A_log`` and ``dt_bias`` near
0: ``A`` is -1 and ``Delta`` near 0.69 a head, so a state HALVES every
token. That is the hard regime for a chunk's exponents (-88 over 128
tokens) and the weak one for memory: what a token wrote 64 tokens back
weighs 2^-64, so the wrong equations that are about the state's LIFE
(carried over a document's boundary, a decay after the update) move
little; and the convolution's taps, drawn N(0, 0.02) too, cut x, B and C
to a twentieth, so the state's part of a mixer's output is a few per
cent of ``D x``. ``published_init`` overwrites five tensors a layer with
the published initialisation (``A = U(1, 16)``, ``Delta`` log-uniform in
[``time_step_min``, ``time_step_max``] through the inverse softplus into
``dt_bias``, ``D = 1``, the taps and their bias uniform in +-1/2), under
which a state lives tens to thousands of tokens and is most of what the
mixer puts out; ``scripts/chip_check.py nemotron_h`` and the tests run
both.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (1.02 to 1.10 nat at the cell's widths).
#: Sized on the chip at those widths (7 layers, 8 of 128 experts held,
#: vocabulary 16,384) by ``scripts/chip_check.py nemotron_h`` and the
#: cell's own runs (my chip runs, PR 48), shares of the spread; left the
#: harness's weights (a state halves every token, taps of 0.02), right
#: ``published_init``; in brackets on ONE document of 4,096 tokens,
#: reference against reference:
#:
#:   engine, bf16, the fixed batch (23 seeds)         0.0075-0.0129  0.0085
#:   ONE packed row of 4096: documents of 1500 .. 496 0.0089-0.0117
#:   prefill of 640, then 127 decode steps, rows 768  0.0092 (decoded 0.0109)
#:   this forward at default matmul precision         0.0069, 0.0073
#:   HELD experts rounded to int8 by row              0.0033, 0.0046
#:   held experts rounded to float8 e4m3              0.0143, 0.0158
#:   every matrix rounded to int8 by row              0.0366, 0.0371
#:   every matrix rounded to float8 e4m3              0.124, 0.125
#:   every matrix rounded to float8 e5m2              0.177, 0.191
#:   WRONG: B and C a head, not a group               0.0081-0.0085 (0.0077) 0.161 (0.157)
#:   WRONG: the norm over the whole width             0.088-0.090 (0.086)    0.144 (0.144)
#:   WRONG: the norm before the gate                  0.268-0.275 (0.275)    0.292 (0.294)
#:   WRONG: no D term                                 0.681-0.708 (0.692)    0.669 (0.663)
#:   WRONG: the decay after the update                0.0035-0.0040 (0.0039) 0.086 (0.088)
#:   WRONG: the convolution's bias left out           0.339-0.367 (0.360)    0.413 (0.406)
#:   WRONG: gated experts                             0.567-0.572 (0.558)    0.546 (0.556)
#:   WRONG: silu for relu2                            0.626-0.644 (0.644)    0.630 (0.628)
#:   WRONG: gates not renormalised                    0.545-0.588 (0.528)    0.538 (0.567)
#:   WRONG: a rotary in the attention layer           0.0371-0.0392 (0.0155) 0.0363 (0.0148)
#:   WRONG: the state carried over a boundary         0.000016* [0.0014]     0.0087* [0.107]
#:   WRONG: a convolution that runs over documents    0.0040* [0.364]        0.0042* [0.264]
#:   engine, FLOAT32 at highest precision, 4096 tok.  (0.00000062)           (0.0000094)
#:   the same on the packed row, a document at a time 5.6e-7-6.9e-7          5.2e-6-8.2e-6
#:   (* on the packed row of 4096, reference against reference, [over
#:   the 8 tokens after each boundary alone]: the fixed batch is a row a
#:   document and reads 0 there)
#:
#: 0.022 is 1.7 times the most bf16 shows over 23 seeds and three
#: fifths of int8 on the whole model: a forward computed below bf16
#: fails, as does every wrong equation of the list under
#: ``published_init`` BUT the two that are about documents, which touch
#: a few tokens a boundary (three boundaries in 4,096 tokens: 0.0087 and
#: 0.0042 of a row's mean, 0.107 and 0.264 of the tokens they touch).
#: WHAT THE CELL'S OWN ``correct`` CANNOT TELL, under the harness's
#: weights: B and C a head (0.0085), the decay after the update (0.0035)
#: and the two about documents; a lower precision ON THE HELD EXPERTS
#: ALONE (inside bf16's noise, as in the other sparse families). Those
#: are held by float32: the tests on the CPU
#: (``tests/model/test_nemotron_h.py``: every entry 50 tolerances away
#: under both initialisations, the packed row against each document
#: alone) and ``chip_check.py``'s float32 rows through the COMPILED
#: program, which read 0.0000006 under the harness's weights and
#: 0.000005 to 0.0000094 under ``published_init`` (a state that lives
#: hundreds of tokens is multiplied by the chip's ``exp`` once a token
#: in this reference and once a chunk in the program).
TOLERANCE = 0.022

_PRE = "backbone.layers.{}."
_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
#: an expert's two matrices in the order up, down
_FFN = ("up_proj", "down_proj")
_SSM = ("in_proj.weight", "conv1d.weight", "conv1d.bias", "A_log", "D",
        "dt_bias", "norm.weight", "out_proj.weight")
#: rows of queries whose scores are held at once
QUERY_BLOCK = 512
#: published key -> the one value of it this reference computes
_ONLY = {"attention_bias": False, "mlp_bias": False, "use_bias": False,
         "mamba_proj_bias": False, "use_conv_bias": True,
         "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
         "n_group": 1, "topk_group": 1, "time_step_limit": None,
         "sliding_window": None}


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    for key, only in _ONLY.items():
        if hf.get(key, only) != only:
            raise NotImplementedError(
                f"the reference computes {key}={only!r} only, not "
                f"{hf[key]!r}")
    pattern = hf["hybrid_override_pattern"]
    eps = hf.get("layer_norm_epsilon", 1e-5)
    if len(pattern) != hf["num_hidden_layers"] or set(pattern) - set("ME*") \
            or hf.get("norm_eps", eps) != eps \
            or hf.get("n_shared_experts", 1) > 1:
        raise NotImplementedError(
            f"hybrid_override_pattern {pattern!r} over "
            f"{hf['num_hidden_layers']} layers of M, E and *, one "
            "epsilon, at most one shared expert")
    share = hf.get("expert_share") or dict(of=hf["n_routed_experts"],
                                           first=0)
    n, p = hf["mamba_num_heads"], hf["mamba_head_dim"]
    g, state = hf["n_groups"], hf["ssm_state_size"]
    return dict(
        layers=len(pattern), kinds=pattern, hidden=hf["hidden_size"],
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        hd=hf["head_dim"], sheads=n, shead=p, width=n * p, groups=g,
        state=state, conv_dim=n * p + 2 * g * state,
        in_dim=2 * n * p + 2 * g * state + n, taps=hf["conv_kernel"],
        moe_inter=hf["moe_intermediate_size"],
        shared=hf["moe_shared_expert_intermediate_size"]
        * hf.get("n_shared_experts", 1),
        vocab=hf["vocab_size"], experts=share["of"],
        top_k=hf["num_experts_per_tok"],
        held=range(share["first"], share["first"] + hf["n_routed_experts"]),
        renorm=bool(hf.get("norm_topk_prob", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)), eps=eps,
        theta=float(hf.get("rope_theta", 10000.0)),
        tied=bool(hf.get("tie_word_embeddings", False)))


def _ssm_matrices(d):
    return d["hidden"] * d["in_dim"] + d["width"] * d["hidden"]


def _attn_matrices(d):
    return d["hidden"] * (d["heads"] + 2 * d["kv_heads"]) * d["hd"] \
        + d["heads"] * d["hd"] * d["hidden"]


def _moe_matrices(d):
    """The router over all experts, the HELD experts' two matrices, the
    shared expert's two."""
    h = d["hidden"]
    return h * d["experts"] + len(d["held"]) * 2 * h * d["moe_inter"] \
        + 2 * h * d["shared"]


def n_params(hf):
    """Parameters the checkpoint HOLDS (528,093,120 in the benchmark's
    cell): the matrices; an M layer's taps and their bias, ``A_log``,
    ``D``, ``dt_bias`` and the grouped norm's scale; an E layer's
    selection bias; every layer's one norm; the final norm."""
    d = dims(hf)
    total = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2) + d["hidden"]
    for kind in d["kinds"]:
        total += d["hidden"]
        if kind == "M":
            total += _ssm_matrices(d) + (d["taps"] + 1) * d["conv_dim"] \
                + 3 * d["sheads"] + d["width"]
        elif kind == "E":
            total += _moe_matrices(d) + d["experts"]
        else:
            total += _attn_matrices(d)
    return total


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes over ALL the router's
    experts: ``num_experts_per_tok`` a token in every E layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * d["kinds"].count("E")


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


def ssm_flops(hf, seqlens):
    """FLOPs of the state-space RECURRENCE as written, for one forward
    over these documents: two products of 2 x P x N a head a token an M
    layer (the rank-one update ``Delta x B^T`` and the read ``S C``; the
    decay's elementwise pass and ``D x`` are left out), 2.10 MFLOP a
    token a layer at 64 heads of 64 and a state of 128. WHATEVER
    implements it (the chunked form runs more, in products of another
    shape): ``ssm.scan_mxu_share`` reads every implementation by this
    yardstick, so it can never pass 100%."""
    d = dims(hf)
    return sum(seqlens) * d["kinds"].count("M") * d["sheads"] \
        * 2 * 2 * d["shead"] * d["state"]


def forward_flops(hf, seqlens):
    """FLOPs of one forward over documents of these lengths, at 2 FLOPs
    a multiply-add, OF THE MATHEMATICS: an M layer's two projections and
    its recurrence as written (``ssm_flops``); the attention layer's
    four projections, scores and values for the pairs the causal mask
    lets through; in an E layer the router over all experts, the shared
    expert on every token, and the HELD experts only, at even routing
    (``num_experts_per_tok x held / experts`` experts a token: 6 x 8/128
    = 0.375 in the benchmark's cell); the vocabulary head on every
    token. Norms, the convolution's taps, elementwise products, softmax,
    sigmoid, the sort and the scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    h = d["hidden"]
    pairs = sum(visible_pairs(n) for n in seqlens)
    total = 2 * tokens * h * d["vocab"] + ssm_flops(hf, seqlens)
    for kind in d["kinds"]:
        if kind == "M":
            total += 2 * tokens * _ssm_matrices(d)
        elif kind == "*":
            total += 2 * tokens * _attn_matrices(d) \
                + 2 * pairs * d["heads"] * 2 * d["hd"]
        else:
            total += 2 * tokens * (
                h * d["experts"] + 2 * h * d["shared"]
                + 2 * h * d["moe_inter"] * d["top_k"]
                * len(d["held"]) / d["experts"])
    return total


def ssm_state_bytes(hf, n_seqs, bytes_per_el=2):
    """The M layers' decode state: a float32 [P, N] a head, and
    ``conv_kernel - 1`` rows of the convolution's input, for each M
    layer and stream."""
    d = dims(hf)
    return d["kinds"].count("M") * n_seqs * (
        4 * d["sheads"] * d["shead"] * d["state"]
        + bytes_per_el * (d["taps"] - 1) * d["conv_dim"])


def kv_bytes_per_token(hf, bytes_per_el=2):
    """What a token adds to the cache in every ATTENTION layer: keys and
    values of ``num_key_value_heads`` heads. An M layer adds nothing a
    token, an E layer holds no state."""
    d = dims(hf)
    return d["kinds"].count("*") * 2 * d["kv_heads"] * d["hd"] \
        * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, every live sequence reads
    its cached prefix in the attention layers and reads and writes its
    state-space state. Prefill is left out."""
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    rows = sum(prompt_len + t for t in range(new_tokens))
    return weights + n_seqs * rows * kv_bytes_per_token(hf, bytes_per_el) \
        + 2 * new_tokens * ssm_state_bytes(hf, n_seqs, bytes_per_el)


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor. ``kind`` is
    ``matrix`` or ``norm``; the taps and their bias, ``A_log``,
    ``dt_bias`` and ``e_score_correction_bias`` are drawn like a matrix,
    N(0, initializer_range) (the module's docstring says what that does
    to the decay); ``D`` like a norm's scale, near its published 1."""
    d = dims(hf)
    h = d["hidden"]
    out = {"backbone.embeddings.weight": ((d["vocab"], h), "matrix"),
           "backbone.norm_f.weight": ((h,), "norm")}
    for i, kind in enumerate(d["kinds"]):
        pre = _PRE.format(i)
        m = pre + "mixer."
        out[pre + "norm.weight"] = ((h,), "norm")
        if kind == "M":
            out[m + "in_proj.weight"] = ((d["in_dim"], h), "matrix")
            out[m + "conv1d.weight"] = ((d["conv_dim"], 1, d["taps"]),
                                        "matrix")
            out[m + "conv1d.bias"] = ((d["conv_dim"],), "matrix")
            out[m + "A_log"] = ((d["sheads"],), "matrix")
            out[m + "dt_bias"] = ((d["sheads"],), "matrix")
            out[m + "D"] = ((d["sheads"],), "norm")
            out[m + "norm.weight"] = ((d["width"],), "norm")
            out[m + "out_proj.weight"] = ((h, d["width"]), "matrix")
        elif kind == "*":
            out[m + "q_proj.weight"] = ((d["heads"] * d["hd"], h), "matrix")
            for name in ("k_proj", "v_proj"):
                out[f"{m}{name}.weight"] = ((d["kv_heads"] * d["hd"], h),
                                            "matrix")
            out[m + "o_proj.weight"] = ((h, d["heads"] * d["hd"]), "matrix")
        else:
            out[m + "gate.weight"] = ((d["experts"], h), "matrix")
            out[m + "gate.e_score_correction_bias"] = ((d["experts"],),
                                                       "matrix")
            for e in d["held"]:
                out.update(_ffn_shapes(f"{m}experts.{e}.", h,
                                       d["moe_inter"]))
            if d["shared"]:
                out.update(_ffn_shapes(m + "shared_experts.", h,
                                       d["shared"]))
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


def _ffn_shapes(pre, h, f):
    up, down = _FFN
    return {f"{pre}{up}.weight": ((f, h), "matrix"),
            f"{pre}{down}.weight": ((h, f), "matrix")}


def published_init(hf, tensors, seed):
    """``tensors`` with what the generator draws UNLIKE the published
    initialisation of an M layer redrawn as published, from the seed:
    ``A_log = log U(1, 16)`` a head, ``dt_bias`` the inverse softplus of
    a step log-uniform in [``time_step_min``, ``time_step_max``] (no
    smaller than ``time_step_floor``), ``D = 1``, and the convolution's
    taps and bias uniform in +-``conv_kernel ** -0.5`` (a depthwise
    ``Conv1d``'s own default, which the published module keeps:
    ``assumed``); in the tensors' own dtype. Taps drawn N(0, 0.02) cut
    x, B and C to a twentieth: the state's part of a mixer's output is
    then a few per cent of ``D x``, and under a long-lived state with
    its small steps less still."""
    d = dims(hf)
    rng = np.random.default_rng(seed)
    lo, hi = hf.get("time_step_min", 1e-3), hf.get("time_step_max", 1e-1)
    bound = d["taps"] ** -0.5
    out = dict(tensors)
    for i, kind in enumerate(d["kinds"]):
        if kind != "M":
            continue
        m = _PRE.format(i) + "mixer."
        dt = np.maximum(
            np.exp(rng.uniform(np.log(lo), np.log(hi), d["sheads"])),
            hf.get("time_step_floor", 1e-4))
        for name, value in (
                (m + "A_log", np.log(rng.uniform(1, 16, d["sheads"]))),
                (m + "dt_bias", dt + np.log(-np.expm1(-dt))),
                (m + "D", np.ones(d["sheads"])),
                (m + "conv1d.weight", rng.uniform(
                    -bound, bound, (d["conv_dim"], 1, d["taps"]))),
                (m + "conv1d.bias", rng.uniform(
                    -bound, bound, d["conv_dim"]))):
            out[name] = np.asarray(value, np.float32).astype(
                tensors[name].dtype)
    return out


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: only to show that the tolerance tells each from the model
#: (``scripts/chip_check.py``, the tests). ``bc_a_head``: every head of
#: a group reads the group's B rotated by the head's place in the group
#: and its C by twice that, a B and C of its own a head. ``rotary_in_attention``: q and k
#: rotated over the whole head at ``rope_theta``, as the config's two
#: unread keys would have it.
WRONG = ("bc_a_head", "norm_over_the_whole_width", "norm_before_the_gate",
         "no_d_term", "decay_after_the_update", "state_over_documents",
         "conv_over_documents", "conv_bias_left_out", "experts_gated",
         "silu_for_relu2", "gates_not_renormalised", "rotary_in_attention")


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


def _mamba(d, u, w, pos, seg, wrong=()):
    """An M layer's mixer on u [B, L, H], the recurrence a token at a
    time."""
    import jax
    import jax.numpy as jnp
    n, p, g, ns = d["sheads"], d["shead"], d["groups"], d["state"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, _ = u.shape
    z, xbc, dt = jnp.split(u @ w["in_proj.weight"].T,
                           [d["width"], d["width"] + d["conv_dim"]], axis=-1)
    xbc = _conv(xbc, w["conv1d.weight"], pos, wrong)
    if "conv_bias_left_out" not in wrong:
        xbc = xbc + w["conv1d.bias"]
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :d["width"]].reshape(b, t, n, p)
    bm = xbc[..., d["width"]:d["width"] + g * ns].reshape(b, t, g, ns)
    cm = xbc[..., d["width"] + g * ns:].reshape(b, t, g, ns)
    # head h reads group h // (n / g)
    bm, cm = (jnp.repeat(m, n // g, axis=2) for m in (bm, cm))
    if "bc_a_head" in wrong:
        place = jnp.arange(n) % (n // g)
        roll = jax.vmap(lambda m, s: jnp.roll(m, s, axis=-1),
                        in_axes=(2, 0), out_axes=2)
        # (by different shifts: the same rotation of both cancels)
        bm, cm = roll(bm, place), roll(cm, 2 * place)
    delta = jax.nn.softplus(dt + w["dt_bias"])  # [B, T, n]
    decay = jnp.exp(-jnp.exp(w["A_log"]) * delta)
    first = (pos == 0) & (seg != 0)
    if "state_over_documents" in wrong:
        first = jnp.zeros_like(first)

    def token(s, tok):
        xt, bt, ct, dl, dc, new, live = tok
        s = jnp.where(new[:, None, None, None], 0.0, s)
        add = (dl[..., None] * xt)[..., None] * bt[:, :, None, :]
        if "decay_after_the_update" in wrong:
            nxt = (s + add) * dc[..., None, None]
        else:
            nxt = s * dc[..., None, None] + add
        # padding leaves the state as it is
        nxt = jnp.where(live[:, None, None, None], nxt, s)
        return nxt, jnp.einsum("bnpk,bnk->bnp", nxt, ct)

    by_token = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        token, jnp.zeros((b, n, p, ns), jnp.float32),
        tuple(map(by_token, (x, bm, cm, delta, decay, first, seg != 0))))
    y = by_token(y)
    if "no_d_term" not in wrong:
        y = y + x * w["D"][:, None]
    y = y.reshape(b, t, n * p)
    gate = jax.nn.silu(z)

    def normed(v):
        groups = 1 if "norm_over_the_whole_width" in wrong else g
        v = v.reshape(b, t, groups, -1)
        v = v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + d["eps"])
        return v.reshape(b, t, n * p)

    if "norm_before_the_gate" in wrong:
        y = normed(y) * w["norm.weight"] * gate
    else:
        y = normed(y * gate) * w["norm.weight"]
    return y @ w["out_proj.weight"].T


def _rotated(x, pos, theta):
    """x [B, T, n, hd] rotated over the whole head, halves (j, j + hd/2)
    together: only ``rotary_in_attention`` (WRONG) comes here."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def _attention(d, u, w, pos, seg, wrong=()):
    """The attention layer on u [B, L, H], no positional term: the
    explicit mask a block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    n, nkv, hd = d["heads"], d["kv_heads"], d["hd"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, _ = u.shape
    q = (u @ w["q_proj.weight"].T).reshape(b, t, n, hd)
    k = (u @ w["k_proj.weight"].T).reshape(b, t, nkv, hd)
    v = (u @ w["v_proj.weight"].T).reshape(b, t, nkv, hd)
    if "rotary_in_attention" in wrong:
        q, k = _rotated(q, pos, d["theta"]), _rotated(k, pos, d["theta"])
    # query head h reads key head h // (n / nkv)
    k, v = (jnp.repeat(m, n // nkv, axis=2) for m in (k, v))
    out = []
    for s in range(0, t, QUERY_BLOCK):
        e = min(s + QUERY_BLOCK, t)
        seen = (seg[:, s:e, None] == seg[:, None, :]) \
            & (seg[:, s:e, None] != 0) \
            & (pos[:, s:e, None] - pos[:, None, :] >= 0)
        score = jnp.einsum("bqhd,bkhd->bhqk", q[:, s:e], k) / np.sqrt(hd)
        # a padding row sees nothing: a large finite value, not -inf,
        # so that its (unused) softmax is no NaN
        p = jax.nn.softmax(jnp.where(seen[:, None], score, -1e30), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    o = jnp.concatenate(out, axis=1)
    return o.reshape(b, t, n * hd) @ w["o_proj.weight"].T


def _relu2_ffn(v, up, down, wrong=()):
    """``W_down relu(W_up v)^2``: two matrices, no gate."""
    import jax
    import jax.numpy as jnp
    up, down = up.astype(jnp.float32), down.astype(jnp.float32)
    a = v @ up.T
    if "silu_for_relu2" in wrong:
        mid = jax.nn.silu(a)
    else:
        mid = jnp.square(jax.nn.relu(a))
    if "experts_gated" in wrong:
        mid = mid * a
    return mid @ down.T


def _route(d, v, gate_w, bias, wrong=()):
    """The gates [B, L, E] over ALL experts: the sigmoid score where
    the expert is among the token's k largest of score + bias, else 0;
    divided by (the k's sum + 1e-20) under ``norm_topk_prob``;
    scaled."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(v @ gate_w.astype(jnp.float32).T)
    choice = s + bias.astype(jnp.float32)
    kth = jax.lax.top_k(choice, d["top_k"])[0][..., -1:]
    gates = jnp.where(choice >= kth, s, 0.0)
    if d["renorm"] and "gates_not_renormalised" not in wrong:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * d["scaling"]


def _blocks(hf, get, ids, seg=None, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per E layer its gates [B, L, E]). Layer by layer and expert by
    expert, each cast up on the way in."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    seg = np.ones(ids.shape, np.int32) if seg is None else np.asarray(seg)
    pos, seg = jnp.asarray(positions(seg)), jnp.asarray(seg)
    normed = jax.jit(lambda x, w: _rms(x, w, d["eps"]))
    mamba = jax.jit(lambda u, w: _mamba(d, u, w, pos, seg, wrong))
    attention = jax.jit(lambda u, w: _attention(d, u, w, pos, seg, wrong))
    ffn = jax.jit(lambda v, *ws: _relu2_ffn(v, *ws, wrong=wrong))
    route = jax.jit(lambda v, g, b: _route(d, v, g, b, wrong))
    expert = jax.jit(lambda v, g, *ws: g * _relu2_ffn(v, *ws, wrong=wrong))
    x = get("backbone.embeddings.weight")[ids].astype(jnp.float32)
    routed = []
    for i, kind in enumerate(d["kinds"]):
        pre = _PRE.format(i)
        m = pre + "mixer."
        u = normed(x, get(pre + "norm.weight"))
        if kind == "M":
            x = x + mamba(u, {n: get(m + n) for n in _SSM})
        elif kind == "*":
            x = x + attention(u, {f"{n}.weight": get(f"{m}{n}.weight")
                                  for n in _ATTN})
        else:
            gates = route(u, get(m + "gate.weight"),
                          get(m + "gate.e_score_correction_bias"))
            for e in d["held"]:  # what the absent experts add is left out
                x = x + expert(u, gates[..., e:e + 1], *(
                    get(f"{m}experts.{e}.{n}.weight") for n in _FFN))
            if d["shared"]:
                x = x + ffn(u, *(get(f"{m}shared_experts.{n}.weight")
                                 for n in _FFN))
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
    x = _rms(x, get("backbone.norm_f.weight"), dims(hf)["eps"])
    head = get("backbone.embeddings.weight"
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
    ``ids`` to in E ``layer`` (its index in the model): bool
    [B, L, E]."""
    import jax
    import jax.numpy as jnp
    cut = dict(hf, num_hidden_layers=layer + 1,
               hybrid_override_pattern=hf["hybrid_override_pattern"][
                   :layer + 1])
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
