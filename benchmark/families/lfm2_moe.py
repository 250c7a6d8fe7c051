"""Everything the benchmark knows about the ``lfm2_moe`` architecture
(LFM2-24B-A2B): the checkpoint's tensors, the plain float32 reference
(forward, training loss and its gradient) with its tolerance, and what
a step needs in parameters, FLOPs, bytes and routed pairs, all from
the PUBLISHED configuration dict and the checkpoint's tensors and
nothing of the program's.

The model, with ``u = RMSNorm(x; operator_norm)``::

    conv layer:  [B, C, z] = split3(u W_in)     W_in [H, 3H], no bias
                 s_t = B_t * z_t                 elementwise
                 c_t = w[:,0] s_{t-2} + w[:,1] s_{t-1} + w[:,2] s_t
                                                 depthwise, causal; s before
                                                 the document's first token is 0
                 h = x + (C * c) W_out
    attn layer:  q, k, v = u Wq, u Wk, u Wv; q and k get an RMSNorm over
                 each HEAD's values (one scale of width head_dim) BEFORE
                 the rotary embedding (rotate-half); causal GQA;
                 h = x + Attn W_out
    both:        y = h + FFN(RMSNorm(h; ffn_norm))
    dense FFN:   w2(silu(w1 v) * w3 v)           layers < num_dense_layers
    sparse FFN:  s = sigmoid(v W_gate)           float32, over ALL experts
                 chosen = the k largest of s + expert_bias
                 g = s[chosen] / (sum of s[chosen] + 1e-6) * routed_scaling_factor
                 FFN = sum over chosen e of g_e w2_e(silu(w1_e v) * w3_e v)
    model:       embed_tokens -> layers -> RMSNorm(embedding_norm) -> head (tied)

The reference takes one document a row (no packing, no cache, no sort,
no ragged product): it loops over the layers and, in a sparse layer,
over the experts, adding each one's output for every token weighted by
a gate that is 0 where the expert is not among the token's k. Weights
are the checkpoint's values cast up exactly; every product is taken at
``default_matmul_precision("highest")``.

**The chip's share.** The configuration may hold a share of the experts
(``num_experts`` held, ``expert_share = {"of": published count,
"first": first global id}``): the router and ``expert_bias`` keep the
published width, the k are chosen among ALL experts, and only the HELD
experts' terms are added; what the absent ones would have added is
left out, here as in the program. With no ``expert_share`` every
expert is held and this is the uncut model. A sliced vocabulary is a
smaller vocabulary: the head is over the ``vocab_size`` rows the
checkpoint holds.

Conv, attention, dense FFN and the layer are ``transformers`` 4.57.6's
``modeling_lfm2.py`` (``Lfm2ShortConv.slow_forward``,
``Lfm2Attention``, ``Lfm2MLP``, ``Lfm2DecoderLayer``), which is
installed here and which ``tests/benchmark/test_benchmark_lfm2.py``
holds this file to (``Lfm2ForCausalLM``, all layers dense). Departures
from it:

- ``conv_bias: true`` is refused (false as published);
- ``Lfm2RMSNorm`` casts the normalised value back to the input's dtype
  before the weight multiplies; everything here is float32;
- ``block_auto_adjust_ff_dim`` is taken as false (``lfm2_moe`` has no
  such key: ``intermediate_size`` is the dense width itself);
- HF masks padding only; here a row is one document, so there is
  nothing to mask.

WRITTEN FROM MEMORY of ``modeling_lfm2_moe.py`` (``lfm2_moe`` is not
in 4.57.6, no network here; all listed under ``assumed`` in the
configuration file): the sparse block above (sigmoid scores, the bias
added for the choice only and held as a buffer without gradient, the
gathered scores divided by their sum + 1e-6 under ``norm_topk_prob``,
then ``routed_scaling_factor``); that layer i is dense iff ``i <
num_dense_layers``; the tensor names ``feed_forward.gate.weight``,
``feed_forward.expert_bias``, ``feed_forward.experts.{e}.w{1,2,3}.weight``;
tied embeddings. The published update rule of ``expert_bias`` belongs
to the training recipe, not to the config: the program leaves the bias
as loaded (no gradient reaches it, the optimizer does not move it).
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (0.89 nat at the cell's widths). Sized on
#: the chip at those widths (5 layers, 8 of 64 experts held,
#: vocabulary 8192) by ``scripts/chip_check_lfm2.py`` (my chip runs,
#: PR 31), shares of the spread at two seeds:
#:
#:   engine, bf16 (0.0079-0.0095 over 9 seeds)           0.0086 0.0087
#:   prefill, then decode through K/V and conv state      0.0086-0.0097
#:   ONE packed row of the four documents                 0.0085
#:   this forward at default matmul precision             0.0053 0.0058
#:   HELD experts rounded to int8 by row                  0.0014 0.0013
#:   held experts rounded to float8 e4m3                  0.0065 0.0055
#:   every matrix rounded to int8 by row                  0.0317 0.0305
#:   every matrix rounded to float8 e4m3                  0.1116 0.1125
#:   every matrix rounded to float8 e5m2                  0.1767 0.1709
#:   WRONG: the convolution crosses a document boundary   0.0240 0.0224
#:   WRONG: query/key norm over the whole width           0.0242 0.0227
#:   WRONG: the bias left out of the choice               0.0277 0.0312
#:   WRONG: softmax in place of sigmoid                   0.0699 0.0705
#:   WRONG: gates not renormalised                        0.2169 0.2118
#:   WRONG: the convolution's taps reversed               0.9106 0.8863
#:
#: 0.015 is 1.6 times the most bf16 shows, two thirds of the mildest
#: wrong equation and half of int8 on the whole model, so a forward
#: computed below bf16 or by a wrong equation fails. What it cannot
#: tell from bf16 is a lower precision ON THE HELD EXPERTS ALONE (int8
#: by row 0.0014, float8 e4m3 0.0065: both inside the engine's own
#: noise): 8 of 64 experts add the smaller part of the residual
#: stream. The harness's fixed batch holds one document a row, so a
#: convolution that crossed a PACKED row's boundaries would pass it:
#: the packed row above and ``tests/model/test_lfm2_moe.py`` hold that.
TOLERANCE = 0.015

_PRE = "model.layers.{}."
_FFN = ("w1", "w3", "w2")


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    if hf.get("conv_bias", False):
        raise NotImplementedError("the reference has no conv_bias")
    n = hf["num_hidden_layers"]
    types = hf.get("layer_types") or ["full_attention"] * n
    if len(types) != n or not set(types) <= {"conv", "full_attention"}:
        raise NotImplementedError(f"layer_types {types} for {n} layers")
    nq = hf["num_attention_heads"]
    share = hf.get("expert_share") or dict(of=hf["num_experts"], first=0)
    rope = hf.get("rope_parameters") or {}
    return dict(
        layers=n, types=types, dense=hf.get("num_dense_layers", 0),
        hidden=hf["hidden_size"], nq=nq,
        nkv=hf.get("num_key_value_heads", nq),
        head=hf.get("head_dim") or hf["hidden_size"] // nq,
        inter=hf["intermediate_size"], moe_inter=hf["moe_intermediate_size"],
        vocab=hf["vocab_size"], taps=hf.get("conv_L_cache", 3),
        experts=share["of"], top_k=hf["num_experts_per_tok"],
        held=range(share["first"], share["first"] + hf["num_experts"]),
        bias=bool(hf.get("use_expert_bias", True)),
        renorm=bool(hf.get("norm_topk_prob", True)),
        scaling=float(hf.get("routed_scaling_factor", 1.0)),
        eps=hf.get("norm_eps", 1e-5),
        theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
        tied=bool(hf.get("tie_word_embeddings", True)))


def _sparse(d, i):
    return i >= d["dense"]


def _operator_params(d, kind):
    h = d["hidden"]
    if kind == "conv":
        return 4 * h * h + d["taps"] * h
    q, kv = d["nq"] * d["head"], d["nkv"] * d["head"]
    return h * (q + 2 * kv) + q * h + 2 * d["head"]


def _ffn_params(d, sparse):
    h = d["hidden"]
    if not sparse:
        return 3 * h * d["inter"]
    return h * d["experts"] + (d["experts"] if d["bias"] else 0) \
        + len(d["held"]) * 3 * h * d["moe_inter"]


def n_params(hf):
    """Parameters the checkpoint HOLDS: embedding (the head too where it
    is not tied), for every layer its operator (conv: ``W_in``, the
    taps, ``W_out``; attention: four projections and two norm scales of
    one head's width), its feed-forward (dense: three matrices; sparse:
    the router over all experts, ``expert_bias``, and the HELD experts'
    three matrices each), two norm scales, and the final norm."""
    d = dims(hf)
    layers = sum(_operator_params(d, t) + _ffn_params(d, _sparse(d, i))
                 + 2 * d["hidden"] for i, t in enumerate(d["types"]))
    embed = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)
    return layers + embed + d["hidden"]


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes, over ALL the router's
    experts: every token goes to ``num_experts_per_tok`` experts in
    every sparse layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * (d["layers"] - d["dense"])


def held_pairs(hf, seqlens):
    """The EXPECTED share of those pairs that land on held experts, at
    even routing: held / experts of them. What a run really multiplies
    is the program's counter ``moe_held_pairs_total``."""
    d = dims(hf)
    return routed_pairs(hf, seqlens) * len(d["held"]) / d["experts"]


def forward_flops(hf, seqlens):
    """FLOPs of one forward over packed sequences of these lengths, at 2
    FLOPs a multiply-add. A conv operator: ``W_in`` (H x 3H), the taps,
    ``W_out``. An attention operator: the projections and causal
    attention at half of the full square. A dense feed-forward: three
    matrices of ``intermediate_size``. A sparse one: the router over all
    experts on every token, and the HELD experts only, at even routing:
    ``num_experts_per_tok x held / experts`` experts a token (4 x 8/64 =
    0.5 in the benchmark's cell), NOT the 4 the whole model runs. The
    vocabulary head on every token. Norms, rotary, gating products,
    softmax, sigmoid, the sort and the scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    sum_sq = sum(n * n for n in seqlens)
    h = d["hidden"]
    conv = 2 * tokens * (4 * h * h + d["taps"] * h)
    attn = 2 * tokens * h * (d["nq"] + 2 * d["nkv"]) * d["head"] \
        + 2 * tokens * d["nq"] * d["head"] * h \
        + 2 * sum_sq * d["nq"] * d["head"]
    dense = 2 * tokens * 3 * h * d["inter"]
    sparse = 2 * tokens * h * d["experts"] \
        + 2 * tokens * 3 * h * d["moe_inter"] \
        * d["top_k"] * len(d["held"]) / d["experts"]
    total = 2 * tokens * h * d["vocab"]
    for i, t in enumerate(d["types"]):
        total += (conv if t == "conv" else attn) \
            + (sparse if _sparse(d, i) else dense)
    return total


def kv_bytes_per_token(hf, bytes_per_el=2):
    """K and V of the ATTENTION layers alone."""
    d = dims(hf)
    return 2 * d["types"].count("full_attention") * d["nkv"] * d["head"] \
        * bytes_per_el


def conv_state_bytes(hf, n_seqs, bytes_per_el=2):
    """The conv layers' decode state: ``conv_L_cache - 1`` rows of the
    hidden width for each conv layer and stream."""
    d = dims(hf)
    return d["types"].count("conv") * n_seqs * (d["taps"] - 1) \
        * d["hidden"] * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of the weights it holds once, every live sequence reads
    its key/value prefix in the attention layers, and reads and writes
    its conv state. Prefill is left out."""
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    kv = sum(n_seqs * (prompt_len + t) for t in range(new_tokens)) \
        * kv_bytes_per_token(hf, bytes_per_el)
    return weights + kv \
        + 2 * new_tokens * conv_state_bytes(hf, n_seqs, bytes_per_el)


def shapes(hf):
    """HF name -> (shape, kind), one entry a tensor: layers of unlike
    kinds hold unlike tensors, so no name stands for every layer
    (``generate.make_weights`` takes a name without ``{}`` as it is).
    ``kind`` is ``matrix``, ``norm`` or ``bias``; the conv taps and
    ``expert_bias`` are drawn like matrices, N(0, initializer_range),
    so the taps differ from each other and the bias moves the choice of
    about a third of the tokens."""
    d = dims(hf)
    h = d["hidden"]
    q, kv = d["nq"] * d["head"], d["nkv"] * d["head"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.embedding_norm.weight": ((h,), "norm"),
    }
    for i, t in enumerate(d["types"]):
        pre = _PRE.format(i)
        out[pre + "operator_norm.weight"] = ((h,), "norm")
        out[pre + "ffn_norm.weight"] = ((h,), "norm")
        if t == "conv":
            out[pre + "conv.in_proj.weight"] = ((3 * h, h), "matrix")
            out[pre + "conv.conv.weight"] = ((h, 1, d["taps"]), "matrix")
            out[pre + "conv.out_proj.weight"] = ((h, h), "matrix")
        else:
            out[pre + "self_attn.q_proj.weight"] = ((q, h), "matrix")
            out[pre + "self_attn.k_proj.weight"] = ((kv, h), "matrix")
            out[pre + "self_attn.v_proj.weight"] = ((kv, h), "matrix")
            out[pre + "self_attn.out_proj.weight"] = ((h, q), "matrix")
            out[pre + "self_attn.q_layernorm.weight"] = ((d["head"],), "norm")
            out[pre + "self_attn.k_layernorm.weight"] = ((d["head"],), "norm")
        ffn = pre + "feed_forward."
        if not _sparse(d, i):
            f = d["inter"]
            out[ffn + "w1.weight"] = ((f, h), "matrix")
            out[ffn + "w3.weight"] = ((f, h), "matrix")
            out[ffn + "w2.weight"] = ((h, f), "matrix")
            continue
        f = d["moe_inter"]
        out[ffn + "gate.weight"] = ((d["experts"], h), "matrix")
        if d["bias"]:
            out[ffn + "expert_bias"] = ((d["experts"],), "bias")
        for e in d["held"]:
            out[f"{ffn}experts.{e}.w1.weight"] = ((f, h), "matrix")
            out[f"{ffn}experts.{e}.w3.weight"] = ((f, h), "matrix")
            out[f"{ffn}experts.{e}.w2.weight"] = ((h, f), "matrix")
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: only to show that the tolerance tells each from the model
#: (``scripts/chip_check_lfm2.py``, the tests)
WRONG = ("softmax_router", "whole_width_qk_norm", "reversed_taps",
         "conv_crosses_documents")


def _rms(x, w, eps):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [B, L, heads, D] -> rotated, rotate-half convention."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _conv(d, u, w, wrong=()):
    """The gated short convolution on u [B, L, H], a row a document."""
    import jax.numpy as jnp
    w_in, taps, w_out = (w[n].astype(jnp.float32) for n in (
        "conv.in_proj.weight", "conv.conv.weight", "conv.out_proj.weight"))
    b_, c_, z = jnp.split(u @ w_in.T, 3, axis=-1)
    s = b_ * z
    taps = taps[:, 0, :]  # [H, K]; column K-1 is the token itself
    if "reversed_taps" in wrong:
        taps = taps[:, ::-1]
    k = d["taps"]
    before = jnp.zeros_like(s[:, :k - 1])
    if "conv_crosses_documents" in wrong:
        # the window reaches into the row before, as if the rows were
        # one packed row and the boundary were not there
        before = jnp.roll(s, 1, axis=0)[:, -(k - 1):].at[0].set(0.0)
    padded = jnp.concatenate([before, s], axis=1)
    n = s.shape[1]
    c = sum(padded[:, j:j + n] * taps[:, j] for j in range(k))
    return (c_ * c) @ w_out.T


def _attention(d, u, w, wrong=()):
    """Causal grouped-query attention on u [B, L, H]."""
    import jax
    import jax.numpy as jnp
    nq, nkv, hd = d["nq"], d["nkv"], d["head"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, n, _ = u.shape
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(b, n, nq, hd)
    k = (u @ w["self_attn.k_proj.weight"].T).reshape(b, n, nkv, hd)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(b, n, nkv, hd)
    qw, kw = w["self_attn.q_layernorm.weight"], w["self_attn.k_layernorm.weight"]
    if "whole_width_qk_norm" in wrong:
        q = _rms(q.reshape(b, n, -1), jnp.tile(qw, nq), d["eps"]) \
            .reshape(b, n, nq, hd)
        k = _rms(k.reshape(b, n, -1), jnp.tile(kw, nkv), d["eps"]) \
            .reshape(b, n, nkv, hd)
    else:
        q, k = _rms(q, qw, d["eps"]), _rms(k, kw, d["eps"])
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, n, nq * hd)
    return o @ w["self_attn.out_proj.weight"].T


def _operator(d, kind, x, w, wrong=()):
    """x + the layer's operator on RMSNorm(x; operator_norm)."""
    u = _rms(x, w["operator_norm.weight"], d["eps"])
    if kind == "conv":
        return x + _conv(d, u, w, wrong)
    return x + _attention(d, u, w, wrong)


def _swiglu(v, w1, w3, w2):
    import jax
    import jax.numpy as jnp
    w1, w3, w2 = (m.astype(jnp.float32) for m in (w1, w3, w2))
    return (jax.nn.silu(v @ w1.T) * (v @ w3.T)) @ w2.T


def _route(d, v, gate_w, bias, wrong=()):
    """The gates [B, L, E] over ALL experts: the score where the expert
    is among the token's k largest of score + bias, else 0; divided by
    (the k's sum + 1e-6) under ``norm_topk_prob``; scaled."""
    import jax
    import jax.numpy as jnp
    logits = v @ gate_w.astype(jnp.float32).T
    s = jax.nn.softmax(logits, axis=-1) if "softmax_router" in wrong \
        else jax.nn.sigmoid(logits)
    choice = s if bias is None else s + bias.astype(jnp.float32)
    kth = jax.lax.top_k(choice, d["top_k"])[0][..., -1:]
    gates = jnp.where(choice >= kth, s, 0.0)
    if d["renorm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    return gates * d["scaling"]


def _blocks(hf, get, ids, wrong=()):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per sparse layer its gates [B, L, E]). Layer by layer and expert by
    expert, each cast up on the way in."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    operator = {t: jax.jit(lambda x, w, t=t: _operator(d, t, x, w, wrong))
                for t in set(d["types"])}
    ffn_in = jax.jit(lambda x, w: _rms(x, w, d["eps"]))
    swiglu = jax.jit(_swiglu)
    route = jax.jit(lambda v, g, b: _route(d, v, g, b, wrong))
    expert = jax.jit(lambda v, g, w1, w3, w2: g * _swiglu(v, w1, w3, w2))
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    for i, t in enumerate(d["types"]):
        pre = _PRE.format(i)
        names = ["operator_norm.weight"] + (
            [f"conv.{n}.weight" for n in ("in_proj", "conv", "out_proj")]
            if t == "conv" else
            [f"self_attn.{n}.weight" for n in (
                "q_proj", "k_proj", "v_proj", "out_proj", "q_layernorm",
                "k_layernorm")])
        x = operator[t](x, {n: get(pre + n) for n in names})
        v = ffn_in(x, get(pre + "ffn_norm.weight"))
        ffn = pre + "feed_forward."
        if not _sparse(d, i):
            x = x + swiglu(v, *(get(f"{ffn}{m}.weight") for m in _FFN))
            continue
        gates = route(v, get(ffn + "gate.weight"),
                      get(ffn + "expert_bias") if d["bias"] else None)
        for e in d["held"]:  # what the absent experts add is left out
            x = x + expert(v, gates[..., e:e + 1], *(
                get(f"{ffn}experts.{e}.{m}.weight") for m in _FFN))
        routed.append(gates)
    return x, routed


def _getter(tensors, cast):
    import jax.numpy as jnp

    def get(name):
        x = jnp.asarray(tensors[name])
        return x if cast is None or x.ndim < 2 else cast(x)
    return get


def _head_weight(hf, get):
    return get("model.embed_tokens.weight"
               if hf.get("tie_word_embeddings", True) else "lm_head.weight")


def _final(hf, x, get):
    import jax.numpy as jnp
    x = _rms(x, get("model.embedding_norm.weight"), dims(hf)["eps"])
    return x @ _head_weight(hf, get).astype(jnp.float32).T


def logits(hf, tensors, ids, cast=None, wrong=()):
    """Float32 logits [B, L, V] of the full forward: what prefill and
    decoding through both caches must agree with."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids, wrong)
        return np.asarray(_final(hf, x, get), np.float32)


def _token_logprobs(logits_, ids):
    import jax
    import jax.numpy as jnp
    lp = jax.nn.log_softmax(logits_, axis=-1)
    return jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None], -1)[..., 0]


def logprobs(hf, tensors, ids, cast=None, wrong=()):
    """log p(ids[:, t+1] | ids[:, :t+1]) as float32 [B, L-1].

    ``tensors`` maps HF names to arrays (bf16 as written). ``cast``
    rounds every matrix on the way and ``wrong`` names equations to get
    wrong (``WRONG``): both only to size TOLERANCE."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids, wrong)
        out = jax.jit(lambda x: _token_logprobs(_final(hf, x, get), ids))(x)
    return np.asarray(out, np.float32)


def top_k_sets(hf, tensors, ids, layer):
    """Which of ALL the experts the reference routes every token of
    ``ids`` to in sparse ``layer`` (its index in the model): bool
    [B, L, E]."""
    import jax
    import jax.numpy as jnp
    d = dims(hf)
    hf1 = dict(hf, num_hidden_layers=layer + 1,
               layer_types=d["types"][:layer + 1])
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
    before). The family has no auxiliary term (its balance is the
    bias's, which the recipe moves and the config does not). Returns
    (loss, dict(nll=, aux=)). A function of ``tensors`` that
    ``jax.grad`` differentiates; nothing reaches ``expert_bias``."""
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
