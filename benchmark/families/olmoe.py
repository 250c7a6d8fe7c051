"""Everything the benchmark knows about the ``olmoe`` architecture
(OLMoE-1B-7B): the checkpoint's tensors, the plain float32 reference
(forward, training loss and its gradient) with its tolerance, and what
a step needs in parameters, FLOPs, bytes and routed pairs, all from
the PUBLISHED configuration dict and the checkpoint's tensors and
nothing of the program's.

The block, as ``transformers``' ``modeling_olmoe.py`` computes it
(written from memory of it, no network here; then held to the file
itself, which the ``transformers`` installed here carries:
``tests/model/test_olmoe.py::test_reference_matches_transformers``,
equal logits to 3e-7 on the CPU)::

    h = x + Wo . Attn(q, k, v)                  pre-norm, RMSNorm, no bias
    q = RMSNorm(RMSNorm(x) Wq ; q_norm.weight)  over the WHOLE projected
    k = RMSNorm(RMSNorm(x) Wk ; k_norm.weight)  width (heads x head_dim),
                                                before the split into heads
                                                and before the rotary
                                                embedding (rotate-half)
    y = h + MoE(RMSNorm(h))
    MoE(u) = sum over the top-k experts e of  p_e . down_e(silu(gate_e u) * up_e u)
    p = softmax(u . W_gate) over ALL experts, in float32

With ``norm_topk_prob: false`` (as published) the k largest p are taken
AS THEY ARE, not divided by their sum: the layer's output is scaled by
a gate mass under 1 that differs by token. The reference has no sort,
no ragged product, no cache, no packing: it loops over the experts and
adds each one's output for every token, weighted by a gate that is 0
where the expert is not among the token's k. Weights are the
checkpoint's values cast up exactly; every product is taken at
``default_matmul_precision("highest")``.

Departures from ``modeling_olmoe.py``:

- ``clip_qkv`` is refused unless null (null as published);
- ``OlmoeRMSNorm`` casts the normalised value back to the input's
  dtype before it multiplies by the weight; here everything is
  float32, so there is no such rounding;
- the auxiliary loss of ``sft_loss`` is the PROGRAM's
  (``realhf_tpu/ops/moe.py:load_balancing_loss``): for every layer
  ``E . sum_e f_e P_e`` with ``f_e`` the share of the (token, k) pairs
  routed to expert e and ``P_e`` the mean router probability of e over
  the microbatch's tokens, SUMMED over layers, times
  ``router_aux_loss_coef``. ``transformers``'
  ``load_balancing_loss_func`` pools the tokens of all layers and
  counts each of a token's k choices as a whole token, which is
  ``num_experts_per_tok`` times this value for one layer and the mean,
  not the sum, over several.
"""

import numpy as np

from benchmark.families.llama_like import (  # noqa: F401
    _head,
    _rms,
    _rope,
    kv_bytes_per_token,
)

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there (0.90 nat at these widths, one layer).
#: Sized on the chip at published widths, one layer, by
#: ``scripts/chip_check_olmoe.py`` (my chip runs, PR 26), shares of
#: the spread at two seeds:
#:
#:   engine, bf16 (0.0074-0.0093 over 8 seeds)         0.0093 0.0083
#:   the same through prefill, then decode and cache    0.0093-0.0096
#:   this forward at default matmul precision           0.0068 0.0076
#:   EXPERT weights rounded to int8 by row              0.0038 0.0039
#:   expert weights rounded to float8 e4m3              0.0161 0.0160
#:   expert weights rounded to float8 e5m2              0.0263 0.0253
#:   every matrix rounded to int8 by row                0.0297 0.0310
#:   every matrix rounded to float8 e4m3                0.0985 0.0989
#:   every matrix rounded to float8 e5m2                0.1549 0.1494
#:   gates renormalised (norm_topk_prob: true)          0.3004 0.3065
#:
#: 0.015 is 1.6 times the most bf16 shows and under every lower
#: precision of the whole model and under float8 on the experts alone,
#: so a forward computed below bf16, or with renormalised gates, fails.
#: What it cannot tell from bf16 is int8 BY ROW ON THE EXPERTS ALONE:
#: that rounding (0.004) is under the engine's own bf16 noise, because
#: with one layer the experts' output is the smaller part of the
#: residual stream, scaled by a gate mass of 0.4, and a row of 2048
#: holds int8 well. 11 and 18 of the 1,024 tokens change their set of
#: 8 experts when the router's input is rounded to bf16 (the ninth
#: overtakes the eighth: their probabilities differ by 0.0014 in the
#: median); the mean absorbs them.
TOLERANCE = 0.015


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    if hf.get("clip_qkv") is not None:
        raise NotImplementedError("the reference has no clip_qkv")
    nq = hf["num_attention_heads"]
    return dict(
        layers=hf["num_hidden_layers"], hidden=hf["hidden_size"],
        nq=nq, nkv=hf.get("num_key_value_heads", nq),
        head=hf["hidden_size"] // nq,
        inter=hf["intermediate_size"], vocab=hf["vocab_size"],
        experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        tied=bool(hf.get("tie_word_embeddings", False)))


def n_params(hf):
    """Parameters of the causal LM: embedding, the head where it is
    not tied, the four attention projections and the two query/key
    norm scales, the router, every expert's three matrices, two norm
    scales a layer and the final norm."""
    d = dims(hf)
    q, kv = d["nq"] * d["head"], d["nkv"] * d["head"]
    attn = d["hidden"] * (q + 2 * kv) + q * d["hidden"] + q + kv
    moe = d["hidden"] * d["experts"] \
        + d["experts"] * 3 * d["hidden"] * d["inter"]
    layer = attn + moe + 2 * d["hidden"]
    embed = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)
    return d["layers"] * layer + embed + d["hidden"]


def routed_pairs(hf, seqlens):
    """(token, expert) pairs one forward routes: every token goes to
    ``num_experts_per_tok`` experts in every layer."""
    d = dims(hf)
    return sum(seqlens) * d["top_k"] * d["layers"]


def forward_flops(hf, seqlens):
    """FLOPs of one forward over packed sequences of these lengths:
    every matrix multiplication at 2 FLOPs a multiply-add, causal
    attention at half of the full square, the router on every token,
    ``num_experts_per_tok`` experts a token (NOT all ``num_experts``),
    the vocabulary head on every token. Norms, rotary, softmax, the
    sort and the scatter-add are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    sum_sq = sum(n * n for n in seqlens)
    qkv = 2 * tokens * d["hidden"] * (d["nq"] + 2 * d["nkv"]) * d["head"]
    attn_o = 2 * tokens * d["nq"] * d["head"] * d["hidden"]
    attn = 2 * sum_sq * d["nq"] * d["head"]  # QK^T and PV, causal half
    router = 2 * tokens * d["hidden"] * d["experts"]
    experts = 2 * tokens * d["top_k"] * d["hidden"] * d["inter"] * 3
    head = 2 * tokens * d["hidden"] * d["vocab"]
    return d["layers"] * (qkv + attn_o + attn + router + experts) + head


def head_share(hf, seqlens):
    """The vocabulary head's share of the forward FLOPs."""
    d = dims(hf)
    return 2 * sum(seqlens) * d["hidden"] * d["vocab"] \
        / forward_flops(hf, seqlens)


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of its weights once, EVERY expert's among them, and
    every live sequence reads its key/value prefix. A step touches an
    expert when one of its ``n_seqs x num_experts_per_tok`` pairs
    lands there, so all of them once ``n_seqs x 8`` is far above 64,
    as it is from 32 sequences on; below that this over-counts the
    experts. Prefill is left out."""
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    kv = sum(n_seqs * (prompt_len + t) for t in range(new_tokens)) \
        * kv_bytes_per_token(hf, bytes_per_el)
    return weights + kv


_EXPERT = "model.layers.{}.mlp.experts.%d.%s_proj.weight"


def shapes(hf):
    """HF name -> (shape, kind); a name with ``{}`` stands for every
    layer and its shape has a leading layer axis. ``kind`` is
    ``matrix`` or ``norm`` (``generate.make_weights``); the query/key
    norm scales are ``norm``, so the generator gives them random
    scales around 1."""
    d = dims(hf)
    n, h, f = d["layers"], d["hidden"], d["inter"]
    q, kv = d["nq"] * d["head"], d["nkv"] * d["head"]
    pre = "model.layers.{}."
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
        pre + "input_layernorm.weight": ((n, h), "norm"),
        pre + "post_attention_layernorm.weight": ((n, h), "norm"),
        pre + "self_attn.q_proj.weight": ((n, q, h), "matrix"),
        pre + "self_attn.k_proj.weight": ((n, kv, h), "matrix"),
        pre + "self_attn.v_proj.weight": ((n, kv, h), "matrix"),
        pre + "self_attn.o_proj.weight": ((n, h, q), "matrix"),
        pre + "self_attn.q_norm.weight": ((n, q), "norm"),
        pre + "self_attn.k_norm.weight": ((n, kv), "norm"),
        pre + "mlp.gate.weight": ((n, d["experts"], h), "matrix"),
    }
    for e in range(d["experts"]):
        out[_EXPERT % (e, "gate")] = ((n, f, h), "matrix")
        out[_EXPERT % (e, "up")] = ((n, f, h), "matrix")
        out[_EXPERT % (e, "down")] = ((n, h, f), "matrix")
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


# ----------------------------------------------------------------------
# The plain float32 forward
# ----------------------------------------------------------------------
def _attention(hf, x, w):
    """x [B, L, H] -> x + Wo . Attn: the query and key norms run over
    the whole projected width, before the heads and the rotation."""
    import jax
    import jax.numpy as jnp
    d = dims(hf)
    nq, nkv, hd = d["nq"], d["nkv"], d["head"]
    eps, theta = hf["rms_norm_eps"], hf.get("rope_theta", 10000.0)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, n, _ = x.shape

    h = _rms(x, w["input_layernorm.weight"], eps)
    q = _rms(h @ w["self_attn.q_proj.weight"].T,
             w["self_attn.q_norm.weight"], eps)
    k = _rms(h @ w["self_attn.k_proj.weight"].T,
             w["self_attn.k_norm.weight"], eps)
    v = h @ w["self_attn.v_proj.weight"].T
    q = _rope(q.reshape(b, n, nq, hd), theta)
    k = _rope(k.reshape(b, n, nkv, hd), theta)
    v = v.reshape(b, n, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, n, nq * hd)
    return x + o @ w["self_attn.o_proj.weight"].T


def _route(hf, x, norm_w, gate_w):
    """The MoE's input u = RMSNorm(x), the router's softmax over all
    experts p [B, L, E], and the gates [B, L, E]: p where the expert
    is among the token's k largest, else 0; divided by the k's sum
    only where the published dict says ``norm_topk_prob``."""
    import jax
    import jax.numpy as jnp
    u = _rms(x, norm_w.astype(jnp.float32), hf["rms_norm_eps"])
    p = jax.nn.softmax(u @ gate_w.astype(jnp.float32).T, axis=-1)
    kth = jax.lax.top_k(p, hf["num_experts_per_tok"])[0][..., -1:]
    gates = jnp.where(p >= kth, p, 0.0)
    if hf.get("norm_topk_prob", False):
        gates = gates / gates.sum(-1, keepdims=True)
    return u, p, gates


def _expert(u, gate_e, wg, wu, wd):
    """One expert over EVERY token, weighted by its gate [B, L, 1]."""
    import jax
    import jax.numpy as jnp
    wg, wu, wd = (m.astype(jnp.float32) for m in (wg, wu, wd))
    return gate_e * ((jax.nn.silu(u @ wg.T) * (u @ wu.T)) @ wd.T)


def _blocks(hf, get, ids):
    """Embedding and every layer: (x [B, L, H] before the final norm,
    per layer (router probabilities p, gates) each [B, L, E]). Layer by
    layer and expert by expert, each cast up on the way in, so the
    device holds one expert in float32 and never the model."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    attention = jax.jit(lambda x, w: _attention(hf, x, w))
    route = jax.jit(lambda x, n, g: _route(hf, x, n, g))
    expert = jax.jit(_expert)
    x = get("model.embed_tokens.weight")[ids].astype(jnp.float32)
    routed = []
    for i in range(d["layers"]):
        pre = f"model.layers.{i}."
        names = ["input_layernorm.weight"] + [
            f"self_attn.{n}.weight" for n in (
                "q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                "k_norm")]
        x = attention(x, {n: get(pre + n) for n in names})
        u, p, gates = route(x, get(pre + "post_attention_layernorm.weight"),
                            get(pre + "mlp.gate.weight"))
        for e in range(d["experts"]):
            x = x + expert(u, gates[..., e:e + 1],
                           get((_EXPERT % (e, "gate")).format(i)),
                           get((_EXPERT % (e, "up")).format(i)),
                           get((_EXPERT % (e, "down")).format(i)))
        routed.append((p, gates))
    return x, routed


def _getter(tensors, cast):
    import jax.numpy as jnp

    def get(name):
        x = jnp.asarray(tensors[name])
        return x if cast is None or x.ndim < 2 else cast(x)
    return get


def _head_weight(hf, get):
    return get("model.embed_tokens.weight"
               if hf.get("tie_word_embeddings") else "lm_head.weight")


def logits(hf, tensors, ids, cast=None):
    """Float32 logits [B, L, V] of the full forward: what prefill and
    decoding through a cache must agree with."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids)
        x = _rms(x, get("model.norm.weight").astype(jnp.float32),
                 hf["rms_norm_eps"])
        return np.asarray(
            x @ _head_weight(hf, get).astype(jnp.float32).T, np.float32)


def logprobs(hf, tensors, ids, cast=None):
    """log p(ids[:, t+1] | ids[:, :t+1]) as float32 [B, L-1].

    ``tensors`` maps HF names to arrays (bf16 as written). ``cast``
    rounds every matrix on the way (used once, to size TOLERANCE
    against a lower precision)."""
    import jax
    import jax.numpy as jnp

    get = _getter(tensors, cast)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, ids)
        out = jax.jit(lambda x, n, h, i: _head(hf, x, n, h, i))(
            x, get("model.norm.weight"), _head_weight(hf, get), ids)
    return np.asarray(out, np.float32)


def top_k_sets(hf, tensors, ids, layer=0):
    """Which experts the reference routes every token of ``ids`` to in
    ``layer``: bool [B, L, E]; and the probabilities p [B, L, E]."""
    import jax
    import jax.numpy as jnp
    hf1 = dict(hf, num_hidden_layers=layer + 1)
    with jax.default_matmul_precision("highest"):
        _, routed = _blocks(hf1, _getter(tensors, None),
                            jnp.asarray(ids, jnp.int32))
    p, gates = routed[layer]
    return np.asarray(gates > 0), np.asarray(p)


# ----------------------------------------------------------------------
# The training loss and its gradient
# ----------------------------------------------------------------------
def sft_loss(hf, tensors, ids, prompt_len):
    """The SFT loss of ONE microbatch whose documents are the rows of
    ``ids`` [n, L] (equal lengths, the first ``prompt_len`` tokens of
    each the prompt): the mean over the answer tokens of -log p(token |
    before), plus the auxiliary term the program's SFT loss adds for
    this family (the module docstring has its formula and how it
    departs from ``transformers``'). Returns (loss, dict(nll=, aux=)).
    A function of ``tensors`` that ``jax.grad`` differentiates."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    get = _getter(tensors, None)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, routed = _blocks(hf, get, ids)
        lp = _head(hf, x, get("model.norm.weight"), _head_weight(hf, get),
                   ids)
        # lp[:, t] scores token t+1: answer tokens are t+1 >= prompt_len
        answer = jnp.arange(1, ids.shape[1]) >= prompt_len
        nll = -(lp * answer).sum() / (answer.sum() * ids.shape[0])
        aux = 0.0
        for p, gates in routed:
            share = (gates > 0).reshape(-1, d["experts"]).mean(0) \
                / d["top_k"]
            prob = p.reshape(-1, d["experts"]).mean(0)
            aux = aux + d["experts"] * (
                jax.lax.stop_gradient(share) * prob).sum()
        aux = hf.get("router_aux_loss_coef", 0.01) * aux
    return nll + aux, dict(nll=nll, aux=aux)


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
