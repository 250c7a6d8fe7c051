"""Everything the benchmark knows about one architecture: the
llama-like causal LM (RMSNorm, rotary embedding, grouped-query
attention, SwiGLU, tied or untied head, optional q/k/v biases), which
``qwen2.py`` and ``mistral.py`` beside this file name as theirs.

A family is found by the ``family`` of a configuration file, as
``families/<family>.py`` under any directory of ``paths``, and gives:

- ``shapes(hf)``: every tensor of a checkpoint under Hugging Face's
  names, for ``generate.make_weights``;
- ``logprobs(hf, tensors, ids)`` and ``TOLERANCE``: the plain float32
  reference and how far from it the engine may be;
- ``n_params``, ``forward_flops``, ``decode_bytes``: what a step needs,
  from the PUBLISHED configuration dict and nothing of the program's.

The reference has no kernels, no cache, no packing, no sharding: it
reads the published config dict and the HF-named tensors the benchmark
wrote, so it also holds the program's loader to account. It follows
the Hugging Face implementations of Qwen2 and Mistral (RMSNorm in
float32, rotary embedding in the rotate-half convention, a causal
mask). Departures: none known; a sliding window is not implemented and
is refused (both configurations run without one). The weights are the
checkpoint's bf16 values cast up exactly; every product is taken at
``default_matmul_precision("highest")``, without which a TPU multiplies
float32 in bf16 passes.

The FLOP and byte formulas were copied from
``realhf_tpu/base/monitor.py`` (``transformer_forward_flops``) and
``bench.py`` (``_decode_roofline_s``); PERF.md lists the originals for
a later PR to delete.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward and
#: this float32 one on the fixed batch (4 x 256 tokens), as a share of
#: the spread (standard deviation) of the reference's own
#: log-probabilities there: logits, and with them every rounding
#: error, scale with the hidden size (spread 0.6 nat at Qwen2.5-0.5B,
#: 1.3 at Mistral's widths). Sized on the chip at published widths (my
#: chip runs, PR 23), shares of the spread:
#:
#:                                   Qwen2.5-0.5B   Mistral-7B, 4 layers
#:   engine, bf16 (every run read)   0.012-0.013    0.016-0.017 (d2t2, d4t1)
#:   this forward, default precision 0.0072         0.0122
#:   weights rounded to int8 by row  0.049          0.062
#:   weights rounded to float8 e4m3  0.195          0.222
#:   weights rounded to float8 e5m2  0.316          0.346
#:
#: 0.03 is 1.7 to 2.4 times what bf16 shows and under every lower
#: precision tried, so a forward computed below bf16 fails.
TOLERANCE = 0.03


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    nq = hf["num_attention_heads"]
    return dict(
        layers=hf["num_hidden_layers"], hidden=hf["hidden_size"],
        nq=nq, nkv=hf.get("num_key_value_heads", nq),
        head=hf.get("head_dim") or hf["hidden_size"] // nq,
        inter=hf["intermediate_size"], vocab=hf["vocab_size"],
        tied=bool(hf.get("tie_word_embeddings", False)),
        qkv_bias=bool(hf.get("attention_bias",
                             hf.get("model_type") == "qwen2")))


def n_params(hf):
    """Parameters of the causal LM: embedding, the head where it is
    not tied, the four attention projections (and the q/k/v biases
    where the family has them), the gated MLP's three matrices, two
    norm scales a layer and the final norm."""
    d = dims(hf)
    qkv_out = (d["nq"] + 2 * d["nkv"]) * d["head"]
    attn = d["hidden"] * qkv_out + d["nq"] * d["head"] * d["hidden"]
    if d["qkv_bias"]:
        attn += qkv_out
    mlp = 3 * d["hidden"] * d["inter"]
    layer = attn + mlp + 2 * d["hidden"]
    embed = d["vocab"] * d["hidden"] * (1 if d["tied"] else 2)
    return d["layers"] * layer + embed + d["hidden"]


def forward_flops(hf, seqlens):
    """FLOPs of one forward over packed sequences of these lengths:
    every matrix multiplication at 2 FLOPs a multiply-add, causal
    attention at half of the full square, the vocabulary head on every
    token. Norms, rotary, softmax and activations are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    sum_sq = sum(n * n for n in seqlens)
    qkv = 2 * tokens * d["hidden"] * (d["nq"] + 2 * d["nkv"]) * d["head"]
    attn_o = 2 * tokens * d["nq"] * d["head"] * d["hidden"]
    attn = 2 * sum_sq * d["nq"] * d["head"]  # QK^T and PV, causal half
    mlp = 2 * tokens * d["hidden"] * d["inter"] * 3
    head = 2 * tokens * d["hidden"] * d["vocab"]
    return d["layers"] * (qkv + attn_o + attn + mlp) + head


def head_share(hf, seqlens):
    """The vocabulary head's share of the forward FLOPs."""
    d = dims(hf)
    return 2 * sum(seqlens) * d["hidden"] * d["vocab"] \
        / forward_flops(hf, seqlens)


def kv_bytes_per_token(hf, bytes_per_el=2):
    d = dims(hf)
    return 2 * d["layers"] * d["nkv"] * d["head"] * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    the whole of its weights once, and every live sequence reads its
    key/value prefix. Prefill is left out."""
    weights = new_tokens * replicas * n_params(hf) * bytes_per_el
    kv = sum(n_seqs * (prompt_len + t) for t in range(new_tokens)) \
        * kv_bytes_per_token(hf, bytes_per_el)
    return weights + kv


def shapes(hf):
    """HF name -> (shape, kind); a name with ``{}`` stands for every
    layer and its shape has a leading layer axis. ``kind`` is
    ``matrix``, ``bias`` or ``norm`` (``generate.make_weights``)."""
    d = dims(hf)
    n, h = d["layers"], d["hidden"]
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
        pre + "mlp.gate_proj.weight": ((n, d["inter"], h), "matrix"),
        pre + "mlp.up_proj.weight": ((n, d["inter"], h), "matrix"),
        pre + "mlp.down_proj.weight": ((n, h, d["inter"]), "matrix"),
    }
    if d["qkv_bias"]:
        out[pre + "self_attn.q_proj.bias"] = ((n, q), "bias")
        out[pre + "self_attn.k_proj.bias"] = ((n, kv), "bias")
        out[pre + "self_attn.v_proj.bias"] = ((n, kv), "bias")
    if not d["tied"]:
        out["lm_head.weight"] = ((d["vocab"], h), "matrix")
    return out


def _rms(x, w, eps):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * w


def _rope(x, theta):
    """x [B, L, H, D] -> rotated, rotate-half convention."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _layer(hf, x, w):
    import jax
    import jax.numpy as jnp
    nq = hf["num_attention_heads"]
    nkv = hf.get("num_key_value_heads", nq)
    hd = hf.get("head_dim") or hf["hidden_size"] // nq
    eps, theta = hf["rms_norm_eps"], hf.get("rope_theta", 10000.0)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, n, _ = x.shape

    h = _rms(x, w["input_layernorm.weight"], eps)

    def proj(name):
        y = h @ w[f"self_attn.{name}_proj.weight"].T
        bias = w.get(f"self_attn.{name}_proj.bias")
        return y if bias is None else y + bias

    q = _rope(proj("q").reshape(b, n, nq, hd), theta)
    k = _rope(proj("k").reshape(b, n, nkv, hd), theta)
    v = proj("v").reshape(b, n, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, n, nq * hd)
    x = x + o @ w["self_attn.o_proj.weight"].T

    h = _rms(x, w["post_attention_layernorm.weight"], eps)
    gate = jax.nn.silu(h @ w["mlp.gate_proj.weight"].T)
    up = h @ w["mlp.up_proj.weight"].T
    return x + (gate * up) @ w["mlp.down_proj.weight"].T


def _head(hf, x, norm_w, head_w, ids):
    import jax
    import jax.numpy as jnp
    x = _rms(x, norm_w.astype(jnp.float32), hf["rms_norm_eps"])
    logits = x @ head_w.astype(jnp.float32).T
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None], -1)[..., 0]


def logprobs(hf, tensors, ids, cast=None):
    """log p(ids[:, t+1] | ids[:, :t+1]) as float32 [B, L-1].

    ``tensors`` maps HF names to arrays (bf16 as written). The layers
    run one at a time, each cast up on the way in, so the device holds
    one layer in float32 and never the model. ``cast`` rounds every
    matrix on the way (used once, to size TOLERANCE against a lower
    precision)."""
    import jax
    import jax.numpy as jnp

    if hf.get("sliding_window") and hf.get("use_sliding_window", True):
        raise NotImplementedError("the reference has no sliding window")

    def get(name):
        x = jnp.asarray(tensors[name])
        return x if cast is None or x.ndim < 2 else cast(x)

    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, w: _layer(hf, x, w))
        head = jax.jit(lambda x, n, h, i: _head(hf, x, n, h, i))
        embed = get("model.embed_tokens.weight")
        x = embed[ids].astype(jnp.float32)
        for i in range(hf["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            w = {k[len(pre):]: get(k) for k in tensors
                 if k.startswith(pre)}
            x = layer(x, w)
        head_w = embed if hf.get("tie_word_embeddings") \
            else get("lm_head.weight")
        out = head(x, get("model.norm.weight"), head_w, ids)
    return np.asarray(out, np.float32)
