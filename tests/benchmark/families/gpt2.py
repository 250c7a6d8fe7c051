"""A family that lives wholly in the tests' directory, of another
architecture than the benchmark's own: GPT-2 (learned positions,
LayerNorm with bias, fused q/k/v stored as Conv1D, GELU, tied head),
which the program loads as its ``gpt2`` family. The harness finds it by
the ``family`` of ``configs/tiny-gpt2.json``, as it would find one a
later PR adds: shapes for the weights, the plain float32 reference
with its tolerance, and the arithmetic. Follows Hugging Face's
``GPT2LMHeadModel``."""

import numpy as np

#: as ``llama_like.TOLERANCE``; never sized on a chip (tests only)
TOLERANCE = 0.03


def dims(hf):
    h = hf["n_embd"]
    return dict(layers=hf["n_layer"], hidden=h, heads=hf["n_head"],
                inner=hf.get("n_inner") or 4 * h, vocab=hf["vocab_size"],
                positions=hf["n_positions"])


def shapes(hf):
    d = dims(hf)
    n, h, inner = d["layers"], d["hidden"], d["inner"]
    pre = "transformer.h.{}."
    out = {"transformer.wte.weight": ((d["vocab"], h), "matrix"),
           "transformer.wpe.weight": ((d["positions"], h), "matrix"),
           "transformer.ln_f.weight": ((h,), "norm"),
           "transformer.ln_f.bias": ((h,), "bias")}
    for ln in ("ln_1", "ln_2"):
        out[pre + ln + ".weight"] = ((n, h), "norm")
        out[pre + ln + ".bias"] = ((n, h), "bias")
    # Conv1D keeps its weights (in, out)
    for name, (i, o) in {"attn.c_attn": (h, 3 * h), "attn.c_proj": (h, h),
                         "mlp.c_fc": (h, inner),
                         "mlp.c_proj": (inner, h)}.items():
        out[pre + name + ".weight"] = ((n, i, o), "matrix")
        out[pre + name + ".bias"] = ((n, o), "bias")
    return out


def n_params(hf):
    return sum(int(np.prod(shape)) for shape, _ in shapes(hf).values())


def forward_flops(hf, seqlens):
    """Every matrix multiplication at 2 FLOPs a multiply-add, causal
    attention at half of the full square, the head on every token."""
    d = dims(hf)
    tokens, sum_sq = sum(seqlens), sum(n * n for n in seqlens)
    h = d["hidden"]
    layer = 2 * tokens * h * (3 * h + h + 2 * d["inner"]) + 2 * sum_sq * h
    return d["layers"] * layer + 2 * tokens * h * d["vocab"]


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    d = dims(hf)
    kv_per_token = 2 * d["layers"] * d["hidden"] * bytes_per_el
    return new_tokens * replicas * n_params(hf) * bytes_per_el \
        + sum(n_seqs * (prompt_len + t) for t in range(new_tokens)) \
        * kv_per_token


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def logprobs(hf, tensors, ids, cast=None):
    """log p(ids[:, t+1] | ids[:, :t+1]) as float32 [B, L-1]."""
    import jax
    import jax.numpy as jnp

    d = dims(hf)
    eps = hf.get("layer_norm_epsilon", 1e-5)
    heads, hd = d["heads"], d["hidden"] // d["heads"]

    def get(name):
        x = jnp.asarray(tensors[name])
        x = x if cast is None or x.ndim < 2 else cast(x)
        return x.astype(jnp.float32)

    ids = jnp.asarray(ids, jnp.int32)
    b, n = ids.shape
    causal = jnp.tril(jnp.ones((n, n), bool))
    with jax.default_matmul_precision("highest"):
        wte = get("transformer.wte.weight")
        x = wte[ids] + get("transformer.wpe.weight")[:n][None]
        for i in range(d["layers"]):
            def w(name):
                return get(f"transformer.h.{i}.{name}")
            h = _layer_norm(x, w("ln_1.weight"), w("ln_1.bias"), eps)
            qkv = h @ w("attn.c_attn.weight") + w("attn.c_attn.bias")
            q, k, v = (t.reshape(b, n, heads, hd)
                       for t in jnp.split(qkv, 3, axis=-1))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            a = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf),
                               axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, n, -1)
            x = x + o @ w("attn.c_proj.weight") + w("attn.c_proj.bias")
            h = _layer_norm(x, w("ln_2.weight"), w("ln_2.bias"), eps)
            up = jax.nn.gelu(h @ w("mlp.c_fc.weight") + w("mlp.c_fc.bias"),
                             approximate=True)  # gelu_new
            x = x + up @ w("mlp.c_proj.weight") + w("mlp.c_proj.bias")
        x = _layer_norm(x, get("transformer.ln_f.weight"),
                        get("transformer.ln_f.bias"), eps)
        lp = jax.nn.log_softmax(x @ wte.T, axis=-1)
        out = jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None], -1)[..., 0]
    return np.asarray(out, np.float32)
