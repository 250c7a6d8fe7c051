"""Everything the benchmark knows about the ``ouro`` architecture
(ByteDance's Ouro-2.6B, a LOOPED language model; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): the
checkpoint's tensors, the plain float32 reference (every pass's hidden
state and exit gate, pass T's log-probabilities, the training objective
and its gradient) with its tolerance, and what a step needs in
parameters, FLOPs and decode bytes, all from the PUBLISHED
configuration dict and the checkpoint's tensors and nothing of the
program's.

The model. N = ``num_hidden_layers`` llama-like layers (RMSNorm at
``rms_norm_eps``, ``num_attention_heads`` = ``num_key_value_heads``
heads of ``head_dim``, the rotate-half rotary embedding at
``rope_theta`` over the whole head, no bias, no window, SwiGLU of
``intermediate_size``, an untied head) that run T = ``total_ut_steps``
times over ONE set of weights. With x^0 the embedding rows of the ids,
for pass t = 1..T, h <- x^(t-1), and for layer l = 1..N with the same
weights in every pass::

    a = h + RMS(Attn_l(RMS(h; input_layernorm_l)); input_layernorm_2_l)
    h = a + RMS(MLP_l(RMS(a; post_attention_layernorm_l));
                post_attention_layernorm_2_l)

(a norm before AND after each operator, the second inside the
residual's add), then::

    x^t      = RMS(h; model.norm)        the final norm after EVERY pass;
                                         pass t+1 starts from x^t
    logits^t = lm_head x^t
    lambda_t = sigmoid(w_g . x^t + b_g)  model.early_exit_gate,
                                         Linear(hidden, 1) WITH a bias

``Attn`` is causal multi-head attention, the same positions in every
pass; ``MLP`` is ``down(silu(gate u) * up u)``. The exit distribution a
token: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < T and
``p_T = prod_{j<T} (1 - lambda_j)`` (the last pass takes what is left).
Training (the paper's first-stage objective), an answer token i with
next-token loss ``nll_{t,i}`` from ``logits^t``::

    l_i = sum_t p_{t,i} nll_{t,i} - beta H(p_{.,i}),  H(p) = -sum p log p

averaged over the answer tokens (``objective``; ``beta`` 0.05, listed
under ``assumed``). Inference and generation read ``logits^T``: at the
published ``early_exit_threshold`` of 1 no token leaves before pass T
(a threshold under 1 is refused). Every pass has keys and values of
its own, because its input differs.

What the catalog row's config does not state is listed in the
configuration file under ``assumed`` (the four norms' names and order,
that ``model.norm``'s output is what the next pass starts from, the
gate's bias, beta). The reference is four WRITTEN-OUT passes: a Python
loop over passes around a Python loop over layers, one jitted layer at
a time with its weights cast up on the way in (the device holds one
layer in float32, never the model), attention's scores a block of
``QUERY_BLOCK`` query rows at a time so that a 4096-token row fits; no
kernels, no cache, no packing (a row is one document), no scan.
Weights are the checkpoint's values cast up exactly; every product is
taken at ``default_matmul_precision("highest")``.
"""

import numpy as np

#: Allowed mean |delta log-prob| between the engine's bf16 forward
#: (pass T) and this float32 one on the fixed batch (4 x 256 tokens), as
#: a share of the spread (standard deviation) of the reference's own
#: log-probabilities there (0.885 to 0.891 nat at the cell's widths).
#: Sized on the chip at those widths (6 layers, T = 4, vocabulary
#: 49,152) by ``scripts/chip_check.py ouro`` and the cell's own runs (my
#: chip runs, PR 53), shares of the spread; in brackets on ONE document
#: of 4,096 tokens, reference against reference:
#:
#:   engine, bf16, the fixed batch (8 seeds)            0.0148-0.0194
#:   ONE packed row of 4096: documents of 1536 .. 512   0.0117-0.0141
#:   prefill of 640, then 127 decode steps, rows 768    0.0144 (decoded 0.0123)
#:   this forward at default matmul precision           0.0105, 0.0108
#:   every matrix rounded to int8 by row                0.0758, 0.0796
#:   every matrix rounded to float8 e4m3                0.301, 0.305
#:   every matrix rounded to float8 e5m2                0.422, 0.461
#:   WRONG: the final norm not fed back                 0.959, 1.003 (1.029)
#:   WRONG: no post-operator norms                      0.673, 0.683 (0.866)
#:   WRONG: one cache for all passes                    1.043, 1.109 (1.105)
#:   WRONG: pass T's loss alone                         0 (the forward is right)
#:   engine, FLOAT32 at highest precision, 4096 tokens  (0.0000012)
#:
#: 0.04 lies between the two readings that bound it, with a factor of
#: two on either side: 2.1 times the most bf16 shows over eight seeds
#: (fresh seeds read higher: 0.0148 to 0.0194 here) and 0.53 of int8 on
#: the whole model, the nearest precision below bf16 tried: a forward
#: computed below bf16 fails, as does each of the
#: three wrong forwards by seventeen tolerances and more. WHAT THE CELL'S
#: OWN ``correct`` CANNOT TELL: the fourth wrong entry and everything
#: else about the objective and its gradient (``correct`` compares pass
#: T's forward and no gradient). Those are held by float32: the tests on
#: the CPU (``tests/model/test_ouro.py``) and ``chip_check.py``'s row
#: ``objective`` at published widths on 512 tokens, which read the
#: float32 engine's loss 0.00004 from ``jax.grad`` of this reference
#: (11.19245 against 11.19249), its gradients 0.00022 to 0.00029 of a
#: tensor's norm away on the shared matrices and norms and 0.0007 on the
#: gate, and the BF16 engine's gradients 0.019 to 0.029 away on the
#: shared matrices (the sum of four passes' gradients in bf16), 0.013 on
#: the gate and the head; pass T's loss alone reads 11.2149 there.
TOLERANCE = 0.04

_PRE = "model.layers.{}."
_ATTN = ("q_proj", "k_proj", "v_proj", "o_proj")
_MLP = ("gate_proj", "up_proj", "down_proj")
#: a layer's four norms in the order they are applied
_NORMS = ("input_layernorm", "input_layernorm_2",
          "post_attention_layernorm", "post_attention_layernorm_2")
_GATE = "model.early_exit_gate."
#: rows of queries whose scores are held at once
QUERY_BLOCK = 512
#: the entropy term's weight (``assumed``: the paper's later-stage
#: value; the program's is ``realhf_tpu/models/hf/ouro.py:
#: ENTROPY_COEFF`` and the tests hold the two equal)
BETA = 0.05
#: published key -> the one value of it this reference computes
_ONLY = {"hidden_act": "silu", "rope_scaling": None,
         "sliding_window": None, "use_sliding_window": False,
         "tie_word_embeddings": False, "attention_bias": False}
#: deliberately WRONG equations, by name, that ``wrong=`` switches on:
#: each is a model that is easy to build by mistake, and the tests and
#: ``scripts/chip_check.py ouro`` hold that the comparison tells each
#: from the right one.
#: ``norm_not_fed_back``: the final norm feeds the head and the gate
#: alone and pass t+1 starts from the un-normed h;
#: ``no_post_norms``: the two norms after the operators left out;
#: ``one_cache_for_all_passes``: keys and values indexed by the layer
#: alone: what a layer wrote in its FIRST pass is what its later passes
#: attend to (they make none of their own);
#: ``last_pass_loss_alone``: the objective is pass T's loss (no exit
#: distribution, no entropy: the forward is right, so only the
#: objective and its gradients can tell).
WRONG = ("norm_not_fed_back", "no_post_norms", "one_cache_for_all_passes",
         "last_pass_loss_alone")


def dims(hf):
    """The sizes the formulas need, from a published config dict."""
    for key, only in _ONLY.items():
        if hf.get(key, only) != only:
            raise NotImplementedError(
                f"the reference computes {key}={only!r} only, not "
                f"{hf[key]!r}")
    if float(hf.get("early_exit_threshold", 1.0)) < 1.0:
        raise NotImplementedError(
            "the reference runs every pass for every token "
            f"(early_exit_threshold={hf['early_exit_threshold']})")
    nq = hf["num_attention_heads"]
    return dict(
        layers=hf["num_hidden_layers"], passes=hf["total_ut_steps"],
        hidden=hf["hidden_size"], nq=nq,
        nkv=hf.get("num_key_value_heads", nq),
        head=hf.get("head_dim") or hf["hidden_size"] // nq,
        inter=hf["intermediate_size"], vocab=hf["vocab_size"],
        eps=hf["rms_norm_eps"], theta=float(hf.get("rope_theta", 10000.0)))


def _layer_matrices(d):
    return d["hidden"] * (d["nq"] + 2 * d["nkv"]) * d["head"] \
        + d["nq"] * d["head"] * d["hidden"] + 3 * d["hidden"] * d["inter"]


def n_params(hf):
    """Parameters the checkpoint HOLDS, each once however often it is
    read (509,661,185 in the benchmark's cell): a layer's seven
    matrices and FOUR norms, embedding and head, the final norm, the
    gate's row and its bias."""
    d = dims(hf)
    return d["layers"] * (_layer_matrices(d) + 4 * d["hidden"]) \
        + 2 * d["vocab"] * d["hidden"] + d["hidden"] + d["hidden"] + 1


def forward_flops(hf, seqlens):
    """FLOPs of one forward of the OBJECTIVE over documents of these
    lengths, at 2 FLOPs a multiply-add: T x N layer applications (seven
    matrices and causal attention at half the square each), and T
    heads and T gates, one a pass, because the objective reads every
    pass's logits (inference reads pass T's alone: ``decode_bytes``).
    Norms, rotary, softmax and activations are left out."""
    d = dims(hf)
    tokens = sum(seqlens)
    pairs = sum(n * (n + 1) // 2 for n in seqlens)
    layer = 2 * tokens * _layer_matrices(d) \
        + 2 * pairs * d["nq"] * 2 * d["head"]
    head = 2 * tokens * d["hidden"] * (d["vocab"] + 1)
    return d["passes"] * (d["layers"] * layer + head)


def head_share(hf, seqlens):
    """The T vocabulary heads' share of the forward FLOPs (22% in the
    benchmark's six-layer cell at rows of 4096, 3.4% in the whole
    48-layer model)."""
    d = dims(hf)
    return d["passes"] * 2 * sum(seqlens) * d["hidden"] * d["vocab"] \
        / forward_flops(hf, seqlens)


def kv_bytes_per_token(hf, bytes_per_el=2):
    """What a token adds to the cache: keys and values of every layer
    ONCE A PASS (T x N layers' worth)."""
    d = dims(hf)
    return 2 * d["passes"] * d["layers"] * d["nkv"] * d["head"] \
        * bytes_per_el


def decode_bytes(hf, n_seqs, prompt_len, new_tokens, replicas=1,
                 bytes_per_el=2):
    """Bytes that decoding ``new_tokens`` tokens for ``n_seqs``
    sequences must stream from HBM: at every step each replica reads
    its layers' weights T times (once a pass), the head and the final
    norm once (pass T's logits alone are sampled from; the embedding's
    rows and the gate are not counted), and every live sequence reads
    its key/value prefix of all T x N cache layers. Prefill is left
    out."""
    d = dims(hf)
    layers = d["layers"] * (_layer_matrices(d) + 4 * d["hidden"])
    weights = d["passes"] * layers + d["vocab"] * d["hidden"] + d["hidden"]
    kv = sum(n_seqs * (prompt_len + t) for t in range(new_tokens)) \
        * kv_bytes_per_token(hf, bytes_per_el)
    return new_tokens * replicas * weights * bytes_per_el + kv


def shapes(hf):
    """HF name -> (shape, kind); a name with ``{}`` stands for every
    layer and its shape has a leading layer axis. ``kind`` is
    ``matrix``, ``bias`` or ``norm`` (``generate.make_weights``). The
    names are the published modelling code's AS REMEMBERED (the
    configuration file's ``assumed``)."""
    d = dims(hf)
    n, h = d["layers"], d["hidden"]
    q, kv = d["nq"] * d["head"], d["nkv"] * d["head"]
    out = {
        "model.embed_tokens.weight": ((d["vocab"], h), "matrix"),
        "model.norm.weight": ((h,), "norm"),
        "lm_head.weight": ((d["vocab"], h), "matrix"),
        _GATE + "weight": ((1, h), "matrix"),
        _GATE + "bias": ((1,), "bias"),
        _PRE + "self_attn.q_proj.weight": ((n, q, h), "matrix"),
        _PRE + "self_attn.k_proj.weight": ((n, kv, h), "matrix"),
        _PRE + "self_attn.v_proj.weight": ((n, kv, h), "matrix"),
        _PRE + "self_attn.o_proj.weight": ((n, h, q), "matrix"),
        _PRE + "mlp.gate_proj.weight": ((n, d["inter"], h), "matrix"),
        _PRE + "mlp.up_proj.weight": ((n, d["inter"], h), "matrix"),
        _PRE + "mlp.down_proj.weight": ((n, h, d["inter"]), "matrix"),
    }
    for name in _NORMS:
        out[_PRE + name + ".weight"] = ((n, h), "norm")
    return out


# ----------------------------------------------------------------------
# The forward: four written-out passes
# ----------------------------------------------------------------------
def _rms(x, w, eps):
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * w


def _rope(x, theta):
    """x [B, L, heads, D] -> rotated, rotate-half convention, position
    = the token's index in its row."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _keys_values(d, u, w):
    """Rotated keys and values [B, L, heads, D] of a layer's normed
    input u."""
    b, n, _ = u.shape
    k = _rope((u @ w["self_attn.k_proj.weight"].T).reshape(
        b, n, d["nkv"], d["head"]), d["theta"])
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(
        b, n, d["nkv"], d["head"])
    rep = d["nq"] // d["nkv"]
    import jax.numpy as jnp
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _attention(d, u, w, kv=None):
    """Causal attention of a layer on its normed input u [B, L, H] ->
    ([B, L, H] after ``o_proj``, (k, v)). ``kv``: keys and values to
    attend to INSTEAD of the layer's own (only the WRONG entry
    ``one_cache_for_all_passes`` passes them)."""
    import jax
    import jax.numpy as jnp
    b, n, _ = u.shape
    q = _rope((u @ w["self_attn.q_proj.weight"].T).reshape(
        b, n, d["nq"], d["head"]), d["theta"])
    own = _keys_values(d, u, w)
    k, v = own if kv is None else kv
    outs = []
    for start in range(0, n, QUERY_BLOCK):
        rows = jnp.arange(start, min(start + QUERY_BLOCK, n))
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, rows], k) \
            / np.sqrt(d["head"])
        causal = rows[:, None] >= jnp.arange(n)[None, :]
        a = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf),
                           axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", a, v))
    o = jnp.concatenate(outs, axis=1).reshape(b, n, d["nq"] * d["head"])
    return o @ w["self_attn.o_proj.weight"].T, own


def _layer(d, h, w, wrong=(), kv=None):
    """One layer application: (h after both residual adds, the keys
    and values it made)."""
    import jax
    import jax.numpy as jnp
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = d["eps"]

    def post(y, name):
        if "no_post_norms" in wrong:
            return y
        return _rms(y, w[name + ".weight"], eps)

    attn, own = _attention(
        d, _rms(h, w["input_layernorm.weight"], eps), w, kv)
    a = h + post(attn, "input_layernorm_2")
    u = _rms(a, w["post_attention_layernorm.weight"], eps)
    mlp = (jax.nn.silu(u @ w["mlp.gate_proj.weight"].T)
           * (u @ w["mlp.up_proj.weight"].T)) @ w["mlp.down_proj.weight"].T
    return a + post(mlp, "post_attention_layernorm_2"), own


def _getter(tensors, cast):
    import jax.numpy as jnp

    def get(name):
        x = jnp.asarray(tensors[name])
        # a matrix is what a product takes: norms and the bias are
        # never rounded (the gate's [1, H] row is a matrix)
        return x if cast is None or x.ndim != 2 else cast(x)
    return get


def _passes(hf, get, ids, wrong=()):
    """Every pass's final hidden state x^t and gate logit, float32:
    ([T, B, L, H], [T, B, L]). FOUR WRITTEN-OUT PASSES: a loop over the
    passes around a loop over the layers, the same tensors every
    pass."""
    import jax
    import jax.numpy as jnp
    d = dims(hf)
    layer = jax.jit(lambda h, w, kv=None: _layer(d, h, w, wrong, kv))
    f32 = jnp.float32
    names = [k for k in shapes(hf) if k.startswith(_PRE)]
    h = get("model.embed_tokens.weight")[ids].astype(f32)
    norm_w = get("model.norm.weight").astype(f32)
    gate_w = get(_GATE + "weight").astype(f32)
    gate_b = get(_GATE + "bias").astype(f32)
    first = {}  # layer -> the keys and values of its first pass
    xs, gates = [], []
    for t in range(d["passes"]):
        for i in range(d["layers"]):
            w = {k[len(_PRE):]: get(k.format(i)) for k in names}
            if "one_cache_for_all_passes" in wrong and t > 0:
                h, _ = layer(h, w, first[i])
            else:
                h, first_kv = layer(h, w)
                first.setdefault(i, first_kv)
        x = _rms(h, norm_w, d["eps"])
        xs.append(x)
        gates.append((x @ gate_w.T)[..., 0] + gate_b[0])
        if "norm_not_fed_back" not in wrong:
            h = x  # the NEXT pass starts from the final norm's output
    return jnp.stack(xs), jnp.stack(gates)


def passes(hf, tensors, ids, cast=None, wrong=()):
    """(every pass's final hidden state [T, B, L, H], every pass's
    exit-gate LOGIT [T, B, L]) as float32 numpy arrays, a row a
    document."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        xs, gates = _passes(hf, _getter(tensors, cast),
                            jnp.asarray(ids, jnp.int32), wrong)
    return np.asarray(xs, np.float32), np.asarray(gates, np.float32)


def _blocks(hf, get, ids, seg=None, wrong=()):
    """(pass T's final hidden state [B, L, H], every pass's (states,
    gate logits)): the signature ``scripts/chip_check.py`` reads every
    family's reference by. ``seg``: refused (a row is one document)."""
    if seg is not None:
        raise NotImplementedError("the reference takes a document a row")
    xs, gates = _passes(hf, get, ids, wrong)
    return xs[-1], (xs, gates)


def _final(hf, x, get):
    """Logits of a pass's final hidden state: the head alone, for the
    final norm ran inside the loop."""
    import jax.numpy as jnp
    return x @ get("lm_head.weight").astype(jnp.float32).T


def _token_logprobs(logits_, ids):
    """log p(ids[:, t+1] | .) [B, L-1] from logits."""
    import jax
    import jax.numpy as jnp
    lp = jax.nn.log_softmax(logits_, axis=-1)
    return jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None], -1)[..., 0]


def logits(hf, tensors, ids, cast=None, wrong=()):
    """Float32 logits [B, L, V] of pass T of the full forward: what
    prefill and decoding through the T x N-deep cache must agree
    with."""
    import jax
    import jax.numpy as jnp
    get = _getter(tensors, cast)
    with jax.default_matmul_precision("highest"):
        x, _ = _blocks(hf, get, jnp.asarray(ids, jnp.int32), None, wrong)
        return np.asarray(_final(hf, x, get), np.float32)


def logprobs(hf, tensors, ids, cast=None, wrong=()):
    """log p(ids[:, t+1] | ids[:, :t+1]) from PASS T's logits as
    float32 [B, L-1], a row a document.

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
# The exit distribution, the training objective and its gradient
# ----------------------------------------------------------------------
def exit_distribution(gate_logits):
    """p [T, ...] from the gate's logits [T, ...]: ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)``, the last pass what is left; written
    out pass by pass."""
    import jax
    import jax.numpy as jnp
    lam = jax.nn.sigmoid(gate_logits)
    left = jnp.ones_like(lam[0])
    out = []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(out + [left])


def objective(hf, tensors, ids, prompt_len, beta=BETA, wrong=()):
    """The looped objective of ONE microbatch whose documents are the
    rows of ``ids`` [n, L] (equal lengths, the first ``prompt_len``
    tokens of each the prompt): the mean over the answer tokens of
    ``sum_t p_t nll_t - beta H(p)``. Returns (loss, dict(nll= [T],
    p= [T], expected_exit_pass=, entropy=)), means over the answer
    tokens. A function of ``tensors`` that ``jax.grad``
    differentiates."""
    import jax
    import jax.numpy as jnp
    get = _getter(tensors, None)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        xs, gates = _passes(hf, get, ids, wrong)
        nll = jnp.stack([-_token_logprobs(_final(hf, x, get), ids)
                         for x in xs])
        # position t scores token t+1: an answer token if t+1 >= prompt_len
        p = exit_distribution(gates[:, :, :-1])
        answer = (jnp.arange(1, ids.shape[1]) >= prompt_len)[None, :]
        count = answer.sum() * ids.shape[0]

        def mean(x):
            return (x * answer).sum((-2, -1)) / count

        entropy = mean(-(p * jnp.log(p)).sum(0))
        if "last_pass_loss_alone" in wrong:
            loss = mean(nll[-1])
        else:
            loss = mean((p * nll).sum(0)) - beta * entropy
        t = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    return loss, dict(nll=mean(nll), p=mean(p), entropy=entropy,
                      expected_exit_pass=(t * mean(p)).sum())


def objective_and_grad(hf, tensors, ids, prompt_len, beta=BETA, wrong=()):
    """(loss, parts, gradient by HF tensor name), all float32, of
    ``objective`` at ``tensors`` cast up to float32. A shared weight's
    gradient is the sum of the passes' in float32: what the program's
    sum in its parameters' dtype is bounded against."""
    import jax
    import jax.numpy as jnp
    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in tensors.items()}
    (loss, parts), grads = jax.value_and_grad(
        lambda t: objective(hf, t, ids, prompt_len, beta, wrong),
        has_aux=True)(f32)
    return float(loss), {k: np.asarray(v) for k, v in parts.items()}, \
        {k: np.asarray(v) for k, v in grads.items()}
