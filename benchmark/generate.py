"""What a run makes from ``--seed``: the checkpoint directory a user
would pass as ``actor.path`` (weights, config.json, tokenizer), the
data files, and the fixed batch the reference comparison uses.

The weights are made on the device in one jitted call, in bf16 as they
are served, and written under the names Hugging Face gives them (the
configuration's family lists them), so the program loads them by the
path it loads any published checkpoint by and the family's reference
reads the same files without the program's loader.
Tokenizer and data writers were copied from ``chip_smoke.py``.
"""

import json
import os
import re

import numpy as np

#: keys of a configuration file that are the benchmark's own; every
#: other key is the published config.json
CONFIG_META = ("source", "family", "reduced", "assumed", "deployment",
               "layout")


def load_config(path):
    """(published dict as run, the benchmark's own keys)."""
    with open(path) as f:
        raw = json.load(f)
    meta = {k: raw[k] for k in CONFIG_META if k in raw}
    hf = {k: v for k, v in raw.items() if k not in CONFIG_META}
    return hf, meta


def parallel_degrees(layout):
    """``d2t2`` -> (2, 2): the data and tensor parallel degrees of a
    layout string in a configuration's ``layout``."""
    m = re.fullmatch(r"d(\d+)t(\d+)", layout)
    if m is None:
        raise ValueError(f"cannot read the layout {layout!r}: "
                         "expected d<N>t<M>")
    return int(m.group(1)), int(m.group(2))


def make_weights(family, hf, seed):
    """Every tensor of the model as bf16 numpy arrays under HF names,
    which ``family.shapes(hf)`` lists. Matrices are
    N(0, initializer_range), biases the same, norm scales
    1 + N(0, initializer_range): nothing is exactly 0 or 1, so a
    forward that drops a bias or a scale disagrees with the
    reference."""
    import jax
    import jax.numpy as jnp

    shapes = family.shapes(hf)
    std = float(hf.get("initializer_range", 0.02))

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            x = std * jax.random.normal(jax.random.fold_in(key, i),
                                        shape, jnp.float32)
            if kind == "norm":
                x = x + 1.0
            out[name] = x.astype(jnp.bfloat16)
        return out

    stacked = jax.device_get(make(jax.random.PRNGKey(seed)))
    state = {}
    for name, arr in stacked.items():
        if "{}" in name:
            for layer in range(arr.shape[0]):
                state[name.format(layer)] = arr[layer]
        else:
            state[name] = arr
    return state


def build_tokenizer(hf):
    """A word-level tokenizer over the model's whole vocabulary: word
    ``t<i>`` is token i, and the published EOS id is EOS and pad."""
    import tokenizers
    import transformers

    eos = hf["eos_token_id"]
    vocab = {f"t{i}": i for i in range(hf["vocab_size"])}
    del vocab[f"t{eos}"]
    vocab["<|endoftext|>"] = eos
    tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(
        vocab, unk_token="<|endoftext|>"))
    tok.pre_tokenizer = tokenizers.pre_tokenizers.WhitespaceSplit()
    return transformers.PreTrainedTokenizerFast(
        tokenizer_object=tok, eos_token="<|endoftext|>",
        pad_token="<|endoftext|>")


def write_checkpoint(path, family, hf, seed):
    """An HF-layout directory: the published config.json as run, one
    bf16 safetensors file, tokenizer files. Returns the parameter count
    and the seconds each part took."""
    import time

    import safetensors.numpy

    os.makedirs(path, exist_ok=True)
    t0 = time.monotonic()
    state = make_weights(family, hf, seed)
    t1 = time.monotonic()
    safetensors.numpy.save_file(
        state, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f, indent=2)
    t2 = time.monotonic()
    build_tokenizer(hf).save_pretrained(path)
    t3 = time.monotonic()
    return sum(v.size for v in state.values()), dict(
        weights=round(t1 - t0, 2), save=round(t2 - t1, 2),
        tokenizer=round(t3 - t2, 2))


def _words(ids):
    return " ".join(f"t{int(t)}" for t in ids)


def _token_ids(rng, n, hf):
    ids = rng.integers(0, hf["vocab_size"], size=n)
    ids[ids == hf["eos_token_id"]] = 0
    return ids


def write_prompts(path, n, prompt_len, hf, seed):
    """``n`` prompts of prompt_len..prompt_len+32 words; the dataset's
    max_seqlen cuts each to exactly prompt_len tokens."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(n):
            ids = _token_ids(rng, prompt_len + int(rng.integers(0, 33)),
                             hf)
            f.write(json.dumps(dict(id=i, prompt=_words(ids))) + "\n")


def write_documents(path, n, doc_len, prompt_len, hf, seed):
    """``n`` prompt-and-answer documents, each a little longer than
    doc_len tokens, so that the dataset's max_seqlen cuts every one to
    exactly doc_len; the first prompt_len tokens are the prompt."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(n):
            ids = _token_ids(rng, doc_len + 1 + int(rng.integers(0, 33)),
                             hf)
            f.write(json.dumps(dict(
                id=i, prompt=_words(ids[:prompt_len]) + " ",
                answer=_words(ids[prompt_len:]))) + "\n")


def fixed_batch(hf, seed, rows=4, length=256):
    """The batch of the reference comparison: [rows, length] token ids
    from the seed."""
    rng = np.random.default_rng(seed + 1)
    return rng.integers(0, hf["vocab_size"],
                        size=(rows, length)).astype(np.int32)
