#!/usr/bin/env python3
"""A builder's tool, no chip: lower the programs of the benchmark's
accepted families (qwen2, mistral, olmoe, lfm2_moe, laguna, deepseek_v3, kimi_linear, keye_vl2, nemotron_h, ouro, smallthinker) under a checkout and write
their StableHLO texts, to show that a change to shared model code left
a model of one block the programs it had.

    git archive <parent> | tar -x -C /root/scratch/parent
    JAX_PLATFORMS=cpu python3 scripts/lowered_programs.py /root/scratch/parent /root/scratch/hlo_parent
    JAX_PLATFORMS=cpu python3 scripts/lowered_programs.py . /root/scratch/hlo_change
    for f in /root/scratch/hlo_parent/*; do cmp $f /root/scratch/hlo_change/$(basename $f); done

The SAME file (this one) runs against both trees: it imports
``realhf_tpu`` and ``benchmark`` from the tree given first. At the
cells' real widths, with abstract parameters: one microbatch's SFT
forward and backward (``T.forward``, the interface's head and loss, a
sparse model's auxiliary terms), of the two GRPO families also the
gradient of a weighted sum over ``shifted_logprobs_from_hidden`` (the
head of every loss that needs the log-probabilities themselves) and
the whole ``generate`` program;
and the engine's own ``train`` and ``logprobs`` programs
(``Engine._train_step_body``: accumulation, optimizer, statistics) at
the tests' tiny widths, where ``Engine`` can hold real arrays. Equal
lowered text is equal input to the compiler. The Pallas kernels engage
only on a TPU backend, so those programs hold none; the flash kernels
are written out by themselves instead, as the jaxpr of their forward
and three gradients at the cells' heads and rows without a window
(``flash.<heads>.jaxpr.txt``: the three ``pallas_call``s with their
bodies, grids, block specs and the ranges' arithmetic; source
locations, which move with any edit of the file, taken out), and of
the forward alone (``.fwd.jaxpr.txt``: what logprobs, values and
prefill programs run of them). A family or a pair of widths (a key of
192 beside a value of 128) that the tree given first does not have yet
is left out and said so: there is nothing of it to compare.
"""
import os
import re
import sys

root, out = os.path.abspath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
os.makedirs(out, exist_ok=True)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from benchmark import generate
from realhf_tpu.models import hf as hf_models, transformer as T
from realhf_tpu.interfaces import sft
from realhf_tpu.ops import moe as moe_ops
from realhf_tpu.engine import generation as gen_mod
from realhf_tpu.ops.sampling import GenerationHyperparameters

def renumbered(txt):
    """``txt`` with its private functions numbered by first appearance
    (``@closed_call_116`` -> ``@closed_call#7``): jax numbers them with
    one counter a PROCESS, so a change to a program lowered earlier
    (one more ``closed_call`` in a gradient) moves the names in every
    text after it."""
    seen = {}
    return re.sub(r"@([A-Za-z_][\w.]*?)_\d+\b", lambda m: seen.setdefault(
        m.group(0), f"@{m.group(1)}#{len(seen)}"), txt)


def dump(name, fn, *args, **kw):
    txt = renumbered(jax.jit(fn, **kw).lower(*args).as_text())
    open(os.path.join(out, name + ".txt"), "w").write(txt)
    print(name, len(txt))

for cfgname, fam, L in (("qwen2.5-0.5b", "qwen2", 4096), ("mistral-7b-v0.3-l4", "mistral", 2048), ("olmoe-1b-7b-0125-l1", "olmoe", 2048), ("lfm2-24b-a2b-l5-ep8", "lfm2_moe", 4096), ("laguna-xs.2-l5-ep16", "laguna", 4096), ("moonlight-16b-a3b-l5-ep8", "deepseek_v3", 4096), ("kimi-linear-48b-a3b-l5-ep32", "kimi_linear", 2048), ("keye-vl-2.0-30b-a3b-l5-ep8", "keye_vl2", 4096), ("nemotron-3-nano-30b-a3b-l7-ep16", "nemotron_h", 4096), ("ouro-2.6b-l6", "ouro", 4096), ("smallthinker-21b-a3b-l4-ep8", "smallthinker", 16384)):
    if fam not in hf_models.HF_FAMILIES:
        print(cfgname, "left out: this tree has no family", fam)
        continue
    hf, meta = generate.load_config(os.path.join(root, "benchmark/configs", cfgname + ".json"))
    cfg = hf_models.config_from_hf(fam, hf)
    cfg.param_dtype = cfg.compute_dtype = "bfloat16"
    cfg.gradient_checkpointing = True
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)
    sds = jax.ShapeDtypeStruct
    mb = dict(input_ids=sds((1, L), jnp.int32), seg_ids=sds((1, L), jnp.int32), prompt_mask=sds((1, L), jnp.bool_))
    loss_fn = sft._make_loss_fn(cfg)
    moe = bool(cfg.n_moe_layers)
    # (a looped model's objective reads every pass's state)
    passes = dict(return_passes=True) if getattr(loss_fn, "every_pass", False) else {}
    def objective(p, mb):
        o = T.forward(cfg, p, mb["input_ids"], mb["seg_ids"], return_aux=moe, **passes)
        aux = o[2] if moe else {}
        loss, stats = loss_fn(p, o[0], mb)
        return loss + moe_ops.aux_loss(aux), {**stats, **aux}
    dump(f"{cfgname}.train_grad", lambda p, mb: jax.value_and_grad(objective, has_aux=True)(p, mb), params, mb)
    if fam in ("qwen2", "mistral"):
        # the head a loss over the log-probabilities THEMSELVES runs (GRPO, PPO, DPO: a clipped ratio, a KL term), under any weights and a temperature
        from realhf_tpu.ops import functional as F
        def lp_objective(p, mb, w):
            return (w * F.shifted_logprobs_from_hidden(cfg, p, T.forward(cfg, p, mb["input_ids"], mb["seg_ids"])[0], mb["input_ids"], mb["seg_ids"], temperature=0.7)).sum()
        dump(f"{cfgname}.logprobs_grad", jax.value_and_grad(lp_objective), params, mb, sds((1, L), jnp.float32))
        g = GenerationHyperparameters(max_new_tokens=256, min_new_tokens=256, greedy=False, force_no_logits_mask=True)
        b = 128 if fam == "qwen2" else 32
        dump(f"{cfgname}.generate",
             lambda p, i, s, pos, k: gen_mod.generate(cfg, p, i, s, pos, k, g, eos_token_id=None, pad_token_id=0),
             params, sds((b, 256), jnp.int32), sds((b, 256), jnp.int32), sds((b, 256), jnp.int32),
             jax.eval_shape(lambda: jax.random.PRNGKey(0)))

# the flash kernels by themselves: (query heads, key/value heads, key's width, value's width, row, window) of cells 1-2, 3, 4, 5, 6's full layers, 7, and 12's two kinds of layer (a row past 4096: the kernels that stream K and V by block, which a tree before them refuses)
from realhf_tpu.ops.flash_attention import flash_attention  # noqa: E402
for nq, nkv, hd, hv, L, window in ((14, 2, 64, 64, 4096, None), (32, 8, 128, 128, 2048, None), (16, 16, 128, 128, 2048, None), (32, 8, 64, 64, 4096, None), (48, 8, 128, 128, 4096, None), (16, 16, 192, 128, 4096, None), (28, 4, 128, 128, 16384, None), (28, 4, 128, 128, 16384, 4096)):
    sds = jax.ShapeDtypeStruct
    q, k, v = (sds((1, L, n, w), jnp.bfloat16) for n, w in ((nq, hd), (nkv, hd), (nkv, hv)))
    heads = f"{nq}x{nkv}x{hd}" + ("" if hv == hd else f"x{hv}")
    # (a call without a window is the call it always was: no keyword)
    more = {} if window is None else dict(sliding_window=window)
    L = f"{L}" + ("" if window is None else f"w{window}")
    def forward(q, k, v, seg):
        return flash_attention(q, k, v, seg, **more)
    def grads(q, k, v, seg):
        return jax.value_and_grad(lambda q, k, v: forward(q, k, v, seg).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    # (.fwd: the forward alone, what a program without a gradient runs)
    for name, fn in ((f"flash.{heads}x{L}.jaxpr", grads), (f"flash.{heads}x{L}.fwd.jaxpr", flash_attention if window is None else forward)):
        try:
            txt = str(jax.make_jaxpr(fn)(q, k, v, sds((1, q.shape[1]), jnp.int32)))
        except Exception as e:  # noqa: BLE001 - a tree before two widths
            print(name, "left out:", type(e).__name__, str(e)[:80])
            continue
        txt = re.sub(r"name_and_src_info=[^\n]*", "", re.sub(r" at [^ \n]*\.py:\d+", "", txt))
        open(os.path.join(out, name + ".txt"), "w").write(txt)
        print(name, len(txt), txt.count("pallas_call"))

# the engine's own train step, inference programs, at the tests' tiny configs (real arrays)
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.parallel import mesh as mesh_lib
for name, fam, path in (("tiny-qwen2", "qwen2", "tests/benchmark/configs/tiny-qwen2.json"), ("tiny-olmoe", "olmoe", "tests/benchmark/olmoe/configs/tiny-olmoe.json")):
    hf, meta = generate.load_config(os.path.join(root, path))
    cfg = hf_models.config_from_hf(fam, hf)
    cfg.param_dtype = "bfloat16"
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(ModelName("default", 0), mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    eng = Engine(cfg, ctx, jax.tree.map(np.asarray, T.init_params(cfg, jax.random.PRNGKey(0))),
                 optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0, lr_scheduler_type="constant"), total_train_steps=10)
    ids = np.ones((2, 64), np.int32); seg = np.ones((2, 64), np.int32)
    mb = dict(input_ids=ids, seg_ids=seg, prompt_mask=np.zeros((2, 64), bool))
    eng.train_batch([mb, mb], sft._make_loss_fn(cfg), loss_fn_key="sft")
    eng.forward_logprobs(ids, seg)
    for prog in ("train", "logprobs"):
        fn, args, static = eng._last_call[prog]
        open(os.path.join(out, f"{name}.engine_{prog}.txt"), "w").write(renumbered(fn.lower(*args, **static).as_text()))
        print(name, prog)
