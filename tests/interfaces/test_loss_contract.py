"""The loss contract between ``interfaces/`` and ``Engine``: an
interface hands the engine a head and an objective
(``LossFn(params, hidden, microbatch)``), the engine runs the model's
forward (``Engine._forward``) and adds the sparse model's auxiliary
losses and statistics itself.

``PINNED`` holds ``loss`` and ``grad_norm`` of one seeded train step of
every train closure on a dense and on a sparse tiny model, as the
commit BEFORE the contract (8c76ee9, closures calling
``common.forward_with_aux`` themselves) returned them for this same
test body (CPU, float32). They pin the numerics through that refactor
and guard every interface's share of the engine's aux handling.
"""

import dataclasses
import pathlib
import re

import numpy as np
import pytest

import jax

from realhf_tpu.api import model as model_api
from realhf_tpu.api.config import ModelName
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces.dpo import DPOInterface
from realhf_tpu.interfaces.grpo import GRPOInterface
from realhf_tpu.interfaces.ppo import PPOActorInterface, PPOCriticInterface
from realhf_tpu.interfaces.reinforce import ReinforceInterface
from realhf_tpu.interfaces.rw import PairedRewardInterface
from realhf_tpu.interfaces.sft import SFTInterface
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.ops import moe as moe_ops
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel.mesh import MeshContext, ParallelismConfig, make_mesh

VOCAB = 64
AUX_KEYS = ("moe_aux_loss", "moe_z_loss", moe_ops.LOAD_STAT)
GCONFIG = GenerationHyperparameters(
    max_new_tokens=8, min_new_tokens=1, force_no_logits_mask=True)


class _Tokenizer:
    pad_token_id = 0
    eos_token_id = 1


def _config(kind: str, is_critic: bool) -> TransformerConfig:
    """``dense``; ``moe``: 4 experts, 2 a token, both auxiliary losses
    on; ``moe0``: the same model with both coefficients zero."""
    coeff = dict(moe=(1e-2, 1e-3), moe0=(0.0, 0.0)).get(kind)
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=VOCAB, apply_rotary=True,
        layer_norm_type="rms", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        is_critic=is_critic,
        mlp_type="llama" if coeff is None else "moe",
        moe=None if coeff is None else MoEConfig(
            num_experts=4, top_k=2, aux_loss_coeff=coeff[0],
            z_loss_coeff=coeff[1]))


def _model(cfg: TransformerConfig, parallel=None, n_devices=1
           ) -> model_api.Model:
    parallel = parallel or ParallelismConfig()
    ctx = MeshContext(
        ModelName("m", 0),
        make_mesh(parallel, devices=jax.devices()[:n_devices]), parallel)
    engine = Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(0)),
                    optimizer=OptimizerConfig(
                        lr=1e-3, warmup_steps_proportion=0.0,
                        lr_scheduler_type="constant"),
                    total_train_steps=100)
    return model_api.Model(ModelName("m", 0), engine, _Tokenizer())


# ----------------------------------------------------------------------
# Seeded batches: what each train_step reads, with no rollout behind it
# ----------------------------------------------------------------------
def _sft_batch(rng, n=6):
    lens, ids, masks = [], [], []
    for _ in range(n):
        pl, al = int(rng.integers(2, 6)), int(rng.integers(4, 12))
        ids.append(rng.integers(2, VOCAB, size=pl + al))
        masks.append(np.arange(pl + al) < pl)
        lens.append(pl + al)
    return SequenceSample.from_default(
        ids=list(range(n)), seqlens=lens,
        data=dict(packed_input_ids=np.concatenate(ids).astype(np.int32),
                  prompt_mask=np.concatenate(masks)))


def _nested(rng, n_elems: int, per_elem: int, paired: bool):
    """``n_elems`` elements of ``per_elem`` sequences sharing a prompt;
    ``paired`` adds what the preference interfaces read (prompt
    lengths, the reference's per-sequence log-probabilities), else
    what the policy-gradient ones read (a rollout's outputs)."""
    samples = []
    for i in range(n_elems):
        pl = int(rng.integers(2, 5))
        prompt = rng.integers(2, VOCAB, size=pl)
        seqs = [np.concatenate([prompt, rng.integers(
            2, VOCAB, size=int(rng.integers(3, 8)))])
            for _ in range(per_elem)]
        lens = [len(s) for s in seqs]
        m1 = [l - 1 for l in lens]
        one = [1] * per_elem
        data = dict(packed_input_ids=(
            lens, np.concatenate(seqs).astype(np.int32)))
        if paired:
            data.update(
                prompt_lens=([1], np.asarray([pl], np.int32)),
                seqlogp=(one, rng.uniform(-30, -10, per_elem)
                         .astype(np.float32)))
        else:
            data.update(
                prompt_mask=(lens, np.concatenate(
                    [np.arange(l) < pl for l in lens])),
                packed_logprobs=(m1, rng.uniform(-5, -3, sum(m1))
                                 .astype(np.float32)),
                packed_ref_logprobs=(m1, rng.uniform(-5, -3, sum(m1))
                                     .astype(np.float32)),
                values=(lens, rng.standard_normal(sum(lens))
                        .astype(np.float32)),
                rewards=(one, rng.standard_normal(per_elem)
                         .astype(np.float32)),
                seq_no_eos_mask=(one, rng.random(per_elem) < 0.3))
        samples.append(SequenceSample(
            keys=list(data),
            trailing_shapes={k: () for k in data},
            dtypes={k: v.dtype for k, (_, v) in data.items()},
            ids=[i], seqlens={k: [l] for k, (l, _) in data.items()},
            data={k: v for k, (_, v) in data.items()}))
    return SequenceSample.gather(samples)


@dataclasses.dataclass
class Case:
    interface: callable
    batch: callable
    critic: bool = False
    n_mbs: int = 2


CASES = dict(
    sft=Case(SFTInterface, _sft_batch),
    rw=Case(PairedRewardInterface,
            lambda rng: _nested(rng, 4, 4, paired=True), critic=True),
    dpo=Case(lambda: DPOInterface(beta=0.5),
             lambda rng: _nested(rng, 4, 4, paired=True)),
    grpo=Case(lambda: GRPOInterface(n_minibatches=2, gconfig=GCONFIG,
                                    group_size=2, kl_coef=0.05),
              lambda rng: _nested(rng, 4, 2, paired=False)),
    ppo_actor=Case(lambda: PPOActorInterface(n_minibatches=2,
                                             gconfig=GCONFIG),
                   lambda rng: _nested(rng, 8, 1, paired=False)),
    ppo_critic=Case(lambda: PPOCriticInterface(n_minibatches=2),
                    lambda rng: _nested(rng, 8, 1, paired=False),
                    critic=True),
    reinforce=Case(lambda: ReinforceInterface(n_minibatches=1,
                                              gconfig=GCONFIG,
                                              kl_coef=0.05),
                   lambda rng: _nested(rng, 4, 2, paired=False)),
)


def _train_step(name: str, kind: str):
    case = CASES[name]
    model = _model(_config(kind, case.critic))
    batch = case.batch(np.random.default_rng(0))
    return case.interface().train_step(model, batch, n_mbs=case.n_mbs)


#: (closure, model) -> (loss, grad_norm), recorded on the parent commit
PINNED = {
    ("sft", "dense"): (4.133089065551758, 1.2885165214538574),
    ("sft", "moe"): (4.158111572265625, 1.2894879579544067),
    ("rw", "dense"): (0.6948541402816772, 2.268254280090332),
    ("rw", "moe"): (0.7194221019744873, 2.2696781158447266),
    ("dpo", "dense"): (2.6823134422302246, 3.1316206455230713),
    ("dpo", "moe"): (2.706392288208008, 3.1268792152404785),
    ("grpo", "dense"): (0.17392658907920122, 1.635568618774414),
    ("grpo", "moe"): (0.19824309647083282, 1.6312676668167114),
    ("ppo_actor", "dense"): (0.036657340824604034, 1.0625924170017242),
    ("ppo_actor", "moe"): (0.061172425746917725, 1.0641246736049652),
    ("ppo_critic", "dense"): (0.833291083574295, 1.5203507542610168),
    ("ppo_critic", "moe"): (0.8576036393642426, 1.5213349610567093),
    ("reinforce", "dense"): (1.8532172441482544, 1.868929386138916),
    ("reinforce", "moe"): (1.8767703771591187, 1.8667852878570557),
}


@pytest.mark.parametrize("kind", ["dense", "moe"])
@pytest.mark.parametrize("name", list(CASES))
def test_train_step_matches_parent(name, kind):
    stats = _train_step(name, kind)
    got = (stats["loss"], stats["grad_norm"])
    assert (name, kind) in PINNED, f'("{name}", "{kind}"): {got!r},'
    np.testing.assert_allclose(got, PINNED[name, kind], rtol=1e-6)
    if kind == "dense":
        assert not set(AUX_KEYS) & set(stats)
        return
    # the engine added the auxiliary losses to the objective and their
    # entries to the statistics; the closure knows of neither
    assert set(AUX_KEYS) <= set(stats)
    assert stats["moe_aux_loss"] > 0 and stats["moe_z_loss"] > 0
    assert stats[moe_ops.LOAD_STAT] >= 1.0
    plain = _train_step(name, "moe0")
    assert abs(stats["loss"] - plain["loss"]) > 1e-4


# ----------------------------------------------------------------------
# Guards of the seam
# ----------------------------------------------------------------------
def test_interfaces_leave_the_forward_to_the_engine():
    """No interface runs the model or names what the engine's mesh
    needs for it; a head (``T.critic_values``) is not a forward."""
    root = pathlib.Path(__file__).parents[2] / "realhf_tpu" / "interfaces"
    banned = re.compile(
        r"attention_fn|pipeline_ctx|moe_constraint|\.forward\(")
    found = [f"{path.name}:{i}: {line.strip()}"
             for path in sorted(root.glob("*.py"))
             for i, line in enumerate(path.read_text().splitlines(), 1)
             if banned.search(line)]
    assert not found, "\n".join(found)


@pytest.mark.parametrize("train", [False, True])
def test_residual_constraint_is_inference_only(train):
    """The one asymmetry between the two forwards of an engine that
    nobody chose (ROADMAP D14): on a d2t2 mesh the inference forward
    pins the residual stream's sharding after the embedding and after
    both halves of every block (``models/transformer.py``), the
    training forward not at all. Whoever removes it does so on purpose
    and with the d2t2 cell's number."""
    parallel = ParallelismConfig(data_parallel_size=2,
                                 tensor_parallel_size=2)
    engine = _model(_config("dense", False), parallel, 4).engine
    ids = np.ones((2, 16), np.int32)
    text = str(jax.make_jaxpr(
        lambda p: engine._forward(p, ids, ids, train=train)[0])(
            engine.params))
    # the embedding's, and the two of the scanned block's body
    assert text.count("sharding_constraint") == (0 if train else 3)
