"""Batch generation interface: generate and dump to JSONL.

Parity with reference ``realhf/impl/model/interface/gen_interface.py``
(GenerationInterface:39) including the locked append-only output file.
"""

import dataclasses
import fcntl
import json
import os
from typing import Optional

import jax
import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.base.datapack import flat2d
from realhf_tpu.engine import packing
from realhf_tpu.ops.sampling import GenerationHyperparameters

logger = logging.getLogger("GenerationInterface")


@dataclasses.dataclass
class GenerationInterface(model_api.ModelInterface):
    output_file: Optional[str] = None
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters)
    # Continuous batching: slots refill from the prompt queue as
    # sequences finish (engine/inflight.py) -- higher throughput for
    # length-skewed batches; requires force_no_logits_mask.
    use_inflight_batching: bool = False
    inflight_slots: int = 0  # 0 = batch size

    def __post_init__(self):
        if isinstance(self.gconfig, dict):
            self.gconfig = GenerationHyperparameters(**self.gconfig)
        self._calls = 0
        self._inflight = None

    def generate(self, model: model_api.Model, input_: SequenceSample,
                 n_mbs: Optional[int] = None) -> SequenceSample:
        tok = model.tokenizer
        prompt_lens = flat2d(input_.seqlens["packed_prompts"])
        flat = input_.data["packed_prompts"]
        prompts, off = [], 0
        for l in prompt_lens:
            prompts.append(np.asarray(flat[off:off + l]))
            off += l
        self._calls += 1
        from realhf_tpu.interfaces.ppo import _base_key
        key = jax.random.fold_in(_base_key(), self._calls)

        if self.use_inflight_batching:
            if model.engine.multiproc:
                # InflightBatchingGenerator keeps process-local jnp
                # state and reads arrays host-side (np.asarray), both
                # invalid when the mesh spans worker processes.
                raise NotImplementedError(
                    "Inflight-batching generation on a multi-process "
                    "(worker-group) mesh is not supported; run the "
                    "generation MFC on a single-process allocation or "
                    "disable use_inflight_batching.")
            # On a pipeline- or context-parallel mesh, decode runs on
            # the collapsed dp x tp decode view (weights resharded per
            # version, engine.decode_engine) -- same path the batch
            # generate takes.
            eng = model.engine.decode_engine()
            from realhf_tpu.engine.inflight import _bucket
            # bucket the cache size so slowly-growing prompt lengths
            # reuse the compiled decode/prefill programs instead of
            # rebuilding the generator every batch
            need = _bucket(max(64, max(len(p) for p in prompts)))
            n_slots = self.inflight_slots or len(prompts)
            if (self._inflight is None
                    or self._inflight.cache_len
                    - self.gconfig.max_new_tokens < need
                    or self._inflight.n_slots != n_slots):
                # (re)build: a later batch may carry longer prompts
                # than the first one sized the cache for, or (with
                # inflight_slots=0 = "track batch size") a different
                # prompt count than the slots were built for
                self._inflight = eng.inflight_generator(
                    self.gconfig, n_slots=n_slots, max_prompt_len=need,
                    eos_token_id=tok.eos_token_id,
                    pad_token_id=tok.pad_token_id)
            self._inflight.params = eng.params  # fresh weights
            finished = self._inflight.generate_all(prompts, key)
            # do not pin the weights pytree (train_batch donates its
            # buffers; a stale reference would keep a second full model
            # resident in HBM between calls)
            self._inflight.params = None
            lengths = np.asarray([len(f.tokens) for f in finished])
            maxg = max(1, int(lengths.max()))
            gen_tokens = np.full((len(prompts), maxg),
                                 tok.pad_token_id, np.int32)
            for i, f in enumerate(finished):
                gen_tokens[i, :len(f.tokens)] = f.tokens
        else:
            ids, seg, pos = packing.left_padded_prompts(
                prompts, pad_id=tok.pad_token_id)
            out = model.engine.generate(
                ids, seg, pos, key, self.gconfig,
                eos_token_id=tok.eos_token_id,
                pad_token_id=tok.pad_token_id)
            out = out.to_host()  # one bundled D2H round-trip
            gen_tokens = np.asarray(out.tokens)
            lengths = np.asarray(out.lengths)

        if self.output_file is not None:
            path = self.output_file
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            records = []
            for i, p in enumerate(prompts):
                g = int(lengths[i])
                records.append(dict(
                    id=str(input_.ids[i]),
                    prompt=tok.decode(p.tolist()),
                    answer=tok.decode(gen_tokens[i, :g].tolist(),
                                      skip_special_tokens=True)))
            with open(path, "a") as f:
                fcntl.flock(f, fcntl.LOCK_EX)
                for r in records:
                    f.write(json.dumps(r, ensure_ascii=False) + "\n")
                fcntl.flock(f, fcntl.LOCK_UN)

        seqlens, in_ids = [], []
        for i, p in enumerate(prompts):
            g = int(lengths[i])
            seqlens.append(len(p) + g)
            in_ids.append(np.concatenate([p, gen_tokens[i, :g]]))
        return SequenceSample.from_default(
            ids=input_.ids, seqlens=seqlens,
            data=dict(packed_input_ids=np.concatenate(in_ids)
                      .astype(np.int32)))


model_api.register_interface("generation", GenerationInterface)
