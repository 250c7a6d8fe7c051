"""HuggingFace checkpoint conversion; importing registers families.

Parity with reference ``realhf/api/from_hf/__init__.py`` +
``impl/model/conversion/hf_registry.py``.
"""

import realhf_tpu.models.hf.llama  # noqa: F401
import realhf_tpu.models.hf.gpt2  # noqa: F401
import realhf_tpu.models.hf.mixtral  # noqa: F401
import realhf_tpu.models.hf.gemma  # noqa: F401
import realhf_tpu.models.hf.olmoe  # noqa: F401
import realhf_tpu.models.hf.lfm2_moe  # noqa: F401
import realhf_tpu.models.hf.laguna  # noqa: F401
import realhf_tpu.models.hf.deepseek_v3  # noqa: F401
import realhf_tpu.models.hf.kimi_linear  # noqa: F401
import realhf_tpu.models.hf.keye_vl2  # noqa: F401
import realhf_tpu.models.hf.nemotron_h  # noqa: F401
import realhf_tpu.models.hf.ouro  # noqa: F401
import realhf_tpu.models.hf.smallthinker  # noqa: F401

from realhf_tpu.models.hf.registry import (  # noqa: F401
    HF_FAMILIES,
    config_from_hf,
    config_to_hf,
    load_hf_checkpoint,
    load_hf_checkpoint_streamed,
    params_from_hf,
    params_to_hf,
    register_hf_family,
    save_hf_checkpoint,
    save_hf_checkpoint_streamed,
)
