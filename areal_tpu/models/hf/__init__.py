"""HF family adapters.  Importing registers all families."""

from areal_tpu.models.hf import (  # noqa: F401
    deepseek_v3,
    dots3_note,
    falcon_h1,
    gpt2,
    granitemoehybrid,
    laguna,
    llama_like,
    mixtral,
    ouro,
    phi4flash,
    qwen3_moe,
    smallthinker,
)
from areal_tpu.models.hf.registry import (  # noqa: F401
    get_hf_family,
    load_hf_config,
    load_hf_model,
    save_hf_model,
)
