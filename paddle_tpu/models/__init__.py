"""Flagship model families (training-scale).

Reference analogue: the fleet example models the reference targets (GPT /
BERT / ERNIE collective configs, SURVEY.md §6) — the reference keeps them in
external repos (PaddleNLP/FleetX); here they are first-class so the
distributed engine has in-tree users.
"""
from .gpt import (  # noqa: F401
    CacheOverflow,
    GPTConfig,
    GPTForPretraining,
    GPTModel,
    GPTPretrainingCriterion,
    gpt2_small,
    gpt2_medium,
    gpt2_345m,
)
from .bert import BertConfig, BertForPretraining, BertModel  # noqa: F401
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig,
    Qwen3NextForCausalLM,
    Qwen3NextModel,
)
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig,
    GraniteHybridForCausalLM,
    GraniteHybridModel,
)
from .sdar_moe import (  # noqa: F401
    BlockDiffusionCriterion,
    SDARMoEConfig,
    SDARMoEForBlockDiffusion,
    SDARMoEModel,
)
from .mellum2 import (  # noqa: F401
    Mellum2Config,
    Mellum2ForCausalLM,
    Mellum2Model,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    NemotronHForCausalLM,
    NemotronHModel,
)
