"""Flagship model families (≈ the reference's fleetx/model-zoo configs used
in its benchmark suites; ref:python/paddle/vision/models/ holds the vision
zoo, which lives in paddle_tpu.vision.models)."""
from .ernie import ErnieConfig, ErnieForPretraining, ErnieForSequenceClassification, ErnieModel, ernie_base, ernie_tiny  # noqa: F401
from .gpt import (  # noqa: F401
    GPTEmbeddingPipe,
    GPTForCausalLMPipe,
    GPTHeadPipe,
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    gpt_1p3b,
    gpt_base,
    gpt_tiny,
)
from .keye import (  # noqa: F401
    KeyeConfig,
    KeyeForCausalLM,
    KeyeModel,
    keye_tiny,
)
from .longcat_flash import (  # noqa: F401
    LongcatFlashConfig,
    LongcatFlashForCausalLM,
    LongcatFlashModel,
    longcat_flash_tiny,
)
from .olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig,
    OlmoHybridForCausalLM,
    OlmoHybridModel,
    olmo_hybrid_tiny,
)
from .phi4flash import (  # noqa: F401
    Phi4FlashConfig,
    Phi4FlashForCausalLM,
    Phi4FlashModel,
    phi4flash_tiny,
)
from .trinity import (  # noqa: F401
    TrinityConfig,
    TrinityForCausalLM,
    TrinityModel,
    trinity_tiny,
)
from .xing4 import (  # noqa: F401
    Xing4Config,
    Xing4ForCausalLM,
    Xing4Model,
    xing4_tiny,
)
from .widedeep import DeepFM, DistributedEmbedding, WideDeep  # noqa: F401
