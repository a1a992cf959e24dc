"""repro.models — composable model zoo for the 10 assigned architectures."""

from repro.models.config import (EncDecConfig, HybridConfig, MLAConfig,
                                 ModelConfig, MoEConfig, VLMConfig,
                                 YarnScaling)
from repro.models.model import Model

__all__ = ["EncDecConfig", "HybridConfig", "MLAConfig", "Model",
           "ModelConfig", "MoEConfig", "VLMConfig", "YarnScaling"]
