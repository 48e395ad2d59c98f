"""Model zoo: composable JAX pytree models with PSL client/server splits."""
from repro.models.config import ModelConfig, ParamSpec
from repro.models.transformer import (EncDecModel, LanguageModel, build_model,
                                      chunked_xent)

__all__ = ["ModelConfig", "ParamSpec", "LanguageModel", "EncDecModel",
           "build_model", "chunked_xent"]
