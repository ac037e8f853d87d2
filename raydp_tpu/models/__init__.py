"""Model zoo: the reference's workload families, TPU-native."""

from raydp_tpu.models.dlrm import DLRM, dlrm_optimizer, dlrm_sharding_rules
from raydp_tpu.models.hybridlm import (
    DeltaHybridLM, HybridLM, LatentDeltaHybridLM, LatentMTPHybridLM,
    RoutedHybridLM,
    hybridlm_optimizer)
from raydp_tpu.models.looplm import LoopLM, looplm_optimizer
from raydp_tpu.models.mlp import MLPClassifier, MLPRegressor
from raydp_tpu.models.transformer import TransformerLM, sequence_parallel_apply

__all__ = [
    "DLRM",
    "DeltaHybridLM",
    "HybridLM",
    "LatentDeltaHybridLM",
    "LatentMTPHybridLM",
    "LoopLM",
    "MLPClassifier",
    "MLPRegressor",
    "RoutedHybridLM",
    "TransformerLM",
    "dlrm_optimizer",
    "dlrm_sharding_rules",
    "hybridlm_optimizer",
    "looplm_optimizer",
    "sequence_parallel_apply",
]
