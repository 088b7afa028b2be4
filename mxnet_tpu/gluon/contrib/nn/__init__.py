"""Contrib neural network layers (reference:
python/mxnet/gluon/contrib/nn/__init__.py)."""
from .basic_layers import (Concurrent, HybridConcurrent, Identity,
                           SparseEmbedding, PixelShuffle1D, PixelShuffle2D,
                           SyncBatchNorm)
from .moe import SwitchMoE, TopKMoE

__all__ = ["Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "PixelShuffle1D", "PixelShuffle2D", "SyncBatchNorm", "SwitchMoE",
           "TopKMoE"]
