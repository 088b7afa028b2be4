"""Model families (flagships of the TPU build).

Re-exports the Gluon model zoo (reference:
python/mxnet/gluon/model_zoo/vision/) plus TPU-first training entry points.
"""
from ..gluon.model_zoo import vision, get_model
from .transformer import TransformerLM, TransformerBlock, \
    MultiHeadSelfAttention
from .decoder import DecoderBlockLM
from .moe_decoder import MoEDecoderLM, MoEDecoderBlock, \
    GroupedQueryAttention, GatedDeltaNet, GatedShortConv, SwiGLU
from .samba_y import SambaYLM, SambaYBlock, MambaMixer, \
    DifferentialAttention, GatedMemoryUnit

__all__ = ["vision", "get_model", "TransformerLM", "TransformerBlock",
           "MultiHeadSelfAttention", "DecoderBlockLM", "MoEDecoderLM",
           "MoEDecoderBlock", "GroupedQueryAttention", "GatedDeltaNet",
           "GatedShortConv", "SwiGLU",
           "SambaYLM", "SambaYBlock", "MambaMixer", "DifferentialAttention",
           "GatedMemoryUnit"]
