from .attention import dot_product_attention, self_attention
from .dropout import Dropout, dropout, quantized_rate
from .flash_attention import flash_attention
from .fused_mlp import fused_ln_mlp_residual, fused_mlp
from .partition import on_mesh

__all__ = ["Dropout", "dot_product_attention", "dropout", "flash_attention",
           "fused_ln_mlp_residual", "fused_mlp", "on_mesh",
           "quantized_rate", "self_attention"]
