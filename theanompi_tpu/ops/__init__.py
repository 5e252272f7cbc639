from theanompi_tpu.ops.fused_bn import scale_bias_act
from theanompi_tpu.ops.lrn import lrn

__all__ = ["lrn", "scale_bias_act"]
