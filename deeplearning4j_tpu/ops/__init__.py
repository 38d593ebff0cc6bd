"""Hand-written TPU kernels (Pallas) for the hot ops.

The XLA lowerings in nn/ are the default compute path; this package holds
the Pallas kernels that beat them where fusion matters most: flash
attention (`flash_attention`) and Kimi Delta Attention's chunk algebra
(`kda_chunk`, reached through `nn/layers/linear_attention.py`). On non-TPU
backends the kernels run in interpret mode (tests) or the callers fall
back to the XLA path.
"""
from deeplearning4j_tpu.ops.flash_attention import flash_attention

__all__ = ["flash_attention"]
