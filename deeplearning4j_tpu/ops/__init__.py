"""Hand-written TPU kernels (Pallas) for the hot ops.

The XLA lowerings in nn/ are the default compute path; this package holds
the Pallas kernels that beat them where fusion matters most: flash
attention (`flash_attention`), Kimi Delta Attention's chunk algebra
(`kda_chunk`, reached through `nn/layers/linear_attention.py`) and learned
sparse attention (`dsa_attention`: an indexer's exact top-k of keys a query
and attention over them, reached through `MultiHeadAttention(indexer=)`).
On non-TPU backends the kernels run in interpret mode (tests) or the
callers fall back to the XLA path.
"""
#: the one name the containers' gradient checkpointing keeps
#: (`nn/multilayer.py::_layer_call`: `save_only_these_names(REMAT_KEEP)`):
#: a result far dearer to make again than to hold carries it through
#: `checkpoint_name`, in a layer or, for a kernel's backward residuals,
#: here. Defined before the kernels' modules, which import it.
REMAT_KEEP = "remat_keep"

from deeplearning4j_tpu.ops.flash_attention import flash_attention  # noqa: E402

__all__ = ["REMAT_KEEP", "flash_attention"]
