"""TPU Pallas kernels for the hot attention ops.

The semantics-defining implementations live in ``ops/attention.py`` (pure
JAX); these kernels must match them bit-approximately and are selected by
``ops.attention.chunk_attention`` / ``cached_attention`` based on
``ModelConfig.kernels`` ("auto" → pallas on TPU backends, XLA elsewhere;
"interpret" runs the same kernels through the pallas interpreter so CPU
tests exercise the kernel code paths).

The reference delegates these ops to llama.cpp's C++/CUDA kernels inside
the `ollama/ollama` image (/root/reference/pkg/model/pod.go:11); here they
are Mosaic/Pallas programs tiled for the MXU with fp32 online-softmax
accumulation.
"""

from .flash import (decode_attention, decode_tileable,  # noqa: F401
                    flash_prefill, prefill_tileable)
