"""Pallas TPU kernels for the framework's hot ops.

On non-TPU backends the kernels can run under the Pallas interpreter
(``interpret=True``), which is how ``tests/test_pallas_kernels.py`` and
``tests/test_paged_attention_kernel.py`` validate them against jnp
references.  Their callers take the kernels on a TPU alone: the training
attention's dispatcher (``distkeras_tpu.parallel.ring.attention``) routes
non-TPU backends to the jnp path, and the serving step's paged read
(``serving.cache.paged_decode_attention``) and the Mamba layers' selective
scan (``selective_scan.selective_scan`` / ``.selective_step``) pick their
kernels when they are lowered for a TPU at shapes the kernels take, so the
CPU device-mesh integration tests do NOT exercise these kernels — only the
dedicated kernel tests (and the compiles for a described chip in
``tests/test_chip_compile.py``) do.
"""

from distkeras_tpu.ops.pallas.flash_attention import flash_attention
from distkeras_tpu.ops.pallas.paged_attention import (
    paged_attention,
    paged_attention_fits,
)
from distkeras_tpu.ops.pallas.selective_scan import ssm_scan, ssm_step

__all__ = ["flash_attention", "paged_attention", "paged_attention_fits",
           "ssm_scan", "ssm_step"]
