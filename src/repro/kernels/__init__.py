"""Public API of the Pallas kernel layer.

Import from here (``from repro.kernels import fused_matmul_nladc``) rather
than deep-importing ``repro.kernels.ops`` / the per-kernel modules — the
wrapper signatures are the stable surface; the module layout underneath is
not.  The jnp oracles stay available as ``repro.kernels.ref`` (they are the
correctness contract for every kernel and the backward rule of the
``"pallas"`` analog backend, see :mod:`repro.core.backend`).

Kernels run compiled on a TPU and in Pallas interpret mode everywhere
else (``interpret_mode()`` decides from the platform alone).
Block sizes resolve through the :mod:`repro.kernels.tune` cache
(``REPRO_KERNEL_CACHE`` / ``--kernel-blocks``), falling back to each
kernel's ``DEFAULT_BLOCKS`` (the fused matmul: its skinny plan below 256
rows, ``fused_matmul_nladc.plan_blocks``).
"""

from repro.kernels import ref, tune
from repro.kernels.ops import (analog_tile, flash_decode_int8,
                               fused_matmul_nladc, interpret_mode,
                               lstm_gates, mla_decode_attention,
                               moe_fused_matmul, nladc, prefill_attention)

__all__ = [
    "analog_tile",
    "flash_decode_int8",
    "fused_matmul_nladc",
    "interpret_mode",
    "lstm_gates",
    "mla_decode_attention",
    "moe_fused_matmul",
    "nladc",
    "prefill_attention",
    "ref",
    "tune",
]
