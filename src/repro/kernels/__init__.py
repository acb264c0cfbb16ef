"""Pallas TPU kernels (validated in interpret mode on CPU, compiled for
a described v5e in ``tests/test_chip_compile.py``).

contract_gemm    — tiled stem-contraction GEMM (the paper's hot-spot)
flash_attention  — fused online-softmax attention for the LM fleet
mamba2_ssd       — SSD intra-chunk kernel for mamba2/zamba2
ops              — jit'd wrappers (padding, complex Karatsuba, GQA, combine)
ref              — pure-jnp oracles

Kernel entry points are re-exported at the package root so the lowering
layer (:mod:`repro.lowering`) and tests import them without reaching
into submodules.
"""

from . import ops, ref  # noqa: F401
from .contract_gemm import tiled_matmul  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .mamba2_ssd import ssd_intra_chunk  # noqa: F401
from .ops import (  # noqa: F401
    attention,
    matmul,
    ssd_scan,
)
