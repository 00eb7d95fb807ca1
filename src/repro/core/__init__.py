"""CUTEv2 core: the paper's contribution as a composable JAX module.

Public surface:
  * ``MatrixUnitConfig`` / presets — paper Table 2 + Eq. 1.
  * ``constraint`` — Eq. 2 at scratchpad, VMEM and ICI levels.
  * ``MatMulTask`` / ``BiasType`` — paper Table 1 interface registers.
  * ``AsyncMatmulEngine`` / ``pipelined_fused_matmul`` — asyncMatMul /
    checkMatmul programming model (Listing 1).
  * ``cute_matmul`` / ``linear`` / ``Epilogue`` — the unified fused-matmul
    API every model routes through.
  * ``simulator`` — cycle-approximate reproduction of the paper's
    evaluation platform.
"""

from repro.core.config import (CASE_STUDY, PLATFORM_2TOPS, MatrixUnitConfig,
                               scaled_config, scaling_sweep)
from repro.core.engine import AsyncMatmulEngine, Handle, pipelined_fused_matmul
from repro.core.fusion import (ACTIVATIONS, Epilogue, EpilogueOperands,
                               NO_EPILOGUE, NO_OPERANDS, apply_epilogue,
                               cute_matmul, linear)
from repro.core.precision import (BF16, DataType, FP8, FP16, FP32, INT8,
                                  PrecisionPolicy, TF32, policy)
from repro.core.task import BiasType, MatMulTask, Status, tile_tasks

__all__ = [
    "CASE_STUDY", "PLATFORM_2TOPS", "MatrixUnitConfig", "scaled_config",
    "scaling_sweep", "AsyncMatmulEngine", "Handle", "pipelined_fused_matmul",
    "ACTIVATIONS", "Epilogue", "EpilogueOperands", "NO_EPILOGUE",
    "NO_OPERANDS", "apply_epilogue", "cute_matmul", "linear", "BF16",
    "DataType", "FP8", "FP16", "FP32", "INT8", "PrecisionPolicy", "TF32",
    "policy", "BiasType", "MatMulTask", "Status", "tile_tasks",
]
