"""repro.core — the paper's contribution as a composable JAX module.

The NTX descriptor ISA (descriptor.py), its functional execution engines
(engine.py), the PCS wide-accumulator precision emulation (precision.py),
the double-buffered tile scheduler (scheduler.py) and the hardware specs
(cluster.py).
"""
from .descriptor import (Agu, Descriptor, Opcode, axpy, gemv, gemm, memcpy,
                         memset, relu, argmax, laplace1d, conv2d,
                         stencil_pass,
                         hw_steps_to_strides, strides_to_hw_steps,
                         NUM_LOOPS, NUM_AGUS, MAX_HW_COUNT)
from .engine import execute, execute_vectorized, execute_jax
from .cluster import NtxClusterSpec, TpuChipSpec, PAPER_CLUSTER, TPU_V5E
from .memory import (NtxMemSpec, PAPER_MEM, fits, working_set_bytes,
                     working_set_spans)
from .scheduler import (TileSchedule, Tile, schedule_axpy, schedule_gemv,
                        schedule_gemm, schedule_conv2d, schedule_stencil,
                        pick_matmul_blocks)
from . import precision
from .dispatch import dispatch
from .stream import CommandStream, plan_stream, program_spans
from .multistream import (ClusterScheduler, StageSchedule, StreamGraph,
                          SubStream)
from .tiling import TileIteration, TilePlan
from .program import BufferHandle, Program, ProgramResult
from .executor import (ExecutionPolicy, Executor,
                       clear_measured_policy_cache)

__all__ = [
    "Agu", "Descriptor", "Opcode", "axpy", "gemv", "gemm", "memcpy",
    "memset", "relu", "argmax", "laplace1d", "conv2d", "stencil_pass",
    "hw_steps_to_strides",
    "strides_to_hw_steps", "NUM_LOOPS", "NUM_AGUS", "MAX_HW_COUNT",
    "execute", "execute_vectorized", "execute_jax",
    "NtxClusterSpec", "TpuChipSpec", "PAPER_CLUSTER", "TPU_V5E",
    "NtxMemSpec", "PAPER_MEM", "fits", "working_set_bytes",
    "working_set_spans",
    "TileSchedule", "Tile", "schedule_axpy", "schedule_gemv",
    "schedule_gemm", "schedule_conv2d", "schedule_stencil",
    "pick_matmul_blocks", "precision", "dispatch",
    "CommandStream", "plan_stream", "program_spans",
    "ClusterScheduler", "StageSchedule", "StreamGraph", "SubStream",
    "TileIteration", "TilePlan",
    "BufferHandle", "Program", "ProgramResult", "ExecutionPolicy",
    "Executor", "clear_measured_policy_cache",
]
