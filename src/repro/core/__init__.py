"""repro.core — the paper's contribution: xMem, a CPU-only a-priori
peak-memory estimator for DL training jobs, adapted to JAX/XLA/TPU.

Pipeline: tracer (CPU jaxpr interpretation) -> analyzer (lifecycles +
attribution) -> orchestrator (device-semantics lifecycle rewriting) ->
simulator (two-level BFC allocator replay) -> peak estimate + OOM verdict.
"""
from .allocator import (AllocatorPolicy, CachingAllocatorSim, CUDA_CACHING,
                        DeviceAllocatorSim, POLICIES, SimOOMError, TPU_ARENA,
                        XLA_BFC)
from .analyzer import (attribute_by_time_window, classify_blocks,
                       layer_report, reconstruct_from_address_events,
                       reconstruct_lifecycles)
from .cache import GLOBAL_TRACE_CACHE, TraceCache, TracedPhase, trace_key
from .estimator import (EstimateReport, XMemEstimator, flatten_kinds,
                        update_grad_coupling)
from .events import (TRACE_SCHEMA_VERSION, BlockKind, BlockLifecycle,
                     ColumnarBlocks, ColumnarTrace, LazyEvents, MemoryEvent,
                     PeriodicBlocks, Phase, Trace, TraceSchemaError,
                     lifecycles_to_events, liveness_curve, peak_live_bytes,
                     periodic_breakdown_peaks, periodic_peak_live,
                     periodic_phase_peaks, reduced_for_breakdown)
from .orchestrator import (CollectiveSpec, MemoryOrchestrator,
                           OrchestratorPolicy)
from .simulator import (ColumnarProgram, MemorySimulator, SimResult,
                        program_from_lifecycles, program_from_periodic)
from .sweep import SweepPoint, SweepService, estimate_many
from .tracer import (FUSIBLE_OPS, JaxprMemoryTracer, aval_bytes, trace_fn,
                     trace_fn_with_shape)

__all__ = [
    "AllocatorPolicy", "CachingAllocatorSim", "CUDA_CACHING",
    "DeviceAllocatorSim", "POLICIES", "SimOOMError", "TPU_ARENA", "XLA_BFC",
    "attribute_by_time_window", "classify_blocks", "layer_report",
    "reconstruct_from_address_events", "reconstruct_lifecycles",
    "GLOBAL_TRACE_CACHE", "TraceCache", "TracedPhase", "trace_key",
    "EstimateReport", "XMemEstimator", "flatten_kinds",
    "update_grad_coupling", "BlockKind", "BlockLifecycle", "MemoryEvent",
    "PeriodicBlocks", "Phase", "Trace", "lifecycles_to_events",
    "liveness_curve", "peak_live_bytes", "periodic_breakdown_peaks",
    "periodic_peak_live", "periodic_phase_peaks", "reduced_for_breakdown",
    "CollectiveSpec", "FUSIBLE_OPS",
    "MemoryOrchestrator", "OrchestratorPolicy", "MemorySimulator",
    "SimResult", "JaxprMemoryTracer", "aval_bytes", "trace_fn",
    "trace_fn_with_shape",
    "TRACE_SCHEMA_VERSION", "TraceSchemaError", "ColumnarBlocks",
    "ColumnarTrace", "ColumnarProgram", "LazyEvents",
    "program_from_lifecycles", "program_from_periodic",
    "SweepPoint", "SweepService", "estimate_many",
]
