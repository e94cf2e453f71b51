from repro.serving.compaction import (
    CompactionPlan, CompactionStats, MemberPlan, bucket_size,
    plan_compaction)
from repro.serving.engine import (
    BatchedACAREngine, BatchResult, QueuedServeResult, ZooModel,
    intern_answers, judge_batch, trace_records)
from repro.serving.jax_backend import JaxModelBackend
from repro.serving.kv_pool import (
    KVStats, PageAccountingError, PagePool, PagePoolError,
    PagedKVServer, PoolExhausted, ProbeHandle, dense_tile_slots,
    pages_for)
from repro.serving.mesh import ServingMesh, ShardedPagedKVServer
from repro.serving.metrics import PromCounters
from repro.serving.queue import (
    AdmissionQueue, MicroBatch, MicroBatchPolicy, Request)
from repro.serving.scheduler import (
    ContinuousBatchingScheduler, ProbeCache, SchedulerStats,
    StepPlanner)
from repro.serving.step_loop import (
    ShardedStepLoopRunner, StepLoopRunner, StepStats)
from repro.serving.tracing import NullTracer, SpanTracer

__all__ = [
    "AdmissionQueue", "BatchedACAREngine", "BatchResult",
    "CompactionPlan", "CompactionStats", "ContinuousBatchingScheduler",
    "JaxModelBackend", "KVStats", "MemberPlan", "MicroBatch",
    "MicroBatchPolicy", "NullTracer", "PageAccountingError",
    "PagePool", "PagePoolError", "PagedKVServer", "PoolExhausted",
    "ProbeCache", "ProbeHandle", "PromCounters", "QueuedServeResult",
    "Request", "SchedulerStats", "ServingMesh", "ShardedPagedKVServer",
    "ShardedStepLoopRunner", "SpanTracer", "StepLoopRunner",
    "StepPlanner", "StepStats", "ZooModel", "bucket_size",
    "dense_tile_slots", "intern_answers", "judge_batch", "pages_for",
    "plan_compaction", "trace_records",
]
