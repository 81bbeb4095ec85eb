"""Continuous-batching serve engine feeding the contextual specialization
runtime.

The production entry point of the framework: an open-loop admission queue
with backpressure, pluggable scheduling (FCFS / SJF / deadline-EDF), a
continuous batcher that packs each step's batch into tuned bucket shapes
(the bucket boundaries are themselves a specialization point, searched
online by a Controller against observed goodput), and a
:class:`~repro.serve.engine.ServeEngine` loop that routes every packed
batch through the handler's per-bucket dispatch snapshot and feeds the
per-context Controller.

Execution is phase-disaggregated: :mod:`repro.serve.kv` keeps every
request's decode state isolated in block-paged device pools, and :mod:`repro.serve.executor` runs
chunked prefill and decode as separate ``(phase, bucket)`` specialization
contexts of one serve handler.

See ``launch/serve.py`` for the LM serving driver built on this package
and ``benchmarks/serve_bench.py`` for the open-loop evaluation scenario.
"""
from repro.serve.request import Completion, Request, next_request_id
from repro.serve.queue import (AdmissionQueue, OpenLoopSource,
                               pseudo_poisson_times, substream_seed)
from repro.serve.scheduler import (SCHEDULERS, DeadlineAware,
                                   DeficitRoundRobin, FCFS, Scheduler,
                                   ShortestJobFirst, make_scheduler)
from repro.serve.metrics import ServeMetrics
from repro.serve.batcher import (BucketTuner, ContinuousBatcher, PackedBatch,
                                 bucket_plan_builder, default_schemes)
from repro.serve.kv import PagedKV, PageError, PagePool, PageTable
from repro.serve.executor import (DecodeExecutor, PhasedExecutor,
                                  PrefillExecutor)
from repro.serve.engine import BatchExecutor, ServeEngine
from repro.serve.shadow import ShadowEvaluator
from repro.serve.tenancy import (ControllerGroup, MultiTenantExecutor,
                                 TenantSpec, make_tenant_context_fn,
                                 parse_tenant_arg)

__all__ = [
    "Completion", "Request", "next_request_id",
    "AdmissionQueue", "OpenLoopSource", "pseudo_poisson_times",
    "substream_seed",
    "SCHEDULERS", "DeadlineAware", "DeficitRoundRobin", "FCFS", "Scheduler",
    "ShortestJobFirst", "make_scheduler", "ServeMetrics",
    "BucketTuner", "ContinuousBatcher", "PackedBatch",
    "bucket_plan_builder", "default_schemes",
    "PagedKV", "PageError", "PagePool", "PageTable",
    "DecodeExecutor", "PhasedExecutor", "PrefillExecutor",
    "BatchExecutor", "ServeEngine", "ShadowEvaluator",
    "ControllerGroup", "MultiTenantExecutor", "TenantSpec",
    "make_tenant_context_fn", "parse_tenant_arg",
]
