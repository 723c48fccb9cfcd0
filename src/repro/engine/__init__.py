"""Inference engine: bind params to a Plan and execute the planned graph.

``compile``/``InferenceSession`` (engine/session.py) is the front door —
plan, tune, bind, specialize per batch size, and persist artifacts;
``compile_model`` is the lower-level bind-one-plan entry it rides on.
``AsyncServer`` (engine/serving.py) turns a session into a dynamic-batching
serving loop with deterministic, padding-based bucket execution, worker
supervision (retries, restarts, hung-batch watchdog), and pluggable
overload shedding; ``engine/faults.py`` provides the deterministic fault
injection the failure paths are tested and benchmarked with;
``engine/supervision.py`` holds the pure decision logic (heartbeats,
stragglers, retry backoff, shed policies).

The traffic subsystem closes the serving loop on *measured* load:
``engine/telemetry.py`` (bounded streaming statistics — fixed-budget
size histograms, P² quantile estimators), ``engine/traffic.py`` (the
learned bucket-set solver behind ``save(buckets="auto")``, priority
classes, synthetic trace generation), and ``engine/fleet.py``
(``FleetServer``: multi-tenant hosting under a shared schedule db and
an LRU memory budget).
"""
from repro.engine.executor import CompiledModel, bind_params, compile_model
from repro.engine.faults import (DelayBatch, FailBatch, FaultInjector,
                                 InjectedFault, InjectedPredictError,
                                 InjectedWorkerCrash, KillWorker,
                                 corrupt_artifact, corrupt_file)
from repro.engine.fleet import (DuplicateModelError, FleetServer,
                                MemoryBudgetError, UnknownModelError)
from repro.engine.lm_session import LMSession, compile_lm
from repro.engine.serving import (AllWorkersUnhealthyError, AsyncServer,
                                  BatchPolicy, DeadlineExceededError,
                                  DynamicBatchPolicy, HostRows,
                                  LoadShedError, QueueFullError,
                                  RequestTooLargeError,
                                  RetriesExhaustedError,
                                  ServerClosedError, ServingError,
                                  ServingStats, StreamRequest, TokenStream,
                                  WorkerCrashError,
                                  nearest_bucket, padded_predict)
from repro.engine.session import (ArtifactCorruptError, ArtifactError,
                                  InferenceSession, Session,
                                  UnverifiedArtifactWarning, compile)
from repro.engine.supervision import (HeartbeatMonitor, RetryPolicy,
                                      SHED_POLICIES, StragglerMitigator,
                                      StragglerPolicy, choose_shed_victim)
from repro.engine.telemetry import (P2Quantile, SizeHistogram,
                                    StreamingQuantiles)
from repro.engine.traffic import (DEFAULT_PRIORITY, PRIORITY_CLASSES,
                                  TRACE_KINDS, TraceRequest,
                                  expected_catchup_tokens,
                                  expected_padded_waste, priority_rank,
                                  solve_buckets, solve_seq_buckets,
                                  synth_trace)

__all__ = ["AllWorkersUnhealthyError", "ArtifactCorruptError",
           "ArtifactError", "AsyncServer", "BatchPolicy", "CompiledModel",
           "DEFAULT_PRIORITY", "DeadlineExceededError", "DelayBatch",
           "DuplicateModelError", "DynamicBatchPolicy",
           "FailBatch", "FaultInjector", "FleetServer", "HeartbeatMonitor",
           "HostRows",
           "InferenceSession", "InjectedFault", "InjectedPredictError",
           "LMSession",
           "InjectedWorkerCrash", "KillWorker", "LoadShedError",
           "MemoryBudgetError", "P2Quantile", "PRIORITY_CLASSES",
           "QueueFullError", "RequestTooLargeError",
           "RetriesExhaustedError", "RetryPolicy",
           "SHED_POLICIES", "ServerClosedError", "ServingError",
           "ServingStats", "Session", "SizeHistogram", "StreamRequest",
           "TokenStream",
           "StragglerMitigator", "StragglerPolicy", "StreamingQuantiles",
           "TRACE_KINDS", "TraceRequest", "UnknownModelError",
           "UnverifiedArtifactWarning", "WorkerCrashError", "bind_params",
           "compile", "compile_lm", "compile_model", "choose_shed_victim",
           "corrupt_artifact", "corrupt_file", "expected_catchup_tokens",
           "expected_padded_waste",
           "nearest_bucket", "padded_predict", "priority_rank",
           "solve_buckets", "solve_seq_buckets", "synth_trace"]
