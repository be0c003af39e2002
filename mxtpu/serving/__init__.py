"""mxtpu.serving — dynamic-batching TPU inference serving (ISSUE 4),
the fault-tolerant serving fleet (ISSUE 7), and the fleet control
plane (ISSUE 11: autoscaling, predictive admission, priority classes).

The TPU-native equivalent of the reference's C predict API +
``BucketingModule`` deployment story (SURVEY.md §3), grown into a
serving layer:

- :class:`ModelRunner` (runner.py): loads ``export``/``save_checkpoint``
  artifacts, AOT-compiles one donated-buffer XLA executable per
  (batch, seq) shape bucket; weights upload once and are shared by
  every bucket (``MXPredReshape``† zero-copy contract).
- :class:`DynamicBatcher` (batcher.py): bounded queue,
  ``max_batch_size``/``max_queue_delay_us`` assembly, per-request
  deadlines, :class:`ServerBusy` backpressure — policy is pure and
  clock-injected (deterministically testable).
- :class:`InferenceServer` (server.py): name→version→runner registry,
  worker threads per model, round-robin across device replicas.
- :class:`ServingStats` (stats.py): rolling p50/p95/p99, queue depth,
  batch fill-rate, req/sec; Speedometer-style log line; chrome-trace
  spans via ``mxtpu.profiler``.
- :class:`GenerateRunner` / :class:`GenerateBatcher` (generate.py,
  ISSUE 19): KV-cache incremental decode — AOT-compiled prefill
  executables per (batch, prompt-bucket) plus ONE decode-step
  executable over a preallocated slot-paged KV cache, continuous
  batching (join/evict at step boundaries), token streaming, and
  deterministic seeded sampling keyed by absolute position (identical
  across runs AND across a replay-on-steal).
- :class:`FleetRouter` / :class:`FleetWorker` (router.py): front-end
  router over N workers — canary health checks driving the
  :class:`WorkerHealth` state machine (health.py), retry with capped
  exponential backoff + hedging, preemption-safe draining with
  compiled-ladder handoff, and requeue-never-drop on worker death.
- :mod:`faults` (faults.py): deterministic scripted fault injection
  (hang, slow-start, crash-at-k, corruption, queue wedge, slow-exec)
  for tier-1 recovery-path tests.
- :mod:`controlplane` (controlplane.py): :class:`Autoscaler` (replica
  scaling from queue depth + ``queue_eta_us`` with hysteresis,
  cooldown, drain-based scale-down and warm-handoff scale-up) and
  :class:`PriorityClass` (weighted-round-robin dispatch shares +
  per-class quotas consumed by ``FleetRouter``'s admission control).

Error taxonomy: :class:`RetriableError` is the base; ``ServerBusy``
and ``WorkerLost`` are retriable, ``RequestTimeout`` is terminal
(``retriable`` attribute says which).

Knobs (also README "Serving" / "Serving fleet"):
``MXTPU_SERVING_*`` and ``MXTPU_FLEET_*``.
"""
from .batcher import (Batch, DynamicBatcher, InferenceRequest,
                      RequestTimeout, RetriableError, ServerBusy,
                      WorkerLost)
from .controlplane import Autoscaler, PriorityClass, parse_classes
from .faults import (CorruptEntry, CrashAt, Corrupt, Fault, FaultPlan,
                     Hang, QueueWedge, ReadOnlyDir, SlowExec,
                     SlowStart, SlowStartError, StaleKey,
                     TruncateEntry, WorkerCrashed)
from .generate import (DeviceLogits, GenerateBatcher, GenerateRequest,
                       GenerateRunner, StateTable, sample_token)
from .health import WorkerHealth, WorkerState
from .router import (FleetGenerateRequest, FleetRequest, FleetRouter,
                     FleetWorker)
from .runner import ModelRunner, batch_ladder
from .server import InferenceServer
from .stats import ServingStats

__all__ = ["ModelRunner", "InferenceServer", "DynamicBatcher",
           "ServingStats", "InferenceRequest", "Batch", "ServerBusy",
           "RequestTimeout", "RetriableError", "WorkerLost",
           "batch_ladder",
           "GenerateRunner", "GenerateBatcher", "GenerateRequest",
           "StateTable", "DeviceLogits", "sample_token",
           "FleetRouter", "FleetWorker", "FleetRequest",
           "FleetGenerateRequest",
           "WorkerHealth", "WorkerState",
           "Autoscaler", "PriorityClass", "parse_classes",
           "Fault", "FaultPlan", "Hang", "SlowStart", "CrashAt",
           "Corrupt", "QueueWedge", "WorkerCrashed", "SlowStartError",
           "SlowExec", "CorruptEntry", "TruncateEntry", "StaleKey",
           "ReadOnlyDir"]
