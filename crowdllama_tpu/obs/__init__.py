"""Swarm-wide observability plane: request tracing + histogram metrics.

Every node (gateway and worker) owns one :class:`NodeObs` holding

- a bounded :class:`~crowdllama_tpu.obs.trace.TraceBuffer` of per-request
  span trees, exposed as JSON at ``GET /debug/trace``;
- a :class:`~crowdllama_tpu.obs.metrics.NodeMetrics` bundle of the three
  fixed-bucket histograms (``crowdllama_request_seconds``,
  ``crowdllama_ttft_seconds``, ``crowdllama_decode_step_seconds``)
  rendered into the Prometheus text exposition on ``GET /metrics``.

Trace ids ride the ``llama.v1.BaseMessage`` envelope (``trace_id`` /
``parent_span``, proto fields 5/6 outside the oneof) so one id follows a
request gateway -> stream pool -> worker peer -> engine, including across
the relay splice (the splice forwards sealed ciphertext, so the fields
cross it untouched).  See docs/OBSERVABILITY.md for the span catalogue and
the ``/debug/trace`` schema.
"""

from __future__ import annotations

from crowdllama_tpu.obs.metrics import (  # noqa: F401
    DECODE_STEP_BUCKETS,
    REQUEST_BUCKETS,
    TTFT_BUCKETS,
    Histogram,
    HistogramVec,
    LabelGuard,
    NodeMetrics,
)
from crowdllama_tpu.obs.trace import (  # noqa: F401
    DEFAULT_TRACE_CAPACITY,
    SPAN_DECODE_STEP,
    SPAN_DISPATCH_WAIT,
    SPAN_PREFILL,
    SPAN_PREFILL_EXEC,
    SPAN_WORKER_QUEUE,
    Span,
    TraceBuffer,
    new_trace_id,
)

GATEWAY_ROOT_SPAN = "gateway"

# Engine/scheduler gauge keys every Engine.obs_gauges() returns; the
# exposition layer maps them to crowdllama_engine_<key> gauges on both the
# gateway and the worker /metrics endpoints.
ENGINE_GAUGES = (
    "pending_depth",
    "active_slots",
    "batch_occupancy",
    "kv_cache_utilization",
)


class NodeObs:
    """One node's tracing + metrics state (gateway or worker).

    ``trace_ttl`` (seconds, 0 = off) age-evicts span fragments so the
    trace collector never stitches stale data; ``exemplars`` enables the
    OpenMetrics trace_id exemplar suffix on the request-path histograms.
    """

    def __init__(self, trace_capacity: int = DEFAULT_TRACE_CAPACITY,
                 node: str = "",
                 trace_ttl: float = 0.0, exemplars: bool = False) -> None:
        self.node = node
        self.trace = TraceBuffer(capacity=trace_capacity, node=node,
                                 ttl=trace_ttl)
        self.metrics = NodeMetrics(exemplars=exemplars)

    def observe_generate(self, trace_id: str, parent: str, model: str,
                         queue_ns: int, prefill_ns: int, decode_ns: int,
                         steps: int, total_ns: int, *, start_ns: int = 0,
                         dispatch_wait_ns: int = 0, prefill_exec_ns: int = 0,
                         **meta) -> None:
        """Record one served generate exchange: worker-side spans + histograms.

        Called at the Engine seam so FakeEngine and JaxEngine produce the
        same span catalogue (worker_queue / prefill / decode_step).
        ``start_ns`` is the absolute monotonic_ns at which the queue wait
        began; the three spans follow it back to back (they are
        consecutive intervals of the engine's stamps), so ``/debug/trace``
        shows a timeline.  A non-zero ``dispatch_wait_ns`` /
        ``prefill_exec_ns`` pair adds the two children of ``prefill``.
        """
        self.metrics.request_seconds.labels(model).observe(
            total_ns / 1e9, exemplar=trace_id)
        self.metrics.ttft_seconds.observe(
            (queue_ns + prefill_ns) / 1e9, exemplar=trace_id)
        if trace_id:
            t = self.trace
            t.begin(trace_id, model=model, **meta)

            def at(offset_ns: int) -> int | None:
                return start_ns + offset_ns if start_ns else None

            t.record(trace_id, SPAN_WORKER_QUEUE, queue_ns, parent=parent,
                     start_ns=at(0))
            t.record(trace_id, SPAN_PREFILL, prefill_ns, parent=parent,
                     start_ns=at(queue_ns))
            if dispatch_wait_ns or prefill_exec_ns:
                t.record(trace_id, SPAN_DISPATCH_WAIT, dispatch_wait_ns,
                         parent=SPAN_PREFILL, start_ns=at(queue_ns))
                t.record(trace_id, SPAN_PREFILL_EXEC, prefill_exec_ns,
                         parent=SPAN_PREFILL,
                         start_ns=at(queue_ns + dispatch_wait_ns))
            t.record(trace_id, SPAN_DECODE_STEP, decode_ns, parent=parent,
                     start_ns=at(queue_ns + prefill_ns), steps=steps)
            t.finish(trace_id, total_ns)
