"""L3 request scheduling: dynamic batching + SLA-aware admission.

The component the whole latency/throughput metric hinges on (SURVEY.md
§3.2): concurrent ``/predict`` requests accumulate into batches under a
max-batch-size (``max_batch=32``, BASELINE.json:10) + max-wait policy,
one jitted dispatch serves the whole batch, and per-item results are
routed back to each request's future.

Round 7 adds the request-lifecycle scheduler on top: priority classes
and deadlines (``policy.DeadlineQueue``), KV-footprint admission and
the drain gate (``admission.AdmissionController``), preemption of
batch-class streams for interactive arrivals (engine/streams.py), and
graceful SIGTERM drain (``Batcher.begin_drain``/``drained``).
"""

from .admission import AdmissionController  # noqa: F401
from .batcher import Batcher, QueueFullError  # noqa: F401
from .policy import (  # noqa: F401
    BATCH,
    CLASSES,
    INTERACTIVE,
    Arrival,
    DeadlineExceededError,
    DeadlineQueue,
)
