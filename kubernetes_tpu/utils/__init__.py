from kubernetes_tpu.utils.trace import (
    PhaseSpan,
    Span,
    chrome_trace,
    current_trace_id,
    current_traceparent,
    format_traceparent,
    parse_traceparent,
    use_traceparent,
)
from kubernetes_tpu.utils.metrics import Histogram, Counter, Gauge, REGISTRY
