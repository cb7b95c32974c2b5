"""``grad_reduce_async_span_ms_per_step`` in a cell whose samples are
images (how long an asynchronous all-reduce is in flight, on the chip
where it is longest), read as that reader reads it.  A per-layer metric
moves ONE end-to-end metric, which every cell it lists reports;
Mistral's four-chip cell reports tokens a second and ResNet's images a
second, so the quantity has an entry for each rate."""

from chipbench.layer_metrics import (
    grad_reduce_async_span_ms_per_step as whole)

COUNTERS, read = whole.COUNTERS, whole.read
