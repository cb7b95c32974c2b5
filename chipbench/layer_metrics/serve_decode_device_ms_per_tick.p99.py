"""``serve_decode_device_ms_per_tick`` in the cell judged by the 99th
percentile of the gaps between a stream's tokens, which is one tick and
the widest prefill."""

from chipbench.layer_metrics.serve_decode_device_ms_per_tick import (  # noqa: F401
    COUNTERS, read)
