"""``step_dispatch_ms`` in the steady served cell (there the median
host time of one ``submit`` call, which waits for the batcher's lock),
under the name that moves what that cell reports: the 99th percentile
of the gaps between a stream's tokens."""

from chipbench.layer_metrics.step_dispatch_ms import (  # noqa: F401
    COUNTERS, read)
