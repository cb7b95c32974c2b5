"""``device_idle_pct`` in the steady served cell, under the name that
moves what that cell reports: the 99th percentile of the gaps between a
stream's tokens."""

from chipbench.layer_metrics.device_idle_pct import (  # noqa: F401
    COUNTERS, read)
