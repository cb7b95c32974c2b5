"""``device_idle_pct`` in the saturated served cell, under the name that
moves what that cell reports: the median gap between a stream's tokens
(a tick is the device's time and the host's between two decodes)."""

from chipbench.layer_metrics.device_idle_pct import (  # noqa: F401
    COUNTERS, read)
