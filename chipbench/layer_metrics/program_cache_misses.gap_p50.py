"""``program_cache_misses`` in the saturated served cell, under the name
that moves what that cell reports: a program built inside the window
stalls every stream (the median gap between their tokens)."""

from chipbench.layer_metrics.program_cache_misses import (  # noqa: F401
    COUNTERS, read)
