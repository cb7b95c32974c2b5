"""``program_cache_misses`` in the steady served cell, under the name
that moves what that cell reports: a program built inside the window
stalls every stream (the 99th percentile of the gaps)."""

from chipbench.layer_metrics.program_cache_misses import (  # noqa: F401
    COUNTERS, read)
