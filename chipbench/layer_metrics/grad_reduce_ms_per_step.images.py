"""``grad_reduce_ms_per_step`` in a cell whose samples are images
(everything under ``hvd_step/grad_reduce`` and ``aux_reduce``, the phase
``reduce``), read as that reader reads it.  A per-layer metric moves ONE
end-to-end metric, which every cell it lists reports; Mistral's four-
chip cell reports tokens a second and ResNet's images a second, so the
quantity has an entry for each rate."""

from chipbench.layer_metrics import (
    grad_reduce_ms_per_step as whole)

COUNTERS, read = whole.COUNTERS, whole.read
