"""``serve_step_mfu`` in the cell judged by the gaps between a stream's
tokens: it stands beside the prefill's roofline, which moves their tail."""

from chipbench.layer_metrics.serve_step_mfu import COUNTERS, read  # noqa: F401
