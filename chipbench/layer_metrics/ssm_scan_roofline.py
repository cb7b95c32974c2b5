"""The selective scans against the chip's roofline: the least time the
chip could take over the device time under ``mamba`` + ``ssd``.  The
least time is the LARGER of the scan's products' FLOPs over the bf16
peak and its unavoidable HBM bytes over the HBM peak
(``chipbench/ssm_flops.py``: the four products of the chunked form at
the chunk length the program ran, the two inside a chunk over the causal
triangle; x, B, C, dt read and y written, forward and backward), for the
tokens the program's own counters say its scans processed in a step
(``horovod_ssm_tokens_total`` / ``horovod_ssm_chunks_total``, summed on
the device: their ratio is the chunk length).  It counts from shapes and
counters, never from the operations an implementation happens to run,
so a later kernel is read by the same yardstick; recomputation earns
nothing."""

from chipbench import scope_join, scope_time, ssm_flops

COUNTERS = ["horovod_ssm_tokens_total", "horovod_ssm_chunks_total"]


def read(ctx):
    tokens, chunks = (scope_join.counter_delta(ctx, name)
                      for name in COUNTERS)
    ms = scope_time.ms_per_step(ctx, scope_time.under("mamba", "ssd"))
    if tokens <= 0 or chunks <= 0 or ms is None:
        return None
    per_step = tokens / ctx["window"]["steps"] / ctx["ranks"]
    floor_s = per_step * max(
        ssm_flops.scan_train_flops_per_token(ctx["config"], tokens / chunks)
        / ctx["peaks"]["bf16_flops_per_s"],
        ssm_flops.scan_train_bytes_per_token(ctx["config"])
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms / 1e3)
