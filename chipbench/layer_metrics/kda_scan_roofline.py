"""The delta rule against the chip's roofline: the least time the chip
could take over the device time under ``kda`` + ``delta``.  The least
time is the LARGER of the rule's FLOPs over the bf16 peak and its
unavoidable HBM bytes over the HBM peak (``chipbench/kimi_linear_flops.py``:
the recurrence's three state-sized products a token and a head, which
no chunk length enters; q, k, v, g, beta read and o written, forward and
backward), for the tokens the program's own counter says its rules
processed in a step (``horovod_kda_tokens_total``, summed on the
device).  It counts from shapes and the counter, never from the
operations an implementation happens to run, so a later kernel is read
by the same yardstick; the chunked form's extra arithmetic and
recomputation earn nothing.  A share over 105% is an error of the count
and is refused."""

from chipbench import kimi_linear_flops, scope_join, scope_time

COUNTERS = ["horovod_kda_tokens_total"]


def read(ctx):
    tokens = scope_join.counter_delta(ctx, COUNTERS[0])
    ms = scope_time.ms_per_step(ctx, scope_time.under("kda", "delta"))
    if tokens <= 0 or ms is None:
        return None
    per_step = tokens / ctx["window"]["steps"] / ctx["ranks"]
    floor_s = per_step * max(
        kimi_linear_flops.rule_train_flops_per_token(ctx["config"])
        / ctx["peaks"]["bf16_flops_per_s"],
        kimi_linear_flops.rule_train_bytes_per_token(ctx["config"])
        / ctx["peaks"]["hbm_bytes_per_s"])
    share = 100.0 * floor_s / (ms / 1e3)
    if share > 105.0:
        raise ValueError(
            f"kda_scan_roofline reads {share:.1f}%: the rule's operations "
            "or bytes are counted too high, or the scope leaves out part "
            "of the work")
    return share
