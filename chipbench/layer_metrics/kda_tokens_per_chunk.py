"""The chunk length the delta rules ran: the tokens their scans
processed in the window over the chunks those took
(``horovod_kda_tokens_total`` / ``horovod_kda_chunks_total``, the
program's own counts, summed on the device).  The configuration's
``kda_chunk_size`` where every row fills its chunks."""

from chipbench import scope_join

COUNTERS = ["horovod_kda_tokens_total", "horovod_kda_chunks_total"]


def read(ctx):
    tokens, chunks = (scope_join.counter_delta(ctx, name)
                      for name in COUNTERS)
    if tokens <= 0 or chunks <= 0:      # a commit without the counters
        return None
    return tokens / chunks
