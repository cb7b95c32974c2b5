"""chipbench: the benchmark of horovod_tpu on the chip (see README.md)."""
