"""Tokens trained per second per chip: the mix's samples are tokens."""

from chipbench.end_to_end.samples_per_s_per_chip import read  # noqa: F401
