"""A served run with the timed path BROKEN underneath: plants one fault
in the program's serving code, then calls ``chipbench.run.main`` with the
remaining arguments.  ``correct`` has to come out false.  The CPU tests
run it with ``--rehearse 1``; the builder runs it on the chip.

    python3 chipbench/tools/serve_fault.py <fault> --workload <cell> --seed <n> --seconds <s> --trace 0

Faults: ``row_unwritten`` (decode leaves the first slot's new row of the
cache as it was), ``table_shifted`` (decode's block tables shifted by one
block), ``window_off_by_one`` (decode attends one key fewer than the
window: seen only where the window binds, at the rehearsal sizes),
``neighbour_slot`` (decode reads and writes each slot through the
neighbouring slot's block table).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plant(fault):
    import jax.numpy as jnp

    from horovod_tpu.serving import kvcache

    decode, attention = kvcache._decode_fwd, kvcache._paged_attention

    def row_unwritten(params, k_pool, v_pool, toks, pos, tables, active, *,
                      cfg, angles, bt):
        tok, k_new, v_new = decode(params, k_pool, v_pool, toks, pos,
                                   tables, active, cfg=cfg, angles=angles,
                                   bt=bt)
        block, offset = tables[0, pos[0] // bt], pos[0] % bt
        return (tok,
                k_new.at[:, block, offset].set(k_pool[:, block, offset]),
                v_new.at[:, block, offset].set(v_pool[:, block, offset]))

    def with_tables(change):
        def broken(params, k_pool, v_pool, toks, pos, tables, active, **kw):
            return decode(params, k_pool, v_pool, toks, pos, change(tables),
                          active, **kw)
        return broken

    def window_off_by_one(q, k, v, q_pos, window):
        return attention(q, k, v, q_pos,
                         None if window is None else window - 1)

    if fault == "row_unwritten":
        kvcache._decode_fwd = row_unwritten
    elif fault == "table_shifted":
        kvcache._decode_fwd = with_tables(
            lambda tables: jnp.roll(tables, 1, axis=1))
    elif fault == "neighbour_slot":
        kvcache._decode_fwd = with_tables(
            lambda tables: jnp.roll(tables, 1, axis=0))
    elif fault == "window_off_by_one":
        kvcache._paged_attention = window_off_by_one
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    from chipbench import run as harness

    plant(sys.argv[1])
    sys.exit(harness.main(sys.argv[2:]))
