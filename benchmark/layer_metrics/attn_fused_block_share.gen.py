"""Prefill q blocks the fused attention kernel ran over all prefill q blocks run (gen.q_blocks_fused / gen.q_blocks_run over the window's batches, from the program's `serve:deliver` records: benchmark/deliver_records.py): the kernel's engagement, the window-less per-head layers where Mosaic compiles. The reference has no such metric."""
from benchmark import deliver_records


def read(rec):
    return deliver_records.share(rec, "gen.q_blocks_fused", "gen.q_blocks_run")
