"""Prefill q blocks run over q blocks the padded rows hold, the attention layers summed (gen.q_blocks_run / gen.q_blocks_total over the window's batches, from the program's `serve:deliver` records: benchmark/deliver_records.py): the part of prefill attention the prompts' lengths ask for, since a block past a row's length is a branch not taken. The reference has no such metric."""
from benchmark import deliver_records


def read(rec):
    return deliver_records.share(rec, "gen.q_blocks_run", "gen.q_blocks_total")
