"""Share of the KV positions the decode gather reads that rows really
hold: per step, the context lengths of the rows that decoded over the
cohort bucket's rows times the positions one row's gather reads
(``blocks_per_slot x block_size``), over the window's steps."""


def read(run):
    read_pos = sum(s.bucket for s in run.steps) * run.kv_read_positions
    if not read_pos:
        return None
    return 100.0 * sum(s.context for s in run.steps) / read_pos
