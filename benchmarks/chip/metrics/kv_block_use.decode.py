"""Step-weighted mean share of the paged KV pool's blocks in use over
the window (``EngineSnapshot.kv_block_utilization``), in %."""


def read(run):
    s = run.snapshot
    return 100.0 * s.kv_block_utilization if s.steps and s.kv_blocks_total \
        else None
