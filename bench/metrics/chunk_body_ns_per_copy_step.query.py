"""Device nanoseconds of the chunk body per copy-step: the device time of
the programs below, summed over devices, over the copy-steps of the
window's queries (``work.Call.copy_steps``). The body is
``queueing._sweep_chunk_cells`` (the cell-update kernel or the scan,
histogram fold included), or its sharded wrapper ``chunk_body``."""

PROGRAMS = ("_sweep_chunk_cells", "chunk_body")


def read(ctx):
    seconds = ctx.reduced.program_s(PROGRAMS)
    steps = sum(c.copy_steps for c in ctx.calls)
    if seconds is None or steps == 0:
        return None
    return seconds * 1e9 / steps
