"""Share of the device's busy time spent in the engine's jitted chunk
samplers (``queueing._sample_chunk_fused``, the pipeline's one dispatch
per chunk, and ``_sample_sweep_arrivals``)."""

PROGRAMS = ("_sample_chunk_fused", "_sample_sweep_arrivals")


def read(ctx):
    seconds = ctx.reduced.program_s(PROGRAMS)
    busy = sum(ctx.reduced.busy_ns) * 1e-9
    if seconds is None or busy <= 0:
        return None
    return seconds / busy
