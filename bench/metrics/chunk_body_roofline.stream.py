"""The chunk body's share of its roofline, in percent: the least time
one chip could take for the window's work (``work.floor_seconds``:
operations over peak FLOP/s or bytes over peak bytes/s, whichever is
larger) over the body's device time summed over devices. The work is
the semantics' own, so every implementation of the body is charged the
same floor."""

from bench import work

PROGRAMS = ("_sweep_chunk_cells", "chunk_body")


def read(ctx):
    seconds = ctx.reduced.program_s(PROGRAMS)
    if not seconds:
        return None
    floor, bound = work.floor_seconds(ctx.calls, ctx.device_kind)
    ctx.notes.append(f"chunk_body_roofline bound_by={bound} "
                     f"floor_s={floor!r} body_s={seconds!r}")
    return 100.0 * floor / seconds
