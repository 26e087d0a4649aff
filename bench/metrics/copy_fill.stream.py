"""Share of the copy slots the chunk body computed that hold a copy within
its cell's budget: the window's copy-steps per arrival within budget (the
sum over engine calls of the sum over cells of k, ``work.Call.ks``) over
the slots the body computed per arrival (the sum over the calls'
``repro.run`` spans of ``lanes`` x ``k_max``: pad lanes, and the masked
copies of cells whose k is under the call's budget, included). The rest
is what grouping cells by k would save. Notes the calls' cells on a
timed policy and with a degradation model. None where the program marks
no ``repro.run`` span in the window or its spans carry no ``k_max``."""
from pathlib import Path

from bench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    win = spans.window_of(ctx, ROOT)
    if win is None:
        return None
    runs = [s.meta for s in win.spans if s.name == spans.ENGINE]
    if not runs or not all("k_max" in m for m in runs):
        return None
    within = sum(sum(c.ks) for c in ctx.calls)
    slots = sum(m["lanes"] * m["k_max"] for m in runs)
    ctx.notes.append(
        f"copy_fill within={within} slots={slots} "
        f"cells={sum(m['cells'] for m in runs)} "
        f"timed={sum(m['timed'] for m in runs)} "
        f"degraded={sum(m['degraded'] for m in runs)}")
    return within / slots
