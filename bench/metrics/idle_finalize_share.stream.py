"""Share of the window in which the devices sat idle, on average, inside
the engine's ``repro.finalize`` spans: the summary's eager dispatch after
the last chunk, and on a mesh across processes the gather in front of it
(``bench/spans.py``). None where the program marks no ``repro.run``
span. Prints the split of the idle share by the engine's spans
(``spans.note_split``)."""
from pathlib import Path

from bench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    win = spans.window_of(ctx, ROOT)
    if win is None:
        return None
    share = win.idle_share_in(("repro.finalize",))
    spans.note_split(ctx, win)
    return share
