"""Share of the window in which the devices sat idle, on average, while
the engine's chunk loop waited for a chunk's inputs: the consumer's
``repro.chunk.input`` spans (the staging queue when pipelined, the
inline sampling when serial; ``bench/spans.py``). None where the program
marks no ``repro.run`` span."""
from pathlib import Path

from bench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    win = spans.window_of(ctx, ROOT)
    return None if win is None else win.idle_share_in(("repro.chunk.input",))
