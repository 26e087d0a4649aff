"""Double-buffered sampling/compute pipeline for the chunked sweep engine.

The chunk loop of ``repro.core.queueing._run_engine`` (and of the
sharded executor ``repro.distributed.sweep_shard``) alternates two
phases per chunk: SAMPLE the chunk's randomness on the host, then
DISPATCH the chunk body to the device. Run serially, the device sits
idle during every sample phase. ``iter_staged`` overlaps them: a
producer thread draws chunk ``c+1``'s inputs (through the engine's
FUSED jitted sampler — one dispatch per chunk instead of dozens of
eager ops) while the main thread dispatches chunk ``c``'s compute, with
a bounded ring of staging slots providing backpressure — the
``TransferBufferPool`` idiom: a fixed pool of in-flight buffers, a slot
is acquired before producing into it and released once the consumer has
dispatched the chunk that used it, so at most ``depth`` sampled chunks
(plus the one being consumed) ever exist at once and peak memory stays
O(depth x chunk inputs), independent of the stream length.

Bit-identity: the pipeline changes WHEN inputs are sampled, never WHAT
is sampled — chunk ``c`` still draws from ``fold_in(key, c)`` through
the same sampler, and the fused sampler is bit-identical to the eager
one (pinned by tests/test_multihost.py) — so ``pipeline="on"`` and
``pipeline="off"`` produce bit-identical summaries.

``PipelineStats`` records the last run's pipeline + sampling shape (per
chunk: rows/bytes actually sampled vs the full input block) and the
seconds each side of the pipeline waited (``Waits``), so the benchmark
harness can carry per-host sampled-bytes provenance in BENCH_*.json rows
(``stats_provenance``). Each wait is also a profiler span,
``repro.chunk.input`` on the consumer and ``repro.chunk.slot`` on the
producer, tagged with the caller's engine-call id and the chunk. This
module is deliberately engine-agnostic (no ``queueing`` import): both
execution layers feed it plain callables.
"""
from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time

from jax.profiler import TraceAnnotation

# Staging slots the producer may fill ahead of the consumer (double
# buffering). More buys nothing: sampling one chunk is faster than
# simulating one, so the producer is never the bottleneck at depth 2.
DEFAULT_DEPTH = 2


@dataclasses.dataclass
class PipelineStats:
    """Pipeline + per-host sampling provenance of the last engine run.

    ``*_rows_sampled`` count the input-block rows THIS process actually
    drew per chunk; ``*_rows_total`` the full block's rows (what every
    process sampled before the per-host reduction). ``bytes_*`` are the
    same reduction in bytes of the (gaps, servers, services) inputs.
    ``input_wait_s`` / ``slot_wait_s`` are the run's ``Waits``.
    """

    enabled: bool
    depth: int
    n_chunks: int
    seed_rows_sampled: int
    seed_rows_total: int
    svc_rows_sampled: int
    svc_rows_total: int
    bytes_sampled_per_chunk: int
    bytes_full_per_chunk: int
    process_count: int = 1
    process_index: int = 0
    input_wait_s: float = 0.0
    slot_wait_s: float = 0.0

    @property
    def locality_factor(self) -> float:
        """full-block bytes / per-host sampled bytes (>= 1.0; the
        multi-host sampling reduction of the ISSUE's acceptance bar)."""
        return self.bytes_full_per_chunk / max(self.bytes_sampled_per_chunk,
                                               1)


@dataclasses.dataclass
class Waits:
    """Seconds one ``iter_staged`` loop waited: the consumer for each
    chunk's inputs (``input_s``: the queue when pipelined, the inline
    ``produce`` when serial) and the producer for a free staging slot
    (``slot_s``). Each side writes only its own field."""

    input_s: float = 0.0
    slot_s: float = 0.0


_LAST_STATS: list[PipelineStats | None] = [None]


def record_stats(stats: PipelineStats) -> None:
    """Engine layers call this once per run; benchmarks read it back."""
    _LAST_STATS[0] = stats


def last_stats() -> PipelineStats | None:
    return _LAST_STATS[0]


def stats_provenance() -> dict | None:
    """The last run's stats as a JSON-ready dict (``run.py --json`` rows
    attach it as the ``sampling`` field)."""
    st = last_stats()
    if st is None:
        return None
    out = dataclasses.asdict(st)
    out["locality_factor"] = round(st.locality_factor, 3)
    return out


def iter_staged(produce, n_chunks: int, *, depth: int = DEFAULT_DEPTH,
                enabled: bool = True, call: int = -1,
                waits: Waits | None = None):
    """Yield ``produce(c)`` for ``c in range(n_chunks)``, prefetching up
    to ``depth`` chunks ahead on a producer thread when ``enabled``.

    The producer acquires a staging slot (blocking when ``depth`` chunks
    are already in flight), fills it with ``produce(c)``, and the
    consumer releases the slot after the yield returns — i.e. once the
    caller has dispatched that chunk's compute and come back for the
    next one. Order is preserved exactly (a single producer fills slots
    in chunk order). A producer exception is re-raised here, in the
    consumer, at the chunk that failed; closing the generator early
    (consumer exception) stops the producer promptly via the stop flag
    the slot-acquire loop polls.

    ``enabled=False`` (or a single chunk, where there is nothing to
    overlap) degrades to the plain serial loop — the pipeline-off
    reference path.

    Each wait is timed into ``waits`` (when given) and marked by a
    ``repro.chunk.input`` / ``repro.chunk.slot`` span carrying ``call``
    (the caller's engine-call id) and the chunk.
    """
    waits = Waits() if waits is None else waits
    if not enabled or n_chunks <= 1 or depth < 1:
        for c in range(n_chunks):
            t0 = time.perf_counter()
            with TraceAnnotation("repro.chunk.input", call=call, chunk=c):
                payload = produce(c)
            waits.input_s += time.perf_counter() - t0
            yield payload
        return

    free = threading.Semaphore(depth)       # staging slots (buffer pool)
    ready: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
    stop = threading.Event()

    def producer() -> None:
        for c in range(n_chunks):
            t0 = time.perf_counter()
            with TraceAnnotation("repro.chunk.slot", call=call, chunk=c):
                while not free.acquire(timeout=0.1):
                    if stop.is_set():
                        return
            waits.slot_s += time.perf_counter() - t0
            if stop.is_set():
                return
            try:
                ready.put((c, produce(c), None))
            except BaseException as e:  # surface in the consumer
                ready.put((c, None, e))
                return

    th = threading.Thread(target=producer, name="chunkflow-producer",
                          daemon=True)
    try:
        for c in range(n_chunks):
            t0 = time.perf_counter()
            with TraceAnnotation("repro.chunk.input", call=call, chunk=c):
                if c == 0:
                    # the wait for chunk 0 starts with its producer, which
                    # may hold the interpreter lock until it has sampled
                    th.start()
                got_c, payload, err = ready.get()
            waits.input_s += time.perf_counter() - t0
            assert got_c == c, (got_c, c)
            if err is not None:
                raise err
            yield payload
            free.release()  # chunk dispatched; its slot is reusable
    finally:
        stop.set()
        free.release()  # wake a producer blocked on a full ring
        th.join(timeout=30.0)
