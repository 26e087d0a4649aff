"""The work of a cell, counted from its definition and never from the code.

A copy-step is one copy of one arrival in one valid cell: a call of
``M`` arrivals over cells with replication ``k_c`` does ``M * sum_c k_c``
of them, whichever body runs them.

The roofline floor of the chunk body charges the work of the semantics:

- operations: per copy-step 3 (start = max(free, dispatch), finish =
  start + service, the response's min over copies); per arrival and
  cell 3 (arrival time = offset / rate, response = finish - arrival +
  overhead); per post-warm-up arrival and cell 1 for the mean's sum and,
  where percentiles are asked for, 1 for the histogram increment; per
  copy-step of a cell with a timed policy (hedge after delay, timeout
  retry) 3 more (dispatch = arrival + delay * offset, a multiply and an
  add; the gate against the best finish so far, a compare); per
  copy-step of a degraded cell 1 more (the straggler select);
- bytes: each sampled input read once (gaps and copy sets per seed row,
  one service column per copy per law and seed row and, where any cell
  is degraded, one degradation uniform per copy per law and seed row,
  4 bytes each), and the per-cell carry (free times, sum, count and,
  with percentiles, the histogram) read and written once per chunk.

A copy-step of a timed cell is one copy of its budget k, whether or not
the copy fires.

Nothing an implementation chooses (Kahan terms, one-hot bin adds,
padding, re-layouts) is counted, so the kernel, the scan and any later
re-layout of one cell are charged the same floor.
"""
from __future__ import annotations

import dataclasses
import math

# Published per-chip peaks, keyed by JAX's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16), 16 GB of
# HBM at 819 GB/s. A kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

BYTES = 4  # float32 / int32


def device_peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind="
                         f"{device_kind!r}; add them to bench/work.py "
                         f"PEAKS with their source") from None


@dataclasses.dataclass(frozen=True)
class Call:
    """One engine call as the cell defines it: ``ks`` the replication of
    each cell, ``seed_rows`` the sampled arrival rows, ``svc_rows`` the
    sampled service rows (laws x seeds), ``k_max`` the copies drawn;
    ``timed`` and ``degraded`` flag each cell with a timed policy or a
    degradation (empty: none)."""

    ks: tuple[int, ...]
    seed_rows: int
    svc_rows: int
    k_max: int
    n_arrivals: int
    chunk: int | None
    warmup: int
    n_servers: int
    n_bins: int  # 0 without percentiles
    timed: tuple[bool, ...] = ()
    degraded: tuple[bool, ...] = ()

    @property
    def copy_steps(self) -> int:
        return self.n_arrivals * sum(self.ks)

    @property
    def n_chunks(self) -> int:
        t = self.n_arrivals if self.chunk is None else self.chunk
        return math.ceil(self.n_arrivals / t)

    @property
    def ops(self) -> float:
        cells = len(self.ks)
        post = self.n_arrivals - self.warmup
        timed = sum(k for k, t in zip(self.ks, self.timed) if t)
        degraded = sum(k for k, d in zip(self.ks, self.degraded) if d)
        return float(3 * self.copy_steps + 3 * self.n_arrivals * cells
                     + (1 + (self.n_bins > 0)) * post * cells
                     + self.n_arrivals * (3 * timed + degraded))

    @property
    def bytes(self) -> float:
        m = self.n_arrivals
        svc_cols = self.k_max * (1 + any(self.degraded))
        inputs = BYTES * m * (self.seed_rows * (1 + self.k_max)
                              + self.svc_rows * svc_cols)
        carry = BYTES * len(self.ks) * (self.n_servers + 2 + self.n_bins)
        return float(inputs + 2 * carry * self.n_chunks)


def floor_seconds(calls, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for ``calls``: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s, and which
    of the two bounds it."""
    peaks = device_peaks(device_kind)
    t_ops = sum(c.ops for c in calls) / peaks["flops"]
    t_bytes = sum(c.bytes for c in calls) / peaks["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), ("operations" if t_ops >= t_bytes
                                 else "bytes")
