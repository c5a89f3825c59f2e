"""Compile/run/transfer profiling for the sharded sweep executor.

Every bucket a jax sweep dispatches gets one :class:`BucketProfile`
with the four phases of its life separated out:

* **pack** — host-side array packing: ``stack_graph_arrays`` / LUT
  stacking / bound-schedule padding plus the engine's geometry build
  (overlaps the *previous* bucket's device compute under the sweep
  engine's async pipeline);
* **compile** — stepper tracing + XLA compilation, attributed from the
  dispatch wall-clock when the call is the *first for its jit cache
  key* (a cache hit dispatches in microseconds, a miss is dominated by
  compilation).  Attribution is per cache key — a set of keys already
  dispatched, not a global cache-size delta — so it stays correct when
  several buckets dispatch concurrently (the streaming service);
* **run** — time spent blocking until the device results are ready
  (under the pipeline this is the wait *remaining* at fetch time, i.e.
  device time not hidden behind host work);
* **transfer** — the single fused device-to-host fetch of the whole
  output pytree;

and its **waves** — the lockstep while-loop iterations the device ran
and the rows' own waves, counted at fetch from the ``steps`` the
stepper already returns (device time over waves is the cost of one) —
and its **settle rounds**, the rounds of each wave's fixed point of
starts and zero-work completions (``_settle``), summed over the rows.

:class:`SweepProfile` aggregates the buckets of one sweep and renders
the one-line summary that ``SweepResult.backend_summary()`` appends.
This module deliberately imports no jax: the sweep engine constructs
profiles even when planning work for jax-free fallbacks, and BENCH
tooling loads :meth:`SweepProfile.to_dict` payloads anywhere.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass
class BucketProfile:
    """One dispatched bucket's accounting (times in seconds)."""

    bucket: str = "?"                #: sweep bucket label
    rows: int = 0                    #: batch rows (before shard padding)
    devices: int = 1                 #: shard count the batch ran on
    #: jit-cache identity: (padded envelope dims, shard count, policy).
    cache_key: Optional[Tuple] = None
    compiled: bool = False           #: did this dispatch grow the cache?
    pack_s: float = 0.0
    dispatch_s: float = 0.0
    compile_s: float = 0.0
    run_s: float = 0.0
    transfer_s: float = 0.0
    #: lockstep while-loop iterations: per shard the most waves of its
    #: real rows, summed over shards (0 until fetched)
    waves: int = 0
    #: waves summed over the real rows (at most ``row_slots``)
    row_waves: int = 0
    #: per shard its real rows times its lockstep waves, summed: the
    #: row-waves stepped, a row's own or idle behind the slowest row
    #: (``rows * waves`` on one shard)
    row_slots: int = 0
    #: rounds of ``_settle``'s fixed point summed over the real rows,
    #: the settle before the first wave included (÷ ``row_waves``:
    #: rounds a wave)
    settle_rounds: int = 0
    #: live rounds of the oracle's water-fill summed over the real rows
    #: (0 where the bucket does not redistribute; ÷ ``row_waves``:
    #: rounds a wave)
    waterfill_rounds: int = 0

    def to_dict(self) -> Dict[str, object]:
        """Flat JSON-ready payload (BENCH records embed these)."""
        return {
            "bucket": self.bucket, "rows": self.rows,
            "devices": self.devices, "compiled": self.compiled,
            "cache_key": (None if self.cache_key is None
                          else [str(k) for k in self.cache_key]),
            "pack_s": self.pack_s, "dispatch_s": self.dispatch_s,
            "compile_s": self.compile_s, "run_s": self.run_s,
            "transfer_s": self.transfer_s,
            "waves": self.waves, "row_waves": self.row_waves,
            "row_slots": self.row_slots,
            "settle_rounds": self.settle_rounds,
            "waterfill_rounds": self.waterfill_rounds,
        }


@dataclass
class SweepProfile:
    """All bucket profiles of one batched sweep."""

    buckets: List[BucketProfile] = field(default_factory=list)

    def add(self, bucket: BucketProfile) -> None:
        """Append one bucket's profile."""
        self.buckets.append(bucket)

    @property
    def compiles(self) -> int:
        """Dispatches that triggered a fresh stepper compilation."""
        return sum(1 for b in self.buckets if b.compiled)

    @property
    def cache_hits(self) -> int:
        """Dispatches served entirely from the jit cache."""
        return sum(1 for b in self.buckets if not b.compiled)

    @property
    def recompiles(self) -> int:
        """Steady-state recompilations: dispatches that compiled for a
        cache key this profile had *already* dispatched earlier.  A
        healthy long-lived service warms each envelope once and then
        reuses it forever — its smoke test asserts this is zero."""
        seen: set = set()
        n = 0
        for b in self.buckets:
            if b.compiled and b.cache_key in seen:
                n += 1
            seen.add(b.cache_key)
        return n

    def compiles_after(self, warmup_buckets: int) -> int:
        """Dispatches beyond the first ``warmup_buckets`` that still
        compiled — the service benchmarks' "zero recompiles after
        warm-up" acceptance gate."""
        return sum(1 for b in self.buckets[warmup_buckets:]
                   if b.compiled)

    def total(self, phase: str) -> float:
        """Sum one phase (``pack``/``dispatch``/``compile``/``run``/
        ``transfer``) over every bucket, in seconds."""
        return sum(getattr(b, f"{phase}_s") for b in self.buckets)

    def summary(self) -> str:
        """The ``backend_summary()`` suffix: jit-cache behaviour plus
        the compile/run/transfer wall-clock split."""
        return (f"jit: {self.compiles} compiled, {self.cache_hits} cached"
                f" | t: pack={self.total('pack'):.3f}s"
                f" compile={self.total('compile'):.3f}s"
                f" run={self.total('run'):.3f}s"
                f" transfer={self.total('transfer'):.3f}s")

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready payload for ``BENCH_*.json`` records."""
        return {
            "compiles": self.compiles, "cache_hits": self.cache_hits,
            "pack_s": self.total("pack"),
            "compile_s": self.total("compile"),
            "run_s": self.total("run"),
            "transfer_s": self.total("transfer"),
            "buckets": [b.to_dict() for b in self.buckets],
        }
