"""Bucketed cohort shapes: the jit-cache contract of the serving tier.

A continuous-ingestion front end closes rounds with whatever cohort
size ``m`` the window produced — naively that means one fresh XLA
compile per distinct ``m`` (tens of entries, each costing hundreds of
milliseconds on a CPU mesh; measured
by ``benchmarks/serving_bench.py``'s bucketed-vs-naive lane). Ragged
Paged Attention solves the same problem for attention by processing
ragged batches through a small set of padded block shapes; here the
ladder is powers of two up to the cohort cap, so EVERY cohort lands in
one of ``log2(cap)+1`` compiled programs and the masked finalize
(``ops.robust``) keeps the result exactly equal to the unpadded
aggregate.
"""

from __future__ import annotations

from typing import Tuple


class BucketLadder:
    """Power-of-two bucket sizes ``min_bucket, 2·min_bucket, ..., cap``.

    ``cap`` is rounded UP to the next power-of-two multiple of
    ``min_bucket`` so the top bucket can always hold a full cohort (the
    scheduler never drains more than ``cap`` submissions per round)."""

    __slots__ = ("sizes",)

    def __init__(self, cap: int, *, min_bucket: int = 2) -> None:
        if cap <= 0 or min_bucket <= 0:
            raise ValueError("cap and min_bucket must be >= 1")
        if min_bucket > cap:
            raise ValueError(f"min_bucket {min_bucket} > cap {cap}")
        sizes = [min_bucket]
        while sizes[-1] < cap:
            sizes.append(sizes[-1] * 2)
        self.sizes: Tuple[int, ...] = tuple(sizes)

    @property
    def cap(self) -> int:
        """Largest bucket (== the scheduler's max cohort size)."""
        return self.sizes[-1]

    def bucket_for(self, m: int) -> int:
        """Smallest ladder size that holds an ``m``-row cohort."""
        if m <= 0:
            raise ValueError(f"cohort size must be >= 1 (got {m})")
        for size in self.sizes:
            if m <= size:
                return size
        raise ValueError(
            f"cohort of {m} exceeds the bucket cap {self.cap} — the "
            "scheduler must drain at most cap submissions per round"
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"BucketLadder(sizes={self.sizes})"


__all__ = ["BucketLadder"]
