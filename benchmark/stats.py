"""The arithmetic behind the end-to-end metrics, apart from any device."""

from __future__ import annotations

from typing import Sequence, Tuple


def completed_rate(
    window_start: float, completions: Sequence[float], work_per_item: float,
    chips: int,
) -> Tuple[float, float]:
    """Work per second per chip over the window's whole time and work.

    ``completions`` are the host-clock times at which each unit of work was
    seen complete; the clock stops at the last of them. -> (rate, seconds)."""
    if not completions:
        raise ValueError("nothing completed inside the window")
    seconds = max(completions) - window_start
    if seconds < 0.25:
        raise ValueError(
            f"a host-clock window of {seconds:.3f} s is too short to time"
        )
    return len(completions) * work_per_item / seconds / chips, seconds
