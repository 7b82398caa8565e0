"""Differences of the engine's exported series over the window."""

from __future__ import annotations

from typing import Any, Dict, Optional


def delta(observed: Dict[str, Any], key: str) -> float:
    return (observed["series_after"].get(key, 0.0)
            - observed["series_before"].get(key, 0.0))


def hist_mean(observed: Dict[str, Any], series: str, labels: str = ""
              ) -> Optional[float]:
    """Mean of a histogram's samples taken inside the window, None
    where it took none."""
    count = delta(observed, f"{series}_count{labels}")
    if count <= 0:
        return None
    return delta(observed, f"{series}_sum{labels}") / count
