"""One series' growth over the window as a share (%) of the growth of
several (itself among them): what part of a counted quantity was of one
kind."""

from benchmark.readers.series import delta


def read(observed, part: str, whole: list):
    if observed.get("series_after") is None:
        return None
    total = sum(delta(observed, key) for key in whole)
    if total <= 0:
        return None
    return 100.0 * delta(observed, part) / total
