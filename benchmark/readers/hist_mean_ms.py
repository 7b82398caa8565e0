"""Mean of one histogram series over the window, in milliseconds."""

from benchmark.readers.series import hist_mean


def read(observed, series: str, labels: str = ""):
    if observed.get("series_after") is None:
        return None
    mean = hist_mean(observed, series, labels)
    return None if mean is None else mean * 1e3
