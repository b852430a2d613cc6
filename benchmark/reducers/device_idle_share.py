"""1 - busy / window, averaged over the chips."""

from benchmark import evidence


def reduce(ev):
    line = evidence.device_line(ev)
    return 1.0 - line["busy_s"] / line["window_s"]
