"""A quantile of a series the client took on its own clock, over every
sample whose request was due (or sent) inside the window."""
from benchmark.lib.window import in_window, quantile


def read(obs, series, q):
    rows = obs["series"].get(series) or []
    return quantile(in_window([t for t, _v in rows], [v for _t, v in rows],
                              obs["seconds"]), q)
