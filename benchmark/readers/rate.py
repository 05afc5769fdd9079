"""Work completed inside the window over the window's length."""
from benchmark.lib.window import rate_per_s


def read(obs, series):
    rows = obs["series"].get(series)
    if not rows:
        return None
    return rate_per_s(rows, obs["seconds"])
