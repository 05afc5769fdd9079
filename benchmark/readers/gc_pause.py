"""Collector pauses that began inside the window, from gc.callbacks:
the longest in ms, or their share of the window in %."""


def read(obs, stat):
    if obs.get("gc_pauses") is None:
        return None
    mine = [s for t, s, _gen in obs["gc_pauses"]
            if 0.0 <= t < obs["seconds"]]
    if stat == "max_ms":
        return max(mine) * 1000.0 if mine else 0.0
    if stat == "share_pct":
        return 100.0 * sum(mine) / obs["seconds"]
    raise ValueError(f"unknown gc_pause stat {stat!r}")
