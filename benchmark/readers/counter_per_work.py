"""How far one of the program's counters moved over the window, per
unit of the work completed inside it (the series the rate reads), in
units of `per`: WAL bytes per placement in KB with per = 1000."""
from benchmark.lib.window import in_window


def read(obs, key, series, per):
    c = obs.get("counters")
    rows = obs["series"].get(series)
    if not c or key not in c["after"] or key not in c["before"] or not rows:
        return None
    work = sum(in_window([t for t, _v in rows], [v for _t, v in rows],
                         obs["seconds"]))
    if work <= 0:
        return None
    return (c["after"][key] - c["before"][key]) / per / work
