"""How far one of the program's counters moved over the window (read at
the window's open and at its close)."""


def read(obs, key):
    c = obs.get("counters")
    if not c or key not in c["after"]:
        return None
    return c["after"][key] - c["before"].get(key, 0.0)
