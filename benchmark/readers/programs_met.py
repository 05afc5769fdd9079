"""Programs XLA compiled, or fetched from the persistent cache, inside
the window: each is a stall the warm-up should have taken."""


def read(obs):
    if obs.get("programs_met") is None:
        return None
    return float(sum(1 for t, _kind, _s in obs["programs_met"]
                     if 0.0 <= t < obs["seconds"]))
