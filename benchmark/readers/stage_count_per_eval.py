"""How many times one stage reported inside the window, over the evals
that completed in it: 0.0 when the tap ran and the stage never did
(nothing only without a tap, or a window that completed no eval)."""


def read(obs, stage):
    if obs.get("stages") is None or not obs.get("evals_done"):
        return None
    n = sum(1 for name, end, _s in obs["stages"]
            if name == stage and 0.0 <= end < obs["seconds"])
    return n / obs["evals_done"]
