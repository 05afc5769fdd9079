"""All the time one stage reported inside the window, in ms, over the
evals that completed in it (a stage that runs only now and then has no
meaningful median)."""


def read(obs, stage):
    if obs.get("stages") is None or not obs.get("evals_done"):
        return None
    total = sum(s for name, end, s in obs["stages"]
                if name == stage and 0.0 <= end < obs["seconds"])
    return total * 1000.0 / obs["evals_done"]
