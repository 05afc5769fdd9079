"""Share of the window's placement dispatches that ran on the named
arms of the program's router AND on the accelerator (an arm that ran on
the host CPU backend ends in @cpu and counts for no one), in %. A batched
fire is one dispatch, whatever its lanes."""


def read(obs, arms):
    r = obs.get("routing")
    if not r:
        return None
    delta = {arm: n - r["before"].get(arm, 0)
             for arm, n in r["after"].items()}
    total = sum(delta.values())
    if total <= 0:
        return None
    return 100.0 * sum(delta.get(arm, 0) for arm in arms
                       if not arm.endswith("@cpu")) / total
