"""Share of the window's placement dispatches that the router sent to
the accelerator (device_stats_snapshot's per-arm counts; arms that ran
on the host CPU backend end in @cpu), in %."""


def read(obs):
    r = obs.get("routing")
    if not r:
        return None
    delta = {arm: n - r["before"].get(arm, 0)
             for arm, n in r["after"].items()}
    total = sum(delta.values())
    if total <= 0:
        return None
    accel = sum(n for arm, n in delta.items() if not arm.endswith("@cpu"))
    return 100.0 * accel / total
