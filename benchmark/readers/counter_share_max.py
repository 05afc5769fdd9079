"""How near its limit the nearest of several counters stood at the
window's close, in %: the largest of value / limit over `pairs` of
counter keys (a snapshot is due when either its byte or its entry count
reaches its trigger)."""


def read(obs, pairs):
    c = obs.get("counters")
    if not c:
        return None
    after = c["after"]
    shares = [after[value] / after[limit] for value, limit in pairs
              if value in after and after.get(limit)]
    return 100.0 * max(shares) if shares else None
