"""The part of the window that lay inside one stage's spans, in %: each
report's interval [end - seconds, end] clipped to the window, merged
where two overlap. 0.0 when the tap ran and the stage never reported."""
from benchmark.lib.tracered import clip, union


def read(obs, stage):
    if obs.get("stages") is None:
        return None
    inside = union(clip(((end - s, end) for name, end, s in obs["stages"]
                         if name == stage), 0.0, obs["seconds"]))
    return 100.0 * sum(b - a for a, b in inside) / obs["seconds"]
