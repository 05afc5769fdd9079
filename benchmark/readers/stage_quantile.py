"""A quantile, in ms, of one utils/stages stage over the reports that
ended inside the window (the harness's tap on the trace hook)."""
from benchmark.lib.window import quantile


def read(obs, stage, q):
    vals = [s * 1000.0 for name, end, s in obs.get("stages") or []
            if name == stage and 0.0 <= end < obs["seconds"]]
    return quantile(vals, q)
