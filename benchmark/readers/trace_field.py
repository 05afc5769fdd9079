"""One number of the profiler trace's reduction (lib/tracered.py):
the device's idle share, the placement programs' device time per eval,
or their share of the memory roofline (lib/kernelcost.py)."""
from benchmark.lib.kernelcost import roofline_share_pct


def read(obs, field):
    tr = obs.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    if field == "idle_share_pct":
        return 100.0 * tr["idle_share"]
    if tr["kernel_s"] <= 0 or not obs.get("traced_evals"):
        return None
    if field == "kernel_ms_per_eval":
        return tr["kernel_s"] * 1000.0 / obs["traced_evals"]
    if field == "roofline_pct":
        return roofline_share_pct(obs["traced_floor_bytes"], tr["kernel_s"],
                                  obs["device"]["kind"])
    raise ValueError(f"unknown trace field {field!r}")
