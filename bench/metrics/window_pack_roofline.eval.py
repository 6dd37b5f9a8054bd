"""Roofline share of ``window_pack`` in a rollout: the bytes of packing
the first K waiting jobs of every environment once per live round, at
the chip's HBM bandwidth, over the device time under
``mrsch.kernel.window_pack``, in %.  K is the window, or the attention
module's queue_cap."""
from harness import readers, work


def read(data):
    if "live_rounds" not in data:
        return None
    cfg = data["config"]
    k = cfg["queue_cap"] if cfg["state_module"] == "attention" else cfg["window"]
    f = len(cfg["resources"]) + 2
    nbytes = work.window_pack_bytes(data["batch_rows"], cfg["trace_jobs"], k, f)
    least = data["live_rounds"] * work.least_time(0, nbytes, data["peaks"])
    return readers.roofline(data, "window_pack", least)
