"""Roofline share of the dense forward layers in a rollout: the least
time of every dense layer the scan ran (one forward of the whole batch
per live round) over the device time under ``mrsch.kernel.fused_mlp``
(the kernel with its pads and slices), in %."""
from harness import readers, work


def read(data):
    if "live_rounds" not in data:
        return None
    least = data["live_rounds"] * work.dense_least_time(
        data["config"], data["batch_rows"], data["peaks"])
    return readers.roofline(data, "fused_mlp", least)
