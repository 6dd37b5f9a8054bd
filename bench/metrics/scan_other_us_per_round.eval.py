"""Device time of the rollout scan outside every ``mrsch.kernel.*``
scope (event pump, obs build, allocation, backfill, control flow) per
scan round, in microseconds."""


def read(data):
    other = (data.get("scope_s") or {}).get("other")
    if other is None or not data.get("scan_rounds"):
        return None
    return 1e6 * other / data["scan_rounds"]
