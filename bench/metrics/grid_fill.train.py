"""Grid plans' fill: rated slots over the slots sent to the statistics
pass, summed over the user and item grid plans and every shard, in %. Read
from the program's own count, `DistributedBPMF.plan_stats()`, made on the
host when the plans are built."""


def read(info):
    stats = info["layer"].get("plan_stats")
    if not stats:
        return None
    rated = sum(sum(side["rated"]) for side in stats.values())
    sent = sum(sum(side["sent"]) for side in stats.values())
    return 100.0 * rated / sent if sent else None
