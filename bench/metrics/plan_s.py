"""Host planner: seconds of sampler construction (CSR, bucket plans for
both sides, plans onto the device), on the harness's host clock."""


def read(info):
    return info["rec"].counters.get("plan_s")
