"""Device-busy microseconds per scan step in the traced window: the
union of the device's busy intervals over the scan steps of the window's
campaigns (all lanes of a step together)."""


def read(run):
    steps = sum(c.log["steps"] for c in run.traced)
    if run.profile is None or not steps or not run.profile.busy_s:
        return None
    return run.profile.busy_s / steps * 1e6
