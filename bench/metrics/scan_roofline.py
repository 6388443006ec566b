"""Share of the HBM roofline: the least time the modelled work of the
traced campaign needs at the device's peak bandwidth
(``bench/peaks.json``), over its device-busy time.  The work is counted by ``workcount`` from the
configuration's geometry and the lanes' exported probe counters; it
moves no floating-point operations, so bandwidth bounds it."""

import workcount


def read(run):
    if run.profile is None or not run.profile.busy_s:
        return None
    system = run.cell.config["system"]
    moved = sum(workcount.campaign_bytes(system, c.log["steps"],
                                         c.log["traces"],
                                         [oi for oi, _ in c.outs])
                for c in run.traced)
    least_s = moved / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.profile.busy_s
