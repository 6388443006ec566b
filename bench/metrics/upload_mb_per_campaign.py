"""Megabytes a campaign puts on the device from the host: the program's
``SCAN_LOG`` counter ``upload_bytes`` (block tables, lane states, lane
trace indices, trace chunks) of the traced campaign, the work behind
``stage_ms_per_campaign``; None where the program does not count it."""


def read(run):
    got = [c.log["upload_bytes"] for c in run.traced
           if "upload_bytes" in c.log]
    return sum(got) / len(got) * 1e-6 if got else None
