"""Host milliseconds of a campaign outside compiling and scanning: the
benchmark's span around ``run_batch`` and the lanes' Metrics rows, less
the campaign's ``SCAN_LOG`` ``compile_s + scan_s``.  That leaves trace
preparation, state construction, lane stacking and the export to
``Metrics``.  Mean over the window's campaigns."""


def read(run):
    if not run.campaigns:
        return None
    host = [(c.t1 - c.t0 - c.log["compile_s"] - c.log["scan_s"]) * 1e3
            for c in run.campaigns]
    return sum(host) / len(host)
