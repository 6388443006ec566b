"""Host milliseconds a campaign spends staging its scan: the program's
``hermes.init_state`` and ``hermes.upload`` spans (lane states built in
numpy; block tables, trace columns and states put on the device and
gathered per lane) inside the traced campaign, summed (see
``program_trace.span_ms_per_campaign``)."""

import program_trace


def read(run):
    return program_trace.span_ms_per_campaign(run, ("init_state", "upload"))
