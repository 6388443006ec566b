"""Host milliseconds a campaign spends turning its lanes' counters into
``Metrics``: the program's ``hermes.metrics_from_outputs`` spans (one
per real lane) inside the traced campaign, summed (see
``program_trace.span_ms_per_campaign``)."""

import program_trace


def read(run):
    return program_trace.span_ms_per_campaign(run, ("metrics_from_outputs",))
