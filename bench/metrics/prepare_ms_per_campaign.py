"""Host milliseconds a campaign spends preparing its traces: the
program's ``hermes.prepare_trace`` spans (digest, ``_PREP_CACHE``
lookup, the frozen block and page tables) inside the traced campaign,
summed (see ``program_trace.span_ms_per_campaign``)."""

import program_trace


def read(run):
    return program_trace.span_ms_per_campaign(run, ("prepare_trace",))
