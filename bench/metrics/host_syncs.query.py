"""Host-device boundary: reads of a device value on the host per traced
request (``repro.sync.*`` spans inside ``repro.service.request`` spans).
A count: it repeats exactly for one query shape."""

import program_spans


def read(run):
    return program_spans.host_syncs(program_spans.traced(run))
