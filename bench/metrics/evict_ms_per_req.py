"""evict_ms_per_req: host milliseconds in the program's ``evict`` spans
(``BufferPool._evict_one``: the victim's choice and the slot's release),
per request answered in the traced window."""
from program_spans import ms_per_req


def read(run):
    return ms_per_req(run, "repro.evict")
