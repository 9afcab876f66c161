"""gather_prep_ms_per_req: host milliseconds in the program's
``gather_prep`` spans, the host side of the kernel call before its
dispatch (residency check, slot remap, hole check, id padding, and the
``upload`` of ids and block map nested inside), per request answered in
the traced window."""
from program_spans import ms_per_req


def read(run):
    return ms_per_req(run, "repro.gather_prep")
