"""schedule_ms_per_req: host milliseconds in the program's ``schedule``
span (the scheduler's ``next_batch`` with the pool's resident set), self
time, per request answered in the traced window."""
from program_spans import ms_per_req


def read(run):
    return ms_per_req(run, "repro.schedule")
