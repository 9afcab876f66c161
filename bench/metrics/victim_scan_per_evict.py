"""victim_scan_per_evict: pages ``BufferPool._pick_victim`` examined per
eviction in the traced window: the change of the program's counters
``victim_scanned`` over ``evictions`` between the first and the last
``repro.evict`` span of the window, each of which carries both."""
from program_spans import stamps


def read(run):
    marks = [a for _, a in stamps(run, "repro.evict")
             if "victim_scanned" in a and "evictions" in a]
    if len(marks) < 2:
        return None
    evictions = marks[-1]["evictions"] - marks[0]["evictions"]
    if not evictions:
        return None
    return (marks[-1]["victim_scanned"] - marks[0]["victim_scanned"]) \
        / evictions
