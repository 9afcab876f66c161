"""page_map_ms_per_req: host milliseconds in the program's ``page_map``
span (``EmbeddingServingEngine._page_map``: the unique rows of a request
and ``WeightServer.embedding_rows_pages``, its page set), self time, per
request answered in the traced window."""
from program_spans import ms_per_req


def read(run):
    return ms_per_req(run, "repro.page_map")
