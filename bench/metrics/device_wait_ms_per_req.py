"""device_wait_ms_per_req: host milliseconds in the program's ``to_host``
span, where the engine dispatches the pooling and head (``head``, nested)
and blocks until the request's logits are on the host, per request
answered in the traced window."""
from program_spans import ms_per_req


def read(run):
    return ms_per_req(run, "repro.to_host")
