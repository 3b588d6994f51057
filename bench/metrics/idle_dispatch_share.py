"""Share of the traced window in which the device idled under the server
step's ``step.prefill`` or ``step.round`` span: dispatching the chunk and
round programs and waiting on their host syncs (``bench/host_spans.py``),
averaged over the cell's chips as ``idle_share`` is."""
from bench import host_spans


def read(run):
    return host_spans.share(run, "dispatch")
