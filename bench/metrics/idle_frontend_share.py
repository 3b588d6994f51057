"""Share of the traced window in which the device idled while no server
step was open on the host: the event loop, the fan-out of streamed tokens,
the executor hop and the drain of new submissions (``bench/host_spans.py``),
averaged over the cell's chips as ``idle_share`` is."""
from bench import host_spans


def read(run):
    return host_spans.share(run, "frontend")
