"""Share of the traced window in which the device idled inside a server
step but under none of its prefill, round and harvest spans: cancels,
admission, block-table uploads and the step's own code
(``bench/host_spans.py``), averaged over the cell's chips as
``idle_share`` is."""
from bench import host_spans


def read(run):
    return host_spans.share(run, "admit")
