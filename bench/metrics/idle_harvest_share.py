"""Share of the traced window in which the device idled under the server
step's ``step.harvest`` span: the token pull for streaming, releasing
finished rows, refilling them and recording the round
(``bench/host_spans.py``), averaged over the cell's chips as
``idle_share`` is."""
from bench import host_spans


def read(run):
    return host_spans.share(run, "harvest")
