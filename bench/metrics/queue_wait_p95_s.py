"""95th percentile of the scheduler's queue wait (submission to admission,
``RequestRecord.queue_wait``) over the window's admitted requests."""
from bench.loadgen import percentile


def read(run):
    return percentile([r.queue_wait for r in run.window.requests
                       if r.started > 0], 95)
