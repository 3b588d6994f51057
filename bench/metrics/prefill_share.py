"""Share of the window the server spent in chunked-prefill programs: the sum
of ``RoundEvent.t_prefill`` (host seconds, dispatch to sync) over rounds that
ended in the window, over the window."""


def read(run):
    w = run.window
    spent = sum(ev.t_prefill or 0.0 for ev in w.events)
    return 100.0 * spent / (w.t_end - w.t0) if w.events else None
