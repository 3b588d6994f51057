"""Mean share of the batch's rows live in a round (``RoundEvent.n_active`` /
batch rows) over the rounds that ended in the window."""


def read(run):
    evs = run.window.events
    if not evs:
        return None
    return 100.0 * sum(ev.n_active for ev in evs) / (len(evs) * run.srv_batch)
