"""Mean ``RoundEvent.t_round`` (host milliseconds from the round's dispatch
to its one host sync) over the rounds that ended in the window."""


def read(run):
    evs = run.window.events
    if not evs:
        return None
    return 1e3 * sum(ev.t_round for ev in evs) / len(evs)
