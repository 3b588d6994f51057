"""Tokens committed per live row per round: the sum of ``RoundEvent.emitted``
over the sum of ``n_active``, over the rounds that ended in the window (an AR
round commits 1; a speculative one 1 to gamma + 1)."""


def read(run):
    evs = run.window.events
    rows = sum(ev.n_active for ev in evs)
    return sum(ev.emitted for ev in evs) / rows if rows else None
