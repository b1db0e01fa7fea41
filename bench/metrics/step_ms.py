"""Model runner: the window's length over the engine iterations that ended
in it (host clock)."""


def read(r):
    n = sum(1 for s in r.steps if r.in_window(s[1]))
    if not n:
        return None
    return 1e3 * r.seconds / n
