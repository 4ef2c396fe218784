"""The cores the server's Python threads burned over the window: the
window's difference of `ipt_thread_cpu_seconds_total`, summed over every
`thread` root (dispatch, lane workers, the side lane's two threads, the
event loop, confirm waiters), against `ctx["seconds"]`.  Near 1.0 while
the rate stays flat says the one interpreter lock is the ceiling.  The
walker processes are not in it.  Nothing to read from a program without
the counter, or where the host's thread clocks read nothing.  Layer: host
threads."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_thread_cpu_seconds_total") is None or ctx["seconds"] <= 0:
        return None
    return w.delta("ipt_thread_cpu_seconds_total") / ctx["seconds"]
