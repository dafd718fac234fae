"""The seconds of the window's plain steps over their number: in a traced
run the profiler's bracket (start_trace, the traced steps, stop_trace and
the writing of the trace) is left out of both."""


def read(ctx):
    return 1e3 * ctx["seconds"] / ctx["steps"] if ctx.get("steps") else None
