"""Seconds of set-up in which the step was traced and lowered: ``trace_s +
lower_s`` of the step's record in the program's build log
(``perfbench/setup_log.py`` says which record that is): the Python of
``models/transformer.py``, ``parallel/moe.py`` and ``ops/`` under trace,
and the lowering to the module the cache's key is made from. Paid from a
warm cache as from an empty one; the program's to shorten. Nothing where
the program keeps no build log."""


def read(ctx):
    from perfbench import setup_log

    found = setup_log.set_up(ctx)
    if found is None:
        return None
    step = found["step"]
    setup_log.say("setup.step_trace_lower_s", f"{step['name']}: traced "
                  f"{step['trace_s']:.3f}s, lowered {step['lower_s']:.3f}s")
    return step["trace_s"] + step["lower_s"]
