"""Seconds of set-up in which one of the programs after the step was being
built: the length of the union of ``[t0, t1]`` over the records that
``setup.programs_after_step`` counts (``perfbench/setup_log.py``), each
from its first trace span's start to its backend span's end. Nothing where
the program keeps no build log."""


def read(ctx):
    from perfbench import setup_log

    found = setup_log.set_up(ctx)
    if found is None:
        return None
    after = found["after"]
    setup_log.say(
        "setup.after_step_build_s",
        f"{len(after)} records: traced "
        f"{sum(r['trace_s'] for r in after):.3f}s, lowered "
        f"{sum(r['lower_s'] for r in after):.3f}s, backend "
        f"{sum(r['backend_s'] for r in after):.3f}s")
    return setup_log.union_s(after)
