"""Programs built between the step's record and the window: the records
of the program's build log that close after the step's and before the
window (``perfbench/setup_log.py`` has both rules): ``change_norms``'
small programs, the eager conversions round ``float(loss)``. Each is a
trace, a lowering and a round trip to the cache. Nothing where the program
keeps no build log."""


def read(ctx):
    from perfbench import setup_log

    found = setup_log.set_up(ctx)
    if found is None:
        return None
    by_name = {}
    for r in found["after"]:
        n, s = by_name.get(r["name"], (0, 0.0))
        by_name[r["name"]] = (n + 1, s + r["t1"] - r["t0"])
    setup_log.say("setup.programs_after_step:", ", ".join(
        f"{n} x {name} {s:.3f}s" for name, (n, s) in by_name.items())
        or "no record")
    return float(len(found["after"]))
