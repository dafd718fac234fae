"""Seconds of set-up in the step's backend span: ``backend_s`` of the
step's record in the program's build log (``perfbench/setup_log.py`` says
which record that is): the executable read from the persistent cache and
put on the chip, or compiled where the cache missed (the record's
``cache`` says which, ``retrieval_s`` the read alone). Follows the
executable's size. Nothing where the program keeps no build log."""


def read(ctx):
    from perfbench import setup_log

    found = setup_log.set_up(ctx)
    if found is None:
        return None
    step = found["step"]
    setup_log.say("setup.step_load_s", f"{step['name']}: cache "
                  f"{step['cache']}, {step['backend_s']:.3f}s in the backend "
                  f"span, {step['retrieval_s']:.3f}s of it the read")
    return step["backend_s"]
