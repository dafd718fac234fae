"""Percent of set-up's records in the program's build log whose program
came out of the persistent cache (``"cache": "hit"``) over all of set-up's
records (``perfbench/setup_log.py`` says which those are): 100 in a run
from a warm cache, near 0 in the run ``first_setup_s`` comes from. Nothing
where the program keeps no build log."""


def read(ctx):
    from perfbench import setup_log

    found = setup_log.set_up(ctx)
    if found is None:
        return None
    records = found["records"]
    hits = sum(r["cache"] == "hit" for r in records)
    setup_log.say("setup.cache_hit_share", f"{hits} of {len(records)} records "
                  f"hit; not hit: "
                  f"{sorted({r['name'] for r in records if r['cache'] != 'hit'})}")
    return 100.0 * hits / len(records)
