"""Python's garbage-collector pauses in the window, a step: the trainer
loop's ``gc_pause_s`` (a ``gc.callbacks`` entry ``run_steps`` holds for
its length; a collection on any thread stops them all) over the window's
steps. The collections and the generation-2 ones go to standard error. A
program that does not count them (before PR 36) reads nothing."""
NAME = "gc_pause_ms_per_step"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    feed, steps = run["counters"].get("feed") or {}, run["counters"]["steps"]
    if "gc_pause_s" not in feed or not steps:
        return None
    run["log"](f"gc_pause_ms_per_step: {feed.get('gc_collections')} "
               f"collections, {feed.get('gc_gen2')} of generation 2, "
               f"{feed['gc_pause_s']} s")
    return feed["gc_pause_s"] * 1e3 / steps
