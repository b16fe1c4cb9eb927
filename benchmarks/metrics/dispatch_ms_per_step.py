"""Host time inside the call of the step, a step: the trainer loop's
``dispatch_s`` (``run_steps`` brackets the key fold, the schedule and the
call of the jitted step: flattening the trees and launching the programs)
of the window's own feed snapshot, over the steps of the window. The same
boundary as the span ``train::dispatch``. A program that does not count it
(before PR 25) reads nothing."""
NAME = "dispatch_ms_per_step"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    feed, steps = run["counters"].get("feed") or {}, run["counters"]["steps"]
    if "dispatch_s" not in feed or not steps:
        return None
    return feed["dispatch_s"] * 1e3 / steps
