"""Share of the window's launches that found the device already done with
the step before, so it had waited on the host: the trainer loop's
``starved_steps`` (``run_steps`` asks the previous loss ``is_ready()``
right before and after each launch) less those it puts down to the
caller's ``on_log`` (``starved_by["callback"]``: the runner starts and
stops the profile there, the benchmark's own doing), over the window's
steps, times 100. The causes go to standard error. A program that does not
count them (before PR 36) reads nothing."""
NAME = "starved_steps_pct"
UNIT = "%"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    feed, steps = run["counters"].get("feed") or {}, run["counters"]["steps"]
    if "starved_steps" not in feed or not steps:
        return None
    by = feed.get("starved_by") or {}
    run["log"](f"starved_steps_pct: {feed['starved_steps']} of {steps} "
               f"launches found the device dry, by {by}, at least "
               f"{feed.get('starved_s')} s idle")
    return (feed["starved_steps"] - by.get("callback", 0)) * 100.0 / steps
