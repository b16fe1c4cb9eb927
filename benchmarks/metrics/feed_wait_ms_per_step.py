"""Time a step waits for its batch: the feed's host-blocked seconds over the
window (``profiler.pipeline_stats()``'s ``host_blocked_s`` of the window's
own prefetcher, read as its snapshot) over the steps of the window."""
NAME = "feed_wait_ms_per_step"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    feed, steps = run["counters"].get("feed") or {}, run["counters"]["steps"]
    if "host_blocked_s" not in feed or not steps:
        return None
    return feed["host_blocked_s"] * 1e3 / steps
