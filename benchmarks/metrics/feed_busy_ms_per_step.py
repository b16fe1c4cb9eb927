"""The feed's time busy, a step: the prefetcher's producer thread's
``producer_busy_s`` (pulling a batch from the source and placing it on the
device) of the window's own feed snapshot, over the steps of the window.
``feed_wait_ms_per_step`` is the time the loop waited for the feed; this is
the work the feed did. Where it nears the step time the feed is next to
bind, before the wait shows it."""
NAME = "feed_busy_ms_per_step"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    feed, steps = run["counters"].get("feed") or {}, run["counters"]["steps"]
    if "producer_busy_s" not in feed or not steps:
        return None
    return feed["producer_busy_s"] * 1e3 / steps
