"""95th percentile of the intervals between successive lagged loss fetches
of the window (``run_steps(log_every=1, on_log=...)``, stamped on the host's
clock by the runner). A stall shows here before it moves the rate. The sample
count goes to standard error; under 20 intervals there is no such tail."""
import statistics

NAME = "step_ms_p95.train"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(run):
    gaps = run["counters"].get("step_gaps_ms") or []
    run["log"](f"step_ms_p95.train: {len(gaps)} intervals, median "
               f"{statistics.median(gaps) if gaps else None} ms")
    if len(gaps) < 20:
        return None
    return statistics.quantiles(gaps, n=20)[-1]
