"""The window's slowest iteration of the trainer loop over its typical one:
``loop_ms``'s max less its p50, from the window's own feed snapshot
(``run_steps`` times each whole iteration, feed_wait + dispatch +
checkpoint + the lagged fetch, with the caller's ``on_log`` left out). A
stall of the program shows here whatever phase held it; the run's stderr
carries ``run_steps``' WARNING line with the phases. Under 20 iterations
there is no such tail. A program that does not record its iterations
(before PR 36) reads nothing."""
NAME = "loop_stall_ms.train"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    loop = (run["counters"].get("feed") or {}).get("loop_ms")
    if not loop or loop["count"] < 20:
        return None
    slowest = (run["counters"]["feed"].get("slowest") or [{}])[0]
    run["log"](f"loop_stall_ms.train: {loop['count']} iterations, p50 "
               f"{loop['p50']:.3f} ms, slowest {slowest.get('step')}: "
               f"{slowest.get('ms')}")
    return loop["max"] - loop["p50"]
