"""Peak device memory after the window:
``device.memory_stats()["peak_bytes_in_use"]``, read before the reference
runs."""
NAME = "peak_hbm_gib"
UNIT = "GiB"
LAYER = "device"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    peak = run["counters"].get("peak_bytes_in_use")
    return None if not peak else peak / 2 ** 30
