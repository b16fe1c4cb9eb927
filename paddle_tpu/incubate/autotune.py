"""paddle.incubate.autotune.set_config (parity: python/paddle/incubate/
autotune.py — JSON/dict config for kernel/layout/dataloader tuning)."""
from __future__ import annotations

import json

__all__ = ["set_config"]


def set_config(config=None):
    """Accepts {"kernel": {"enable": bool, "tuning_range": ...},
    "layout": {...}, "dataloader": {...}} or a JSON file path, and returns
    the config as a dict. It switches nothing: there is no search to turn
    on. The attention kernels' tiles and route are functions of the call's
    shape (``ops/pallas/flash_attention.py``: ``tile_plan``,
    ``attention_route``), and XLA tunes its own kernels and layouts."""
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    return config or {}
