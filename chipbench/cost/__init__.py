"""Operation and byte counts, one module per program, looked up by name
(``decoder.decode_step``).  ``least_seconds`` is the roofline bound."""

from __future__ import annotations

import importlib


def lookup(name: str):
    module, fn = name.rsplit(".", 1)
    return getattr(importlib.import_module(f"chipbench.cost.{module}"), fn)


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which of the two bounds it."""
    by_flops = cost["flops"] / peak["flops_per_s"]
    by_bytes = cost["bytes"] / peak["bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "bandwidth")
