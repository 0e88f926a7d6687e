"""Readers of metrics, one module per kind, looked up by name.

A metric's file (``end_to_end/<metric>.json``, ``layer_metrics/<metric>.json``)
names its reader kind and that kind's parameters; a parameter may itself
be a reader's spec, so a ratio or a roofline takes its terms from counters.
A reader that finds nothing to read returns ``None`` and the metric is
left out of the line.

The context a reader is given: ``before`` and ``after`` (the program's
ledgers around the window, ``builders.common.probe_program``), ``trace``
(``trace_reduce.reduce`` of the traced seconds, or None), ``setup_s``,
``span_s`` (the window: its start to the last response), ``latencies_ms``,
``work`` (the deployment's count of useful work in the window),
``sections`` (the configuration's blocks by name) and ``peak`` (the chip's
row of ``peaks.json``).
"""

from __future__ import annotations

import importlib


def evaluate(spec, ctx: dict):
    """A number as it stands, or a reader's spec read."""
    if spec is None or isinstance(spec, (int, float)):
        return spec
    module = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    return module.read(spec, ctx)
