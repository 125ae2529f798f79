"""What the program recorded about itself in this process, for the
``program_span`` metrics: the self seconds of its ``repro.obs`` spans.

A metric reader runs in the process that ran the cell, after it, so the
program's tracer still holds the spans of that run's set-up. A program
that records no such span reads None, and the metric is left out.
"""
from __future__ import annotations


def span_self_s(*names: str) -> float | None:
    """Self seconds summed over every span named in ``names``, or None
    where the program recorded none of them."""
    from repro import obs

    rows = {row["name"]: row for row in obs.tracer().summary()}
    found = [rows[n]["self_s"] for n in names
             if n in rows and "self_s" in rows[n]]
    return sum(found) if found else None
