"""Small adapters over JAX APIs whose return shape varies."""
from __future__ import annotations


def compiled_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (some backends wrap it in a
    1-list)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return dict(cost)
