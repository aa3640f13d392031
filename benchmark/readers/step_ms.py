"""Median host wall of a train step ended by block_until_ready, over the
traced run's fenced steps."""

import statistics


def read(art: dict, args: dict):
    walls = (art.get("task") or {}).get("step_walls_s")
    if not walls:
        return None
    return 1e3 * statistics.median(walls)
