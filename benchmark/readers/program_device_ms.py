"""Median device duration (ms) of the executions, inside the traced
slice, of the jitted programs whose name on the device plane's
``XLA Modules`` line matches ``pattern``."""

import re

from benchmark import stats


def read(obs: dict, params: dict):
    if obs.get("trace") is None:
        return None
    pattern = re.compile(params["pattern"])
    runs = [d for name, ds in obs["trace"]["programs"].items()
            if pattern.search(name) for d in ds]
    if not runs:
        return None
    return stats.median(runs) * 1000.0
