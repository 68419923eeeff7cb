"""A percentile over the window's finished requests of one field of the
engine's per-request critical-path record (``Request.cp``: seconds per
segment, as ``GET /debug/requests`` shows them)."""

from benchmark import stats


def read(obs: dict, params: dict):
    values = [r.get(params["field"], 0.0) for r in obs["requests"]]
    if not values:
        return None
    return stats.percentile(values, params["percentile"]) * params.get(
        "scale", 1.0)
