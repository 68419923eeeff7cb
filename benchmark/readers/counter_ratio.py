"""One counter of the run over another, times ``scale`` (100 for a share
in percent). A counter the run did not take gives nothing."""


def read(obs: dict, params: dict):
    c = obs["counters"]
    num, den = c.get(params["numerator"]), c.get(params["denominator"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
