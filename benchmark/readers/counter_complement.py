"""``scale * (1 - numerator / denominator)`` of two counters of the run
(100 for the share in percent of the denominator that the numerator does
NOT cover). A counter the run did not take gives nothing."""


def read(obs: dict, params: dict):
    c = obs["counters"]
    num, den = c.get(params["numerator"]), c.get(params["denominator"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * (1.0 - num / den)
