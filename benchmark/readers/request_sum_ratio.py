"""Over the window's finished requests: the sum of some fields of the
engine's per-request critical-path record (``Request.cp``) over the sum
of others, times ``scale`` (100 for a share in percent). ``numerator``
and ``denominator`` are lists of field names. A program that books none
of the numerator's fields (an older one) gives nothing, as does a window
whose requests hold nothing under the denominator's."""


def read(obs: dict, params: dict):
    requests = obs["requests"]
    if not any(f in r for r in requests for f in params["numerator"]):
        return None
    num = sum(r.get(f, 0.0) for r in requests for f in params["numerator"])
    den = sum(r.get(f, 0.0) for r in requests for f in params["denominator"])
    if not den:
        return None
    return params.get("scale", 1.0) * num / den
