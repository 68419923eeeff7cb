"""One quantity of the window as the client's clock gave it
(``obs["e2e"]``, which the runner fills in traced and untraced runs
alike), for a cell in which ``BENCHMARK.json`` does not list it end to
end. A window that did not yield it gives nothing."""


def read(obs: dict, params: dict):
    return obs.get("e2e", {}).get(params["name"])
