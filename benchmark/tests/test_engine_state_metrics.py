"""The engine thread's time by state (PR 41): six per-layer metrics, all
data files over ``request_sum_ratio``, reading the ``engine_*`` overlays
the engine books to ``Request.cp``."""

import pytest

from benchmark import spec

SHARES = {"engine_cpu_share": ("engine_cpu", "lower"),
          "engine_device_wait_share": ("engine_blocked", "higher"),
          "engine_stall_share": ("engine_stalled", "lower")}
TOKENS_CELLS = ["qwen3-8b.prefill-heavy", "sdar-30b-a3b.block-decode",
                "deepseek-v3.long-doc-qa", "mimo-v2.5.agent-context"]
NAMES = [base + suffix for suffix in ("", ".ttft") for base in SHARES]

# two requests as the engine books them: all four keys together, a 0.0
# included; the second sat through four times the steps
REQUESTS = [
    {"engine_wall": 1.0, "engine_cpu": 0.25, "engine_blocked": 0.75,
     "engine_stalled": 0.0, "decode_dispatch": 0.9, "host_gap": 0.1},
    {"engine_wall": 4.0, "engine_cpu": 1.0, "engine_blocked": 2.5,
     "engine_stalled": 0.5, "prefill_stall": 3.0},
]
EXPECTED = {"engine_cpu": 25.0, "engine_blocked": 65.0,
            "engine_stalled": 10.0}


def _read(name, requests):
    mf = spec.metric_file(name)
    return spec.reader(mf["reader"]).read(
        {"requests": requests, "counters": {}}, mf["params"])


@pytest.mark.parametrize("name", NAMES)
def test_metric_is_wired(name):
    bench = spec.benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    base = name.removesuffix(".ttft")
    overlay, better = SHARES[base]
    moves, cells = (("ttft_p95_ms", ["qwen3-8b.chat-steady"])
                    if name.endswith(".ttft")
                    else ("serve_tokens_per_s", TOKENS_CELLS))
    assert entry == {"name": name, "unit": "%", "better": better,
                     "source": "program_span",
                     "layer": "scheduler / engine step", "moves": moves,
                     "workloads": cells}
    # every cell it lists reports the end-to-end metric it moves
    for cell in cells:
        assert moves in [m["name"] for m in
                         spec.metrics_for(bench, cell, "end_to_end")]
        assert name in [m["name"] for m in
                        spec.metrics_for(bench, cell, "per_layer")]
    mf = spec.metric_file(name)
    assert mf["reader"] == "request_sum_ratio"
    assert mf["params"] == {"numerator": [overlay],
                            "denominator": ["engine_wall"], "scale": 100.0}
    assert overlay in mf["reads"]


@pytest.mark.parametrize("name", NAMES)
def test_hand_made_requests_read_to_the_expected_share(name):
    overlay, _ = SHARES[name.removesuffix(".ttft")]
    assert _read(name, REQUESTS) == pytest.approx(EXPECTED[overlay])


@pytest.mark.parametrize("suffix", ["", ".ttft"])
def test_the_three_shares_of_a_window_sum_to_100(suffix):
    assert sum(_read(base + suffix, REQUESTS) for base in SHARES) \
        == pytest.approx(100.0)
    # a window in which nothing stalled reads 0, not nothing
    assert _read("engine_stall_share" + suffix, REQUESTS[:1]) == 0.0


@pytest.mark.parametrize("requests", [
    [],
    # the parent commit, or the recorder off: no overlay anywhere
    [{"prefill_dispatch": 0.1, "decode_dispatch": 0.5, "host_gap": 0.2}],
], ids=["no-request", "older-program"])
@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_gives_nothing(name, requests):
    assert _read(name, requests) is None
