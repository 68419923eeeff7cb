"""Test harness: 8 virtual CPU devices so mesh/collective code paths run
without TPU hardware (SURVEY §4 — the test infra the reference lacks).

XLA_FLAGS / JAX_PLATFORMS must be set before jax is imported.
"""

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402 — after the environment is pinned
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def tiers_run(monkeypatch):
    """The bodies of ``infer/sampling.py::sample_token_batched`` that
    really ran on the device, in order: a debug callback in each
    (programs traced after this fixture see them)."""
    from llm_in_practise_tpu.infer import sampling

    ran = []
    for name in sampling.SAMPLER_TIERS:
        def spied(*args, _stock=getattr(sampling, "_" + name), _name=name):
            jax.debug.callback(lambda: ran.append(_name))
            return _stock(*args)
        monkeypatch.setattr(sampling, "_" + name, spied)

    def read():
        jax.effects_barrier()
        return ran
    return read
