"""The selective scan with a carried state (``ops/selective_scan.py``): the
Pallas kernel in interpret mode against the sequential recurrence, over
chunk splits and padded chunks; the one-position update; tiny sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.ops import selective_scan as ssm

L, C, N = 48, 256, 16


@pytest.fixture(scope="module")
def data():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (2, L, C))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, L, C)) - 2)
    b, c = (jax.random.normal(k, (2, L, N)) for k in ks[2:4])
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, C))
    s0 = jax.random.normal(ks[4], (2, N, C))
    want = ssm.chunk_scan_reference(x, dt, b, c, a, jnp.ones((C,)), s0)
    return (x, dt, b, c, a, jnp.ones((C,)), s0), want


def _run(args, splits, form):
    x, dt, b, c, a, d, s = args
    ys, at = [], 0
    for n in splits:
        y, s = form(x[:, at:at + n], dt[:, at:at + n], b[:, at:at + n],
                    c[:, at:at + n], a, d, s)
        ys.append(y)
        at += n
    return jnp.concatenate(ys, axis=1), s


KERNEL = jax.jit(lambda *a: ssm.chunk_scan(
    *a, block_t=8, block_c=128, interpret=True))


@pytest.mark.parametrize("splits", [(48,), (24, 24), (8, 16, 8, 8, 8)],
                         ids=["one", "two", "five-uneven"])
def test_kernel_matches_the_recurrence_over_chunk_splits(data, splits):
    """The carried state crosses every boundary: y and the last state are
    the one-shot recurrence's to float32 tolerance, however the sequence
    is cut."""
    args, (y_want, s_want) = data
    y, s = _run(args, splits, KERNEL)
    np.testing.assert_allclose(y, y_want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, s_want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["kernel", "scan"])
@pytest.mark.parametrize("valid", [0, 13, 24])
def test_padding_does_not_advance_the_state(data, form, valid):
    """A chunk of 24 of which ``valid`` are real leaves the state of its
    last real position, bit for bit the state in where none is."""
    (x, dt, b, c, a, d, s0), _ = data
    run = KERNEL if form == "kernel" else ssm.chunk_scan_reference
    masked = ssm.mask_steps(dt[:, :24], jnp.asarray([valid, valid]))
    _, s = run(x[:, :24], masked, b[:, :24], c[:, :24], a, d, s0)
    if valid == 0:
        np.testing.assert_array_equal(s, s0)
        return
    _, want = ssm.chunk_scan_reference(
        x[:, :valid], dt[:, :valid], b[:, :valid], c[:, :valid], a, d, s0)
    np.testing.assert_allclose(s, want, rtol=2e-5, atol=2e-5)


def test_one_position_update_is_one_step_of_the_recurrence(data):
    (x, dt, b, c, a, d, s0), _ = data
    y, s = ssm.state_update(x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, d, s0)
    y_want, s_want = ssm.chunk_scan_reference(
        x[:, :1], dt[:, :1], b[:, :1], c[:, :1], a, d, s0)
    np.testing.assert_allclose(y, y_want[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s, s_want, rtol=1e-6, atol=1e-6)
    # and the dispatcher picks it for one position, the scan off the TPU
    y1, _ = ssm.selective_scan(x[:, :1], dt[:, :1], b[:, :1], c[:, :1], a,
                               d, s0)
    np.testing.assert_allclose(y1[:, 0], y, rtol=1e-6, atol=1e-6)
    y8, _ = ssm.selective_scan(x[:, :8], dt[:, :8], b[:, :8], c[:, :8], a,
                               d, s0)
    assert y8.shape == (2, 8, C)


def test_sizes_that_are_not_whole_tiles_are_refused():
    assert ssm.can_tile(2048, 5120) and not ssm.can_tile(2048, 100)
    with pytest.raises(ValueError, match="whole tiles"):
        ssm.chunk_scan(jnp.zeros((1, 8, 100)), jnp.zeros((1, 8, 100)),
                       jnp.zeros((1, 8, 4)), jnp.zeros((1, 8, 4)),
                       jnp.zeros((4, 100)), jnp.zeros((100,)),
                       jnp.zeros((1, 4, 100)), interpret=True)
