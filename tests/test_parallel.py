"""Sharding-strategy tests on the 8-virtual-device CPU mesh.

This is the test infrastructure the reference lacks entirely (SURVEY §4:
"multi-node w/o cluster: none") — every DDP/ZeRO/FSDP/TP strategy is
validated without hardware, including numerical parity of sharded vs
single-device training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from llm_in_practise_tpu.core import mesh as mesh_lib
from llm_in_practise_tpu.models.gpt import GPT, minigpt_v1_config
from llm_in_practise_tpu.parallel import strategy as S
from llm_in_practise_tpu.train.step import make_train_step
from tests import envcaps


VOCAB = 64


def tiny_model():
    # dims chosen divisible by 8 so fsdp/model axes can shard them
    cfg = minigpt_v1_config(VOCAB, embed_dim=64, n_head=4, seq_len=32, dropout=0.0)
    return GPT(cfg), cfg


def fake_batch(batch=16, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, VOCAB, (batch, seq)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    return jnp.asarray(x), jnp.asarray(y)


def build_state(strat, devices):
    model, cfg = tiny_model()
    mesh = strat.build_mesh(devices)
    tx = optax.adamw(1e-3)
    state = S.shard_init(
        model, strat, mesh, tx, jax.random.PRNGKey(0), jnp.ones((2, 8), jnp.int32)
    )
    return model, mesh, state


@pytest.mark.parametrize(
    "strat_fn",
    [S.ddp, S.zero1, S.zero2, S.fsdp, lambda: S.tensor_parallel(4, data=2),
     lambda: S.fsdp_tp(4, 2)],
    ids=["ddp", "zero1", "zero2", "fsdp", "tp", "fsdp_tp"],
)
def test_strategy_trains(strat_fn, devices):
    strat = strat_fn()
    model, mesh, state = build_state(strat, devices)
    step = make_train_step()
    batch = fake_batch()
    with mesh:
        batch = jax.device_put(batch, mesh_lib.batch_sharding(mesh))
        state, m1 = step(state, batch)
        state, m2 = step(state, batch)
    assert np.isfinite(float(m1["loss"]))
    # training on the same batch decreases loss
    assert float(m2["loss"]) < float(m1["loss"])


def test_fsdp_param_placement(devices):
    strat = S.fsdp()
    model, mesh, state = build_state(strat, devices)
    q_kernel = state.params["block_0"]["attn"]["q_proj"]["kernel"]
    spec = q_kernel.sharding.spec
    assert spec == P("fsdp", "model")
    # 8-way fsdp: each shard holds 1/8 of the rows
    assert q_kernel.addressable_shards[0].data.shape[0] == q_kernel.shape[0] // 8


def test_ddp_params_replicated_opt_replicated(devices):
    strat = S.ddp()
    model, mesh, state = build_state(strat, devices)
    q_kernel = state.params["block_0"]["attn"]["q_proj"]["kernel"]
    assert q_kernel.sharding.is_fully_replicated


def test_zero1_shards_opt_state_only(devices):
    """ZeRO-1 parity: params replicated, Adam moments sharded
    (reference DeepSpeed-GPTLike-ZeRO-1/ds_config.json:4-10)."""
    strat = S.zero1()
    model, mesh, state = build_state(strat, devices)
    q_kernel = state.params["block_0"]["attn"]["q_proj"]["kernel"]
    assert q_kernel.sharding.is_fully_replicated
    mu = state.opt_state[0].mu["block_0"]["attn"]["q_proj"]["kernel"]
    assert not mu.sharding.is_fully_replicated
    assert mu.sharding.spec == P("fsdp", "model")


@pytest.mark.skipif(not envcaps.shard_map_has_check_vma(),
                    reason=envcaps.SHARD_MAP_CHECK_VMA_REASON)
def test_sharded_matches_single_device(devices):
    """The load-bearing guarantee: every strategy computes the SAME training
    trajectory as one device — sharding is placement, not math."""
    model, cfg = tiny_model()
    tx = optax.adamw(1e-3)
    batch = fake_batch()
    step = make_train_step(donate=False)

    def run(strat, devs, steps=3):
        mesh = strat.build_mesh(devs)
        state = S.shard_init(
            model, strat, mesh, tx, jax.random.PRNGKey(0),
            jnp.ones((2, 8), jnp.int32),
        )
        losses = []
        with mesh:
            b = jax.device_put(batch, mesh_lib.batch_sharding(mesh))
            for _ in range(steps):
                state, m = step(state, b)
                losses.append(float(m["loss"]))
        return losses

    ref = run(S.ddp(devices=1), devices[:1])
    for strat in (S.ddp(), S.fsdp(), S.fsdp_tp(4, 2)):
        got = run(strat, devices)
        np.testing.assert_allclose(got, ref, rtol=2e-4, err_msg=strat.name)


def test_fit_spec_falls_back_on_indivisible(devices):
    """Rules degrade to replication when a dim doesn't divide the axis."""
    mesh = S.fsdp().build_mesh(devices)
    spec = S.spec_for("block_0/attn/q_proj/kernel", (6, 64), mesh, S.DEFAULT_RULES)
    # 6 % 8 != 0 → fsdp entry dropped; model axis (size 1) divides 64 → kept
    assert spec == P(None, "model")


def test_expert_rules_not_shadowed(devices):
    """MoE expert kernels must pick up the 3-entry expert spec, not the
    generic 2-entry MLP spec (rule order matters: first match wins)."""
    mesh = S.expert_parallel(expert=2, fsdp_size=2, data=2).build_mesh(devices)
    spec = S.spec_for(
        "block_0/moe/experts/fc_in/kernel", (2, 64, 128), mesh, S.DEFAULT_RULES
    )
    assert spec == P("expert", "fsdp", "model")
    spec_out = S.spec_for(
        "block_0/moe/experts/fc_out/kernel", (2, 128, 64), mesh, S.DEFAULT_RULES
    )
    assert spec_out == P("expert", "model", "fsdp")


def test_by_name():
    assert S.by_name("zero3").name == "fsdp"
    with pytest.raises(ValueError):
        S.by_name("nope")


def test_sharded_checkpoint_roundtrip(tmp_path, devices):
    """Orbax tier: sharded save/restore preserves values AND placement,
    rotates old steps, resumes latest."""
    import optax

    from llm_in_practise_tpu.ckpt.sharded import ShardedCheckpointer
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.parallel import strategy as S

    model = GPT(GPTConfig(vocab_size=64, seq_len=16, n_layer=1, n_head=2,
                          embed_dim=32, dropout=0.0))
    strat = S.fsdp(data=1)
    mesh = strat.build_mesh(devices)
    state = S.shard_init(model, strat, mesh, optax.adamw(1e-3),
                         jax.random.PRNGKey(0), jnp.ones((2, 8), jnp.int32))

    ckptr = ShardedCheckpointer(str(tmp_path), keep=2, async_save=True)
    for step in (1, 2, 3):
        scaled = state.replace(params=jax.tree_util.tree_map(
            lambda x: x * (1.0 + step / 10), state.params))
        assert ckptr.save(step, scaled)
    ckptr.wait()
    assert ckptr.all_steps() == [2, 3]  # keep=2 rotated step 1 out

    restored = ckptr.restore(state)  # latest
    expect = jax.tree_util.tree_map(lambda x: x * 1.3, state.params)
    for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        # placement preserved: restored shards live on the same devices
    kernel = restored.params["block_0"]["attn"]["q_proj"]["kernel"]
    assert len(kernel.sharding.device_set) == len(devices)
    ckptr.close()
