"""ZeRO-1 shard-plan edge cases and checkpoint layout independence.

Companion to tests/test_dp_parity.py::test_zero1_matches_zero0 (trajectory
parity + the 8x state reduction); here: the per-tensor plan on scalar /
non-divisible shapes, precedence passthrough, and the stage-crossing
checkpoint round trips the plan's gather/scatter guarantees.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import checkpoint as ckpt
from paddle_tpu import layer, optimizer, trainer
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.zero import build_zero_plan


@pytest.fixture
def mesh():
    return make_mesh((8,), ("data",))


# ---------------------------------------------------------------------------
# shard plan
# ---------------------------------------------------------------------------


def test_shard_plan_edge_shapes_roundtrip(mesh):
    """Scalars, non-divisible shapes (padding), and exactly-divisible
    shapes all survive shard_tree -> gather_tree bit-exactly."""
    import jax.numpy as jnp

    params = {
        "scalar": jnp.asarray(3.5),                       # size 1 -> pad 8
        "odd": jnp.arange(15, dtype=jnp.float32).reshape(3, 5),  # pad 16
        "exact": jnp.arange(16, dtype=jnp.float32),       # no padding
        "big": jnp.asarray(np.random.RandomState(0).randn(7, 9)
                           .astype(np.float32)),          # 63 -> pad 64
    }
    plan = build_zero_plan(mesh, params)
    assert plan.entries["scalar"].padded == 8
    assert plan.entries["odd"].padded == 16
    assert plan.entries["exact"].padded == 16
    assert plan.entries["big"].padded == 64
    flat = plan.shard_tree(params)
    for name, v in flat.items():
        assert v.shape == (plan.entries[name].padded,), name
        # physically sharded: 1/8 of the padded flat size per device
        assert np.prod(v.sharding.shard_shape(v.shape)) == v.size // 8, name
    back = plan.gather_tree(flat)
    for name in params:
        np.testing.assert_array_equal(np.asarray(back[name]),
                                      np.asarray(params[name]), err_msg=name)


def test_shard_plan_respects_declared_sharding_and_static(mesh):
    """ParamAttr.sharding precedence and static params pass through: their
    state keeps the declared layout instead of the flat 1/N view."""
    import jax.numpy as jnp

    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.topology import ParamSpec

    params = {"plain": jnp.zeros((16, 8)), "placed": jnp.zeros((16, 8)),
              "frozen": jnp.zeros((16, 8))}
    specs = {
        "placed": ParamSpec(shape=(16, 8),
                            attr=ParamAttr(sharding=("data", None))),
        "frozen": ParamSpec(shape=(16, 8), attr=ParamAttr(is_static=True)),
    }
    plan = build_zero_plan(mesh, params, specs=specs)
    assert plan.is_sharded("plain")
    assert not plan.is_sharded("placed")
    assert not plan.is_sharded("frozen")


def test_reused_optimizer_does_not_leak_plan(mesh):
    """An optimizer instance reused across trainers must not carry the
    previous trainer's shard plan: the second (zero=0) trainer clears it
    and its slots come out full-shape replicated."""
    cost = _build()
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=7)
    opt = optimizer.Momentum(momentum=0.9, learning_rate=0.05)
    trainer.SGD(cost=cost, parameters=params, update_equation=opt,
                mesh=mesh, zero=1)
    assert opt._zero_plan is not None
    cost2 = _build()
    params2 = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost2]), seed=7)
    sgd2 = trainer.SGD(cost=cost2, parameters=params2, update_equation=opt,
                       mesh=mesh, zero=0)
    assert opt._zero_plan is None
    for slot in sgd2.opt_state["slots"].values():
        for name, arr in slot.items():
            assert arr.shape == np.asarray(params2[name]).shape, name


# ---------------------------------------------------------------------------
# checkpoint round trips
# ---------------------------------------------------------------------------


def _build():
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(16))
    y = layer.data(name="y", type=paddle.data_type.integer_value(4))
    h = layer.fc(input=x, size=30, act="relu")  # 30-wide bias: pad path
    return layer.classification_cost(input=layer.fc(input=h, size=4), label=y)


def _batches(seed, n_batches=3, batch=32):
    r = np.random.RandomState(seed)
    return [[(r.randn(16).astype(np.float32), int(r.randint(4)))
             for _ in range(batch)] for _ in range(n_batches)]


def _make(zero):
    cost = _build()
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=7)
    return trainer.SGD(cost=cost, parameters=params,
                       update_equation=optimizer.Adam(learning_rate=1e-2),
                       mesh=make_mesh((8,), ("data",)), zero=zero)


def _run(sgd, batches):
    sgd.train(lambda: iter(batches), num_passes=1,
              event_handler=lambda ev: None)


@pytest.mark.resilience
@pytest.mark.parametrize("z_save,z_load", [(1, 0), (0, 1)],
                         ids=["zero1_to_zero0", "zero0_to_zero1"])
def test_step_cursor_resume_across_zero_stages(tmp_path, z_save, z_load):
    """Cross-layout resume under chaos, STEP-granular: a zero=z
    run is killed MID-PASS between step checkpoints; a trainer under the
    OTHER zero stage resumes via the cursor (pass, step-in-pass, rng)
    and the post-resume loss trajectory + final params match the
    replicated run that never died — the layout-independence guarantee
    extended from pass boundaries to arbitrary step cuts."""
    from paddle_tpu.resilience import InjectedTrainerDeath, TrainFaultPlan

    batches = _batches(0, n_batches=6)
    costs_ref, costs_b = [], []

    def recorder(out):
        def handler(ev):
            if isinstance(ev, paddle.event.EndIteration):
                out.append((ev.batch_id, float(ev.cost)))
        return handler

    ref = _make(0)
    ref.train(lambda: iter(batches), num_passes=1,
              event_handler=recorder(costs_ref))

    save = str(tmp_path / "ck")
    a = _make(z_save)
    a._faults = TrainFaultPlan(kill_at={4})
    with pytest.raises(InjectedTrainerDeath):
        # checkpoints after steps 2 and 4; the kill fires BEFORE step 4
        # runs, so the newest durable cursor is (pass 0, step 4)... the
        # save after step 3 (save_period_steps=2 -> after b1, b3)
        a.train(lambda: iter(batches), num_passes=1, save_dir=save,
                save_period_steps=2, resume=True, async_save=False)

    b = _make(z_load)
    b.train(lambda: iter(batches), num_passes=1, save_dir=save,
            save_period_steps=2, resume=True, async_save=False,
            event_handler=recorder(costs_b))
    # post-resume trajectory: b re-ran exactly steps 4 and 5
    assert [bid for bid, _ in costs_b] == [4, 5]
    ref_tail = dict(costs_ref)
    for bid, c in costs_b:
        np.testing.assert_allclose(c, ref_tail[bid], rtol=1e-6, atol=1e-8,
                                   err_msg=f"loss at step {bid}")
    for k in ref.parameters.names():
        np.testing.assert_allclose(np.asarray(b.parameters[k]),
                                   np.asarray(ref.parameters[k]),
                                   rtol=1e-6, atol=1e-8, err_msg=k)


@pytest.mark.parametrize("z_save,z_load", [(1, 0), (0, 1), (1, 1)],
                         ids=["zero1_to_zero0", "zero0_to_zero1",
                              "zero1_to_zero1"])
def test_checkpoint_roundtrip_across_zero_stages(tmp_path, z_save, z_load):
    """Checkpoints are layout-independent: save under one zero stage, load
    under another, and the continued trajectory is bit-identical to the
    replicated run that never checkpointed."""
    first, second = _batches(0), _batches(1)
    ref = _make(0)
    _run(ref, first)
    _run(ref, second)

    a = _make(z_save)
    _run(a, first)
    a.save_checkpoint(str(tmp_path), 0)
    # the artifact itself must hold FULL tensor shapes, not flat shards
    _, st, _, _ = ckpt.load_checkpoint(str(tmp_path), 0)
    for slot in st["slots"].values():
        for name, arr in slot.items():
            assert arr.shape == np.asarray(a.parameters[name]).shape, name

    b = _make(z_load)
    b.load_checkpoint(str(tmp_path), 0)
    _run(b, second)
    for k in ref.parameters.names():
        np.testing.assert_allclose(np.asarray(b.parameters[k]),
                                   np.asarray(ref.parameters[k]),
                                   rtol=1e-6, atol=1e-8, err_msg=k)
