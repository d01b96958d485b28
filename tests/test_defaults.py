"""What an engine, a fleet and a trainer are built with when they are told
nothing: each is constructed with its required arguments alone and read
back.  The values are the keywords' own defaults."""

import os

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import checkpoint as ckpt
from paddle_tpu import layer, optimizer, trainer
from paddle_tpu.serving import (DecoderLM, FleetRouter, NGramProposer,
                                ServingEngine)


def _model():
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=64)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_engine_defaults():
    model, params = _model()
    eng = ServingEngine(model, params, eos_id=1)
    cfg = eng.kv_cfg
    assert (cfg.page_size, cfg.num_pages, eng._max_slots) == (128, 512, 8)
    # a sequence may claim up to half the usable pool
    assert cfg.max_pages_per_seq == 255
    assert cfg.dtype == jnp.float32 and not cfg.quantized
    assert eng._buckets == (32, 64, 128, 256, 512)
    assert eng._prefill_chunk == 256
    assert eng.cache is not None                      # prefix cache built
    assert eng.host_tier is None and eng._swap_in_budget == 8
    assert eng.spec_mode == "off" and eng._proposer is None
    assert eng.spec_k == 4 and eng._k1 == 1
    assert NGramProposer().n == 3
    assert eng.queue_deadline_s is None               # no admission deadline
    assert eng.scheduler.cfg.preempt_budget == 3
    assert eng.watchdog_ticks == 16
    assert eng.role == "unified" and eng.tp == 1


def test_fleet_defaults():
    model, params = _model()

    def mk(i, time_fn):
        return ServingEngine(model, params, eos_id=1, page_size=4,
                             num_pages=16, max_pages_per_seq=4, max_slots=2,
                             buckets=(4, 8), time_fn=time_fn)

    fl = FleetRouter(mk)
    assert len(fl.replicas) == 4
    assert fl.heartbeat_s == 1.0 and fl.lease_ttl_s == 3.0
    assert fl.resubmit_budget == 2
    assert fl.migrate_budget == 16
    assert all(r.role == "unified" for r in fl.replicas)
    assert fl.tenants is None and fl.wfq is None and fl.autoscaler is None
    assert fl.routing == "affinity"


def test_trainer_defaults(tmp_path):
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(4))
    y = layer.data(name="y", type=paddle.data_type.integer_value(2))
    cost = layer.classification_cost(input=layer.fc(input=x, size=2),
                                     label=y)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=0)
    sgd = trainer.SGD(cost=cost, parameters=params,
                      update_equation=optimizer.Momentum(learning_rate=0.1))
    assert sgd._zero_plan is None                     # ZeRO off
    assert sgd._guard is None                         # unguarded step
    data = [([0.1 * i, 0.0, 1.0, -1.0], i % 2) for i in range(8)]
    # four saves, one a step: synchronous (no writer thread is made),
    # and the two newest verified checkpoints stay
    sgd.train(paddle.batch(lambda: iter(data), 2), num_passes=1,
              save_dir=str(tmp_path), save_period_steps=1)
    assert sgd._async_ckpt is None
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("pass-"))
    assert len(kept) == 2 and ckpt.latest_pass(str(tmp_path)) is not None
