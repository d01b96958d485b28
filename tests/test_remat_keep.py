"""What ``train.remat`` keeps (``topology.KEPT`` / ``topology.keep``): the
values an op tags survive a recomputed segment, so the backward pass reads
them where it would run their makers again.  Tiny ``qwen3_next`` (heads of
128 lanes, so the delta rule's kernels run, in interpret mode) and
``glm_moe_lite`` models on the CPU: same loss and gradients as the
checkpoint with no policy, bit for bit; which kernels and products the
backward half still holds; a segment with no tagged value lowers as it
did; the gauge the group publishes."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer, topology, trainer
from paddle_tpu.models import glm_moe_lite, qwen3_next, transformer
from paddle_tpu.obs import default_registry

T = 128                 # tokens of the one sequence: two chunks of the scan
HIDDEN, HK, HV, D = 32, 1, 2, 128
ROUTED, HELD, TOP_K = 8, 4, 2


def qwen3_next_cost():
    """``[delta, attention]``, each with its expert layer: four segments."""
    *_, cost = qwen3_next.build(
        vocab_size=64, hidden_size=HIDDEN, num_layers=2,
        full_attention_interval=2, num_heads=2, num_kv_heads=1, head_dim=16,
        linear_num_key_heads=HK, linear_num_value_heads=HV,
        linear_key_head_dim=D, linear_value_head_dim=D,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts=ROUTED, held_experts=(0, HELD),
        num_experts_per_tok=TOP_K, max_len=T, remat=True)
    return cost


def glm_cost():
    """A dense block, an expert block and the MTP module's: sigmoid
    routing, one segment a block."""
    *_, cost = glm_moe_lite.build(
        vocab_size=64, hidden_size=HIDDEN, n_dense_layers=1, n_moe_layers=1,
        num_heads=2, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, intermediate_size=32,
        moe_intermediate_size=16, n_routed_experts=ROUTED,
        held_experts=(0, HELD), num_experts_per_tok=TOP_K, max_len=T,
        remat=True)
    return cost


def transformer_cost():
    *_, cost = transformer.build(vocab_size=64, d_model=HIDDEN, n_heads=2,
                                 n_layers=2, max_len=T, remat=True)
    return cost


FEEDING = {"tokens": 0, "pos": 1, "target": 2}


def one_sequence():
    t = (np.arange(T + 1, dtype=np.int32) * 7) % 64
    return [(t[:-1], np.arange(T, dtype=np.int32), t[1:])]


def program(make_cost):
    """(loss(params) -> (scalar, the step's counters), params, trainer)."""
    paddle.topology.reset_name_scope()
    cost = make_cost()
    costs = cost if isinstance(cost, list) else [cost]
    sgd = trainer.SGD(
        cost=cost, update_equation=optimizer.Adam(learning_rate=2e-4),
        parameters=paddle.Parameters.from_topology(
            paddle.topology.Topology(costs), seed=0))
    feeds = sgd._make_feeder(FEEDING).feed(one_sequence())

    def loss(p):
        counted = {}
        outs, _ = sgd.topology.forward(
            p, sgd.model_state, feeds, train=True,
            rng=jax.random.PRNGKey(0), counters=counted)
        return sum(trainer._reduce_cost(o) for o in outs[:len(costs)]), counted

    return loss, sgd.parameters.as_dict(), sgd


@pytest.fixture
def no_policy(monkeypatch):
    """Call it to run what follows as before this mechanism:
    ``jax.checkpoint(segment)`` with no policy."""
    return lambda: monkeypatch.setattr(topology, "_KEEP_POLICY", None)


# ---- (a) the same numbers ----------------------------------------------------

@pytest.mark.parametrize("make_cost", [qwen3_next_cost, glm_cost],
                         ids=["qwen3_next", "glm_moe_lite"])
def test_loss_and_gradients_equal_the_policy_free_checkpoint_bit_for_bit(
        make_cost, no_policy):
    loss, params, _ = program(make_cost)
    step = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: loss(p)[0]))(params)
    got = step()
    no_policy()
    want = step()
    assert float(got[0]) == float(want[0]) and np.isfinite(float(got[0]))
    for name, g in want[1].items():
        assert np.array_equal(np.asarray(got[1][name]), np.asarray(g)), name
        # (the correction bias only chooses: its gradient is zero)
        assert np.asarray(g).any() or name.endswith("_moe.bias"), name


# ---- (b) what the backward half still runs -----------------------------------

def _inner_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def ops_by_half(jaxpr, backward=False, found=None, branch=None):
    """{(in the backward half, what): count} over a gradient's jaxpr: the
    kernels by name, the products by output shape and precision, ``top_k``.
    The forward pass of a differentiated ``jax.checkpoint`` lies inline;
    its recomputation and backward pass lie inside ``remat2`` equations
    (the primitive of ``jax.checkpoint``).  With ``branch`` a ``cond``
    counts for that branch alone (its last where it has fewer): the path
    a step takes."""
    found = collections.Counter() if found is None else found
    for e in jaxpr.eqns:
        prim = e.primitive.name
        if prim == "cond" and branch is not None:
            taken = e.params["branches"][min(branch, len(
                e.params["branches"]) - 1)]
            ops_by_half(taken.jaxpr, backward, found, branch)
            continue
        if prim == "pallas_call":
            found[backward, e.params["name"]] += 1
        elif prim == "dot_general":
            found[backward, "dot", tuple(e.outvars[0].aval.shape),
                  e.params["precision"] is not None] += 1
        elif prim == "top_k":
            found[backward, "top_k"] += 1
        for inner in _inner_jaxprs(e):
            ops_by_half(inner, backward or prim == "remat2", found, branch)
    return found


def test_backward_of_a_qwen3_next_block_runs_no_scan_prologue_or_router_again(
        no_policy):
    loss, params, _ = program(qwen3_next_cost)
    grad = lambda: ops_by_half(jax.make_jaxpr(  # noqa: E731
        jax.grad(lambda p: loss(p)[0]))(params).jaxpr)
    router = ("dot", (T, ROUTED), True)     # x W_r, at the highest precision
    kept = grad()
    assert kept[False, "gdn_chunk_fwd"] == kept[False, "qkv_conv_fwd"] == 1
    assert kept[False, "moe_gmm"] == 2 * 3 and kept[(False,) + router] == 2
    assert kept[True, "gdn_chunk_fwd"] == kept[True, "qkv_conv_fwd"] == 0
    assert kept[(True,) + router] == kept[True, "top_k"] == 0
    assert kept[True, "gdn_chunk_bwd"] == kept[True, "qkv_conv_bwd"] == 1
    # the readers of the benchmark count these a step: as they were
    calls = {k: kept[True, k] for k in ("moe_gmm", "moe_tgmm", "flash_fwd",
                                        "flash_bwd_dkv", "flash_bwd_dq")}
    assert calls == {"moe_gmm": 2 * 6, "moe_tgmm": 2 * 3, "flash_fwd": 1,
                     "flash_bwd_dkv": 1, "flash_bwd_dq": 1}
    no_policy()
    bare = grad()
    assert bare[True, "gdn_chunk_fwd"] == bare[True, "qkv_conv_fwd"] == 1
    assert bare[(True,) + router] == bare[True, "top_k"] == 2
    assert {k: bare[True, k] for k in calls} == calls


@pytest.mark.parametrize("rung", [0, 1, 2])
def test_a_step_at_any_rung_runs_twelve_grouped_kernels_a_layer(
        rung, monkeypatch):
    """One rank of sixteen (1 of 16 experts, tiles of 8 rows): the expert
    layer's buffer has three lengths (40, 72 and the worst case, 264 rows)
    and a ``cond`` each way chooses.  Whichever a step takes, a layer runs
    its three products forward and, in the backward half, three again (the
    gradient's branch makes its own forward: the segment's recomputation
    has nothing of the experts left to make) and the six gradients: the 12
    the benchmark's reader divides a trace's calls by."""
    from paddle_tpu.ops import grouped_matmul as gm
    from paddle_tpu.parallel import moe as pmoe

    monkeypatch.setattr(gm, "TILE_M", 8)
    monkeypatch.setitem(globals(), "ROUTED", 16)
    monkeypatch.setitem(globals(), "HELD", 1)
    assert pmoe.dropless_rungs(T, TOP_K, 1, 16, 8) == (40, 72, 264)
    loss, params, _ = program(qwen3_next_cost)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss(p)[0]))(params).jaxpr
    taken = ops_by_half(jaxpr, branch=rung)
    calls = {k: taken[k] for k in taken if k[1] in ("moe_gmm", "moe_tgmm")}
    assert calls == {(False, "moe_gmm"): 2 * 3, (True, "moe_gmm"): 2 * 6,
                     (True, "moe_tgmm"): 2 * 3}
    # and the program holds every rung's: three branches each way
    every = ops_by_half(jaxpr)
    assert every[False, "moe_gmm"] == 3 * 2 * 3
    assert every[True, "moe_gmm"] == 3 * 2 * 6


# ---- (c) a segment with no tagged value ---------------------------------------

def test_a_remat_transformer_traces_as_with_no_policy(no_policy):
    """The gradient's jaxpr, residuals and recomputed segments and all, is
    the policy-free one but for the ``policy=`` the equations print."""
    def traced():
        loss, params, _ = program(transformer_cost)
        jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: loss(p)[0]))(params))
        return re.sub(r"policy=.*", "policy=", jaxpr), loss(params)[1]

    jaxpr, counted = traced()
    assert jaxpr.count(" remat2[") == 2 and " name[" not in jaxpr
    # no gauge, so no further output of the step: nothing was tagged
    assert not [key for key in counted if key[1] == "remat_kept_bytes"]
    no_policy()
    assert traced()[0] == jaxpr


# ---- (d) the gauge ------------------------------------------------------------

def test_remat_kept_bytes_is_the_sum_of_the_tagged_shapes():
    _, _, sgd = program(qwen3_next_cost)
    before = default_registry().snapshot()
    sgd.train(lambda: iter([one_sequence()]), num_passes=1,
              event_handler=lambda ev: None, feeding=FEEDING)
    snap = default_registry().snapshot()
    gauge = lambda g: snap["remat_kept_bytes{group=%s}" % g]  # noqa: E731
    n, rep = T // 64, HV // HK
    f32 = {
        "qkvz": (T, 2 * HK * D + 2 * HV * D), "ba": (T, 2 * HV),
        "q": (T, HK * D), "k": (T, HK * D), "v": (T, HV * D),
        "rows": (n, HK, 2, rep * 64), "marks": (n, 3, rep * 64),
        "o": (T, HV * D), "states": (n, HV, D, D)}
    assert gauge("blk0_mix") == 4 * sum(map(np.prod, f32.values()))
    buffer = T * TOP_K + HELD * 128         # rows of the sorted buffer
    route = {"logits": (T, ROUTED), "top_k values": (T, TOP_K),
             "experts": (T, TOP_K), "g": (T, TOP_K), "dest": (T, TOP_K),
             "row_token": (buffer,), "row_pair": (buffer,),
             "tile_group": (buffer // 128,), "n_active": (1,)}
    assert gauge("blk0_moe") == gauge("blk1_moe") \
        == 4 * sum(map(np.prod, route.values()))
    # the attention half tags nothing and publishes nothing (the registry
    # is the process's: another test's model may have a group of the name)
    idle = "remat_kept_bytes{group=blk1_mix}"
    assert snap.get(idle) == before.get(idle)
    assert not [k for k in sgd._counter_keys if "blk1_mix" in str(k)]


def test_a_name_outside_the_list_is_refused():
    with pytest.raises(Exception, match="not one of"):
        topology.keep("flash_o", jnp.zeros(4))
