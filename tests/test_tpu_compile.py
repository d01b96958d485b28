"""AOT compiles of the main-path pallas kernels for a DESCRIBED TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described (``get_topology_desc``) and not attached, so these
tests catch what interpret mode cannot: block shapes Mosaic refuses,
VMEM overflows, kernels that cannot be partitioned under ``shard_map``.
Nothing runs — a compile that passes is a compile, not a chip run.

Shapes are the real widths of the d2048 train/serve cells.  The
persistent compile cache is switched off around the module: an entry
written for a described device cannot be read back without a chip, and
the next run would warn and recompile.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import rnn
from paddle_tpu.ops.attention import flash_attention
from paddle_tpu.serving.decode_attention import (ragged_paged_attention,
                                                 ragged_paged_attention_tp)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu in this installation
        pytest.skip(f"cannot describe a TPU v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    """Compile ``fn`` for the avals' (described) devices; returns the
    number of Mosaic kernels in the optimized program."""
    compiled = jax.jit(fn).lower(*avals).compile()
    return compiled.as_text().count("tpu_custom_call")


def _on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


# ---- flash attention: the train cell's shape ------------------------------

@pytest.mark.parametrize("mode", ["fwd", "bwd_pallas"])
def test_flash_attention_compiles_for_v5e(topo, mode):
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    q = aval((4, 1024, 16, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    n = _compile(fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1, 2)),
                 q, q, q)
    # fwd kernel, plus dKV and dQ kernels on the backward
    assert n == {"fwd": 1, "bwd_pallas": 3}[mode]


def test_flash_attention_under_a_mesh_needs_per_device(topo):
    """GSPMD cannot partition a Mosaic kernel: a jit that spans four
    chips refuses the bare call and takes it through
    ``kernel_util.per_device`` — what the attention and recurrent layers
    do under ``SGD(mesh=...)``."""
    from paddle_tpu.ops.kernel_util import per_device

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    q = _on(NamedSharding(mesh, P()))((1, 1024, 16, 128), jnp.bfloat16)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(attend, q, q, q)
    assert _compile(per_device(attend, mesh), q, q, q) == 1


# ---- ragged paged attention: the serve cell's shape -----------------------

def _ragged_avals(aval_for, t, h, kvh, dtype, d=128, page=128, pages=64,
                  slots=8, pm=8):
    """(q, k, v, table, lens, row_seq, qpos[, k_scale, v_scale]) avals,
    K and V a stored pool of ONE layer; ``aval_for(spec)`` places one
    argument."""
    head, pool = P(None, "model", None), P(None, None, None, "model")
    quant = dtype == "int8"
    q = aval_for(head)((t, h, d), jnp.float32 if quant else dtype)
    kv = aval_for(pool)((1, pages, page, kvh * d),
                        jnp.int8 if quant else dtype)
    i32 = lambda *shape: aval_for(P())(shape, jnp.int32)  # noqa: E731
    out = [q, kv, kv, i32(slots, pm), i32(slots), i32(t), i32(t)]
    if quant:
        sc = aval_for(pool)((1, pages, page, kvh), jnp.float32)
        out += [sc, sc]
    return out


def _scales(rest):
    return dict(k_scale=rest[0], v_scale=rest[1]) if rest else {}


RAGGED_CASES = [
    # rows, heads, kv heads, pool dtype
    (64, 16, 16, jnp.float32),
    (64, 16, 16, jnp.bfloat16),
    (64, 16, 4, jnp.bfloat16),          # GQA: 4 query heads per KV head
    (64, 16, 16, "int8"),
    (64, 16, 4, "int8"),
    (8 * 8 + 512, 16, 16, jnp.float32),  # a full tick: 8 slots + 512 prefill
    # the 6.7B's 32 heads: 8 a chip under TP=4 (the benchmark's cell,
    # all of them in one grid cell); on one chip all 32 still fold, at
    # the edge of heads_per_cell's VMEM budget (8 MiB of K and V tiles)
    (8 * 8 + 256, 32, 32, jnp.float32),
]


@pytest.mark.parametrize("t,h,kvh,dtype", RAGGED_CASES)
def test_ragged_paged_attention_compiles_for_v5e(topo, t, h, kvh, dtype):
    one = SingleDeviceSharding(topo.devices[0])

    def fn(q, k, v, table, lens, row_seq, qpos, *rest):
        return ragged_paged_attention(q, k, v, table, lens, row_seq, qpos,
                                      layer=0, use_kernel=True,
                                      interpret=False, **_scales(rest))

    assert _compile(fn, *_ragged_avals(lambda spec: _on(one), t, h, kvh,
                                       dtype)) == 1


@pytest.mark.parametrize("t,h,kvh,dtype", RAGGED_CASES[:5] + RAGGED_CASES[6:])
def test_ragged_paged_attention_tp4_compiles_for_v5e(topo, t, h, kvh, dtype):
    """The same kernel under ``shard_map`` over a four-chip model axis
    (the TP=4 engine's attention): each chip gets H/4 query and KVH/4 KV
    heads, down to ONE KV head per chip for GQA 16/4."""
    mesh = Mesh(np.asarray(topo.devices), ("model",))

    def fn(q, k, v, table, lens, row_seq, qpos, *rest):
        return ragged_paged_attention_tp(mesh, "model", q, k, v, table, lens,
                                         row_seq, qpos, layer=0,
                                         use_kernel=True, interpret=False,
                                         **_scales(rest))

    avals = _ragged_avals(lambda spec: _on(NamedSharding(mesh, spec)),
                          t, h, kvh, dtype)
    assert _compile(fn, *avals) == 1


# ---- the pool where it lies: the step's kernel on the WHOLE stored pool ----

POOL_CASES = [
    # chips, layers, pages, heads (= KV heads), rows: a decode + prefill tick
    (4, 32, 128, 32, 32 * 8 + 256),   # serve-6.7b-tp4-chat: a chip's shard
                                      # is [32 * 128, 128, 1024] f32 to the
                                      # kernel, 2.1 GB each of K and V
    (1, 24, 128, 16, 32 * 8 + 256),   # the one-chip 1.3B: [24 * 128, 128,
                                      # 2048], 3.2 GB each
]


@pytest.mark.parametrize("chips,layers,pages,h,t", POOL_CASES,
                         ids=["6.7b_tp4_shard", "1.3b_one_chip"])
def test_ragged_kernel_on_the_whole_pool_compiles_for_v5e(topo, chips, layers,
                                                          pages, h, t):
    """The serving step's call: K and V are the pool's leaves as stored
    (``[L, pages, page, H * D]``), the layer a traced scalar that rides
    with the scalar-prefetch operands.  The optimized program holds the
    kernel and NO copy, slice or transpose of the pool or of a layer of
    it: the kernel's operand is a bitcast of the parameter."""
    import re

    d, page, slots, pm = 128, 128, 32, 10
    if chips == 1:
        place = lambda spec: _on(SingleDeviceSharding(topo.devices[0]))  # noqa: E731,E501
    else:
        mesh = Mesh(np.asarray(topo.devices), ("model",))
        place = lambda spec: _on(NamedSharding(mesh, spec))  # noqa: E731
    q = place(P(None, "model", None))((t, h, d), jnp.float32)
    pool = place(P(None, None, None, "model"))((layers, pages, page, h * d),
                                               jnp.float32)
    i32 = lambda *shape: place(P())(shape, jnp.int32)  # noqa: E731

    def fn(q, k, v, layer, table, lens, row_seq, qpos):
        kw = dict(layer=layer, use_kernel=True, interpret=False)
        if chips == 1:
            return ragged_paged_attention(q, k, v, table, lens, row_seq,
                                          qpos, **kw)
        return ragged_paged_attention_tp(mesh, "model", q, k, v, table, lens,
                                         row_seq, qpos, **kw)

    text = jax.jit(fn).lower(q, pool, pool, i32(), i32(slots, pm),
                             i32(slots), i32(t), i32(t)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    shard = f"{layers},{pages},{page},{h * d // chips}"
    rows = f"{layers * pages},{page},{h * d // chips}"
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf" = f32\[(?:{shard}|{rows}|{pages},{page},)"
                          r"[\d,]*\][^ ]* "
                          r"(?:copy|slice|dynamic-slice|transpose|reshape|"
                          r"fusion|copy-start)\(", line)]
    assert not moved, moved


# ---- the walk (PR 42): short and tall blocks at the serve cells' shapes ------

WALK_CELLS = [
    # rows in short blocks, bucket, heads and KV heads a chip, window,
    # slots, the page table's width, chips
    ("laguna_full", 256, 1024, 48, 8, None, 32, 130, 1),
    ("laguna_full_decode", 256, 0, 48, 8, None, 32, 130, 1),
    ("laguna_window", 256, 1024, 64, 8, 512, 32, 9, 1),
    ("laguna_window_decode", 256, 0, 64, 8, 512, 32, 9, 1),
    ("6.7b_tp4", 256, 256, 32, 32, None, 32, 10, 4),
    ("6.7b_tp4_decode", 256, 0, 32, 32, None, 32, 10, 4),
    ("sdar_block4", 256, 256, 32, 4, None, 32, 12, 1),   # 4 rows a slot
    ("sdar_block4_decode", 256, 0, 32, 4, None, 32, 12, 1),
    # falcon-h1: 20 query heads on 4 KV heads (a GQA group of 5, no power
    # of two), 64 slots, a 38-page table
    ("falconh1_group5", 512, 1024, 20, 4, None, 64, 38, 1),
    ("falconh1_group5_decode", 512, 0, 20, 4, None, 64, 38, 1),
]


@pytest.mark.parametrize("td,pb,h,kvh,window,slots,pm,chips",
                         [c[1:] for c in WALK_CELLS],
                         ids=[c[0] for c in WALK_CELLS])
def test_the_walk_compiles_for_v5e_at_the_serve_cells_shapes(
        topo, td, pb, h, kvh, window, slots, pm, chips):
    """One Mosaic kernel for the decode rows' short blocks and the bucket's
    tall ones together (laguna: 48 and 64 heads over 8 KV heads, 64-row
    tall blocks, with a window of 512 and without, a 130-page table whose
    walk of up to 20,800 visits rides in SMEM; 6.7B under TP 4: 8 heads a
    chip, the 256-row bucket one tall block; sdar: group 8), the walk made
    by the caller and handed in as the engine does.  A block shape or a
    scalar-prefetch size Mosaic refuses is met here, on the CPU."""
    from paddle_tpu.serving.decode_attention import ragged_walk

    d, page, t = 128, 128, td + pb
    if chips == 1:
        mesh = None
        place = lambda spec: _on(SingleDeviceSharding(topo.devices[0]))  # noqa: E731,E501
    else:
        mesh = Mesh(np.asarray(topo.devices), ("model",))
        place = lambda spec: _on(NamedSharding(mesh, spec))  # noqa: E731
    q = place(P(None, "model", None))((t, h, d), jnp.float32)
    pool = place(P(None, None, None, "model"))((2, 64, page, kvh * d),
                                               jnp.float32)
    i32 = lambda *shape: place(P())(shape, jnp.int32)  # noqa: E731

    def fn(q, k, v, layer, table, lens, row_seq, qpos):
        walk = ragged_walk(table, lens, row_seq, qpos, num_heads=h // chips,
                           num_kv_heads=kvh // chips, head_dim=d,
                           page_size=page, kv_itemsize=4, decode_rows=td,
                           window=window)
        kw = dict(layer=layer, use_kernel=True, interpret=False,
                  decode_rows=td, walk=walk)
        if mesh is None:
            return ragged_paged_attention(q, k, v, table, lens, row_seq,
                                          qpos, window=window, **kw)
        return ragged_paged_attention_tp(mesh, "model", q, k, v, table, lens,
                                         row_seq, qpos, **kw)

    assert _compile(fn, q, pool, pool, i32(), i32(slots, pm), i32(slots),
                    i32(t), i32(t)) == 1


# ---- the latent-attention and expert-layer kernels at their cell's widths ---

@pytest.mark.parametrize("mode", ["fwd", "bwd_pallas"])
def test_flash_attention_at_head_width_256_compiles_for_v5e(topo, mode):
    """MLA's heads (192 + 64 for q/k, 256 for v) give the three kernels
    D = 256 at block 512: twice the VMEM of every other cell's tiles."""
    q = _on(SingleDeviceSharding(topo.devices[0]))((1, 4096, 20, 256),
                                                   jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    n = _compile(fwd if mode == "fwd" else jax.grad(loss, argnums=(0, 1, 2)),
                 q, q, q)
    assert n == {"fwd": 1, "bwd_pallas": 3}[mode]


@pytest.mark.parametrize("heads,width", [(16, 128), (20, 256), (16, 256)])
def test_flash_kernels_walk_a_schedule_at_the_cells_shapes_for_v5e(
        topo, heads, width):
    """The train cells hand the kernels ONE row of 8192 tokens with segment
    ids (1.3B: 16 x 128; glm: 20 x 256; qwen3-next: 16 x 256): the grid's
    last extent is the traced number of live blocks and the index maps read
    the blocks from scalar-prefetch operands, which Mosaic has to lower."""
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    q = aval((1, 8192, heads, width), jnp.bfloat16)
    seg = aval((1, 8192), jnp.int32)

    def loss(q, k, v, seg):
        return jnp.sum(flash_attention(q, k, v, segment_ids=seg, causal=True,
                                       interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q, seg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in text, name


@pytest.mark.parametrize("matrix", ["up", "down"])
def test_grouped_matmul_compiles_for_v5e(topo, matrix, monkeypatch):
    """``moe_gmm`` and, through the gradient, its transposed form and
    ``moe_tgmm``, over the worst-case buffer of 8192 tokens x 4 choices on
    8 held experts of 2048 x 1536."""
    from paddle_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "interpret_default", lambda: False)
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    rows = gm.padded_rows(8192 * 4, 8)
    a, b = (2048, 1536) if matrix == "up" else (1536, 2048)
    avals = (aval((rows, a), jnp.bfloat16), aval((8, a, b), jnp.float32),
             aval((rows // gm.TILE_M,), jnp.int32), aval((1,), jnp.int32))

    def loss(x, w, tile_group, n_active):
        return jnp.sum(gm.grouped_matmul(x, w, tile_group, n_active))

    assert _compile(gm.grouped_matmul, *avals) == 1
    assert _compile(jax.grad(loss, argnums=(0, 1)), *avals) == 2


@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_delta_rule_kernels_compile_for_v5e_at_the_cell_size(topo, mode,
                                                             monkeypatch):
    """``gdn_chunk_fwd`` and, through the gradient, ``gdn_chunk_bwd`` at
    the qwen3-next cell's size: 8192 tokens (128 chunks), 16 key and 32
    value heads of 128; a cell's 128 x 128 tiles, the stacked q, k, v and
    the two heads' state have to fit the kernel's VMEM."""
    from paddle_tpu.ops import gated_delta, gdn_kernels

    monkeypatch.setattr(gdn_kernels, "interpret_default", lambda: False)
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    t, hk, hv, d = 8192, 16, 32, 128
    avals = (aval((t, hk, d), jnp.float32), aval((t, hk, d), jnp.float32),
             aval((t, hv, d), jnp.float32), aval((t, hv), jnp.float32),
             aval((t, hv), jnp.float32), aval((t,), jnp.int32))

    def loss(*a):
        o = gated_delta.gated_delta_rule(*a)
        return jnp.sum(o * o)

    n = _compile(gated_delta.gated_delta_rule if mode == "fwd"
                 else jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *avals)
    assert n == {"fwd": 1, "grad": 2}[mode]


@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_delta_rule_prologue_kernels_compile_for_v5e_at_the_cell_size(
        topo, mode, monkeypatch):
    """``qkv_conv_fwd`` and, through the gradient, ``qkv_conv_bwd`` at the
    qwen3-next cell's size: the projection ``[8192, 12288]``, of which the
    first 8192 columns (16 + 16 + 32 heads of 128) are convolved over 4
    taps: blocks of 256 rows by 1024 lanes, their halos, the shifted
    slices of a chunk's window and three parked outputs have to pass
    Mosaic and fit its VMEM."""
    from paddle_tpu.ops import gdn_conv_kernels as gck

    monkeypatch.setattr(gck, "interpret_default", lambda: False)
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    dims = gck.Dims(16, 32, 128, 128)
    avals = (aval((8192, 12288), jnp.float32),
             aval((dims.channels, 4), jnp.float32), aval((8192,), jnp.int32))

    def loss(x, w, seg):
        q, k, v = gck.qkv_conv(x, w, seg, dims)
        return jnp.sum(q * q) + jnp.sum(k) + jnp.sum(v * v)

    fn = (lambda x, w, seg: gck.qkv_conv(x, w, seg, dims)) if mode == "fwd" \
        else jax.grad(loss, argnums=(0, 1))
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert text.count("tpu_custom_call") == {"fwd": 1, "grad": 2}[mode]
    if mode == "fwd":
        # the projection is read where it lies and q, k, v leave as the
        # kernel wrote them: XLA makes no array of 8192 rows of its own
        made = [line[:160] for line in text.splitlines()
                if " = f32[8192," in line and "parameter(" not in line
                and "get-tuple-element(" not in line]
        assert not made, made


# ---- a whole train step of the delta-rule / gated-attention model ------------

def test_qwen3_next_train_step_compiles_for_v5e_with_its_scopes(topo,
                                                                 monkeypatch):
    """``models.qwen3_next.build`` under ``trainer.SGD`` at a small size
    (heads of 128, one sequence of 256): the whole step, Adam included,
    lowers and compiles for a described v5e; its text names the scopes the
    benchmark's readers select by, and holds the flash kernels of the one
    attention block, the grouped products of four expert layers and the
    delta rule's two kernels, whose 64 x 64 tiles never reach HBM."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, trainer
    from paddle_tpu.analysis import retrace
    from paddle_tpu.models import qwen3_next
    from paddle_tpu.ops import attention as pattn
    from paddle_tpu.ops import gdn_conv_kernels, gdn_kernels
    from paddle_tpu.ops import grouped_matmul as gm

    # the code asks jax.default_backend() and sees the CPU
    monkeypatch.setattr(pattn, "_interpret_default", lambda: False)
    monkeypatch.setattr(gm, "interpret_default", lambda: False)
    monkeypatch.setattr(gdn_kernels, "interpret_default", lambda: False)
    monkeypatch.setattr(gdn_conv_kernels, "interpret_default", lambda: False)
    monkeypatch.setattr(retrace, "_backend_jit_kwargs", lambda kw: kw)
    paddle.topology.reset_name_scope()
    *_, cost = qwen3_next.build(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=128, linear_num_key_heads=2,
        linear_num_value_heads=4, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, num_experts=8,
        held_experts=(0, 4), num_experts_per_tok=2, max_len=256, remat=True)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]))
    sgd = trainer.SGD(cost=cost, parameters=params,
                      update_equation=optimizer.Adam(learning_rate=2e-4))
    t = np.arange(257, dtype=np.int32)
    feeds = sgd._make_feeder({"tokens": 0, "pos": 1, "target": 2}).feed(
        [(t[:-1], t[:-1], t[1:])])
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    tree = lambda x: jax.tree.map(  # noqa: E731
        lambda a: aval(np.shape(a), a.dtype), x)
    compiled = sgd._build_step().lower(
        tree(sgd.parameters.as_dict()), tree(sgd.opt_state),
        tree(sgd.model_state), tree(jax.random.PRNGKey(0)),
        tree(feeds)).compile()
    text = compiled.as_text()
    for scope in ("gdn/gdn.proj", "gdn/gdn.conv", "gdn/gdn.scan",
                  "gdn/gdn.out", "gattn", "moe.route", "moe.experts",
                  "moe.shared"):
        assert scope + "/" in text, scope
    # one attention block: forward twice (remat), dKV, dQ; four expert
    # layers: 6 + 3 moe_gmm and 3 moe_tgmm each; three delta-rule layers:
    # gdn_chunk_fwd and gdn_chunk_bwd once each, and before them
    # qkv_conv_fwd and qkv_conv_bwd once each: the segment keeps the
    # forward kernels' outputs by name (``topology.KEPT``)
    assert text.count("tpu_custom_call") == 4 + 4 * 12 + 3 * 2 + 3 * 2
    by_name = {line.split("=")[0].split()[-1]: line
               for line in text.splitlines() if " = " in line}
    calls = {n: line for n, line in by_name.items()
             if "tpu_custom_call" in line}
    named = lambda prefix: sorted(  # noqa: E731
        n.lstrip("%").split(".")[0] for n in calls if n.startswith(prefix))
    # the scan's roofline reads every kernel named ``gdn_*``: still two
    assert named("%gdn_") == ["gdn_chunk_bwd"] * 3 + ["gdn_chunk_fwd"] * 3
    assert all("gdn/gdn.scan/" in line or "gdn.scan)" in line
               for n, line in calls.items() if n.startswith("%gdn_"))
    assert named("%qkv_conv") == ["qkv_conv_bwd"] * 3 + ["qkv_conv_fwd"] * 3
    assert all("gdn/gdn.conv/" in line
               for n, line in calls.items() if n.startswith("%qkv_conv"))
    # q, k, v go from the one kernel to the other as they are, and their
    # cotangents back: no copy, slice or re-tiling of a [T, H d] between

    def made_by(operand):
        through = re.search(r" (?:get-tuple-element|bitcast)\((%[\w.-]+)",
                            by_name[operand])
        return made_by(through.group(1)) if through else operand

    def operands(line):
        return re.findall(r"%[\w.-]+", line.split("custom-call(")[1]
                          .split(")")[0])

    for n, line in calls.items():
        if n.startswith("%gdn_chunk_fwd"):
            left = [by_name[made_by(o)][:200] for o in operands(line)[:3]
                    if not made_by(o).startswith("%qkv_conv_fwd")]
            assert not left, left
        if n.startswith("%qkv_conv_bwd"):
            left = [by_name[made_by(o)][:200]
                    for o in operands(line)[6:12]
                    if not made_by(o).startswith("%gdn_chunk_bwd")]
            assert not left, left
    # [n, Hv, c, c]: 4 chunks of 64 rows, 4 value heads; no K K^T, Q K^T,
    # decay, A or inverse of every chunk and head as an array in HBM
    assert "f32[4,4,64,64]" not in text and "bf16[4,4,64,64]" not in text


# ---- a serving step of the block-diffusion expert model -----------------------

@pytest.mark.parametrize("pb", [0, 256])
def test_block_moe_serving_step_compiles_for_v5e_at_published_widths(
        topo, monkeypatch, pb):
    """``serving.BlockMoeLM`` behind ``ServingEngine`` at the cell's
    widths (hidden 2048, 32 : 4 heads of 128, 128 experts of 768 top-8,
    the whole vocabulary; 2 of its 4 layers), 32 slots of 8 rows (the
    block a slot commits and the one it opens): the decode-only step and
    the one with the prefill bucket lower and compile for a described
    v5e; the experts' float32 matrices go into ``moe_gmm`` as they lie
    (no rounded or re-laid copy), and what leaves the step for the host
    is a slot's best tokens, not its logits."""
    from paddle_tpu.analysis import retrace
    from paddle_tpu.ops import grouped_matmul as gm
    from paddle_tpu.serving import BlockMoeLM, ServingEngine
    from paddle_tpu.serving import decode_attention as da
    from paddle_tpu.serving import engine as eng_mod

    monkeypatch.setattr(da, "_interpret_default", lambda: False)
    monkeypatch.setattr(gm, "interpret_default", lambda: False)
    monkeypatch.setattr(retrace, "_backend_jit_kwargs", lambda kw: kw)
    # the engine asks the backend which attention path compiles here, and
    # makes a pool on it: the kernel path, and a pool of shapes alone
    monkeypatch.setattr(eng_mod, "attention_path", lambda *a, **k: "kernel")
    make_pool = eng_mod.init_kv_pages
    monkeypatch.setattr(
        eng_mod, "init_kv_pages",
        lambda cfg, **kw: jax.eval_shape(lambda: make_pool(cfg, **kw)))
    model = BlockMoeLM(
        vocab_size=151936, num_layers=2, embed_dim=2048, num_heads=32,
        num_kv_heads=4, head_dim=128, num_experts=128, experts_per_token=8,
        expert_dim=768, block_length=4, denoise_steps=2,
        mask_token_id=151669)
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    params = {k: aval(v.shape, v.dtype) for k, v in jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)).items()}
    eng = ServingEngine(model, params, eos_id=model.vocab_size,
                        page_size=128, max_slots=32, pool_bytes=1 << 29,
                        max_pages_per_seq=10, buckets=(256,),
                        prefill_chunk=256)
    assert eng._ragged_kernel and eng._k1 == 8
    buf = eng._empty_tick(pb, 8)
    # (and the words of the step before: the tokens it fixed are read there)
    words = np.zeros(32 * 3 + 32 + 5, np.int32)
    compiled = eng._step_fn(pb, 8).lower(
        params, jax.tree.map(lambda a: aval(a.shape, a.dtype), eng._kv),
        aval(buf.shape, buf.dtype), aval(words.shape, words.dtype)).compile()
    text = compiled.as_text()
    # a layer: the ragged attention kernel and the three grouped products
    assert text.count("tpu_custom_call") == 2 * 4
    for scope in ("moe.route", "moe.experts"):
        assert scope + "/" in text, scope
    # the 805 MB of a layer's gate (or up, or down) matrices are read by
    # the kernels alone: nothing else produces an array of their shape
    for shape in ("[128,2048,768]", "[128,768,2048]"):
        made = [line for line in text.splitlines() if " = " in line
                and shape in line.split(" = ")[1].split("(")[0]
                and "parameter(" not in line]
        assert not made, made[:2]
    out = jax.eval_shape(eng._step_fn(pb, 8), params, eng._kv, buf, words)
    # one small vector: picks [32, 3], the chunk guard's [32], five counts
    assert out[0].shape == (32 * 3 + 32 + 5,) and out[0].dtype == jnp.int32
    assert out[1].shape == (32, 2, 151936)       # the logits stay behind
    # parameters 5.0 GB and the pool; a few tens of MB beside them
    mem = compiled.memory_analysis()
    print("block step", pb, "temp bytes", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 200e6


# ---- a serving step of the window-and-full expert model ----------------------

@pytest.mark.parametrize("pb", [0, 1024])
def test_window_moe_serving_step_compiles_for_v5e_at_published_widths(
        topo, monkeypatch, pb):
    """``serving.WindowMoeLM`` behind ``ServingEngine`` at the cell's
    widths (hidden 2048, 48 and 64 query heads on 8 KV heads of 128, a
    512-token window, 128 of 256 experts of 512 top-8 with a shared one,
    the whole vocabulary; the dense layer, two window layers and a full
    one), 32 slots, the decode-only step and the one with the 1024-row
    prefill bucket: both lower and compile for a described v5e with the
    ragged kernel at GQA groups of 6 and of 8, the window layers' calls
    on their rings; the experts' float32 matrices go into ``moe_gmm`` as
    they lie."""
    from paddle_tpu.analysis import retrace
    from paddle_tpu.ops import grouped_matmul as gm
    from paddle_tpu.serving import ServingEngine, WindowMoeLM
    from paddle_tpu.serving import decode_attention as da
    from paddle_tpu.serving import engine as eng_mod

    monkeypatch.setattr(da, "_interpret_default", lambda: False)
    monkeypatch.setattr(gm, "interpret_default", lambda: False)
    monkeypatch.setattr(retrace, "_backend_jit_kwargs", lambda kw: kw)
    monkeypatch.setattr(eng_mod, "attention_path", lambda *a, **k: "kernel")
    make_pool = eng_mod.init_kv_pages
    monkeypatch.setattr(
        eng_mod, "init_kv_pages",
        lambda cfg, **kw: jax.eval_shape(lambda: make_pool(cfg, **kw)))
    yarn = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}
    model = WindowMoeLM(
        vocab_size=100352, embed_dim=2048, layer_heads=[48, 64, 64, 48],
        layer_windows=[None, 512, 512, None],
        layer_sparse=[False, True, True, True], num_kv_heads=8, head_dim=128,
        dense_dim=8192, num_experts=256, held=(0, 128), experts_per_token=8,
        expert_dim=512, shared_dim=512, routed_scaling=2.5, rope_full=yarn,
        rope_window={"rope_type": "default", "rope_theta": 10000,
                     "partial_rotary_factor": 1})
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    params = {k: aval(v.shape, v.dtype) for k, v in jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)).items()}
    eng = ServingEngine(model, params, eos_id=model.vocab_size,
                        page_size=128, max_slots=32, pool_bytes=1 << 31,
                        max_pages_per_seq=130, buckets=(1024,),
                        prefill_chunk=512)
    (ring,) = eng._rings
    assert eng._ragged_kernel and eng._k1 == 1 and ring.ring_pages == 9
    assert eng.kv_cfg.num_layers == 2 and ring.cfg.num_layers == 2
    buf = eng._empty_tick(pb, 1)
    words = np.zeros(2 * (32 + 32) + 6, np.int32)
    pools = [jax.tree.map(lambda a: aval(a.shape, a.dtype), kv)
             for kv in (eng._kv,) + eng._ring_kv]
    compiled = eng._step_fn(pb, 1).lower(
        params, pools[0], aval(buf.shape, buf.dtype),
        aval(words.shape, words.dtype), *pools[1:]).compile()
    text = compiled.as_text()
    # a layer: the ragged attention kernel, and the three grouped products
    # of the three expert layers
    assert text.count("tpu_custom_call") == 4 + 3 * 3
    for scope in ("attn.full", "attn.window", "moe.route", "moe.experts",
                  "moe.shared", "ffn.dense"):
        assert scope + "/" in text, scope
    for shape in ("[128,2048,512]", "[128,512,2048]"):
        made = [line for line in text.splitlines() if " = " in line
                and shape in line.split(" = ")[1].split("(")[0]
                and "parameter(" not in line]
        assert not made, made[:2]
    out = jax.eval_shape(eng._step_fn(pb, 1), params, eng._kv, buf, words,
                         *eng._ring_kv)
    assert out[0].shape == (2 * 64 + 6,) and out[0].dtype == jnp.int32
    assert out[1].shape == (64, 100352)          # the logits stay behind
    assert len(out) == 4                         # ... the pool and the ring
    mem = compiled.memory_analysis()
    print("window moe step", pb, "temp bytes", mem.temp_size_in_bytes,
          "args", mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 300e6


# ---- a serving step of the hybrid state-space model -------------------------

def test_a_gqa_group_of_five_lays_its_blocks_out():
    from paddle_tpu.serving.decode_attention import (heads_per_cell,
                                                     tall_rows_for)

    # all 4 KV heads of 128 in one grid cell; 512 // 5 = 102 score rows a
    # head: the power of two below
    cell = heads_per_cell(4, 128, 128, 4, False)
    assert cell == 4
    assert tall_rows_for(1024, 5, cell, 128) == 64


@pytest.mark.parametrize("pb", [0, 1024])
def test_hybrid_ssm_serving_step_compiles_for_v5e_at_published_widths(
        topo, monkeypatch, pb):
    """``serving.HybridSsmLM`` behind ``ServingEngine`` at the cell's
    widths (hidden 5120, 20 query heads on 4 KV heads of 128, 32
    state-space heads of 128 with state 256 in 2 groups, 4 taps, SwiGLU
    21,504, an eighth of the vocabulary; 4 blocks), 64 slots under the
    cell's 3.5 GiB pool, the decode-only step and the one with the
    1024-row prefill bucket: both lower and compile for a described v5e
    with the ragged kernel at a GQA group of 5; the slots' states enter
    donated and alias back out, no copy of a layer's [64, 32, 128, 256]
    is made, and the step holds under the chip's 16 GB."""
    from paddle_tpu.analysis import retrace
    from paddle_tpu.serving import HybridSsmLM, ServingEngine
    from paddle_tpu.serving import decode_attention as da
    from paddle_tpu.serving import engine as eng_mod
    from paddle_tpu.serving import kv_cache

    monkeypatch.setattr(da, "_interpret_default", lambda: False)
    monkeypatch.setattr(retrace, "_backend_jit_kwargs", lambda kw: kw)
    monkeypatch.setattr(eng_mod, "attention_path", lambda *a, **k: "kernel")
    make_pool, make_states = eng_mod.init_kv_pages, \
        kv_cache.RecurrentState.init
    monkeypatch.setattr(
        eng_mod, "init_kv_pages",
        lambda cfg, **kw: jax.eval_shape(lambda: make_pool(cfg, **kw)))
    monkeypatch.setattr(kv_cache.RecurrentState, "init",
                        lambda self: jax.eval_shape(
                            lambda: make_states(self)))
    model = HybridSsmLM(
        vocab_size=32640, embed_dim=5120, num_layers=4, num_heads=20,
        num_kv_heads=4, head_dim=128, ffn_dim=21504, ssm_heads=32,
        ssm_head_dim=128, ssm_state=256, ssm_groups=2, conv_taps=4,
        chunk=128, rope_theta=1e11, norm_eps=1e-5,
        multipliers={"embedding_multiplier": 5.656854249492381,
                     "key_multiplier": 0.011048543456039804,
                     "ssm_multipliers": (0.3535533905932738, 0.25,
                                         0.1767766952966369, 0.5,
                                         0.3535533905932738)})
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    params = {k: aval(v.shape, v.dtype) for k, v in jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)).items()}
    eng = ServingEngine(model, params, eos_id=model.vocab_size,
                        page_size=128, max_slots=64, pool_bytes=3758096384,
                        max_pages_per_seq=38, buckets=(1024,),
                        prefill_chunk=512)
    assert eng._ragged_kernel and eng._k1 == 1 and eng.cache is None
    assert eng.kv_cfg.num_pages == 1272
    assert eng._recurrent.kv_bytes() == 1_089_470_464
    buf = eng._empty_tick(pb, 1)
    words = np.zeros(2 * (64 + 64) + 4, np.int32)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: aval(a.shape, a.dtype), tree)
    compiled = eng._step_fn(pb, 1).lower(
        params, on_chip(eng._kv), aval(buf.shape, buf.dtype),
        aval(words.shape, words.dtype), on_chip(eng._rec_kv)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4       # a layer: the kernel
    for scope in ("ssm.proj", "ssm.conv", "ssm.scan/ssd_step", "ssm.out",
                  "attn", "ffn", "head"):
        assert scope + "/" in text, scope
    assert ("ssm.scan/ssd_chunks/while" in text) == (pb > 0)
    # a layer's states are updated where they lie
    copies = [line for line in text.splitlines()
              if "f32[64,32,128,256]" in line.split(" = ")[-1].split("(")[0]
              and " copy(" in line]
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    print("hybrid ssm step", pb, "temp bytes", mem.temp_size_in_bytes,
          "args", mem.argument_size_in_bytes, "aliased",
          mem.alias_size_in_bytes)
    # the pool and the 8 state arrays alias their outputs
    assert mem.alias_size_in_bytes > 3.7e9
    assert mem.temp_size_in_bytes < 400e6
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.5e9


@pytest.mark.parametrize("pb", [0, 512])
def test_looped_serving_step_compiles_for_v5e_at_published_widths(
        topo, monkeypatch, pb):
    """``serving.LoopedLM`` behind ``ServingEngine`` at the cell's widths
    (hidden 2048, 16 heads of 128, SwiGLU 5632, the whole vocabulary; 8
    layers run 4 times), 32 slots under the cell's 10 GiB pool of 32
    cache layers, the decode-only step and the one with the 512-row
    prefill bucket: both lower and compile for a described v5e with one
    ragged kernel a CACHE layer; the step holds each weight leaf once
    (the arguments are the 2.45 GB of parameters and the pool, nothing
    stacked over the passes), no slab of the pool is copied, and it holds
    under the chip's 16 GB."""
    from paddle_tpu.analysis import retrace
    from paddle_tpu.serving import LoopedLM, ServingEngine
    from paddle_tpu.serving import decode_attention as da
    from paddle_tpu.serving import engine as eng_mod

    monkeypatch.setattr(da, "_interpret_default", lambda: False)
    monkeypatch.setattr(retrace, "_backend_jit_kwargs", lambda kw: kw)
    monkeypatch.setattr(eng_mod, "attention_path", lambda *a, **k: "kernel")
    make_pool = eng_mod.init_kv_pages
    monkeypatch.setattr(
        eng_mod, "init_kv_pages",
        lambda cfg, **kw: jax.eval_shape(lambda: make_pool(cfg, **kw)))
    model = LoopedLM(vocab_size=49152, embed_dim=2048, num_layers=8,
                     num_heads=16, head_dim=128, ffn_dim=5632, loops=4,
                     rope_theta=1e6, norm_eps=1e-6)
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    params = {k: aval(v.shape, v.dtype) for k, v in jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)).items()}
    weights = sum(int(np.prod(v.shape)) * 4 for v in params.values())
    assert weights == 612_438_017 * 4
    eng = ServingEngine(model, params, eos_id=model.vocab_size,
                        page_size=128, max_slots=32, pool_bytes=10 << 30,
                        max_pages_per_seq=10, buckets=(512,),
                        prefill_chunk=256)
    assert eng._ragged_kernel and eng._k1 == 1 and eng._loops == 4
    assert eng.kv_cfg.num_layers == 32 and eng.kv_cfg.num_pages == 160
    assert eng.kv_cfg.bytes_per_page() == 64 << 20
    buf = eng._empty_tick(pb, 1)
    words = np.zeros(2 * (32 + 32) + 3, np.int32)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: aval(a.shape, a.dtype), tree)
    compiled = eng._step_fn(pb, 1).lower(
        params, on_chip(eng._kv), aval(buf.shape, buf.dtype),
        aval(words.shape, words.dtype)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 32      # a cache layer: the kernel
    for scope in ("pass0/l0/attn/proj", "pass3/l7/ffn", "pass3/close",
                  "head"):
        assert scope + "/" in text, scope
    # a pass's K/V go into the pool where it lies
    copies = [line for line in text.splitlines() if " copy(" in line
              and ("f32[32,160,128,2048]" in line
                   or "f32[160,128,2048]" in line)]
    assert not copies, copies[:2]
    mem = compiled.memory_analysis()
    print("looped step", pb, "temp bytes", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes, "aliased", mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes == 10 << 30       # the pool, donated
    # the parameters once and the pool: nothing tiled over the passes
    assert mem.argument_size_in_bytes < weights + (10 << 30) + (1 << 20)
    assert mem.temp_size_in_bytes < 200e6


# ---- a model of one pass builds the step it built ---------------------------

# sha256 (16 hex digits) of the StableHLO text of the tiny engines' steps
# at the parent of PR 47 (f8f22da), decode-only and with a prefill bucket:
# the passes' loop, the cache-layer rule and the counters of PR 47 change
# nothing for a model that says no ``loops``
PARENT_STEPS = {
    ("dense", 0): "bf364f38ecb6dc86", ("dense", 8): "dc7b0946b8254b69",
    ("falcon", 0): "34b79fc639f3b914", ("falcon", 16): "4da76c14b71cd70b",
    ("laguna", 0): "867bf6d3f7813a27", ("laguna", 16): "a4c51928964e110f",
    # (the block model's are PR 49's: a slot brings the block it commits
    # and the one it opens, ``k1 = 2 B``)
    ("sdar", 0): "f996ce5d52884da0", ("sdar", 16): "b72a8fa27853838a",
    # (the looped model's at the parent of PR 49, 1ed5ac8: the fold is a
    # block model's and changes nothing for a model of several passes)
    ("ouro", 0): "1e311d9f6febf472", ("ouro", 16): "af8d484f7fe64b44"}
TINY_FAMILIES = {"falcon": ("falcon_h1", "tiny-falcon-h1"),
                 "laguna": ("laguna", "tiny-laguna"),
                 "sdar": ("sdar_moe", "tiny-sdar"),
                 "ouro": ("ouro", "tiny-ouro")}


@pytest.fixture(scope="module")
def tiny_engines():
    """The dense model and the three special families at their tiny
    sizes, each behind an engine (built once: two steps a model)."""
    import sys

    from paddle_tpu.serving import DecoderLM, ServingEngine

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import cells, weights

    model = DecoderLM(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                      num_kv_heads=2)
    engines = {"dense": ServingEngine(
        model, model.init_params(jax.random.PRNGKey(0)), eos_id=1,
        page_size=8, pool_bytes=100_000, max_pages_per_seq=8, max_slots=4,
        buckets=(8,))}
    for name, (family, tiny) in TINY_FAMILIES.items():
        fam = cells.load_module(os.path.join(bench, "families",
                                             family + ".py"))
        cfg = cells.load_json(os.path.join(bench, "tests", "configs",
                                           tiny + ".json"))
        prog = fam.serve_program(cfg, [None])
        made = weights.make(fam.leaves(cfg, "serve"), 7)
        kw = dict(page_size=16, num_pages=40, max_pages_per_seq=4,
                  max_slots=4, buckets=(16,), prefill_chunk=8) \
            if name == "sdar" else dict(
                page_size=4, num_pages=80, max_pages_per_seq=24,
                max_slots=4, buckets=(8, 16), prefill_chunk=8)
        engines[name] = ServingEngine(
            prog["model"], {n: made[r] for n, r in prog["names"].items()},
            eos_id=cfg["vocab_size"], **kw)
    return engines


@pytest.mark.parametrize("name,pb", sorted(PARENT_STEPS))
def test_a_model_of_one_pass_lowers_to_the_parents_step(tiny_engines, name,
                                                        pb):
    import hashlib

    eng = tiny_engines[name]
    assert (eng._loops == 1 and eng._loop_counted == ()) == (name != "ouro")
    text = eng._step_fn(pb, eng._k1).lower(
        eng.params, eng._kv, eng._empty_tick(pb, eng._k1),
        eng._last_words(), *eng._kind_kv()).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_STEPS[name, pb]


# ---- names in the device trace ---------------------------------------------

def _kernel_names(fn, *avals):
    """The HLO instruction names of the Mosaic kernels of the optimized
    program: what a profiler's device trace lists them under."""
    import re

    text = jax.jit(fn).lower(*avals).compile().as_text()
    return sorted(re.match(r"\s*(?:ROOT )?%([A-Za-z_\-]+)", line).group(1)
                  for line in text.splitlines() if "tpu_custom_call" in line)


@pytest.mark.parametrize("which", ["flash", "ragged", "ragged_tp4", "gdn"])
def test_kernels_carry_stable_names(topo, which, monkeypatch):
    """``name=`` on the ``pallas_call`` sites the benchmark's cells run
    reaches the compiled program, alone and under ``shard_map``."""
    one = SingleDeviceSharding(topo.devices[0])
    if which == "gdn":
        from paddle_tpu.ops import gated_delta, gdn_kernels

        monkeypatch.setattr(gdn_kernels, "interpret_default", lambda: False)
        f32 = lambda *shape: _on(one)(shape, jnp.float32)  # noqa: E731

        def loss(q, k, v, g, beta, seg):
            o = gated_delta.gated_delta_rule(q, k, v, g, beta, seg)
            return jnp.sum(o * o)

        # the trace's readers select them by ``%gdn_``: no wrapped names
        assert _kernel_names(
            jax.grad(loss, argnums=(0, 1, 2, 3, 4)), f32(256, 2, 128),
            f32(256, 2, 128), f32(256, 4, 128), f32(256, 4), f32(256, 4),
            _on(one)((256,), jnp.int32)) == \
            ["gdn_chunk_bwd", "gdn_chunk_fwd"]
        return
    if which == "flash":
        q = _on(one)((4, 1024, 16, 128), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           interpret=False)
                           .astype(jnp.float32))

        # under autodiff the names come wrapped (``jvp_flash_fwd_``)
        got = _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
        assert len(got) == 3 and all(
            sum(want in name for name in got) == 1
            for want in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")), got
        return
    mesh = Mesh(np.asarray(topo.devices), ("model",))

    def fn(*a):
        if which == "ragged":
            return ragged_paged_attention(*a, layer=0, use_kernel=True,
                                          interpret=False)
        return ragged_paged_attention_tp(mesh, "model", *a, layer=0,
                                         use_kernel=True, interpret=False)

    place = (lambda spec: _on(one)) if which == "ragged" else \
        (lambda spec: _on(NamedSharding(mesh, spec)))
    assert _kernel_names(fn, *_ragged_avals(place, 64, 16, 16,
                                            jnp.float32)) == \
        ["ragged_paged_attention"]


# ---- fused recurrent cells: the LSTM guard cell's shape -------------------

@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_rnn_cell_compiles_for_v5e(topo, cell):
    aval = _on(SingleDeviceSharding(topo.devices[0]))
    b, t, hid = 64, 4, 512
    gates = 4 if cell == "lstm" else 3
    scan = rnn.lstm_scan if cell == "lstm" else rnn.gru_scan

    def loss(x, mask, w_h, bias):
        hs, _ = scan(x, mask, None, w_h, bias, interpret=False)
        return jnp.sum(hs)

    n = _compile(jax.grad(loss, argnums=(0, 2)),
                 aval((b, t, gates * hid), jnp.float32),
                 aval((b, t), jnp.float32),
                 aval((hid, gates * hid), jnp.float32),
                 aval((gates * hid,), jnp.float32))
    assert n >= 1
