#!/usr/bin/env python
"""Benchmark suite. Prints exactly ONE JSON line.

Primary metric: ResNet-50 224x224 training throughput, images/sec/chip,
with achieved FLOP/s and MFU (BASELINE.json's north-star metric). The
reference publishes no ResNet-50 number, so ``vs_baseline`` is computed
from the one apples-to-apples headline it does publish: AlexNet bs=128
train ms/batch (PaddlePaddle on K40m: 334 ms — reference
benchmark/README.md:33-38). vs_baseline > 1 means faster by that factor.

Also measured (reported as extra fields on the same line):
  - alexnet_ms_per_batch       (vs 334 ms, K40m)
  - lstm_ms_per_batch          IMDB 2xLSTM h=512 bs=64 seq=100
                               (vs 184 ms, K40m — benchmark/README.md:114-119)
  - scaling_virtual8           1-vs-8-device step-time ratio at FIXED global
                               batch on a serialized virtual CPU mesh: pure
                               collective/partition overhead (compute is
                               identical), the tracked scaling-efficiency
                               number until multi-chip hardware exists.

Process model: the parent never touches jax (a process that has
touched jax holds the chip); every measurement runs in a subprocess with
its own timeout, one chip-holding worker at a time.  The chip is probed
FIRST: with no TPU, or with any chip worker failing, the error record is
printed and the exit code is non-zero — no stale numbers are attached.
"""

import json
import os
import subprocess
import sys
import time

GLOBAL_DEADLINE_S = 900.0


def _full_sweep() -> bool:
    """Deep-measurement mode, on only when BENCH_FULL_SWEEP=1: the extra
    reference-table rows (AlexNet bs sweep, SmallNet/GoogLeNet extra
    batches, LSTM bs128 column) AND the transformer diagnostics beyond
    the headline + bf16-resid variant (fused head, seq2048/seq8192
    long-context tiers, best-combo, L4 ablation). The driver's plain
    `python bench.py` keeps its original duration so the 900s global
    deadline still reaches every worker."""
    return os.environ.get("BENCH_FULL_SWEEP", "") == "1"


ALEXNET_BASELINE_MS = 334.0   # reference Paddle, AlexNet bs=128, K40m
LSTM_BASELINE_MS = 184.0      # reference Paddle, IMDB LSTM h=512 bs=64, K40m

# bf16 peak FLOP/s per chip (compute path runs bf16 matmuls, fp32 accum)
PEAK_FLOPS = {
    "TPU v2": 45e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5": 197e12,
    "TPU v5p": 459e12, "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def _peak_for(kind: str) -> float:
    # longest key first: "TPU v5 lite" must not resolve through "TPU v5"
    for k in sorted(PEAK_FLOPS, key=len, reverse=True):
        if kind.lower().startswith(k.lower()):
            return PEAK_FLOPS[k]
    raise KeyError(f"no peak FLOP/s known for device kind {kind!r}; add it "
                   "to PEAK_FLOPS with its source")


def _time_steps(step, args, iters):
    """Time ``iters`` chained train steps; a concrete value fetch is the
    completion barrier."""
    p, opt_state, mstate, key, feeds = args
    loss, p, opt_state, mstate, _ = step(p, opt_state, mstate, key, feeds)
    float(loss)  # compile + warmup
    loss, p, opt_state, mstate, _ = step(p, opt_state, mstate, key, feeds)
    float(loss)
    start = time.perf_counter()
    for _ in range(iters):
        loss, p, opt_state, mstate, _ = step(p, opt_state, mstate, key, feeds)
    float(loss)
    return (time.perf_counter() - start) / iters


def _init_paddle():
    import paddle_tpu as paddle

    paddle.init()
    return paddle


def _make_sgd(cost, params, opt=None):
    from paddle_tpu import optimizer, trainer

    return trainer.SGD(cost=cost, parameters=params,
                       update_equation=opt or optimizer.Momentum(
                           momentum=0.9, learning_rate=0.01))


def _dense_feeds(sgd, batch, dim, n_classes, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    samples = [(rng.randn(dim).astype(np.float32), int(rng.randint(n_classes)))
               for _ in range(batch)]
    return sgd._make_feeder(None).feed(samples)


def _step_args(sgd, feeds):
    import jax

    return (sgd.parameters.as_dict(), sgd.opt_state, sgd.model_state,
            jax.random.PRNGKey(0), feeds)


def _aot_compile(step, args):
    """Compile ONCE via AOT lowering; returns (callable, flops-or-None).

    The compiled object is used directly for timing so the program isn't
    compiled a second time by the first traced call — for the big workers
    (resnet sweep, transformer) that halves the compile budget."""
    compiled = step.lower(*args).compile()
    try:  # a cost-analysis failure must not discard the compile
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = float(cost.get("flops", 0.0))
        return compiled, (f if f > 0 else None)
    except Exception:
        return compiled, None


# ---------------------------------------------------------------------------
# workers — each prints one JSON line on success
# ---------------------------------------------------------------------------


def _measure_image_model(build_fn, img, batch, iters=20, with_flops=False,
                         **build_kw):
    """Shared image-model measurement harness: build -> SGD -> device-resident
    NHWC feeds (layer._to_nhwc passes 4-D through, so no per-step layout
    change) -> timed chained steps. Returns sec or (sec, flops)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    paddle.topology.reset_name_scope()
    images, label, logits, cost = build_fn(img_size=img, **build_kw)
    topo = paddle.topology.Topology([cost])
    params = paddle.Parameters.from_topology(topo, seed=0)
    sgd = _make_sgd(cost, params)
    feeds = {
        "image": jax.device_put(
            rng.randn(batch, img, img, 3).astype(np.float32)),
        "label": jax.device_put(
            rng.randint(0, logits.size, size=batch).astype(np.int32)),
    }
    step = sgd._build_step()
    args = _step_args(sgd, feeds)
    if with_flops:
        step, flops = _aot_compile(step, args)
        return _time_steps(step, args, iters=iters), flops
    return _time_steps(step, args, iters=iters)


def worker_resnet50():
    """ResNet-50 train step, images/sec/chip + MFU. Batch sweep picks the
    best throughput; activations ride bf16 (FLAGS.bf16_activations)."""
    import jax

    paddle = _init_paddle()
    from paddle_tpu.models import resnet

    img = 224

    def measure(batch, iters=20):
        return _measure_image_model(resnet.build, img, batch, iters=iters,
                                    with_flops=True, depth=50,
                                    num_classes=1000)

    kind = jax.devices()[0].device_kind
    peak = _peak_for(kind)

    def emit(results, first_err):
        batch, (sec, flops) = max(
            results.items(), key=lambda kv: kv[0] / kv[1][0])
        flops_source = "xla_cost_analysis"
        if flops is None:
            # analytic: ResNet-50 fwd ~4.09 GFLOP/img (2*MACs); ~3x train
            flops = 3 * 4.089e9 * batch
            flops_source = "analytic"
        achieved = flops / sec
        extra = ({"batch_sweep_error": repr(first_err)} if first_err else {})
        print(json.dumps({
            **extra,
            "resnet50_images_per_sec_per_chip": round(batch / sec, 1),
            "resnet50_ms_per_batch": round(sec * 1000, 2),
            "resnet50_achieved_tflops": round(achieved / 1e12, 2),
            "resnet50_mfu": round(achieved / peak, 4),
            "resnet50_flops_per_step": flops,
            "flops_source": flops_source,
            "device_kind": kind,
            "peak_tflops_assumed": peak / 1e12,
            "batch": batch,
            "batch_sweep": {str(b): round(b / s, 1)
                            for b, (s, _) in results.items()},
            "feed_layout": "NHWC device-resident",
        }), flush=True)

    results = {}
    first_err = None
    for batch in (128, 256):
        try:
            results[batch] = measure(batch)
        except Exception as e:  # keep the smaller-batch result if any
            first_err = e
            break
        # print after EVERY successful size: a hang in the next sweep
        # step can only lose the sweep, never the measured headline
        emit(results, first_err)
    if not results:
        raise first_err  # surface the root cause, not an empty-max error
    if first_err is not None:
        emit(results, first_err)


def worker_alexnet():
    """AlexNet train ms/batch across the reference's full batch sweep
    (BASELINE.md:15-18 — 195/334/602/1629 ms on K40m). bs=128 first: it
    is the vs_baseline headline basis."""
    paddle = _init_paddle()
    from paddle_tpu.models import alexnet

    img = 227

    def measure(batch, iters=30):
        paddle.topology.reset_name_scope()
        images, label, logits, cost = alexnet.build(img_size=img)
        topo = paddle.topology.Topology([cost])
        params = paddle.Parameters.from_topology(topo, seed=0)
        sgd = _make_sgd(cost, params)
        feeds = _dense_feeds(sgd, batch, 3 * img * img, 1000)
        return _time_steps(sgd._build_step(), _step_args(sgd, feeds),
                           iters=iters)

    out = {"alexnet_ms_per_batch": round(measure(128) * 1000, 3)}
    out["alexnet_bs128_vs_baseline"] = round(
        ALEXNET_BASELINE_MS / out["alexnet_ms_per_batch"], 1)
    print(json.dumps(out), flush=True)  # headline before the sweep
    sweep = ((64, 195.0), (256, 602.0), (512, 1629.0)) if _full_sweep() \
        else ()
    for batch, base in sweep:
        try:
            ms = round(measure(batch, iters=20) * 1000, 3)
        except Exception as e:
            out[f"alexnet_bs{batch}_error"] = repr(e)
            print(json.dumps(out), flush=True)  # error rows print too
            continue
        out[f"alexnet_bs{batch}_ms"] = ms
        out[f"alexnet_bs{batch}_vs_baseline"] = round(base / ms, 1)
        print(json.dumps(out), flush=True)
    print(json.dumps(out), flush=True)


def worker_lstm():
    """IMDB benchmark config: 2xLSTM h=512 + fc, bs=64, seq len 100,
    dict 30k (reference benchmark/paddle/rnn/rnn.py)."""
    import numpy as np

    paddle = _init_paddle()
    from paddle_tpu.models import text_lstm

    from paddle_tpu.platform.flags import FLAGS

    batch, seq_len, hidden = 64, 100, 512
    rng = np.random.RandomState(0)

    def measure(use_pallas, iters=20, hidden=hidden, batch=batch):
        FLAGS.use_pallas = use_pallas
        paddle.topology.reset_name_scope()
        words, label, logits, cost = text_lstm.build(hidden=hidden)
        topo = paddle.topology.Topology([cost])
        params = paddle.Parameters.from_topology(topo, seed=0)
        sgd = _make_sgd(cost, params)
        samples = [(rng.randint(0, 30000, size=seq_len).tolist(),
                    int(rng.randint(2))) for _ in range(batch)]
        feeds = sgd._make_feeder(None).feed(samples)
        return _time_steps(sgd._build_step(), _step_args(sgd, feeds),
                           iters=iters)

    # headline (shipping default, use_pallas on) FIRST, and PRINT it
    # before the diagnostic runs: the orchestrator keeps the last JSON
    # line — so a timeout in the plain-XLA comparison can only lose the
    # comparison, never the already-emitted headline
    sec_fused = measure(True)
    out = {
        "lstm_ms_per_batch": round(sec_fused * 1000, 3),
        "lstm_fused_pallas_ms": round(sec_fused * 1000, 3),
        "lstm_config": f"h={hidden} bs={batch} seq={seq_len}",
    }
    print(json.dumps(out), flush=True)
    try:
        out["lstm_plain_xla_ms"] = round(measure(False, iters=8) * 1000, 3)
    except Exception as e:
        out["lstm_plain_xla_error"] = repr(e)
    print(json.dumps(out), flush=True)
    # more rows of the reference RNN table (BASELINE.md: h=1280 bs=64 ->
    # 641 ms, h=512 bs=256 -> 414 ms on K40m), printed incrementally so a
    # timeout loses at most the not-yet-measured rows
    lstm_rows = [("lstm_h1280_bs64_ms", 1280, 64, 641.0),
                 ("lstm_h256_bs64_ms", 256, 64, 83.0),
                 ("lstm_h512_bs256_ms", 512, 256, 414.0)]
    if _full_sweep():
        # bs=128 column + the largest cell (BASELINE.md:40-42)
        lstm_rows += [("lstm_h256_bs128_ms", 256, 128, 110.0),
                      ("lstm_h512_bs128_ms", 512, 128, 261.0),
                      ("lstm_h1280_bs128_ms", 1280, 128, 1007.0),
                      ("lstm_h1280_bs256_ms", 1280, 256, 1655.0)]
    for key, h, b, base in lstm_rows:
        try:
            out[key] = round(measure(True, iters=10, hidden=h, batch=b)
                             * 1000, 3)
            out[key.replace("_ms", "_vs_baseline")] = round(base / out[key], 1)
        except Exception as e:
            # rows are independent configs (a h=1280 OOM must not skip
            # the h=512 bs=256 row)
            out[key.replace("_ms", "_error")] = repr(e)
            print(json.dumps(out), flush=True)  # error rows print too
            continue
        print(json.dumps(out), flush=True)
    print(json.dumps(out), flush=True)


def worker_convnets():
    """GoogleNet + SmallNet train ms/batch at the reference's benchmark
    batch sizes (BASELINE.md: GoogleNet 613 ms bs=64 / 1149 ms bs=128,
    SmallNet 10.46 ms bs=64 — all K40m)."""
    _init_paddle()
    from paddle_tpu.models import googlenet, smallnet

    rows = [("googlenet_bs64", googlenet.build, 224, 64, 15, 613.0),
            ("smallnet_bs64", smallnet.build, 32, 64, 30, 10.463),
            ("googlenet_bs128", googlenet.build, 224, 128, 15, 1149.0)]
    if _full_sweep():
        # remaining cells of the reference table (BASELINE.md:19-25)
        rows += [("googlenet_bs256", googlenet.build, 224, 256, 10, 2348.0),
                 ("smallnet_bs128", smallnet.build, 32, 128, 30, 18.184),
                 ("smallnet_bs256", smallnet.build, 32, 256, 30, 33.113),
                 ("smallnet_bs512", smallnet.build, 32, 512, 30, 63.039)]
    out = {}
    for key, build_fn, img, batch, iters, base in rows:
        try:  # rows are independent; isolate errors per measurement
            ms = round(_measure_image_model(build_fn, img, batch,
                                            iters=iters) * 1000, 3)
        except Exception as e:
            out[f"{key}_error"] = repr(e)
            print(json.dumps(out), flush=True)  # error rows print too
            continue
        out[f"{key}_ms"] = ms
        out[f"{key}_vs_baseline"] = round(base / ms, 1)
        print(json.dumps(out), flush=True)  # incremental (timeout rule)
    print(json.dumps(out), flush=True)


def worker_transformer():
    """Decoder-only transformer LM (models/transformer.py): tokens/sec and
    MFU. The high-MFU headline: all FLOPs are large bf16 MXU matmuls, so
    this is where the framework's compute efficiency shows without the
    HBM-roofline ceiling that bounds ResNet-50's BN traffic."""
    import jax
    import numpy as np

    paddle = _init_paddle()
    from paddle_tpu.models import transformer

    rng = np.random.RandomState(0)
    kind = jax.devices()[0].device_kind
    peak = _peak_for(kind)

    def measure(d, layers, heads, seq, bs, vocab=32768, iters=6,
                fused_head=False, remat=False):
        paddle.topology.reset_name_scope()
        tokens, pos, target, logits, cost = transformer.build(
            vocab_size=vocab, d_model=d, n_layers=layers, n_heads=heads,
            max_len=seq, fused_head=fused_head, remat=remat)
        topo = paddle.topology.Topology([cost])
        params = paddle.Parameters.from_topology(topo, seed=0)
        sgd = _make_sgd(cost, params)
        samples = []
        for _ in range(bs):
            t = rng.randint(0, vocab, size=seq)
            samples.append((t.tolist(), list(range(seq)),
                            np.roll(t, -1).tolist()))
        feeds = sgd._make_feeder(
            {"tokens": 0, "pos": 1, "target": 2}).feed(samples)
        step = sgd._build_step()
        args = _step_args(sgd, feeds)
        step, flops = _aot_compile(step, args)
        sec = _time_steps(step, args, iters=iters)
        out = {
            "transformer_tokens_per_sec": round(bs * seq / sec, 1),
            "transformer_ms_per_batch": round(sec * 1000, 2),
            "transformer_config": f"d{d} L{layers} h{heads} seq{seq} "
                                  f"bs{bs} vocab{vocab}"
                                  + (" remat" if remat else ""),
        }
        if flops:
            out["transformer_mfu"] = round(flops / sec / peak, 4)
            out["transformer_achieved_tflops"] = round(flops / sec / 1e12, 2)
        return out

    # ~400M-param config sized for one v5e chip (params+momentum+grads
    # ~6.5GB f32, saved activations ~4GB at 4096 tokens): ONE fixed
    # configuration, so the reported row always means the same thing — a
    # config that does not fit fails the worker instead of quietly
    # reporting a smaller one
    d_used, bs_used, remat_used = 2048, 4, False
    out = measure(d=d_used, layers=8, heads=16, seq=1024, bs=bs_used,
                  remat=remat_used)
    print(json.dumps(out), flush=True)  # headline before the variants
    # The tier ladder + bf16-resid variant run in EVERY path; the other
    # variants (fused head, long-context tiers, best-combo, ablation —
    # ~6 more compiles) only under BENCH_FULL_SWEEP: in the driver's
    # plain bench.py the worker has a 420s attempt budget and burning it
    # on variants would starve the resnet50 headline behind it.
    if _full_sweep():
        try:  # fused blockwise LM-head xent (layer.lm_head_cost): logits
            # never reach HBM; candidate replacement headline if faster
            fh = measure(d=d_used, layers=8, heads=16, seq=1024, bs=bs_used,
                         fused_head=True, remat=remat_used)
            out["transformer_fused_head_tokens_per_sec"] = \
                fh["transformer_tokens_per_sec"]
            if "transformer_mfu" in fh:
                out["transformer_fused_head_mfu"] = fh["transformer_mfu"]
        except Exception as e:
            out["transformer_fused_head_error"] = repr(e)
        print(json.dumps(out), flush=True)
    try:  # bf16 residual-stream variant (FLAGS.bf16_dense_activations)
        from paddle_tpu.platform.flags import FLAGS

        FLAGS.bf16_dense_activations = True
        try:
            bf = measure(d=d_used, layers=8, heads=16, seq=1024,
                         bs=bs_used, remat=remat_used)
        finally:
            FLAGS.bf16_dense_activations = False
        out["transformer_bf16_resid_tokens_per_sec"] = \
            bf["transformer_tokens_per_sec"]
        if "transformer_mfu" in bf:
            out["transformer_bf16_resid_mfu"] = bf["transformer_mfu"]
    except Exception as e:
        out["transformer_bf16_resid_error"] = repr(e)
    print(json.dumps(out), flush=True)
    if _full_sweep():
        try:  # long-context tier: seq=2048 only fits with per-block remat
            # (saved activations scale with tokens; checkpoint caps them at
            # one block's boundary per layer)
            lc = measure(d=d_used, layers=8, heads=16, seq=2048,
                         bs=max(bs_used // 2, 2), remat=True, iters=4)
            out["transformer_seq2048_remat_tokens_per_sec"] = \
                lc["transformer_tokens_per_sec"]
            if "transformer_mfu" in lc:
                out["transformer_seq2048_remat_mfu"] = lc["transformer_mfu"]
        except Exception as e:
            out["transformer_seq2048_remat_error"] = repr(e)
        print(json.dumps(out), flush=True)
        try:  # single-sequence long-context tier: 8192 tokens in ONE segment
            # (not 8 packed ones), the shape the streamed flash kernels
            # unlocked — the round-4 kernels hit the 16MB scoped-vmem wall
            # here; remat caps saved activations per block
            lc8 = measure(d=d_used, layers=8, heads=16, seq=8192, bs=1,
                          remat=True, iters=4)
            out["transformer_seq8192_remat_tokens_per_sec"] = \
                lc8["transformer_tokens_per_sec"]
            if "transformer_mfu" in lc8:
                out["transformer_seq8192_remat_mfu"] = lc8["transformer_mfu"]
        except Exception as e:
            out["transformer_seq8192_remat_error"] = repr(e)
        print(json.dumps(out), flush=True)
        try:  # best-known combo for the MFU headline: the largest batch with
            # the bf16 residual stream (halves saved activations, so plain
            # bs8 may fit where f32 OOM'd; measured faster at bs4 both
            # windows), falling back to +remat. Reported as transformer_best_*
            # with its exact config — the number to quote for the >=0.40 gate.
            from paddle_tpu.platform.flags import FLAGS

            # candidate pool: the bf16-resid variant already measured at the
            # headline config, plus the d2048 bs8 attempts (skipping any combo
            # the variant already covers so 'best' can never silently be a
            # strictly worse config)
            cands = []
            if "transformer_bf16_resid_tokens_per_sec" in out:
                cands.append((out.get("transformer_bf16_resid_mfu"),
                              out["transformer_bf16_resid_tokens_per_sec"],
                              f"d{d_used} bs{bs_used} bf16resid"
                              + (" remat" if remat_used else "")))
            FLAGS.bf16_dense_activations = True
            try:
                for bs_b, remat_b in ((8, False), (8, True)):
                    if d_used == 2048 and bs_b == bs_used \
                            and remat_b == remat_used and cands:
                        # the bf16-resid variant above IS this combo — but
                        # only skip when it actually measured (cands
                        # non-empty); if it failed, measure it here
                        continue
                    try:
                        r = measure(d=2048, layers=8, heads=16, seq=1024,
                                    bs=bs_b, remat=remat_b, iters=6)
                        cands.append((r.get("transformer_mfu"),
                                      r["transformer_tokens_per_sec"],
                                      f"d2048 bs{bs_b} bf16resid"
                                      + (" remat" if remat_b else "")))
                        break
                    except Exception as e:
                        out["transformer_best_attempt_error"] = repr(e)
            finally:
                FLAGS.bf16_dense_activations = False
            if cands:
                # the gate metric is MFU; tokens/sec breaks ties (and orders
                # candidates whose cost analysis failed)
                mfu_b, tps_b, cfg_b = max(
                    cands, key=lambda c: (c[0] if c[0] is not None else -1.0,
                                          c[1]))
                out["transformer_best_tokens_per_sec"] = tps_b
                out["transformer_best_config"] = cfg_b
                if mfu_b is not None:
                    out["transformer_best_mfu"] = mfu_b
        except Exception as e:
            out["transformer_best_error"] = repr(e)
        print(json.dumps(out), flush=True)
        try:  # layer ablation: (t8 - t4)/4 = marginal ms per block, and
            # t8 - 8*marginal = fixed cost (embedding + LM head + optimizer +
            # dispatch). The profiler-free split of where the step time
            # goes. L=4 rather than L=16 so the ablation never OOMs a config
            # the headline fit.
            l4 = measure(d=d_used, layers=4, heads=16, seq=1024, bs=bs_used,
                         remat=remat_used, iters=4)
            t8 = out["transformer_ms_per_batch"]
            t4 = l4["transformer_ms_per_batch"]
            per_block = (t8 - t4) / 4.0
            out["transformer_ablation_ms_per_block"] = round(per_block, 2)
            out["transformer_ablation_fixed_ms"] = round(t8 - 8 * per_block, 2)
        except Exception as e:
            out["transformer_ablation_error"] = repr(e)
        print(json.dumps(out), flush=True)



def worker_attention():
    """Flash-attention BACKWARD: pallas dQ/dKV kernels vs the plain-JAX
    blockwise fallback (FLAGS.use_pallas toggle), long-context shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _init_paddle()
    from paddle_tpu.ops import attention
    from paddle_tpu.platform.flags import FLAGS

    B, S, H, D = 4, 4096, 8, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32),
                    dtype=jnp.bfloat16)

    def fetch(out):
        # concrete value fetch as the completion barrier (see _time_steps)
        leaf = jax.tree.leaves(out)[0]
        return float(jnp.asarray(leaf).ravel()[0])

    def timeit(fn, iters=10):
        fetch(fn(q, k, v))
        start = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v)
        fetch(out)
        return (time.perf_counter() - start) / iters

    @jax.jit
    def fwd_fn(q, k, v):
        return attention.flash_attention(q, k, v, causal=True)

    t_fwd = timeit(fwd_fn)

    def time_grad(use_pallas):
        FLAGS.use_pallas = use_pallas

        @jax.jit
        def grad_fn(q, k, v):
            def loss(q, k, v):
                o = attention.flash_attention(q, k, v, causal=True)
                return jnp.sum(o.astype(jnp.float32))

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        return timeit(grad_fn)

    t_plain = time_grad(False)
    t_pallas = time_grad(True)
    # the forward (same pallas kernel both ways) is subtracted so the
    # ratio compares the BACKWARD implementations, not fwd+bwd totals
    bwd_pallas = max(t_pallas - t_fwd, 1e-9)
    bwd_plain = max(t_plain - t_fwd, 1e-9)
    print(json.dumps({
        "attention_bwd": {
            "shape": f"B{B}xS{S}xH{H}xD{D} bf16 causal",
            "fwd_ms": round(t_fwd * 1000, 3),
            "pallas_fwdbwd_ms": round(t_pallas * 1000, 3),
            "plain_jax_fwdbwd_ms": round(t_plain * 1000, 3),
            "bwd_pallas_ms": round(bwd_pallas * 1000, 3),
            "bwd_plain_jax_ms": round(bwd_plain * 1000, 3),
            "bwd_speedup": round(bwd_plain / bwd_pallas, 2),
        }}), flush=True)


def worker_scaling():
    """Fixed-GLOBAL-batch 1-vs-8-device DP step time for a ResNet train
    step on the serialized virtual CPU mesh (the headline model family,
    not a toy MLP).

    Method note: the virtual mesh shares ONE host core, so the 8-device
    run executes the 8 partitions serially — total compute is identical
    to the 1-device run and t1/t8 isolates partition + collective
    overhead, a LOWER bound on real-chip scaling efficiency (real ICI
    runs partitions concurrently and overlaps the psum). Measured
    breakdown (resnet18@48px bs=64, this host): 8x the bs/8 single-dev
    step = 18.2s of pure per-shard compute vs t8 = 22.1s, i.e. ~22%
    partition+collective overhead; with a toy 3-layer MLP the same
    harness reports 0.29-0.43 "efficiency" because per-partition
    dispatch overhead dominates its tiny matmuls — that artifact, not
    collectives, produced round 2's 0.43."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import make_mesh

    batch, img, depth = 64, 48, 18

    def build_and_time(mesh, iters=2):
        import numpy as np

        paddle.topology.reset_name_scope()
        images, label, logits, cost = resnet.build(depth=depth, img_size=img,
                                                   num_classes=100)
        params = paddle.Parameters.from_topology(
            paddle.topology.Topology([cost]), seed=0)
        from paddle_tpu import optimizer, trainer

        sgd = trainer.SGD(cost=cost, parameters=params,
                          update_equation=optimizer.Momentum(
                              momentum=0.9, learning_rate=0.01),
                          mesh=mesh)
        rng = np.random.RandomState(0)
        feeds = sgd._shard_feeds({
            "image": jax.device_put(
                rng.randn(batch, img, img, 3).astype(np.float32)),
            "label": jax.device_put(
                rng.randint(0, 100, size=batch).astype(np.int32)),
        })
        step = sgd._build_step()
        p, o, m, key, f = _step_args(sgd, feeds)
        loss, p, o, m, _ = step(p, o, m, key, f)  # compile + warmup
        float(loss)
        # min over iters: the single shared core is contended, and min is
        # the standard de-noised estimator for that regime
        best = float("inf")
        for _ in range(iters):
            start = time.perf_counter()
            loss, p, o, m, _ = step(p, o, m, key, f)
            float(loss)
            best = min(best, time.perf_counter() - start)
        return best

    devs = jax.devices()
    assert len(devs) >= 8, f"need 8 virtual devices, have {len(devs)}"
    N_MIN = 3
    t1 = build_and_time(None, iters=N_MIN)
    t8 = build_and_time(make_mesh((8,), ("data",), devs[:8]), iters=N_MIN)
    print(json.dumps({
        "scaling_virtual8": {
            "model": f"resnet{depth}_img{img}_bs{batch}",
            "t_step_1dev_ms": round(t1 * 1000, 3),
            "t_step_8dev_ms": round(t8 * 1000, 3),
            "efficiency_fixed_global_batch": round(t1 / t8, 3),
            "min_of": N_MIN,
            "method": "serialized 1-core virtual mesh, min-of-"
                      f"{N_MIN} steps: t1/t8 isolates partition+collective "
                      "overhead. PROXY ONLY — a contended single host core, "
                      "not chip timing; a lower bound on real-chip DP "
                      "efficiency. This JSON field is the one canonical "
                      "number for this metric.",
        }}), flush=True)


def worker_zero1():
    """ZeRO-1 sharded weight update (arXiv 2004.13336) vs the replicated
    optimizer path on the serialized virtual-8 CPU mesh: same ResNet DP
    train step, zero_stage 0 vs 1. Reports per-chip optimizer-state bytes
    (exact, from the slot arrays' shard shapes — the N x HBM headroom
    claim) and the step-time delta (PROXY ONLY on the contended single
    host core: the 8 partitions run serially, so the reduce-scatter/
    all-gather pair shows up as overhead here while on real ICI it
    REPLACES the grad all-reduce)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer, trainer
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import make_mesh, opt_state_bytes_per_device

    batch, img, depth = 32, 48, 18
    devs = jax.devices()
    assert len(devs) >= 8, f"need 8 virtual devices, have {len(devs)}"

    def build(zero, opt_factory):
        paddle.topology.reset_name_scope()
        images, label, logits, cost = resnet.build(depth=depth, img_size=img,
                                                   num_classes=100)
        params = paddle.Parameters.from_topology(
            paddle.topology.Topology([cost]), seed=0)
        return trainer.SGD(cost=cost, parameters=params,
                           update_equation=opt_factory(),
                           mesh=make_mesh((8,), ("data",), devs[:8]),
                           zero=zero)

    def time_step(sgd, iters=3):
        rng = np.random.RandomState(0)
        feeds = sgd._shard_feeds({
            "image": rng.randn(batch, img, img, 3).astype(np.float32),
            "label": rng.randint(0, 100, size=batch).astype(np.int32),
        })
        args = _step_args(sgd, feeds)
        step, _ = _aot_compile(sgd._build_step(), args)
        return _time_steps(step, args, iters=iters)

    momentum = lambda: optimizer.Momentum(momentum=0.9, learning_rate=0.01)
    out = {"zero1_model": f"resnet{depth}_img{img}_bs{batch}_mesh8"}
    s0 = build(0, momentum)
    out["zero0_opt_state_bytes_per_chip"] = opt_state_bytes_per_device(
        s0.opt_state["slots"])
    out["zero0_step_ms"] = round(time_step(s0) * 1000, 3)
    print(json.dumps(out), flush=True)  # headline before the zero1 twin
    del s0
    s1 = build(1, momentum)
    out["zero1_opt_state_bytes_per_chip"] = opt_state_bytes_per_device(
        s1.opt_state["slots"])
    out["zero1_step_ms"] = round(time_step(s1) * 1000, 3)
    out["zero1_opt_state_reduction"] = round(
        out["zero0_opt_state_bytes_per_chip"]
        / max(1, out["zero1_opt_state_bytes_per_chip"]), 2)
    print(json.dumps(out), flush=True)
    del s1
    # Adam doubles the slot set — the config where the N x matters most
    adam = lambda: optimizer.Adam(learning_rate=1e-3)
    out["zero0_adam_opt_state_bytes_per_chip"] = opt_state_bytes_per_device(
        build(0, adam).opt_state["slots"])
    out["zero1_adam_opt_state_bytes_per_chip"] = opt_state_bytes_per_device(
        build(1, adam).opt_state["slots"])
    print(json.dumps(out), flush=True)


def worker_serving():
    """Paged-KV continuous-batching serving engine under a Poisson
    arrival trace on the virtual-8 host: 24 ragged-length requests
    (prompts 4..48 tokens, 16 generated each) stream into a
    DecoderLM-backed ServingEngine with a page pool sized to force real
    multiplexing.  Reports end-to-end tokens/s (prefill + decode
    emissions over the first-submit..last-token window), time-to-first-
    token, and page-pool occupancy — the serving analog of the training
    workers' step-time numbers.  CPU timings are PROXY ONLY (interpret-
    mode host math); the structure (fused decode batch, admission,
    growth, preemption) is what's being exercised."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import DecoderLM, ServingEngine

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=256)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=eos, page_size=16,
                        num_pages=64, max_pages_per_seq=8, max_slots=8,
                        buckets=(16, 32, 48))
    n_req, rate = 24, 50.0          # Poisson arrivals, ~50 req/s offered
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = [rng.randint(2, vocab, size=rng.randint(4, 49)).tolist()
               for _ in range(n_req)]

    # warm every prefill bucket + the fused decode step outside the
    # measured window (compile time would otherwise swamp TTFT on the
    # CPU proxy), then reset counters — the pages all come back, so the
    # measured run starts from an empty pool
    from paddle_tpu.serving import ServingMetrics

    for warm_len in (8, 20, 40):    # buckets 16 / 32 / 48
        eng.submit(rng.randint(2, vocab, size=warm_len).tolist(),
                   max_tokens=2)
    eng.run()
    # warmup pages may stay parked in the prefix cache (reclaimable);
    # zero live refs is the no-leak invariant
    assert eng.pool.total_refs == 0
    eng.metrics = ServingMetrics(pool_pages=eng.pool.num_usable)
    eng._results.clear()

    t0 = time.monotonic()
    i = 0
    while i < n_req or eng.has_work:
        now = time.monotonic() - t0
        while i < n_req and arrivals[i] <= now:
            eng.submit(prompts[i], max_tokens=16)
            i += 1
        had_work = eng.step()
        if not had_work and i < n_req:
            time.sleep(max(0.0, min(arrivals[i] - (time.monotonic() - t0),
                                    0.002)))
    snap = eng.metrics.snapshot()
    out = {
        "serving_model": "decoderlm_L2_H2_D16_v512_page16_pool64_slots8",
        "serving_tokens_per_s": snap["tokens_per_s"],
        "serving_ttft_ms": snap["ttft_ms_mean"],
        "serving_ttft_ms_p95": snap["ttft_ms_p95"],
        "serving_page_occupancy_peak": snap["page_occupancy_peak"],
        "serving_preemptions": snap["preemptions"],
        "serving_requests_completed": snap["requests_completed"],
        "serving_tokens_generated": snap["tokens_generated"],
        "serving_ticks": snap["ticks"],
    }
    print(json.dumps(out), flush=True)


def worker_serving_chaos():
    """worker_serving's Poisson trace re-run under the default seeded
    FaultPlan — page-pool pressure, one NaN-poisoned rid, random
    transient decode errors, and slow ticks — on the INJECTED clock (no
    wall-clock dependence, so the numbers replay bit-identically).  The
    SLO contract is asserted, not just reported: every non-poisoned
    request completes within its deadline or is shed with a terminal
    status, the poisoned rid ends FAILED while its fused batchmates keep
    greedy parity with the non-paged oracle, and the free-list
    conservation check passes at drain (a violation raises PageLeakError
    and fails the worker)."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                    RequestStatus, ServingEngine,
                                    greedy_decode_reference)

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=256)
    params = model.init_params(jax.random.PRNGKey(0))
    clock = ManualClock(tick_s=0.02)
    plan = FaultPlan(seed=0, clock=clock,
                     decode_error_rate=0.05,          # transient, retried
                     slow_ticks={7: 0.3, 19: 0.5},    # injected tail ticks
                     page_pressure=(6, 26, 44))       # squeeze the pool
    eng = ServingEngine(model, params, eos_id=eos, page_size=16,
                        num_pages=64, max_pages_per_seq=8, max_slots=8,
                        buckets=(16, 32, 48), faults=plan,
                        watchdog_ticks=32, preempt_budget=3)
    n_req, rate = 24, 50.0          # same offered trace as worker_serving
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = [rng.randint(2, vocab, size=rng.randint(4, 49)).tolist()
               for _ in range(n_req)]
    poison_idx, deadline_s = 5, 10.0

    rids = [None] * n_req
    i = 0
    while i < n_req or eng.has_work:
        while i < n_req and arrivals[i] <= clock():
            rids[i] = eng.submit(prompts[i], max_tokens=16,
                                 deadline_s=deadline_s)
            if i == poison_idx:
                plan.poison_nan(rids[i])
            i += 1
        eng.step()                  # advances the injected clock
        assert eng.metrics.ticks < 5000, "chaos trace failed to drain"
    results = eng.run(max_ticks=1)  # drained: runs the conservation check

    parity_checked = parity_ok = 0
    terminal_ok = True
    for j, rid in enumerate(rids):
        st = eng.status(rid)
        if j == poison_idx:
            assert st is RequestStatus.FAILED, f"poisoned rid: {st}"
            continue
        if st is RequestStatus.COMPLETED:
            parity_checked += 1
            want = greedy_decode_reference(model, params, prompts[j], 16,
                                           eos)
            parity_ok += int(results[rid] == want)
        else:
            # shed, not wedged: only terminal statuses are acceptable
            terminal_ok &= st in (RequestStatus.TIMED_OUT,
                                  RequestStatus.REJECTED,
                                  RequestStatus.CANCELLED)
    assert terminal_ok, "non-terminal survivor after drain"
    assert parity_checked == parity_ok, "greedy parity broke under chaos"
    leaked = eng.pool.total_refs          # live refs after a drain = leaks
    assert leaked == 0, f"{leaked} page refs leaked"

    snap = eng.metrics.snapshot()
    hz = eng.healthz()
    out = {
        "serving_chaos_model": "decoderlm_L2_H2_D16_v512_page16_pool64"
                               "_slots8_faultplan_seed0",
        "serving_chaos_completed": snap["requests_completed"],
        "serving_chaos_timed_out": snap["requests_timed_out"],
        "serving_chaos_shed": snap["requests_shed"],
        "serving_chaos_failed": snap["requests_failed"],
        "serving_chaos_retries": snap["retries"],
        "serving_chaos_preemptions": snap["preemptions"],
        "serving_chaos_deadline_miss_rate": snap["deadline_miss_rate"],
        "serving_chaos_queue_wait_ms_p95": snap["queue_wait_ms_p95"],
        "serving_chaos_page_leaks": leaked,
        "serving_chaos_parity_ok": parity_ok,
        "serving_chaos_parity_checked": parity_checked,
        "serving_chaos_healthz_ok": int(bool(hz["ok"])),
        "serving_chaos_ticks": snap["ticks"],
    }
    print(json.dumps(out), flush=True)


def worker_serving_prefix():
    """Automatic prefix caching A/B: the Poisson trace re-shaped so every
    request shares a 256-token system prompt (16 full pages at page 16)
    ahead of a unique 4..16-token tail, replayed TWICE on the same
    injected clock and seed — cache OFF then cache ON.  Chunked prefill
    (64-token chunks) runs in both, so the delta isolates the cache.
    Asserts, not just reports: token-identical outputs between the runs
    (and vs the non-paged oracle on a spot-check), prefix_hit_rate >
    0.5, prefill_tokens_saved > 0, and zero page-ref leaks at both
    drains.  Reports hit rate, tokens saved, COW forks, and TTFT p95
    on/off in injected-clock ms (replays bit-identically)."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                    RequestStatus, ServingEngine,
                                    greedy_decode_reference)

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=512)
    params = model.init_params(jax.random.PRNGKey(0))
    n_req, rate = 24, 50.0
    system = rng.randint(2, vocab, size=256).tolist()   # 16 full pages
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = [system + rng.randint(2, vocab,
                                    size=rng.randint(4, 17)).tolist()
               for _ in range(n_req)]

    def replay(prefix_cache):
        clock = ManualClock(tick_s=0.02)
        eng = ServingEngine(model, params, eos_id=eos, page_size=16,
                            num_pages=192, max_pages_per_seq=20,
                            max_slots=8, buckets=(16, 32, 64),
                            prefill_chunk=64, prefix_cache=prefix_cache,
                            faults=FaultPlan(clock=clock))
        rids = [None] * n_req
        i = 0
        while i < n_req or eng.has_work:
            while i < n_req and arrivals[i] <= clock():
                rids[i] = eng.submit(prompts[i], max_tokens=16)
                i += 1
            eng.step()
            assert eng.metrics.ticks < 5000, "prefix trace failed to drain"
        results = eng.run(max_ticks=1)      # drained: conservation check
        assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
        assert eng.pool.total_refs == 0, "page refs leaked"
        return [results[r] for r in rids], eng.metrics.snapshot()

    outs_off, snap_off = replay(False)
    outs_on, snap_on = replay(True)

    # greedy parity: token-identical with the cache on, and the oracle
    # agrees on a spot-check (the full sweep would dominate the worker)
    assert outs_on == outs_off, "prefix caching broke greedy parity"
    for j in (0, 7, 23):
        want = greedy_decode_reference(model, params, prompts[j], 16, eos)
        assert outs_on[j] == want, f"oracle parity broke on request {j}"
    assert snap_on["prefix_hit_rate"] > 0.5, snap_on["prefix_hit_rate"]
    assert snap_on["prefill_tokens_saved"] > 0
    assert snap_off["prefill_tokens_saved"] == 0

    out = {
        "serving_prefix_model": "decoderlm_L2_H2_D16_v512_page16_pool192"
                                "_slots8_sys256_chunk64",
        "serving_prefix_hit_rate": snap_on["prefix_hit_rate"],
        "serving_prefix_tokens_saved": snap_on["prefill_tokens_saved"],
        "serving_prefix_prefill_tokens_on": snap_on["prefill_tokens"],
        "serving_prefix_prefill_tokens_off": snap_off["prefill_tokens"],
        "serving_prefix_cow_forks": snap_on["cow_forks"],
        "serving_prefix_cache_evictions": snap_on["cache_evictions"],
        "serving_prefix_ttft_ms_p95_on": snap_on["ttft_ms_p95"],
        "serving_prefix_ttft_ms_p95_off": snap_off["ttft_ms_p95"],
        "serving_prefix_ticks_on": snap_on["ticks"],
        "serving_prefix_ticks_off": snap_off["ticks"],
        "serving_prefix_completed": snap_on["requests_completed"],
        "serving_prefix_parity_ok": int(outs_on == outs_off),
    }
    print(json.dumps(out), flush=True)


def worker_serving_mixed():
    """Ragged-paged-attention-v2 A/B (round 12) on the trace shape the
    v1 tick interleave handled worst: mixed long-prefill/heavy-decode
    Poisson traffic — long shared-prefix prompts chunking while short
    chatty requests decode.  Four deterministic replays on one injected
    arrival clock:

    1. ``fuse_tick=False`` f32 — the v1 two-dispatch tick shape (the
       baseline control: same math, prefill and decode as separate
       dispatches);
    2. ``fuse_tick=True``  f32 — the unified step (one dispatch, one
       ragged softmax pass per tick);
    3. unified + prefix cache, f32  — at a FIXED pool byte budget;
    4. unified + prefix cache, int8 — same byte budget, ~3x the pages.

    Asserts, not just reports: 1 and 2 token-identical with 2 paying
    strictly fewer dispatches; int8 admits >= 1.8x the f32 pages at the
    same pool bytes; every replay completes everything with 0 page/ref
    leaks.  Wall-clock tokens/s is CPU PROXY ONLY (the 1.3x unified-vs-
    interleave acceptance target is a chip number); the structure —
    dispatch counts, prefill rows, hit rates, effective pages — replays
    bit-identically on the injected clock."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                    RequestStatus, ServingEngine,
                                    greedy_decode_reference)

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=512)
    params = model.init_params(jax.random.PRNGKey(0))
    pool_bytes = 96 * 16384     # 96 f32 pages at page 16 (L2, H2, D16)

    system = rng.randint(2, vocab, size=64).tolist()   # 4 shared pages
    n_long, n_short = 8, 16
    reqs = []                   # (prompt, max_tokens)
    for _ in range(n_long):     # long prefill, short decode
        tail = rng.randint(2, vocab, size=int(rng.randint(96, 160))).tolist()
        reqs.append((system + tail, 6))
    for _ in range(n_short):    # short prefill, heavy decode
        reqs.append((rng.randint(2, vocab,
                                 size=int(rng.randint(4, 13))).tolist(), 32))
    order = rng.permutation(len(reqs))
    arrivals = np.cumsum(rng.exponential(1.0 / 40.0, len(reqs)))

    def replay(fuse, kv_dtype, prefix_cache):
        clock = ManualClock(tick_s=0.02)
        eng = ServingEngine(model, params, eos_id=eos, page_size=16,
                            num_pages=None, pool_bytes=pool_bytes,
                            max_pages_per_seq=16, max_slots=8,
                            buckets=(32, 64, 128), prefill_chunk=64,
                            fuse_tick=fuse, kv_dtype=kv_dtype,
                            prefix_cache=prefix_cache,
                            faults=FaultPlan(clock=clock))
        rids = [None] * len(reqs)
        t0 = time.monotonic()
        i = 0
        while i < len(reqs) or eng.has_work:
            while i < len(reqs) and arrivals[i] <= clock():
                p, mt = reqs[order[i]]
                rids[order[i]] = eng.submit(p, max_tokens=mt)
                i += 1
            eng.step()
            assert eng.metrics.ticks < 8000, "mixed trace failed to drain"
        wall = time.monotonic() - t0
        results = eng.run(max_ticks=1)      # drained: conservation check
        assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
        assert eng.pool.total_refs == 0, "page refs leaked"
        outs = [results[r] for r in rids]
        snap = eng.metrics.snapshot()
        return outs, snap, wall, eng.pool.num_usable

    outs_base, snap_base, wall_base, _ = replay(False, "float32", False)
    outs_fuse, snap_fuse, wall_fuse, pages_f32 = replay(True, "float32",
                                                        False)
    assert outs_fuse == outs_base, "unified step broke greedy parity"
    assert snap_fuse["step_dispatches"] < snap_base["step_dispatches"]
    for j in (0, n_long, n_long + n_short - 1):   # oracle spot-check
        p, mt = reqs[j]
        assert outs_fuse[j] == greedy_decode_reference(model, params, p,
                                                       mt, eos)
    outs_f32c, snap_f32c, _, _ = replay(True, "float32", True)
    assert outs_f32c == outs_base, "prefix cache broke greedy parity"
    outs_i8c, snap_i8c, _, pages_i8 = replay(True, "int8", True)
    assert pages_i8 >= int(1.8 * pages_f32), (pages_i8, pages_f32)
    i8_agree = sum(int(a == b) for a, b in zip(outs_i8c, outs_base))

    out = {
        "serving_mixed_model": "decoderlm_L2_H2_D16_v512_page16_"
                               f"{pool_bytes >> 10}KiB_slots8_chunk64",
        "serving_mixed_tokens_per_s_interleave": round(
            snap_base["tokens_generated"] / max(wall_base, 1e-9), 2),
        "serving_mixed_tokens_per_s_unified": round(
            snap_fuse["tokens_generated"] / max(wall_fuse, 1e-9), 2),
        "serving_mixed_unified_speedup": round(wall_base /
                                               max(wall_fuse, 1e-9), 3),
        "serving_mixed_dispatches_interleave": snap_base["step_dispatches"],
        "serving_mixed_dispatches_unified": snap_fuse["step_dispatches"],
        "serving_mixed_ticks": snap_fuse["ticks"],
        "serving_mixed_prefill_rows": snap_fuse["prefill_rows"],
        "serving_mixed_ttft_ms_p95_interleave": snap_base["ttft_ms_p95"],
        "serving_mixed_ttft_ms_p95_unified": snap_fuse["ttft_ms_p95"],
        "serving_mixed_pages_f32": pages_f32,
        "serving_mixed_pages_int8": pages_i8,
        "serving_mixed_capacity_ratio": round(pages_i8 / pages_f32, 2),
        "serving_mixed_hit_rate_f32": snap_f32c["prefix_hit_rate"],
        "serving_mixed_hit_rate_int8": snap_i8c["prefix_hit_rate"],
        "serving_mixed_ttft_ms_p95_int8_cache": snap_i8c["ttft_ms_p95"],
        "serving_mixed_parity_ok": int(outs_fuse == outs_base),
        "serving_mixed_int8_token_agreement": round(i8_agree / len(reqs),
                                                    4),
        "serving_mixed_completed": snap_i8c["requests_completed"],
    }
    print(json.dumps(out), flush=True)


def worker_serving_tp():
    """Tensor-parallel serving A/B (round 13): the mixed long-prefill /
    heavy-decode Poisson trace replayed THREE times on one injected
    clock — replicated (mesh=None), tp=2 and tp=4 over a `model` mesh
    axis of the virtual-8 host — with ``FLAGS.jit_audit`` on so every
    replay's ``serving.step`` is captured and statically audited by the
    sharding-propagation auditor (paddle_tpu.analysis.sharding).

    Asserts, not just reports: tp=2 and tp=4 greedy outputs are
    TOKEN-IDENTICAL to the replicated control, every replay completes
    everything with 0 page/ref leaks, the audited
    ``comm_bytes_total{site=serving.step}`` equals the closed-form
    megatron psum budget (2 row-parallel psums per layer, 2*b*(N-1)/N
    each) with ZERO sharding-audit errors (no implicit all-gather on
    the decode hot path), and the same per-chip pool byte budget admits
    tp x the pages.  Wall-clock tokens/s is CPU PROXY ONLY (GSPMD over
    virtual CPU devices pays host-thread collectives; the per-chip
    speedup is a chip number) — the structure is what's pinned."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.analysis import sharding as shard_audit
    from paddle_tpu.analysis.retrace import auditor
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.platform.flags import FLAGS
    from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                    RequestStatus, ServingEngine)

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=4,
                      head_dim=16, max_positions=512)
    params = model.init_params(jax.random.PRNGKey(0))
    pool_bytes = 96 * _tp_page_bytes(model)       # per-CHIP budget

    system = rng.randint(2, vocab, size=32).tolist()   # 2 shared pages
    reqs = []
    for _ in range(6):          # long prefill, short decode
        tail = rng.randint(2, vocab, size=int(rng.randint(48, 81))).tolist()
        reqs.append((system + tail, 6))
    for _ in range(10):         # short prefill, heavy decode
        reqs.append((rng.randint(2, vocab,
                                 size=int(rng.randint(4, 13))).tolist(), 16))
    order = rng.permutation(len(reqs))
    arrivals = np.cumsum(rng.exponential(1.0 / 40.0, len(reqs)))

    old_audit = FLAGS.jit_audit
    FLAGS.jit_audit = True

    def replay(tp):
        auditor().reset()
        mesh = None if tp == 1 else make_mesh((tp,), ("model",),
                                              jax.devices()[:tp])
        clock = ManualClock(tick_s=0.02)
        eng = ServingEngine(model, params, eos_id=eos, page_size=16,
                            num_pages=None, pool_bytes=pool_bytes,
                            max_pages_per_seq=16, max_slots=8,
                            buckets=(32, 64, 128), prefill_chunk=64,
                            kv_dtype="float32", prefix_cache=True,
                            faults=FaultPlan(clock=clock), mesh=mesh)
        rids = [None] * len(reqs)
        t0 = time.monotonic()
        i = 0
        while i < len(reqs) or eng.has_work:
            while i < len(reqs) and arrivals[i] <= clock():
                p, mt = reqs[order[i]]
                rids[order[i]] = eng.submit(p, max_tokens=mt)
                i += 1
            eng.step()
            assert eng.metrics.ticks < 8000, "tp trace failed to drain"
        wall = time.monotonic() - t0
        results = eng.run(max_ticks=1)      # drained: conservation check
        assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
        assert eng.pool.total_refs == 0, "page refs leaked"
        reps = shard_audit.audit_sharding_sites(sites=["serving.step"])
        rep = reps["serving.step"]
        assert not rep.errors, [d.message for d in rep.errors]
        rec = auditor().sites["serving.step"]
        budget = max((eng.tp_step_comm_bytes(cap.args[2].shape[0]
                                             + cap.args[5].shape[0])
                      for cap in rec.captured.values()), default=0.0)
        assert rep.comm_bytes == budget, (rep.comm_bytes, budget)
        outs = [results[r] for r in rids]
        snap = eng.metrics.snapshot()
        return outs, snap, wall, eng.pool.num_usable, rep.comm_bytes

    try:
        outs_rep, snap_rep, wall_rep, pages_rep, comm_rep = replay(1)
        outs_tp2, snap_tp2, wall_tp2, pages_tp2, comm_tp2 = replay(2)
        outs_tp4, snap_tp4, wall_tp4, pages_tp4, comm_tp4 = replay(4)
    finally:
        FLAGS.jit_audit = old_audit
        auditor().reset()
    assert outs_tp2 == outs_rep, "tp=2 broke greedy parity"
    assert outs_tp4 == outs_rep, "tp=4 broke greedy parity"
    assert comm_rep == 0.0
    assert pages_tp2 >= 2 * pages_rep and pages_tp4 >= 4 * pages_rep

    def per_chip(snap, wall, tp):
        return round(snap["tokens_generated"] / max(wall, 1e-9) / tp, 2)

    out = {
        "serving_tp_model": "decoderlm_L2_H4_D16_v512_page16_"
                            f"{pool_bytes >> 10}KiB_per_chip_slots8",
        "serving_tp_tokens_per_s_per_chip_rep": per_chip(snap_rep,
                                                         wall_rep, 1),
        "serving_tp_tokens_per_s_per_chip_tp2": per_chip(snap_tp2,
                                                         wall_tp2, 2),
        "serving_tp_tokens_per_s_per_chip_tp4": per_chip(snap_tp4,
                                                         wall_tp4, 4),
        "serving_tp_ttft_ms_p95_rep": snap_rep["ttft_ms_p95"],
        "serving_tp_ttft_ms_p95_tp2": snap_tp2["ttft_ms_p95"],
        "serving_tp_ttft_ms_p95_tp4": snap_tp4["ttft_ms_p95"],
        "serving_tp_comm_bytes_step_rep": comm_rep,
        "serving_tp_comm_bytes_step_tp2": comm_tp2,
        "serving_tp_comm_bytes_step_tp4": comm_tp4,
        "serving_tp_pages_per_chip_budget_rep": pages_rep,
        "serving_tp_pages_per_chip_budget_tp2": pages_tp2,
        "serving_tp_pages_per_chip_budget_tp4": pages_tp4,
        "serving_tp_parity_ok": int(outs_tp2 == outs_rep
                                    and outs_tp4 == outs_rep),
        "serving_tp_hit_rate_tp2": snap_tp2["prefix_hit_rate"],
        "serving_tp_completed": snap_tp4["requests_completed"],
    }
    print(json.dumps(out), flush=True)


def worker_serving_spec():
    """Speculative decoding A/B (round 18): a CHATTY Poisson trace —
    short repetitive prompts (a shared greeting + a repeated phrase),
    short replies — replayed THREE times on one injected clock:
    spec-off (control), n-gram/prompt-lookup speculation, and
    draft-model speculation (a 1-layer draft with its own paged pool).
    All greedy, so the control IS the oracle trajectory.

    Asserts, not just reports: the n-gram replay is token-identical to
    the spec-off control; decode ticks per emitted token drop >= 1.5x
    under n-gram speculation at the measured acceptance rate; and all
    three replays drain with 0 page/ref leaks (draft pool included).
    Wall-clock tokens/s is CPU PROXY ONLY; ticks-per-token, acceptance
    rate and TTFT replay bit-identically on the injected clock."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                    RequestStatus, ServingEngine)

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos, gen = 512, 1, 24
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=256)
    params = model.init_params(jax.random.PRNGKey(0))
    # the draft: a 1-layer model wearing the target's embeddings, first
    # layer and head — the "distilled draft" stand-in (random draft
    # weights would accept ~nothing and say nothing about the machinery)
    draft = DecoderLM(vocab_size=vocab, num_layers=1, num_heads=2,
                      head_dim=16, max_positions=256)
    dparams = {k: params[k] for k in
               ("emb", "pos", "out", "l0.wq", "l0.wk", "l0.wv",
                "l0.wo", "l0.w1", "l0.w2")}
    n_req, rate = 24, 50.0
    greeting = rng.randint(2, vocab, size=6).tolist()
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = []
    for _ in range(n_req):
        phrase = rng.randint(2, vocab, size=3).tolist()
        prompts.append(greeting + phrase * 3 +
                       rng.randint(2, vocab, size=2).tolist())

    def replay(mode, **kw):
        clock = ManualClock(tick_s=0.02)
        eng = ServingEngine(model, params, eos_id=eos, page_size=16,
                            num_pages=96, max_pages_per_seq=8,
                            max_slots=8, buckets=(16, 32),
                            spec_mode=mode, spec_k=4,
                            faults=FaultPlan(clock=clock), **kw)
        rids = [None] * n_req
        i = 0
        t0 = time.monotonic()
        while i < n_req or eng.has_work:
            while i < n_req and arrivals[i] <= clock():
                rids[i] = eng.submit(prompts[i], max_tokens=gen)
                i += 1
            eng.step()
            assert eng.metrics.ticks < 5000, "spec trace failed to drain"
        wall = time.monotonic() - t0
        eng.run(max_ticks=1)          # drained: conservation check
        assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
        assert eng.pool.total_refs == 0, "page refs leaked"
        snap = eng.metrics.snapshot()
        results = [eng.result(r) for r in rids]
        # decode ticks per emitted decode token: each request's verify-
        # tick participations (decode_slots: one per running slot per
        # step) over the tokens those ticks emitted (first tokens come
        # from prefill, not a decode tick)
        decode_tokens = snap["tokens_generated"] - len(rids)
        tpt = snap["decode_slots"] / max(1, decode_tokens)
        return results, snap, tpt, wall

    outs_off, snap_off, tpt_off, wall_off = replay("off")
    outs_ng, snap_ng, tpt_ng, wall_ng = replay("ngram")
    outs_dr, snap_dr, tpt_dr, wall_dr = replay(
        "draft", draft_model=draft, draft_params=dparams)

    assert outs_ng == outs_off, "ngram speculation broke greedy parity"
    assert outs_dr == outs_off, "draft speculation broke greedy parity"
    assert snap_ng["spec_tokens_accepted"] > 0
    reduction = tpt_off / max(tpt_ng, 1e-9)
    assert reduction >= 1.5, (
        f"decode ticks/token only improved {reduction:.2f}x "
        f"(acceptance {snap_ng['spec_acceptance_rate']})")

    out = {
        "serving_spec_model": "decoderlm_L2_H2_D16_v512_page16_pool96"
                              "_slots8_chatty24_k4",
        "serving_spec_ticks_per_token_off": round(tpt_off, 4),
        "serving_spec_ticks_per_token_ngram": round(tpt_ng, 4),
        "serving_spec_ticks_per_token_draft": round(tpt_dr, 4),
        "serving_spec_reduction_ngram": round(reduction, 4),
        "serving_spec_acceptance_ngram": snap_ng["spec_acceptance_rate"],
        "serving_spec_acceptance_draft": snap_dr["spec_acceptance_rate"],
        "serving_spec_rollbacks_ngram": snap_ng["spec_rollbacks"],
        "serving_spec_suspended_ngram": snap_ng["spec_suspended"],
        "serving_spec_draft_steps": snap_dr["draft_steps"],
        "serving_spec_draft_time_s": snap_dr["draft_time_s"],
        "serving_spec_tokens_per_s_off": round(
            snap_off["tokens_generated"] / max(wall_off, 1e-9), 2),
        "serving_spec_tokens_per_s_ngram": round(
            snap_ng["tokens_generated"] / max(wall_ng, 1e-9), 2),
        "serving_spec_tokens_per_s_draft": round(
            snap_dr["tokens_generated"] / max(wall_dr, 1e-9), 2),
        "serving_spec_ttft_ms_p95_off": snap_off["ttft_ms_p95"],
        "serving_spec_ttft_ms_p95_ngram": snap_ng["ttft_ms_p95"],
        "serving_spec_ticks_off": snap_off["ticks"],
        "serving_spec_ticks_ngram": snap_ng["ticks"],
        "serving_spec_completed": snap_ng["requests_completed"],
        "serving_spec_parity_ok": int(outs_ng == outs_off
                                      and outs_dr == outs_off),
    }
    print(json.dumps(out), flush=True)


def _tp_page_bytes(model):
    """f32 bytes one tp=1 page costs for ``model`` at page 16 — the
    per-chip pool budget unit worker_serving_tp sizes with."""
    from paddle_tpu.serving.kv_cache import PagedKVConfig

    return PagedKVConfig(num_layers=model.num_layers,
                         num_heads=model.num_heads,
                         head_dim=model.head_dim, page_size=16,
                         num_pages=2, max_pages_per_seq=1).bytes_per_page()


def worker_serving_fleet():
    """Fleet-level serving A/B: FOUR ServingEngine replicas behind a
    FleetRouter on one injected clock, a Poisson trace of SIX tenants —
    each tenant's requests share a 128-token system prompt (8 full
    pages) ahead of unique 4..16 token tails — and replica 0 KILLED
    mid-trace; replayed twice with the same seed, prefix-affinity
    routing vs round-robin.  The pool is sized so ONE replica cannot
    cache every tenant's prefix (6 x 8 = 48 prefix pages vs ~20 spare):
    round-robin makes every replica serve every tenant, so caches churn
    under LRU eviction and the PR 4 hit rate collapses under fan-out,
    while affinity gives each prefix one home (arXiv 2604.15464).  The
    robustness contract is asserted, not just reported: every request
    reaches a terminal status under both policies, nothing completes
    twice (duplicate_completions == 0), the fleet conservation check
    passes at both drains (0 page/ref leaks across ALL replicas, dead
    one included), and requests completed under both policies are
    token-identical (greedy parity survives the kill-resubmit path).
    The A/B claim: affinity beats round-robin on aggregate
    prefix_hit_rate AND deadline_miss_rate on this shared-prefix
    trace."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FleetFaultPlan, FleetRouter,
                                    ManualClock, RequestStatus,
                                    ServingEngine)

    paddle.init()
    rng = np.random.RandomState(0)
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=512)
    params = model.init_params(jax.random.PRNGKey(0))
    n_req, rate, n_tenants = 36, 50.0, 6
    systems = [rng.randint(2, vocab, size=128).tolist()
               for _ in range(n_tenants)]              # 8 full pages each
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = [systems[j % n_tenants] +
               rng.randint(2, vocab, size=rng.randint(4, 17)).tolist()
               for j in range(n_req)]
    # 0.8 injected-seconds sits between the two policies' tail latencies
    # on this trace (affinity completes everything by ~0.66; round-robin's
    # cache-churn tail runs to ~0.80, and its kill-victim's resubmission
    # pays a full cache-miss re-prefill it can no longer afford): tight
    # enough that round-robin sheds, loose enough that affinity serves all
    deadline_s, kill_tick = 0.8, 25

    def replay(routing):
        from paddle_tpu.obs import MetricsRegistry, Tracer

        clock = ManualClock(tick_s=0.02)
        plan = FleetFaultPlan(seed=0, clock=clock,
                              kill_at={kill_tick: 0})   # 1-of-4 dies

        def mk(i, time_fn):
            return ServingEngine(model, params, eos_id=eos, page_size=16,
                                 num_pages=56, max_pages_per_seq=12,
                                 max_slots=4, buckets=(16, 64),
                                 prefill_chunk=64, time_fn=time_fn)

        # obs: one explicit tracer + registry per replay (same injected
        # clock), so the bench ships a trace artifact and a per-stage
        # latency breakdown without touching the global FLAGS gate
        registry = MetricsRegistry()
        tracer = Tracer(time_fn=clock, registry=registry)
        fleet = FleetRouter(mk, 4, heartbeat_s=0.1, resubmit_budget=2,
                            routing=routing, faults=plan, tracer=tracer,
                            registry=registry)
        rids = []
        i = 0
        while i < n_req or fleet.has_work:
            while i < n_req and arrivals[i] <= clock():
                rids.append(fleet.submit(prompts[i], max_tokens=16,
                                         deadline_s=deadline_s))
                i += 1
            fleet.step()
            assert fleet._tick < 5000, "fleet trace failed to drain"
        fleet.run(max_ticks=1)      # drained: fleet conservation check
        statuses = [fleet.status(r) for r in rids]
        assert all(s.terminal for s in statuses), "non-terminal survivor"
        snap = fleet.snapshot()
        assert snap["fleet_duplicate_completions"] == 0
        outs = {j: fleet.result(r) for j, r in enumerate(rids)
                if fleet.status(r) is RequestStatus.COMPLETED}
        return outs, snap, fleet

    outs_aff, snap_aff, fleet_aff = replay("affinity")
    outs_rr, snap_rr, _ = replay("round_robin")

    # per-stage latency attribution (injected-clock seconds) from the
    # unified registry — the baseline future kernel PRs diff against:
    # where does a request's time go, queue vs prefill vs decode, and
    # how much re-dispatch churn did the kill cause
    def stage_ms(fleet):
        stages = {}
        hist = fleet.registry.histogram("serving_stage_seconds")
        for key, s in hist.series():
            stage = dict(key)["stage"]
            tot, cnt = stages.get(stage, (0.0, 0))
            stages[stage] = (tot + s.sum, cnt + s.count)
        return {stage: round(1000.0 * tot / cnt, 2) if cnt else 0.0
                for stage, (tot, cnt) in stages.items()}

    stages_aff = stage_ms(fleet_aff)

    # trace artifact: the affinity replay's full timeline as
    # Chrome-trace JSON (open in ui.perfetto.dev), next to the numbers
    from paddle_tpu.obs import save_chrome_trace
    from paddle_tpu.platform.flags import FLAGS as _FLAGS

    os.makedirs(str(_FLAGS.obs_dump_dir), exist_ok=True)
    trace_path = os.path.join(str(_FLAGS.obs_dump_dir),
                              "worker_serving_fleet_trace.json")
    save_chrome_trace(fleet_aff.tracer.events, trace_path)

    # greedy parity across policies: a request completed under BOTH saw
    # token-identical output no matter which replicas computed it (and
    # no matter whether the kill forced a resubmission)
    common = sorted(set(outs_aff) & set(outs_rr))
    assert common, "no common completions to compare"
    assert all(outs_aff[j] == outs_rr[j] for j in common), \
        "fleet routing broke greedy parity"
    assert snap_aff["fleet_prefix_hit_rate"] > \
        snap_rr["fleet_prefix_hit_rate"], (
        snap_aff["fleet_prefix_hit_rate"], snap_rr["fleet_prefix_hit_rate"])
    assert snap_aff["fleet_deadline_miss_rate"] < \
        snap_rr["fleet_deadline_miss_rate"], (
        snap_aff["fleet_deadline_miss_rate"],
        snap_rr["fleet_deadline_miss_rate"])

    out = {
        "serving_fleet_model": "decoderlm_L2_H2_D16_v512_page16_pool56x4"
                               "_slots4_sys128x6tenants_chunk64_kill1of4",
        "serving_fleet_hit_rate_affinity": snap_aff["fleet_prefix_hit_rate"],
        "serving_fleet_hit_rate_rr": snap_rr["fleet_prefix_hit_rate"],
        "serving_fleet_miss_rate_affinity":
            snap_aff["fleet_deadline_miss_rate"],
        "serving_fleet_miss_rate_rr": snap_rr["fleet_deadline_miss_rate"],
        "serving_fleet_tokens_per_s_affinity":
            snap_aff["fleet_tokens_per_s"],
        "serving_fleet_tokens_per_s_rr": snap_rr["fleet_tokens_per_s"],
        "serving_fleet_completed_affinity": snap_aff["fleet_completed"],
        "serving_fleet_completed_rr": snap_rr["fleet_completed"],
        "serving_fleet_resubmits_affinity": snap_aff["fleet_resubmits"],
        "serving_fleet_resubmits_rr": snap_rr["fleet_resubmits"],
        "serving_fleet_shed_affinity": snap_aff["fleet_shed"],
        "serving_fleet_shed_rr": snap_rr["fleet_shed"],
        "serving_fleet_duplicate_completions": 0,
        "serving_fleet_parity_ok": int(all(outs_aff[j] == outs_rr[j]
                                           for j in common)),
        "serving_fleet_parity_checked": len(common),
        # per-stage breakdown (affinity replay, injected-ms means) +
        # the exported trace artifact — the latency-attribution
        # baseline for ROADMAP item 2's kernel work
        "serving_fleet_stage_queue_ms": stages_aff.get("queue", 0.0),
        "serving_fleet_stage_prefill_ms": stages_aff.get("prefill", 0.0),
        "serving_fleet_stage_decode_ms": stages_aff.get("decode", 0.0),
        "serving_fleet_trace_path": trace_path,
        "serving_fleet_trace_events": len(fleet_aff.tracer.events),
    }
    print(json.dumps(out), flush=True)


def worker_serving_disagg():
    """Disaggregated prefill/decode fleet A/B (round 16): the SAME
    seeded hot-tenant trace — one 128-token system prompt behind ~70%
    of requests plus three 64-token cold tenants, Poisson arrivals —
    replayed through four replicas unified vs disaggregated (2 prefill
    + 2 decode with live KV chain migration) on one injected clock.

    The mechanism under test: unified prefix-affinity pins the hot
    tenant to ONE owner replica, so its prompts queue head-of-line
    behind that replica's busy decode slots while other replicas sit
    idle; disaggregation routes prompts by the O(1)
    ``prefill_backlog_tokens`` probe across BOTH prefill replicas and
    keeps the hit rate via cross-replica prefix seeding, then hands
    finished prefills to the decode side through the page plane.
    Asserted, not just reported: token-identical outputs across the two
    deployments (migration changes WHERE, never WHAT), TTFT p95
    improved >= 1.2x, decode ticks/token no worse, chain migrations
    actually ran, 0 leaks (fleet + migration conservation at both
    drains).  Two follow-up replays measure the interconnect: int8
    pages migrate stored-bytes + scales at (D+4)/4D = 0.3125x the f32
    bytes per request (asserted <= 0.35), and a kill-one-decode chaos
    replay must re-adopt surviving prefix pages through the page plane
    (migration_resubmits > 0) instead of re-prefilling from scratch."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FleetFaultPlan, FleetRouter,
                                    ManualClock, ServingEngine)
    from paddle_tpu.serving.migrate import check_migration_conservation

    paddle.init()
    vocab, eos = 512, 1
    model = DecoderLM(vocab_size=vocab, num_layers=2, num_heads=2,
                      head_dim=16, max_positions=512)
    params = model.init_params(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    n_req, rate, hot_w = 32, 60.0, 0.7
    hot = rng.randint(2, vocab, size=128).tolist()       # 8 full pages
    cold = [rng.randint(2, vocab, size=64).tolist() for _ in range(3)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_req))
    prompts = []
    for _ in range(n_req):
        sysp = hot if rng.random_sample() < hot_w else cold[rng.randint(3)]
        prompts.append(sysp +
                       rng.randint(2, vocab, size=rng.randint(4, 17))
                       .tolist())
    roles_disagg = ("prefill", "prefill", "decode", "decode")

    def replay(roles, kv_dtype="float32", kill=None):
        clock = ManualClock(tick_s=0.02)
        plan = FleetFaultPlan(seed=0, clock=clock, kill_at=(kill or {}))

        def mk(i, time_fn):
            return ServingEngine(model, params, eos_id=eos, page_size=16,
                                 num_pages=72, max_pages_per_seq=14,
                                 max_slots=4, buckets=(16, 64),
                                 prefill_chunk=64, kv_dtype=kv_dtype,
                                 time_fn=time_fn)

        kw = {"roles": roles} if roles else {}
        fleet = FleetRouter(mk, 4, heartbeat_s=0.1, resubmit_budget=2,
                            faults=plan, migrate_budget=16, **kw)
        sub_t, first_t = {}, {}
        rids = []
        i = 0
        while i < n_req or fleet.has_work:
            while i < n_req and arrivals[i] <= clock():
                frid = fleet.submit(prompts[i], max_tokens=24)

                def cb_for(f):
                    def cb(tok):
                        first_t.setdefault(f, clock())
                    return cb

                # TTFT on the injected clock: submit -> first EMITTED
                # token (the exactly-once stream's, replay-safe)
                fleet._requests[frid].on_token = cb_for(frid)
                sub_t[frid] = clock()
                rids.append(frid)
                i += 1
            fleet.step()
            assert fleet._tick < 8000, "disagg trace failed to drain"
        fleet.run(max_ticks=1)      # drained: fleet conservation check
        check_migration_conservation(fleet)
        snap = fleet.snapshot()
        assert snap["fleet_duplicate_completions"] == 0
        assert all(fleet.status(r).terminal for r in rids)
        ttft = sorted(first_t[f] - sub_t[f] for f in rids if f in first_t)
        p95 = ttft[int(0.95 * (len(ttft) - 1))] if ttft else 0.0
        toks = sum(len(fleet.result(r) or []) for r in rids)
        outs = [fleet.result(r) for r in rids]
        return {"p95": p95, "ticks": fleet._tick, "tokens": toks,
                "outs": outs, "snap": snap}

    uni = replay(None)
    dis = replay(roles_disagg)

    # migration is a placement optimization: byte-for-byte the same
    # greedy streams, no matter which replica computed which token
    assert uni["outs"] == dis["outs"], "disaggregation broke parity"
    assert dis["snap"]["fleet_migrations_applied"] > 0
    assert uni["snap"]["fleet_migrations_started"] == 0   # paths dormant
    ttft_ratio = uni["p95"] / max(dis["p95"], 1e-9)
    tpt_uni = uni["ticks"] / max(uni["tokens"], 1)
    tpt_dis = dis["ticks"] / max(dis["tokens"], 1)
    assert ttft_ratio >= 1.2, (uni["p95"], dis["p95"])
    assert tpt_dis <= tpt_uni * 1.05, (tpt_dis, tpt_uni)

    # interconnect arithmetic: int8 chains move stored int8 payload +
    # f32 scales — (D+4)/4D of the f32 bytes at D=16
    bytes_per_req = {}
    for kv_dtype in ("float32", "int8"):
        s = replay(roles_disagg, kv_dtype=kv_dtype)["snap"]
        assert s["fleet_migrations_applied"] > 0
        bytes_per_req[kv_dtype] = (s["fleet_migration_bytes"] /
                                   s["fleet_migrations_applied"])
    int8_ratio = bytes_per_req["int8"] / bytes_per_req["float32"]
    assert int8_ratio <= 0.35, int8_ratio

    # chaos: kill one decode replica mid-trace — its in-flight chains
    # resubmit AND re-adopt surviving prefix pages through the page
    # plane (seeded from whichever replica still holds them) instead of
    # re-prefilling from token 0
    chaos = replay(roles_disagg, kill={30: 3})
    cs = chaos["snap"]
    assert cs["fleet_resubmits"] > 0
    assert cs["fleet_migration_resubmits"] > 0
    assert cs["fleet_seed_pages"] > 0
    assert cs["fleet_completed"] == n_req

    out = {
        "serving_disagg_model": "decoderlm_L2_H2_D16_v512_page16_pool72x4"
                                "_slots4_hot128_w0.7_2p2d_budget16",
        "serving_disagg_ttft_p95_s_unified": round(uni["p95"], 4),
        "serving_disagg_ttft_p95_s_disagg": round(dis["p95"], 4),
        "serving_disagg_ttft_p95_ratio": round(ttft_ratio, 3),
        "serving_disagg_ticks_per_token_unified": round(tpt_uni, 4),
        "serving_disagg_ticks_per_token_disagg": round(tpt_dis, 4),
        "serving_disagg_parity_ok": int(uni["outs"] == dis["outs"]),
        "serving_disagg_migrations_applied":
            dis["snap"]["fleet_migrations_applied"],
        "serving_disagg_pages_migrated":
            dis["snap"]["fleet_pages_migrated"],
        "serving_disagg_cross_replica_seeds":
            dis["snap"]["fleet_cross_replica_seeds"],
        "serving_disagg_hit_rate_unified":
            uni["snap"]["fleet_prefix_hit_rate"],
        "serving_disagg_hit_rate_disagg":
            dis["snap"]["fleet_prefix_hit_rate"],
        "serving_disagg_bytes_per_req_f32":
            round(bytes_per_req["float32"], 1),
        "serving_disagg_bytes_per_req_int8":
            round(bytes_per_req["int8"], 1),
        "serving_disagg_int8_bytes_ratio": round(int8_ratio, 4),
        "serving_disagg_chaos_resubmits": cs["fleet_resubmits"],
        "serving_disagg_chaos_migration_resubmits":
            cs["fleet_migration_resubmits"],
        "serving_disagg_chaos_seed_pages": cs["fleet_seed_pages"],
        "serving_disagg_chaos_completed": cs["fleet_completed"],
        "serving_disagg_duplicate_completions": 0,
    }
    print(json.dumps(out), flush=True)


def worker_serving_control():
    """Multi-tenant control-plane A/B (round 17): the six-tenant
    shared-prefix trace of worker_serving_fleet, sharpened into an
    adversarial 10x swing — one batch-class tenant storms at ten times
    the polite tenants' rate (FleetFaultPlan.tenant_storm, its own
    seeded RNG stream) while two interactive and three standard tenants
    submit steadily under their SLO-class deadlines.  The SAME arrivals
    replay twice through two replicas: weighted-fair queuing ON vs OFF
    (FIFO dispatch, the control).  The claim is isolation, asserted
    per tenant and not on averages: with WFQ on, EVERY non-storming
    tenant finishes with zero deadline misses — the storm's backlog is
    charged to the storming tenant's own virtual-time queue — while the
    FIFO control makes polite interactive tenants miss behind the
    storm's head-of-line burst.  The storm tenant is also token-bucket
    metered, so the admission ledger shows real quota_deferred work
    (identical across replays: the bucket sees the same costs at the
    same injected times).  A third replay turns the autoscaler on and
    KILLS a replica mid-storm: the fleet grows under the kill (join
    races death), shrinks back once drained, and the exactly-once +
    CONTROL-LEAK contracts hold through every scaling event — ledger
    partitions per tenant, no duplicate completions, zero page/ref
    leaks on every replica including the killed and drained ones.  A
    static fleet pinned at the autoscaler's max handles the same trace
    for the efficiency claim: the elastic fleet spends fewer
    replica-ticks at token-identical outputs (greedy parity — scaling
    changes WHERE, never WHAT)."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (AutoscalePolicy, DecoderLM,
                                    FleetFaultPlan, FleetRouter,
                                    ManualClock, RequestStatus,
                                    ServingEngine, TenantRegistry,
                                    check_control_conservation)

    paddle.init()
    vocab, eos = 256, 1
    model = DecoderLM(vocab_size=vocab, num_layers=1, num_heads=2,
                      head_dim=16, max_positions=256)
    params = model.init_params(jax.random.PRNGKey(0))

    tenants = ["web", "chat", "app", "api", "etl", "storm"]
    classes = {"web": "interactive", "chat": "interactive",
               "app": "standard", "api": "standard", "etl": "standard",
               "storm": "batch"}
    rng0 = np.random.RandomState(0)
    systems = {t: rng0.randint(2, vocab, size=32).tolist()
               for t in tenants}                    # 2 full pages each
    storm_mult, window_end = 10, 10

    def mk_registry():
        reg = TenantRegistry()
        for t in tenants:
            if t == "storm":
                # metered: the storm pays for its own burst at the
                # bucket, before it can even reach the WFQ
                reg.register(t, classes[t], quota_tokens_per_s=3000.0,
                             burst_tokens=800.0)
            else:
                reg.register(t, classes[t])
        return reg

    def replay(wfq, autoscale=None, n=2, kill=None, idle_tail=0):
        clock = ManualClock(tick_s=0.02)
        plan = FleetFaultPlan(seed=0, clock=clock, kill_at=(kill or {}),
                              tenant_storm=("storm", 0, window_end,
                                            storm_mult))

        def mk(i, time_fn):
            return ServingEngine(model, params, eos_id=eos, page_size=16,
                                 num_pages=48, max_pages_per_seq=6,
                                 max_slots=4, buckets=(16, 64),
                                 prefill_chunk=32, time_fn=time_fn)

        fleet = FleetRouter(mk, n, heartbeat_s=0.1, resubmit_budget=2,
                            faults=plan, tenants=mk_registry(), wfq=wfq,
                            autoscale=autoscale)
        rng = np.random.RandomState(1)
        rids = []
        tick = 0
        while tick < window_end or fleet.has_work:
            if tick < window_end and tick % 2 == 0:
                for t in tenants:
                    for _ in range(plan.storm_factor(tick, t)):
                        prompt = systems[t] + rng.randint(
                            2, vocab, size=int(rng.randint(4, 10))).tolist()
                        rids.append((t, fleet.submit(prompt, max_tokens=6,
                                                     tenant=t)))
            fleet.step()
            tick += 1
            assert tick < 5000, "control trace failed to drain"
        snap_at_drain = fleet.snapshot()
        for _ in range(idle_tail):      # cold ticks: let scale-downs land
            fleet.step()
        check_control_conservation(fleet)
        assert all(fleet.status(r).terminal for _, r in rids)
        snap = fleet.snapshot()
        assert snap["fleet_duplicate_completions"] == 0
        # keyed by submission index, NOT frid: the frid counter is
        # process-global, so only the arrival order lines replays up
        outs = {j: fleet.result(frid) for j, (_, frid) in enumerate(rids)
                if fleet.status(frid) is RequestStatus.COMPLETED}
        hz = fleet.healthz()
        led = fleet.ledger.snapshot()
        # a polite tenant's misses live in two places: engine-side
        # timeouts (healthz aggregation) and router-side WFQ sheds
        # (ledger) — isolation must hold across BOTH
        misses = {t: hz["tenants"].get(t, {}).get("deadline_misses", 0) +
                  led.get(t, {}).get("shed", 0) for t in tenants}
        return {"outs": outs, "snap": snap, "snap_at_drain": snap_at_drain,
                "misses": misses, "ledger": led, "ticks": tick,
                "fleet": fleet}

    on = replay(wfq=True)
    off = replay(wfq=False)

    polite = [t for t in tenants if t != "storm"]
    # THE isolation claim, per tenant: WFQ keeps every polite tenant at
    # zero misses under the 10x storm; FIFO lets the storm starve them
    assert all(on["misses"][t] == 0 for t in polite), on["misses"]
    assert sum(off["misses"][t] for t in polite) > 0, off["misses"]
    # the bucket metered the storm identically in both replays — same
    # costs at the same injected times, WFQ on or off
    assert on["ledger"]["storm"]["quota_deferred"] > 0
    assert (on["ledger"]["storm"]["quota_deferred"] ==
            off["ledger"]["storm"]["quota_deferred"])
    # greedy parity on common completions: queuing policy changes WHEN
    # a request runs, never WHAT it decodes
    common = sorted(set(on["outs"]) & set(off["outs"]))
    assert common and all(on["outs"][f] == off["outs"][f] for f in common)

    # elastic replay: kill replica 0 mid-storm with the autoscaler live
    policy = AutoscalePolicy(min_replicas=2, max_replicas=4,
                             buffered_hi=4, cooldown_ticks=3)
    auto = replay(wfq=True, autoscale=policy, kill={4: 0}, idle_tail=20)
    scaler = auto["fleet"].autoscaler
    assert auto["snap"]["fleet_replicas_dead"] >= 1
    assert scaler.scale_ups >= 1, "fleet never grew under the kill"
    assert scaler.scale_downs >= 1, "fleet never shrank after the storm"
    # static control pinned at the autoscaler's ceiling, same arrivals
    static = replay(wfq=True, n=policy.max_replicas)
    elastic_common = sorted(set(auto["outs"]) & set(static["outs"]))
    assert elastic_common and all(
        auto["outs"][j] == static["outs"][j] for j in elastic_common), \
        "autoscaling broke greedy parity"
    auto_rt = auto["snap_at_drain"]["control_replica_ticks"]
    static_rt = policy.max_replicas * static["ticks"]
    assert auto_rt < static_rt, (auto_rt, static_rt)

    out = {
        "serving_control_model": "decoderlm_L1_H2_D16_v256_page16_pool48"
                                 "_slots4_6tenants_storm10x_sys32",
        "serving_control_requests": (len(on["outs"]) +
                                     sum(v["quota_deferred"]
                                         for v in on["ledger"].values())),
        "serving_control_polite_misses_wfq":
            sum(on["misses"][t] for t in polite),
        "serving_control_polite_misses_fifo":
            sum(off["misses"][t] for t in polite),
        "serving_control_storm_quota_deferred":
            on["ledger"]["storm"]["quota_deferred"],
        "serving_control_storm_submitted":
            on["ledger"]["storm"]["submitted"],
        "serving_control_parity_ok": int(all(on["outs"][f] == off["outs"][f]
                                             for f in common)),
        "serving_control_parity_checked": len(common),
        "serving_control_scale_ups": scaler.scale_ups,
        "serving_control_scale_downs": scaler.scale_downs,
        "serving_control_replica_ticks_auto": auto_rt,
        "serving_control_replica_ticks_static": static_rt,
        "serving_control_replica_ticks_saved":
            round(1.0 - auto_rt / max(1, static_rt), 4),
        "serving_control_chaos_resubmits":
            auto["snap"]["fleet_resubmits"],
        "serving_control_duplicate_completions": 0,
    }
    print(json.dumps(out), flush=True)


def worker_serving_hosttier():
    """Hierarchical KV cache A/B (round 21): a tenant-count sweep whose
    per-tenant system prefixes OVERFLOW the device pool — each tenant's
    cached prefix is evicted before its next request arrives — replayed
    tier-off vs tier-on on the same injected clock and trace.  Tier-off,
    every revisit re-prefills the full prefix; tier-on, eviction spills
    the pages (checksummed) to host RAM and the revisit swaps them back
    in under the per-tick budget.  Asserts, not just reports:
    token-identical outputs between the replays at every tenant count,
    hit rate strictly higher and prefill tokens strictly lower with the
    tier on, zero HOSTTIER-CORRUPT pages, and clean three-state page
    conservation at both drains.  Then the crash-warm restart replay: a
    fleet replica whose host tier holds spilled pages is killed at a
    tick and ``restart_replica`` rebuilds it; asserts pages_restored >
    0, token parity on the re-served prompt, and 0 duplicate
    completions.  Reports hit rate / TTFT p95 / prefill tokens per
    tenant count, swap traffic, and the restart numbers."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import (DecoderLM, FaultPlan, FleetFaultPlan,
                                    FleetRouter, ManualClock,
                                    RequestStatus, ServingEngine)

    paddle.init()
    vocab, eos, page = 256, 1, 8
    model = DecoderLM(vocab_size=vocab, num_layers=1, num_heads=2,
                      head_dim=16, max_positions=256)
    params = model.init_params(jax.random.PRNGKey(0))
    out = {"serving_hosttier_model":
           "decoderlm_L1_H2_D16_v256_page8_pool28_slots2_sys64_chunk32"}

    def replay(n_tenants, host_bytes, rng_seed=0):
        rng = np.random.RandomState(rng_seed)
        systems = [rng.randint(2, vocab, size=64).tolist()   # 8 pages each
                   for _ in range(n_tenants)]
        prompts, tenants = [], []
        for rnd in range(3):                # 3 visits per tenant
            for t in range(n_tenants):
                prompts.append(systems[t] +
                               rng.randint(2, vocab, size=8).tolist())
                tenants.append(f"t{t}")
        clock = ManualClock(tick_s=0.02)
        eng = ServingEngine(model, params, eos_id=eos, page_size=page,
                            num_pages=28, max_pages_per_seq=12,
                            max_slots=2, buckets=(16, 32),
                            prefill_chunk=32,
                            faults=FaultPlan(seed=0, clock=clock),
                            host_tier_bytes=host_bytes, swap_in_budget=10)
        rids = [None] * len(prompts)
        i = 0
        # paced arrivals: one request every 2 ticks, so each tenant's
        # prefix is long evicted (pool 28 pages, working set
        # n_tenants*9) before its next visit
        while i < len(prompts) or eng.has_work:
            if i < len(prompts) and eng.metrics.ticks % 2 == 0:
                rids[i] = eng.submit(prompts[i], max_tokens=8,
                                     tenant=tenants[i])
                i += 1
            eng.step()
            assert eng.metrics.ticks < 20000, "hosttier trace stuck"
        results = eng.run(max_ticks=1)      # drained: conservation check
        assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
        eng.check_page_conservation()
        return [results[r] for r in rids], eng.metrics.snapshot()

    for n_tenants in (3, 5):
        outs_off, off = replay(n_tenants, host_bytes=0)
        outs_on, on = replay(n_tenants, host_bytes=1 << 22)
        assert outs_on == outs_off, \
            f"host tier broke greedy parity at {n_tenants} tenants"
        assert on["host_corrupt"] == 0
        assert on["host_swap_ins"] > 0, "tier never swapped in"
        assert on["prefix_hit_rate"] > off["prefix_hit_rate"], \
            (on["prefix_hit_rate"], off["prefix_hit_rate"])
        assert on["prefill_tokens"] < off["prefill_tokens"]
        tag = f"serving_hosttier_t{n_tenants}"
        out.update({
            f"{tag}_hit_rate_on": on["prefix_hit_rate"],
            f"{tag}_hit_rate_off": off["prefix_hit_rate"],
            f"{tag}_ttft_ms_p95_on": on["ttft_ms_p95"],
            f"{tag}_ttft_ms_p95_off": off["ttft_ms_p95"],
            f"{tag}_prefill_tokens_on": on["prefill_tokens"],
            f"{tag}_prefill_tokens_off": off["prefill_tokens"],
            f"{tag}_swap_ins": on["host_swap_ins"],
            f"{tag}_swap_outs": on["host_swap_outs"],
            f"{tag}_host_hits": on["host_hits"],
            f"{tag}_parity_ok": int(outs_on == outs_off),
        })

    # crash-warm restart replay: spill -> kill at a tick -> restart ->
    # the successor serves the same prompt from adopted host pages
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.02))

    def mk(i, time_fn):
        return ServingEngine(model, params, eos_id=eos, page_size=page,
                             num_pages=48, max_pages_per_seq=12,
                             max_slots=4, buckets=(16, 32),
                             time_fn=time_fn, host_tier_bytes=1 << 22,
                             swap_in_budget=10)

    fleet = FleetRouter(mk, 2, heartbeat_s=0.1, resubmit_budget=2,
                        faults=plan)
    rng = np.random.RandomState(7)
    prompt = rng.randint(2, vocab, size=64).tolist()
    f1 = fleet.submit(list(prompt), max_tokens=8)
    fleet.run(max_ticks=400)
    cold = fleet.result(f1)
    victim = next(r.idx for r in fleet.replicas
                  if r.engine.cache is not None and len(r.engine.cache))
    fleet.replicas[victim].engine.cache.flush()
    kill_tick = fleet._tick
    fleet.kill_replica(victim)
    new_idx = fleet.restart_replica(victim)
    fleet.drain_replica(1 - victim)
    for _ in range(5):
        fleet.step()
    f2 = fleet.submit(list(prompt), max_tokens=8)
    fleet.run(max_ticks=400)
    warm = fleet.result(f2)
    assert warm == cold, "warm restart broke greedy parity"
    assert fleet.metrics.pages_restored > 0, "restart restored 0 pages"
    assert fleet.metrics.duplicate_completions == 0
    fleet.check_fleet_conservation()
    succ = fleet.replicas[new_idx].engine.host_tier.snapshot()
    out.update({
        "serving_hosttier_restart_kill_tick": kill_tick,
        "serving_hosttier_restart_pages_restored":
            fleet.metrics.pages_restored,
        "serving_hosttier_restart_swap_ins": succ["host_swap_ins"],
        "serving_hosttier_restart_parity_ok": int(warm == cold),
        "serving_hosttier_restart_duplicate_completions":
            fleet.metrics.duplicate_completions,
    })
    print(json.dumps(out), flush=True)


def worker_moe():
    """MoE transformer LM vs its dense twin on one chip: single-chip
    Switch-style MoE (top-1 routing, dense dispatch formulation) at the
    same d_model/L/seq as a dense FFN model — the active FLOPs per token
    match, so moe_vs_dense_tokens_ratio isolates the routing +
    dispatch/combine overhead (the single-chip analog of the EP
    all_to_all cost; cross-chip EP needs the mesh the driver doesn't
    have)."""
    import jax
    import numpy as np

    paddle = _init_paddle()
    from paddle_tpu.models import transformer

    rng = np.random.RandomState(0)
    d, layers, heads, seq, bs, vocab, experts = (1024, 8, 16, 1024, 4,
                                                 32768, 8)
    samples = []
    for _ in range(bs):
        t = rng.randint(0, vocab, size=seq)
        samples.append((t.tolist(), list(range(seq)),
                        np.roll(t, -1).tolist()))

    def measure(n_experts, n_layers=layers):
        paddle.topology.reset_name_scope()
        tokens, pos, target, logits, costs = transformer.build(
            vocab_size=vocab, d_model=d, n_layers=n_layers, n_heads=heads,
            max_len=seq, moe_experts=n_experts)
        topo = paddle.topology.Topology(
            costs if isinstance(costs, list) else [costs])
        params = paddle.Parameters.from_topology(topo, seed=0)
        sgd = _make_sgd(costs, params)
        feeds = sgd._make_feeder({"tokens": 0, "pos": 1, "target": 2}).feed(
            samples)
        step = sgd._build_step()
        args = _step_args(sgd, feeds)
        step, flops = _aot_compile(step, args)
        sec = _time_steps(step, args, iters=6)
        return sec, flops

    # a small fast-compiling config FIRST: the worker's budget can run out
    # during a big first compile (round-5 capture: this worker's L8 config
    # produced nothing in 600s), and a printed small row beats an
    # unprinted big one
    out = {}
    try:
        sec_s, _ = measure(experts, n_layers=2)
        out["moe_small_tokens_per_sec"] = round(bs * seq / sec_s, 1)
        out["moe_small_config"] = f"d{d} L2 E{experts} seq{seq} bs{bs}"
        print(json.dumps(out), flush=True)
        dense_s, _ = measure(0, n_layers=2)
        # > 1.0 means the MoE model moves FEWER tokens/sec than its dense
        # twin; the excess is routing + dispatch/combine overhead
        out["moe_small_vs_dense_step_ratio"] = round(sec_s / dense_s, 3)
        print(json.dumps(out), flush=True)
    except Exception as e:
        out["moe_small_error"] = repr(e)
        print(json.dumps(out), flush=True)

    sec, flops = measure(experts)
    out.update({
        "moe_tokens_per_sec": round(bs * seq / sec, 1),
        "moe_ms_per_batch": round(sec * 1000, 2),
        "moe_config": f"d{d} L{layers} E{experts} seq{seq} bs{bs}",
    })
    if flops:
        kind = jax.devices()[0].device_kind
        out["moe_achieved_tflops"] = round(flops / sec / 1e12, 2)
        out["moe_mfu"] = round(flops / sec / _peak_for(kind), 4)
    print(json.dumps(out), flush=True)  # full config before the dense twin
    try:
        dense_sec, _ = measure(0)
        out["moe_dense_twin_tokens_per_sec"] = round(bs * seq / dense_sec, 1)
        # > 1.0 means the MoE model moves FEWER tokens/sec than its dense
        # twin; the excess is routing + dispatch/combine overhead
        out["moe_vs_dense_step_ratio"] = round(sec / dense_sec, 3)
    except Exception as e:
        out["moe_dense_twin_error"] = repr(e)
    print(json.dumps(out), flush=True)


def worker_train_chaos():
    """Fault-tolerant training runtime under seeded chaos (ISSUE 14,
    cpu pass): the shared ``resilience.chaos.seeded_chaos`` replay —
    kill-at-step deaths, a kill between blob write and meta commit,
    injected NaN gradients (skipped in-graph by the bad-step guard),
    a slow-step window on the injected clock, step-granular ASYNC
    checkpoints — restarted by the resume supervisor and pinned
    bit-identical (final params + optimizer slots + per-step loss
    trajectory) against an uninterrupted control running the same
    poison schedule.  Also measures the async-save win directly: the
    train-loop stall (snapshot + pipeline waits) vs a fully synchronous
    save of the same state, plus the guarded step's overhead vs the
    unguarded step."""
    import shutil
    import tempfile
    import time as _t

    _init_paddle()
    import paddle_tpu as paddle
    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu.resilience.chaos import (_build_trainer, _dataset,
                                             seeded_chaos)
    from paddle_tpu.resilience.guard import BadStepGuard

    root = tempfile.mkdtemp(prefix="bench_train_chaos_")
    try:
        out = seeded_chaos(root + "/chaos")
        problems = out.pop("problems")
        out["train_chaos_ok"] = int(not problems)
        if problems:
            out["train_chaos_problems"] = problems[:4]
        print(json.dumps(out), flush=True)  # headline before diagnostics

        # async-save win: stall the loop actually paid vs the same
        # checkpoint written synchronously
        sgd = _build_trainer(BadStepGuard())
        data = _dataset(0, 64)
        sgd.train(paddle.batch(lambda: iter(data), 8), num_passes=1)
        t0 = _t.perf_counter()
        ckpt.save_checkpoint(root + "/sync", 0, sgd.parameters,
                             opt_state=sgd.opt_state,
                             model_state=sgd.model_state)
        sync_s = _t.perf_counter() - t0
        t0 = _t.perf_counter()
        host = ckpt.snapshot_checkpoint(sgd.parameters,
                                        opt_state=sgd.opt_state,
                                        model_state=sgd.model_state)
        snap_s = _t.perf_counter() - t0
        del host
        out["train_ckpt_sync_save_ms"] = round(sync_s * 1000, 3)
        out["train_ckpt_snapshot_stall_ms"] = round(snap_s * 1000, 3)
        out["train_ckpt_async_stall_fraction"] = round(
            snap_s / max(sync_s, 1e-9), 3)
        print(json.dumps(out), flush=True)

        # guard overhead: guarded vs unguarded step time on one model
        def time_train(guard):
            s = _build_trainer(guard)
            r = paddle.batch(lambda: iter(data), 8)
            s.train(r, num_passes=1)          # compile + warm
            t0 = _t.perf_counter()
            for _ in range(3):
                s.train(r, num_passes=1)
            return (_t.perf_counter() - t0) / (3 * 8)

        guarded = time_train(BadStepGuard())
        plain = time_train(None)
        out["train_guard_step_overhead"] = round(
            guarded / max(plain, 1e-9), 3)
        out["train_guard_step_us"] = round(guarded * 1e6, 1)
        print(json.dumps(out), flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def worker_train_pipeline():
    """Pipeline-parallel train step (ISSUE 19, cpu pass) on the
    virtual-8 host: SGD(pipeline=PipelineConfig) over a 4-stage
    transformer.  Two probes:

    Parity — the first-2-step loss trajectory vs the sequential DSL
    baseline (rtol 5e-3: flash kernel vs mha_reference forward delta
    under Adam), plus tokens/s for both.

    Bubble — the GPipe schedule runs M+S-1 ticks, all of which execute
    full stage compute (fill/drain ticks chew on masked garbage), so on
    a SERIALIZED host (the virtual devices share one core; wall time =
    summed work) the wasted fraction is directly (S-1)/(M+S-1).  The
    baseline is an S=1 PIPELINE at the same M/batch — identical
    mha_reference kernels, identical microbatching, zero fill/drain —
    so measured_bubble = 1 - T(S=1)/T(S=4) isolates the schedule (a
    dense baseline would smuggle in the flash-vs-reference kernel
    difference).  The bubble probe uses a longer sequence than the
    parity probe so per-tick compute dwarfs the M-independent overhead
    (Adam update + grad psums, ~100ms) that would otherwise dilute the
    measurement.  ISSUE acceptance pin: within 10% of the closed
    form."""
    import jax
    import numpy as np

    paddle = _init_paddle()
    from paddle_tpu import optimizer, trainer
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.pipeline import PipelineConfig

    devs = jax.devices()
    assert len(devs) >= 8, f"need 8 virtual devices, have {len(devs)}"
    vocab, d, layers, heads = 512, 128, 4, 4
    micro, mb_size = 4, 2
    bs = micro * mb_size
    rng = np.random.RandomState(0)

    def _samples(seq):
        out = []
        for _ in range(bs):
            t = rng.randint(0, vocab, size=seq)
            out.append((t.tolist(), list(range(seq)),
                        np.roll(t, -1).tolist()))
        return out

    def build(stages, seq):
        paddle.topology.reset_name_scope()
        _, _, _, _, cost = transformer.build(
            vocab_size=vocab, d_model=d, n_layers=layers, n_heads=heads,
            max_len=seq)
        params = paddle.Parameters.from_topology(
            paddle.topology.Topology([cost]), seed=0)
        kw = {}
        if stages:
            kw["pipeline"] = PipelineConfig(
                num_stages=stages, microbatches=micro, n_layers=layers,
                n_heads=heads)
            kw["mesh"] = make_mesh((stages,), ("stage",), devs[:stages])
        sgd = trainer.SGD(cost=cost, parameters=params,
                          update_equation=optimizer.Adam(
                              learning_rate=1e-2), **kw)
        feeds = sgd._shard_feeds(sgd._make_feeder(
            {"tokens": 0, "pos": 1, "target": 2}).feed(_samples(seq)))
        return sgd, feeds

    def measure(stages, seq, iters=4):
        sgd, feeds = build(stages, seq)
        args = _step_args(sgd, feeds)
        step, _ = _aot_compile(sgd._build_step(), args)
        # 2-step loss pin alongside the timing
        p, o, m, key, f = args
        losses = []
        for _ in range(2):
            loss, p, o, m = [x for x in step(p, o, m, key, f)][:4]
            losses.append(float(loss))
        return _time_steps(step, args, iters=iters), losses

    parity_seq = 64
    seq_s, seq_losses = measure(0, parity_seq)
    pipe_s, pipe_losses = measure(4, parity_seq)
    out = {
        "pipeline_config": (f"d{d} L{layers} S4 M{micro} "
                            f"seq{parity_seq} bs{bs}"),
        "pipeline_tokens_per_sec": round(bs * parity_seq / pipe_s, 1),
        "pipeline_dense_tokens_per_sec": round(
            bs * parity_seq / seq_s, 1),
        "pipeline_loss_parity_ok": int(bool(np.allclose(
            pipe_losses, seq_losses, rtol=5e-3))),
        "pipeline_losses_2step": [round(x, 4) for x in pipe_losses],
    }
    print(json.dumps(out), flush=True)  # parity headline before bubble
    bubble_seq = 192
    s1_s, _ = measure(1, bubble_seq, iters=3)
    s4_s, _ = measure(4, bubble_seq, iters=3)
    closed = (4 - 1) / (micro + 4 - 1)
    measured = 1.0 - s1_s / max(s4_s, 1e-9)
    out.update({
        "pipeline_bubble_config": (f"d{d} L{layers} S4vsS1 M{micro} "
                                   f"seq{bubble_seq} bs{bs}"),
        "pipeline_bubble_measured": round(measured, 4),
        "pipeline_bubble_closed_form": round(closed, 4),
        "pipeline_bubble_rel_err": round(
            abs(measured - closed) / closed, 4),
    })
    print(json.dumps(out), flush=True)


def worker_train_moe():
    """Expert-parallel MoE dispatch (ISSUE 19, cpu pass) on the
    virtual-8 expert mesh: parallel.moe.moe_ffn (all_to_all dispatch/
    combine, top-2 gates renormalized) against moe_ffn_reference at
    generous capacity — outputs must agree to fp32 tolerance when
    nothing is dropped — plus the drop-rate stats the metrics registry
    records and EP tokens/s vs the dense reference formulation."""
    import jax
    import numpy as np

    _init_paddle()
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel import moe as pmoe

    devs = jax.devices()
    assert len(devs) >= 8, f"need 8 virtual devices, have {len(devs)}"
    n, d, hidden, tokens = 8, 64, 256, 512
    mesh = make_mesh((n,), ("expert",), devs[:n])
    params = pmoe.init_moe_params(jax.random.PRNGKey(0), d_model=d,
                                  hidden=hidden, num_experts=n)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d))

    yr, _ = pmoe.moe_ffn_reference(x, params, capacity_factor=float(n),
                                   top_k=2)
    ye, _, _ = pmoe.moe_ffn(mesh, x, params, capacity_factor=float(n),
                            top_k=2, return_stats=True)
    parity = float(np.max(np.abs(np.asarray(ye) - np.asarray(yr))))
    # drop-rate stats at the PRODUCTION capacity factor, recorded on the
    # metrics registry the way the zoo layer does
    _, _, stats = pmoe.moe_ffn(mesh, x, params, capacity_factor=1.25,
                               top_k=2, return_stats=True)
    pmoe.record_moe_stats(stats)
    drop = float(np.asarray(stats["drop_rate"]))

    def time_fn(fn, iters=8):
        fn()  # warm/compile
        import time as _t
        t0 = _t.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn())
        return (_t.perf_counter() - t0) / iters

    ep = jax.jit(lambda v: pmoe.moe_ffn(mesh, v, params,
                                        capacity_factor=1.25, top_k=2)[0])
    ref = jax.jit(lambda v: pmoe.moe_ffn_reference(
        v, params, capacity_factor=1.25, top_k=2)[0])
    ep_s, ref_s = time_fn(lambda: ep(x)), time_fn(lambda: ref(x))
    out = {
        "moe_ep_config": f"E{n} d{d} h{hidden} tok{tokens} top2 mesh8",
        "moe_ep_parity_max_abs": round(parity, 6),
        "moe_ep_parity_ok": int(parity < 1e-4),
        "moe_ep_tokens_per_sec": round(tokens / ep_s, 1),
        "moe_ep_vs_reference_step_ratio": round(ep_s / ref_s, 3),
    }
    out["moe_ep_drop_rate_cap1.25"] = round(drop, 4)
    out["moe_ep_stats_recorded"] = 1
    print(json.dumps(out), flush=True)


def worker_probe():
    """Fast TPU liveness check: init + one tiny matmul."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: jax found platform {dev.platform!r}")
    x = jnp.ones((256, 256), jnp.bfloat16)
    v = float((x @ x).sum())
    print(json.dumps({"probe_device_kind": dev.device_kind,
                      "probe_ok": v > 0}), flush=True)


def worker_matmul():
    """Achievable dense-MFU ceiling on this chip: chained bf16 matmuls at
    the transformer's dominant shapes. Calibrates the roofline the model
    MFU numbers are judged against — if [4096,2048]x[2048,8192] tops out
    at X, a model step cannot beat X and the gap model-vs-X is what
    optimization can actually recover."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _init_paddle()
    kind = jax.devices()[0].device_kind
    peak = _peak_for(kind)
    rng = np.random.RandomState(0)
    out = {}
    for label, (m, k_, n) in (("ffn", (4096, 2048, 8192)),
                              ("proj", (4096, 2048, 2048)),
                              ("lmhead", (4096, 2048, 32768))):
        a = jnp.asarray(rng.randn(m, k_).astype(np.float32),
                        dtype=jnp.bfloat16)
        b = jnp.asarray(rng.randn(k_, n).astype(np.float32),
                        dtype=jnp.bfloat16)

        @jax.jit
        def chain(a, b):
            # 8 dependent matmuls so dispatch/transfer amortizes; the next
            # input reduces over ALL output columns (n is a multiple of k)
            # so XLA cannot dead-code-eliminate any part of the dot — a
            # plain slice would let it compute only the kept columns
            x = a
            for _ in range(8):
                y = jax.lax.dot(x, b, preferred_element_type=jnp.float32)
                x = y.reshape(m, n // k_, k_).sum(axis=1).astype(jnp.bfloat16)
            return x

        float(jnp.asarray(chain(a, b)).ravel()[0])  # compile
        float(jnp.asarray(chain(a, b)).ravel()[0])  # warm
        iters = 5
        start = time.perf_counter()
        for _ in range(iters):
            x = chain(a, b)
        float(jnp.asarray(x).ravel()[0])
        sec = (time.perf_counter() - start) / iters
        flops = 8 * 2.0 * m * k_ * n
        out[f"matmul_{label}_tflops"] = round(flops / sec / 1e12, 1)
        out[f"matmul_{label}_mfu"] = round(flops / sec / peak, 3)
        print(json.dumps(out), flush=True)
    print(json.dumps(out), flush=True)


WORKERS = {
    "probe": worker_probe,
    "matmul": worker_matmul,
    "resnet50": worker_resnet50,
    "alexnet": worker_alexnet,
    "lstm": worker_lstm,
    "convnets": worker_convnets,
    "transformer": worker_transformer,
    "attention": worker_attention,
    "scaling": worker_scaling,
    "zero1": worker_zero1,
    "serving": worker_serving,
    "serving_chaos": worker_serving_chaos,
    "serving_prefix": worker_serving_prefix,
    "serving_mixed": worker_serving_mixed,
    "serving_spec": worker_serving_spec,
    "serving_tp": worker_serving_tp,
    "serving_fleet": worker_serving_fleet,
    "serving_disagg": worker_serving_disagg,
    "serving_control": worker_serving_control,
    "serving_hosttier": worker_serving_hosttier,
    "train_chaos": worker_train_chaos,
    "train_pipeline": worker_train_pipeline,
    "train_moe": worker_train_moe,
    "moe": worker_moe,
}


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


def _last_json_line(text):
    """Parse the last JSON object line from worker stdout (or None)."""
    if isinstance(text, bytes):
        text = text.decode(errors="ignore")
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                pass
    return None


def _run_worker(name, deadline, cpu=False, attempt_timeout=420,
                max_attempts=3):
    """Run one worker in a subprocess with retry/backoff under the global
    deadline. Returns (dict-or-None, error-string-or-None)."""
    last_err = None
    attempt = 0
    while True:
        remaining = deadline - time.monotonic()
        if remaining < 30:
            return None, last_err or "global deadline exhausted"
        attempt += 1
        env = dict(os.environ)
        if cpu:
            from paddle_tpu.platform.virtual import virtual_cpu_env

            env = virtual_cpu_env(
                env, 8,
                extra_pythonpath=os.path.dirname(os.path.abspath(__file__)))
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--worker", name],
                env=env, timeout=min(remaining - 10, attempt_timeout),
                capture_output=True, text=True)
        except subprocess.TimeoutExpired as te:
            # salvage a partial result: workers print their headline JSON
            # early (before diagnostics) exactly so a later hang doesn't
            # lose the measurement — but MARK the run as cut short
            got = _last_json_line(te.stdout)
            if got is not None:
                got["salvaged_after"] = "timeout"
                return got, None
            last_err = f"{name}: timeout (attempt {attempt})"
            if attempt >= max_attempts:
                return None, last_err
            continue
        if r.returncode == 0:
            got = _last_json_line(r.stdout)
            if got is not None:
                return got, None
            last_err = f"{name}: no JSON in output"
        else:
            # a crash AFTER the early headline print still keeps the
            # measurement (annotated) instead of burning retries
            got = _last_json_line(r.stdout)
            if got is not None:
                got["salvaged_after"] = f"rc={r.returncode}"
                return got, None
            tail = (r.stderr or r.stdout or "").strip().splitlines()[-3:]
            last_err = f"{name}: rc={r.returncode} {' | '.join(tail)}"
        if attempt >= max_attempts:
            return None, last_err
        # transient backend unavailability: back off before retrying
        time.sleep(min(15 * attempt, max(0.0, deadline - time.monotonic())))


def main():
    deadline = time.monotonic() + GLOBAL_DEADLINE_S
    record = {}
    errors = {}

    # the chip first: without one there is nothing to measure, and the
    # CPU replays below must not spend the deadline before that is known
    probe, perr = _run_worker("probe", deadline, attempt_timeout=120,
                              max_attempts=1)
    if not probe:
        errors["tpu"] = f"missing: {perr}"
        _emit_result(record, errors, final=True)
        return 1
    record.update(probe)
    for name in ("transformer", "resnet50", "lstm", "convnets",
                 "alexnet", "attention", "moe"):
        out, err = _run_worker(name, deadline)
        if out:
            record.update(out)
        else:
            errors[name] = err
        _emit_result(record, errors, final=False)
    chip_failed = bool(errors) or "salvaged_after" in record

    # structural CPU replays (counts and parity, not device timings)
    for cpu_worker in ("scaling", "zero1", "serving", "serving_chaos",
                       "serving_prefix", "serving_mixed", "serving_spec",
                       "serving_tp",
                       "serving_fleet", "serving_disagg", "serving_control",
                       "serving_hosttier", "train_chaos",
                       "train_pipeline", "train_moe"):
        out, err = _run_worker(cpu_worker, deadline, cpu=True,
                               attempt_timeout=380, max_attempts=1)
        if out:
            record.update(out)
        else:
            errors[cpu_worker] = err

    _emit_result(record, errors, final=True)
    return 1 if chip_failed else 0


def _emit_result(record, errors, *, final):
    """Assemble and print the aggregate result line. Called after EVERY
    worker (not just at the end): if the driver kills this process before
    all workers finish, the last printed line is still a complete,
    parseable result with everything measured so far."""
    value = record.get("resnet50_images_per_sec_per_chip")
    alex = record.get("alexnet_ms_per_batch")
    result = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": value if value is not None else 0.0,
        "unit": "images/sec/chip",
        # only published reference headline: AlexNet bs=128, 334 ms on K40m
        "vs_baseline": (round(ALEXNET_BASELINE_MS / alex, 3)
                        if alex else 0.0),
        "vs_baseline_basis": "alexnet_bs128_ms_per_batch_K40m_334ms",
    }
    if record.get("lstm_ms_per_batch"):
        result["lstm_vs_baseline"] = round(
            LSTM_BASELINE_MS / record["lstm_ms_per_batch"], 3)
    result.update(record)
    if errors:
        result["errors"] = dict(errors)
    if not final:
        result["partial"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        WORKERS[sys.argv[2]]()
        sys.exit(0)
    sys.exit(main())
