#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of the d2048 decoder (8 layers, 16 heads of 128,
vocabulary 32768, sequence 1024; random weights from ``--seed``):

- *train*: ``models.transformer.build`` + ``trainer.SGD(...).train`` for
  a few Momentum steps over a repeated batch of 4 x 1024 tokens — every
  cost finite, the last lower than the first;
- *serve*: ``DecoderLM`` in a ``ServingEngine`` (default page size and
  slots, pool sized by bytes, fused tick) answering 8 seeded requests
  with prefill and decode mixed in one tick — the pallas ragged kernel
  in the compiled step, tokens agreeing with the reference-path engine,
  page conservation holding.

``--chips 4`` runs ONLY the cross-chip paths and what they are compared
with: DP + ZeRO-1 training against a one-device control, and the TP=4
engine against the replicated engine.

One process, one touch of JAX.  Fails (non-zero exit, no result line)
without a TPU; there is no CPU fallback.  Times printed on the way are
orientation, not claims.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import statistics
import sys
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    vocab: int = 32768
    layers: int = 8
    heads: int = 16
    head_dim: int = 128            # d_model = heads * head_dim = 2048
    seq: int = 1024
    seed: int = 0
    # train: the batch that fit one v5e with Momentum state.  The cost is
    # a per-sequence SUM over seq tokens, so its gradient is ~seq times a
    # per-token mean's: lr 0.01 diverges on the chip by the third step
    batch: int = 4
    steps: int = 6
    lr: float = 5e-4
    # serve: long and short prompts so prefill and decode share ticks
    prompt_lens: Tuple[int, ...] = (512, 384, 300, 200, 128, 96, 64, 32)
    new_tokens: int = 32
    pool_bytes: int = 2 << 30
    # one bucket: the phase is two compiles (decode-only, decode+prefill)
    # per engine, not the whole 32..512 ladder
    buckets: Tuple[int, ...] = (512,)

    @property
    def d_model(self) -> int:
        return self.heads * self.head_dim


FULL = SmokeConfig()

# A divergence between two greedy streams is admitted only as an argmax
# near-tie: both chosen tokens must lie within this many logit units of
# the oracle's maximum.  f32 matmuls run as single bf16 passes on the
# TPU by default, so two correct programs that round in another order
# flip ties: on a v5e 5 of 8 kernel-vs-reference streams diverged, every
# chosen token within 0.010 of the oracle's maximum (PR 21 chip run),
# over logits that spread like N(0, 1).  A wrong attention path lands
# whole units away.
TIE_TOL = 0.1


class SmokeFailure(AssertionError):
    """A phase ran and its result is wrong."""


def info(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The devices to run on; fails unless JAX found ``chips`` TPU chips."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax found platform {devs[0].platform!r}")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices, jax found {len(devs)}")
    return devs


def count_kernels_in_step(eng, pb: int) -> int:
    """Mosaic kernels in the engine's lowered unified step for prefill
    bucket ``pb`` — fails unless the engine chose the pallas path AND the
    program it dispatches carries the kernel."""
    check(eng._ragged_kernel,
          "the engine chose the reference attention path, not the kernel")
    lowered = eng._step_fn(pb, eng._k1).lower(
        eng.params, eng._kv, eng._empty_tick(pb, eng._k1),
        eng._last_words())
    n = lowered.as_text().count("tpu_custom_call")
    check(n > 0, f"no tpu_custom_call in the lowered serving step (pb={pb})")
    return n


class CacheCounter:
    """Hits and misses of the persistent compilation cache, as jax
    reports them."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes(dev) -> Optional[int]:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

FEEDING = {"tokens": 0, "pos": 1, "target": 2}


def build_trainer(cfg: SmokeConfig, mesh=None, zero: Optional[int] = None):
    """The trainer and the one seeded batch it repeats."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, trainer
    from paddle_tpu.models import transformer

    paddle.topology.reset_name_scope()
    *_, cost = transformer.build(
        vocab_size=cfg.vocab, d_model=cfg.d_model, n_layers=cfg.layers,
        n_heads=cfg.heads, max_len=cfg.seq)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=cfg.seed)
    sgd = trainer.SGD(cost=cost, parameters=params, mesh=mesh, zero=zero,
                      update_equation=optimizer.Momentum(
                          momentum=0.9, learning_rate=cfg.lr))
    rng = np.random.RandomState(cfg.seed)
    batch = []
    for _ in range(cfg.batch):
        t = rng.randint(0, cfg.vocab, size=cfg.seq)
        batch.append((t.tolist(), list(range(cfg.seq)),
                      np.roll(t, -1).tolist()))
    return sgd, batch


def run_training(cfg: SmokeConfig, mesh=None, zero: Optional[int] = None):
    """``cfg.steps`` Momentum steps over one repeated seeded batch through
    ``SGD.train``.  Returns ``(sgd, costs, step_seconds)``; each step's
    time is read after its cost reached the host, so step 0 carries the
    compile."""
    from paddle_tpu import event

    sgd, batch = build_trainer(cfg, mesh, zero)
    costs: List[float] = []
    seconds: List[float] = []
    began = [0.0]

    def on_event(ev) -> None:
        if isinstance(ev, event.BeginIteration):
            began[0] = time.perf_counter()
        elif isinstance(ev, event.EndIteration):
            costs.append(ev.cost)      # waits for the step on the device
            seconds.append(time.perf_counter() - began[0])

    sgd.train(lambda: iter([batch] * cfg.steps), num_passes=1,
              event_handler=on_event, feeding=FEEDING)
    check(len(costs) == cfg.steps,
          f"trainer ran {len(costs)} steps, wanted {cfg.steps}")
    check(bool(np.isfinite(costs).all()), f"non-finite cost in {costs}")
    check(costs[-1] < costs[0],
          f"cost did not fall over a repeated batch: {costs}")
    return sgd, costs, seconds


def report_training(tag: str, cfg: SmokeConfig, costs, seconds) -> None:
    steady = statistics.median(seconds[1:])
    info(f"{tag}: d{cfg.d_model} L{cfg.layers} h{cfg.heads} seq{cfg.seq} "
         f"V{cfg.vocab} bs{cfg.batch}; costs "
         + " ".join(f"{c:.4f}" for c in costs))
    info(f"{tag}: step ms " + " ".join(f"{s * 1e3:.1f}" for s in seconds)
         + f"; median after the first {steady * 1e3:.1f} ms "
         f"({cfg.batch * cfg.seq / steady:.0f} tokens/s); compile about "
         f"{seconds[0] - steady:.1f} s (orientation, not measured by the "
         "driver)")


def train_phase(cfg: SmokeConfig, dev) -> None:
    _, costs, seconds = run_training(cfg)
    report_training("train", cfg, costs, seconds)
    info(f"train: peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(cfg: SmokeConfig) -> List[List[int]]:
    rng = np.random.RandomState(cfg.seed + 1)
    return [rng.randint(0, cfg.vocab - 1, size=n).tolist()
            for n in cfg.prompt_lens]


def build_engine(cfg: SmokeConfig, model, params, **engine_kw):
    """Default page size and slots, pool sized by bytes, fused tick; a
    sequence may hold ``cfg.seq`` tokens."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.kv_cache import PAGE_SIZE

    return ServingEngine(
        model, params, eos_id=cfg.vocab - 1, pool_bytes=cfg.pool_bytes,
        max_pages_per_seq=-(-cfg.seq // PAGE_SIZE),
        buckets=cfg.buckets, **engine_kw)


def run_engine(cfg: SmokeConfig, model, params, prompts, **engine_kw):
    """Answer ``prompts`` through submit/step/run.  The long half is
    submitted first and stepped once, so the short half arrives while it
    is still prefilling and the ticks mix prefill with decode.  Returns
    ``(engine, streams, tick_seconds)``."""
    eng = build_engine(cfg, model, params, **engine_kw)
    half = len(prompts) // 2
    ticks: List[float] = []
    mixed = [0]

    def step() -> bool:
        m = eng.metrics
        before = (m.decode_rows, m.prefill_rows)
        t0 = time.perf_counter()
        more = eng.step()              # returns after the words of the step
        #                                before this one reached the host
        ticks.append(time.perf_counter() - t0)
        mixed[0] += m.decode_rows > before[0] and m.prefill_rows > before[1]
        return more

    rids = [eng.submit(p, cfg.new_tokens) for p in prompts[:half]]
    step()
    rids += [eng.submit(p, cfg.new_tokens) for p in prompts[half:]]
    while step():
        pass
    eng.run()                          # drained: asserts conservation
    eng.check_page_conservation()
    streams = [eng.result(r) for r in rids]
    check(all(s for s in streams),
          f"requests without a completed stream: "
          f"{[str(eng.status(r)) for r in rids]}")
    check(len(prompts) < 2 or mixed[0] > 0,
          "no tick carried prefill and decode rows together")
    return eng, streams, ticks


def oracle_logits(model, params, tokens: Sequence[int], pad_to: int):
    """Next-token logits after ``tokens`` from the NON-paged oracle: one
    full causal forward (``mha_reference``, no KV cache) in true f32.
    Padded to ``pad_to`` so every call shares one compile; causality
    keeps the padding out of the answer."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import mha_reference

    @jax.jit
    def forward(params, toks, n):
        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(toks.shape[0], dtype=jnp.int32)
            x = model.embed(params, toks[None], pos[None])
            for l in range(model.num_layers):
                q, k, v = model.qkv(params, l, x)
                x = model.attn_out(params, l,
                                   mha_reference(q, k, v, causal=True), x)
            return model.logits(params, x[0, n - 1])

    toks = np.zeros((pad_to,), np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(forward(params, jnp.asarray(toks), len(tokens)))


def compare_streams(tag: str, cfg: SmokeConfig, model, params, prompts,
                    got, want) -> None:
    """Greedy streams must be identical; where two diverge, the first
    divergence must be an argmax near-tie under the oracle."""
    ties = 0
    for i, (prompt, a, b) in enumerate(zip(prompts, got, want)):
        if a == b:
            continue
        at = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                  min(len(a), len(b)))
        check(at < min(len(a), len(b)),
              f"{tag}: request {i} streams differ in length only: "
              f"{len(a)} vs {len(b)} tokens")
        logits = oracle_logits(model, params, prompt + a[:at], cfg.seq)
        gaps = [float(logits.max() - logits[t]) for t in (a[at], b[at])]
        info(f"{tag}: request {i} diverges at token {at}: {a[at]} vs "
             f"{b[at]}, {gaps[0]:.4f} and {gaps[1]:.4f} below the oracle "
             f"maximum")
        check(max(gaps) <= TIE_TOL,
              f"{tag}: request {i} diverges at token {at} and it is no "
              f"tie: chosen logits {gaps} below the oracle maximum "
              f"(tolerance {TIE_TOL})")
        ties += 1
    info(f"{tag}: {len(got) - ties} of {len(got)} streams identical, "
         f"{ties} diverge at an argmax tie within {TIE_TOL}")


def report_ticks(tag: str, ticks: Sequence[float]) -> None:
    info(f"{tag}: {len(ticks)} ticks, median {statistics.median(ticks) * 1e3:.1f}"
         f" ms, slowest (compiles) {max(ticks):.1f} s (orientation, not "
         "measured by the driver)")


def serve_phase(cfg: SmokeConfig, dev) -> None:
    import jax

    from paddle_tpu.serving import DecoderLM

    model = DecoderLM(vocab_size=cfg.vocab, num_layers=cfg.layers,
                      num_heads=cfg.heads, head_dim=cfg.head_dim,
                      max_positions=cfg.seq)
    params = model.init_params(jax.random.PRNGKey(cfg.seed))
    prompts = make_requests(cfg)

    eng, got, ticks = run_engine(cfg, model, params, prompts)
    for pb in (0,) + tuple(cfg.buckets):
        info(f"serve: {count_kernels_in_step(eng, pb)} tpu_custom_call in "
             f"the lowered step at prefill bucket {pb}")
    info(f"serve: d{cfg.d_model} L{cfg.layers} h{cfg.heads} V{cfg.vocab}; "
         f"{len(prompts)} requests, prompts {list(cfg.prompt_lens)}, "
         f"{cfg.new_tokens} new tokens each; pool "
         f"{eng.kv_cfg.num_pages} pages of {eng.kv_cfg.page_size}")
    report_ticks("serve (kernel)", ticks)
    del eng
    gc.collect()                       # its pool frees before the next one

    _, want, ticks = run_engine(cfg, model, params, prompts,
                                use_kernel=False)
    report_ticks("serve (reference path)", ticks)
    compare_streams("serve kernel vs reference", cfg, model, params,
                    prompts, got, want)
    info(f"serve: peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# four chips: only what exists across chips, and its one-device control
# ---------------------------------------------------------------------------

def cross_chip_phase(cfg: SmokeConfig, devs) -> None:
    import jax

    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.zero import opt_state_bytes_per_device
    from paddle_tpu.serving import DecoderLM

    n = len(devs)

    # (a) data parallel + ZeRO-1 against one device, same global batch
    runs = {}
    for tag, sub in (("dp", devs), ("control", devs[:1])):
        sgd, costs, seconds = run_training(
            cfg, mesh=make_mesh((len(sub),), ("data",), sub), zero=1)
        report_training(f"train {tag} x{len(sub)} zero1", cfg, costs,
                        seconds)
        slots = sgd.opt_state["slots"]
        leaves = jax.tree.leaves(slots)
        runs[tag] = (costs, opt_state_bytes_per_device(slots),
                     min(len(x.sharding.device_set) for x in leaves))
        del sgd, slots, leaves
        gc.collect()                   # this run's state frees first
    (costs, slot_bytes, spread), (ref_costs, ref_bytes, _) = \
        runs["dp"], runs["control"]
    check(abs(costs[0] - ref_costs[0]) <= 1e-4 * abs(ref_costs[0]),
          f"first costs differ: {costs[0]} on {n} chips vs {ref_costs[0]}")
    check(spread == n, f"an optimiser slot spans {spread} devices, not {n}")
    check(slot_bytes <= 1.05 * ref_bytes / n,
          f"optimiser slots hold {slot_bytes} bytes per device; a 1/{n} "
          f"share of {ref_bytes} was expected")
    info(f"train: first cost {costs[0]:.6f} on {n} chips vs "
         f"{ref_costs[0]:.6f} on one; optimiser slots {slot_bytes} bytes "
         f"per device vs {ref_bytes} ({slot_bytes / ref_bytes:.3f})")

    # (b) tensor-parallel engine against the replicated engine
    model = DecoderLM(vocab_size=cfg.vocab, num_layers=cfg.layers,
                      num_heads=cfg.heads, head_dim=cfg.head_dim,
                      max_positions=cfg.seq)
    params = model.init_params(jax.random.PRNGKey(cfg.seed))
    prompts = make_requests(cfg)
    eng, got, ticks = run_engine(cfg, model, params, prompts,
                                 mesh=make_mesh((n,), ("model",), devs))
    for pb in (0,) + tuple(cfg.buckets):
        info(f"serve tp{n}: {count_kernels_in_step(eng, pb)} "
             f"tpu_custom_call in the lowered step at prefill bucket {pb}")
    check(len(eng._kv.k.sharding.device_set) == n,
          "the KV pool does not span every chip")
    report_ticks(f"serve tp{n}", ticks)
    held = [peak_bytes(d) for d in devs]
    info(f"peak_bytes_in_use per device: {held}")
    check(all(held), f"a device holds no bytes: {held}")
    del eng
    gc.collect()
    _, want, ticks = run_engine(cfg, model, params, prompts)
    report_ticks("serve replicated", ticks)
    compare_streams(f"serve tp{n} vs replicated", cfg, model, params,
                    prompts, got, want)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None, cfg: SmokeConfig = FULL) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip phase and its control")
    ap.add_argument("--seed", type=int, default=cfg.seed)
    args = ap.parse_args(argv)
    cfg = dataclasses.replace(cfg, seed=args.seed)

    import importlib.metadata

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.platform.compile_cache import enable_compile_cache

    paddle.init()
    devs = require_tpu(args.chips)
    cache = CacheCounter()
    info(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
         f"count={len(devs)} jax={jax.__version__} "
         f"libtpu={importlib.metadata.version('libtpu')} "
         f"compile_cache={enable_compile_cache()}")
    if args.chips == 1:
        train_phase(cfg, devs[0])
        gc.collect()                   # the trainer's state frees first
        serve_phase(cfg, devs[0])
    else:
        cross_chip_phase(cfg, devs[:args.chips])
    info(f"compile cache: {cache.hits} hits, {cache.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
