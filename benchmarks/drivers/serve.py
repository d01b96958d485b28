"""Driver of the ``serve`` kind: one ``ServingEngine`` behind a closed loop.

``clients`` clients, each with one request in flight: a client submits its
next request as soon as the step in which its last one ended returns.  The
engine is driven by ``submit``/``step`` in this one thread, as its own
``run`` does.  Token times are the benchmark's, taken in ``on_token``.

Set-up builds the model (weights made sharded on the device from the
seed), the engine, and warms up: the clients join one every ``ramp_ticks``
ticks and the loop runs for ``warmup_ticks`` ticks or more, until it has
made ticks with and without a prefill chunk (both step programs compiled).  Then the window: the
same loop for ``--seconds``.  Requests still in flight at its end are
cancelled, the engine drains, and its page accounting is checked.

``correct``: a sample, drawn from the seed, of the requests the window
finished, with the longest in it.  The plain reference runs once over each
prompt with its served tokens; the number compared is the widest gap by
which a served token's logit lies below the reference's best.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from harness import cells, measure, weights
from harness.measure import say


def build_engine(cell, seed: int, devs):
    """(engine, weights by the reference's names, the family's program).
    The weights are made once, placed as the family's program says (the
    engine's own plan), and shared by the engine and the reference
    (neither makes them)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.serving import ServingEngine

    cfg, dep = cell.config, cell.config["serve"]
    prog = cell.family().serve_program(cfg, devs)
    model, mesh, names = prog["model"], prog["mesh"], prog["names"]
    leaves = cell.family().leaves(cfg, "serve")
    if set(leaves) != set(names.values()) or len(leaves) != len(names):
        raise cells.CellError(
            "the model's parameters and the reference's leaves differ: "
            f"{sorted(set(leaves) ^ set(names.values()))}")
    shardings = None
    if mesh is not None:
        shardings = {ref: NamedSharding(mesh, P(*prog["placement"].get(
            name, ()))) for name, ref in names.items()}
    made = weights.make(leaves, seed, shardings)
    params = {name: made[ref] for name, ref in names.items()}
    longest = int(cell.traffic["prompt"]["max"]) + \
        int(cell.traffic["answer"]["max"])
    page = int(dep["page_size"])
    eng = ServingEngine(
        model, params, eos_id=model.vocab_size,   # no token takes it
        page_size=page, max_slots=int(dep["max_slots"]),
        pool_bytes=int(dep["pool_bytes"]),
        max_pages_per_seq=-(-longest // page),
        buckets=tuple(dep["prefill_buckets"]),
        prefill_chunk=int(dep["prefill_chunk"]), mesh=mesh)
    return eng, made, prog


class Client:
    def __init__(self):
        self.rid: Optional[int] = None
        self.req: Optional[dict] = None


def run(cell, args, devs, started: float, watch: measure.CompileWatch,
        broken=None):
    """``broken`` (tests only) may alter a token where it is produced."""
    traffic = cell.traffic
    eng, made, prog = build_engine(cell, args.seed, devs)
    say(f"engine: {eng.kv_cfg.num_pages} pages of {eng.kv_cfg.page_size}, "
        f"{eng._max_slots} slots, kernel path {eng._ragged_kernel}, "
        f"tp {eng.tp}")
    source = iter(cell.generator().make(traffic, cell.config, args.seed))
    spans = measure.Spans()
    tracing = measure.Tracing(cell.name) if args.trace else None
    clients = [Client() for _ in range(int(traffic["clients"]))]
    requests: List[dict] = []       # every request sent, in order
    ticks: List[dict] = []          # every step of the window

    def submit(c: Client) -> None:
        prompt, n_answer = next(source)
        req = {"prompt": prompt, "max_tokens": n_answer, "tokens": [],
               "times": [], "submitted": time.perf_counter(), "status": None}

        def on_token(tok: int, req=req) -> None:
            req["tokens"].append(int(tok) if broken is None
                                 else broken(int(tok), req))
            req["times"].append(time.perf_counter())

        c.rid = eng.submit(prompt, n_answer, on_token=on_token)
        c.req = req
        requests.append(req)

    def tick(submitting: bool) -> dict:
        return tick_of(clients, submitting)

    def tick_of(clients: List[Client], submitting: bool) -> dict:
        m = eng.metrics
        before = (m.decode_slots, m.decode_rows, m.prefill_rows,
                  m.prefill_pad_rows)
        live = sum(len(c.req["prompt"]) + len(c.req["tokens"])
                   for c in clients if c.req is not None
                   and c.req["tokens"])
        t0 = time.perf_counter()
        with spans.span("engine_step"):
            eng.step()
        t1 = time.perf_counter()
        with spans.span("submit"):
            for c in clients:
                if c.req is None:
                    continue
                status = eng.status(c.rid)
                if not status.terminal:
                    continue
                c.req["status"] = status.name
                c.req["ended"] = t1
                if submitting:
                    submit(c)
                else:
                    c.req = None
        return {"t0": t0, "t1": t1, "live_kv_tokens": live,
                "decode_slots": m.decode_slots - before[0],
                "decode_rows": m.decode_rows - before[1],
                "prefill_rows": m.prefill_rows - before[2],
                "prefill_pad_rows": m.prefill_pad_rows - before[3]}

    # warm-up: the clients join one every `ramp_ticks` ticks, so their
    # prompts do not all prefill at once, and the loop runs on until it
    # has made ticks with and without a prefill chunk (both programs are
    # compiled) and at least `warmup_ticks` in all
    ramp, kinds, n = int(traffic["ramp_ticks"]), set(), 0
    joined = []
    while n < int(traffic["warmup_ticks"]) or len(kinds) < 2 \
            or len(joined) < len(clients):
        if n % ramp == 0 and len(joined) < len(clients):
            joined.append(clients[len(joined)])
            submit(joined[-1])
        kinds.add(tick_of(joined, True)["prefill_rows"] > 0)
        n += 1
    t0 = time.perf_counter()
    t_end = t0 + args.seconds
    counters0 = eng.metrics.snapshot()
    trace_at = float(traffic.get("trace_after_s", 2.0))
    trace_for = float(traffic.get("trace_seconds", 3.0))
    while time.perf_counter() < t_end:
        if tracing is not None:
            into = time.perf_counter() - t0
            if tracing.t0 is None and into >= trace_at:
                tracing.start()
                spans.open("trace_window")
            elif tracing.on and \
                    time.perf_counter() - tracing.t0 >= trace_for:
                spans.close("trace_window")
                tracing.stop()
        ticks.append(tick(True))
    if tracing is not None and tracing.on:
        spans.close("trace_window")
        tracing.stop()
    counters1 = eng.metrics.snapshot()
    # the window has closed: let what it submitted reach its first token
    # (a few ticks), then cancel what is still running and drain
    inside = [r for r in requests if t0 <= r["submitted"] < t_end]
    for _ in range(int(traffic["drain_ticks"])):
        if all(r["tokens"] or r["status"] for r in inside):
            break
        tick(False)
    for c in clients:
        if c.req is not None:
            eng.cancel(c.rid)
    leak = None
    try:
        eng.run()
        eng.check_page_conservation()
    except Exception as e:                     # reported, and not correct
        leak = f"{type(e).__name__}: {e}"
    memory_peak = measure.memory_peak_bytes(devs)

    emitted = [t for r in requests for t in r["times"] if t0 <= t < t_end]
    gaps = [b - a for r in requests
            for a, b in zip(r["times"], r["times"][1:]) if t0 <= b < t_end]
    worst = args.seconds            # a request with no first token
    ttft = [(r["times"][0] - r["submitted"]) if r["times"] else worst
            for r in inside]
    failed = [r for r in inside if r["status"] not in (None, "COMPLETED")]
    finished = [r for r in requests if r["status"] == "COMPLETED"
                and t0 <= r["submitted"] and r["ended"] <= t_end + 1e-9]
    say(f"window: {len(ticks)} ticks, {len(inside)} requests submitted, "
        f"{len(finished)} of them finished, {len(failed)} failed, "
        f"{len(emitted)} tokens emitted; p95 of {len(ttft)} first-token "
        f"times and of {len(gaps)} token gaps")
    say_ticks(ticks, t0)
    record = {
        "kind": "serve", "window": (t0, t_end), "seconds": args.seconds,
        "setup_s": t0 - started, "ticks": ticks, "spans": spans.rows,
        "tokens": len(emitted), "ttft_s": ttft, "gaps_s": gaps,
        "attempted": len(inside), "failed": len(failed),
        "compiles_in_window": watch.inside(t0, t_end),
        "memory_peak_bytes": memory_peak, "tracing": tracing,
        "counters": {k: v - counters0[k] for k, v in counters1.items()
                     if isinstance(v, int)},
        "max_slots": eng._max_slots, "layers_run": prog["layers"],
        "tp": eng.tp,
        "end_to_end": {
            "serve_tokens_per_s": len(emitted) / args.seconds,
            "ttft_p95_ms": 1e3 * measure.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * measure.percentile(gaps, 95),
            "setup_s": t0 - started},
    }
    del eng
    gc.collect()
    sample = sample_requests(
        finished, int(cell.traffic["check"]["requests"]), args.seed)
    record["check"] = check(cell, made, finished, sample, leak)
    record["made"], record["sample"] = made, sample
    return record


def say_ticks(ticks: List[dict], t0: float) -> None:
    """Where the window's time went, tick by tick, on an earlier line: the
    median period of the ticks without and with prefill rows, and the five
    longest periods with the second of the window each began in (a stalled
    host shows here, and in no metric's name)."""
    periods = [(b["t0"] - a["t0"], a) for a, b in zip(ticks, ticks[1:])]
    if not periods:
        return
    for name, rows in (("decode-only", [p for p, t in periods
                                        if not t["prefill_rows"]]),
                       ("with prefill rows", [p for p, t in periods
                                              if t["prefill_rows"]])):
        if rows:
            say(f"ticks {name}: {len(rows)}, median period "
                f"{1e3 * float(np.median(rows)):.2f} ms, mean "
                f"{1e3 * float(np.mean(rows)):.2f} ms")
    longest = sorted(periods, key=lambda r: -r[0])[:5]
    say("ticks, the five longest periods: " + ", ".join(
        f"{1e3 * p:.0f} ms at {t['t0'] - t0:.1f} s" for p, t in longest))


def sample_requests(finished: List[dict], n: int, seed: int) -> List[dict]:
    """``n`` of the finished requests, drawn from the seed, the longest
    (prompt and answer together) always among them."""
    if not finished:
        return []
    order = np.random.default_rng(seed).permutation(len(finished))
    longest = max(range(len(finished)), key=lambda i: (
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    picked = [longest] + [int(i) for i in order if i != longest][:n - 1]
    return [finished[i] for i in picked]


def make_forward(cell, pad_to: int, mode: str):
    """One jitted reference forward at one padded length: (weights tree,
    tokens [pad_to]) -> logits [pad_to, V]."""
    import jax
    import jax.numpy as jnp

    fam, ref = cell.family(), cell.reference()

    @jax.jit
    def forward(tree, toks):
        pos = jnp.arange(pad_to, dtype=jnp.int32)
        return fam.reference_logits(
            ref, cell.config, tree, toks, pos,
            jnp.zeros((pad_to,), jnp.int32), mode=mode,
            block_rows=int(cell.traffic["check"]["block_rows"]))

    return forward


def token_gaps(cell, made: dict, sample: List[dict], mode: str
               ) -> Dict[str, float]:
    """Over the sample, with the f32 reference's logits at each position
    that produced a served token: the widest and the mean gap between the
    reference's best logit and the logit of the token that ``mode`` puts
    there - the served token itself for ``f32`` (the program is judged),
    the lower precision's own first choice otherwise (the control)."""
    import jax.numpy as jnp

    block = int(cell.traffic["check"]["block_rows"])
    longest = int(cell.traffic["prompt"]["max"]) + \
        int(cell.traffic["answer"]["max"])
    pad_to = -(-longest // block) * block
    tree = weights.unflatten(made)
    forward = make_forward(cell, pad_to, "f32")
    lower = make_forward(cell, pad_to, mode) if mode != "f32" else None
    gaps: List[float] = []
    for r in sample:
        n_p, n_a = len(r["prompt"]), len(r["tokens"])
        toks = np.zeros(pad_to, np.int32)
        toks[:n_p] = r["prompt"]
        toks[n_p:n_p + n_a] = r["tokens"]
        toks = jnp.asarray(toks)
        logits = forward(tree, toks)[n_p - 1:n_p + n_a - 1]
        if lower is None:
            chosen = jnp.asarray(np.asarray(r["tokens"], np.int32))
        else:
            chosen = jnp.argmax(lower(tree, toks)[n_p - 1:n_p + n_a - 1],
                                axis=-1)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        gaps.extend(np.asarray(jnp.max(logits, axis=-1) - picked).tolist())
    return {"served_token_gap_max": max(gaps) if gaps else float("nan"),
            "served_token_gap_mean": float(np.mean(gaps)) if gaps
            else float("nan"), "tokens_compared": len(gaps)}


def check(cell, made: dict, finished: List[dict], sample: List[dict],
          leak: Optional[str]) -> dict:
    t = time.perf_counter()
    limits = cell.limits
    got = token_gaps(cell, made, sample, "f32")
    short = sum(len(r["tokens"]) != r["max_tokens"] for r in finished)
    rows = {
        "served_token_gap_max": (got["served_token_gap_max"],
                                 limits["served_token_gap_max"]),
        "served_token_gap_mean": (got["served_token_gap_mean"],
                                  limits["served_token_gap_mean"]),
        "answers_of_wrong_length": (float(short), 0.0),
        "page_accounting_faults": (0.0 if leak is None else 1.0, 0.0),
    }
    ok = bool(sample)
    for name, (value, limit) in rows.items():
        fine = bool(np.isfinite(value)) and value <= limit
        ok = ok and fine
        say(f"check {name}: {value:.6g} (limit {limit:g})"
            f"{'' if fine else '  <-- over'}")
    if leak:
        say(f"check: the engine's drain raised {leak}")
    say(f"check: reference (f32) over {len(sample)} of {len(finished)} "
        f"finished requests, {got['tokens_compared']} served tokens, took "
        f"{time.perf_counter() - t:.1f} s")
    return {"correct": ok, "rows": {k: v[0] for k, v in rows.items()},
            "limits": {k: v[1] for k, v in rows.items()}}


def control(cell, args, devs, started, watch) -> dict:
    """The control of ``correct``: a short window at the cell's own load
    finishes requests; then, at each position of the sampled prompts and
    served tokens, the token that the reference in the precision below the
    stated one (float8 operands) puts first is held to the same limits."""
    record = run(cell, args, devs, started, watch)
    rows = dict(record["check"]["rows"])
    got = token_gaps(cell, record["made"], record["sample"], "fp8")
    rows["served_token_gap_max"] = got["served_token_gap_max"]
    rows["served_token_gap_mean"] = got["served_token_gap_mean"]
    lim = cell.limits
    return {"correct": all(rows[k] <= lim[k] for k in lim), "rows": rows,
            "program_rows": record["check"]["rows"]}
