"""Share of its roofline that the state-space recurrence reaches: the
least time a layer's scan of an average tick could take
(``kernels/ssd_scan.py``: the states of the slots that have a row read
and written once, the rows' ``xs``, ``B``, ``C``, ``dt`` and ``y``, over
the chip's bandwidth, or the chunked products of the chunks' rows over
its peak, whichever is larger; the rows, and the chunks that began or
continued a sequence, from the program's counters ``ssm_rows_decode``,
``ssm_rows_prefill``, ``ssm_segments_started`` and
``ssm_segments_continued``, which sum over the layers), times the layers
and the traced window's ticks, over the device time of the operations
under the scope ``ssm.scan`` or of Pallas kernels named ``ssd_*`` (the
union of their intervals: a ``while`` and its body overlap), on one chip.
The program moves every slot's state whether it has a row or not and
computes the products in float32: both show as a share under 100.
``None`` where the program counts no such rows or ran nothing under that
scope."""

from harness import cells, program_ops as P, trace as T
from harness.measure import say

COUNTERS = ("ssm_rows_decode", "ssm_rows_prefill", "ssm_segments_started",
            "ssm_segments_continued")
SIZES = {"heads": "mamba_n_heads", "lanes": "mamba_d_head",
         "state": "mamba_d_state", "groups": "mamba_n_groups",
         "chunk": "mamba_chunk_size"}


def read(run):
    if run["peaks"] is None or run["kind"] != "serve":
        return None
    tr, c, cfg = run.get("trace"), run["counters"], run["cell"].config
    if tr is None or not tr.chips or not c.get("step_dispatches") \
            or any(k not in c for k in COUNTERS) \
            or any(k not in cfg for k in SIZES.values()):
        return None
    ticks = len(T.spans_named(tr, "engine_step"))
    names = P.programs(run["tracing"].file())
    ops = P.ops_under(tr, names, "ssm.scan") + [
        o for o in P.ops_under(tr, names, "ssm", T.is_kernel)
        if "ssd_" in o.name]
    seconds = P.union_seconds(tr, ops)
    if not ticks or not seconds:
        return None
    layers = run["layers_run"]
    per_tick = [c[k] / layers / c["step_dispatches"] for k in COUNTERS]
    least = cells.kernel("ssd_scan").least_seconds(
        *per_tick, run["peaks"], **{k: cfg[v] for k, v in SIZES.items()})
    say(f"ssm_scan_roofline.serve: {1e3 * seconds / ticks:.3f} ms a tick "
        f"under ssm.scan over {ticks} ticks against a least "
        f"{1e3 * layers * least['seconds']:.3f} ms for {layers} layers at "
        f"{per_tick[0]:.1f} decode and {per_tick[1]:.1f} chunk rows a "
        f"tick; bound {least['bound']}")
    return 100.0 * layers * least["seconds"] * ticks / seconds
