"""Share of its roofline that the gated delta rule reaches: the least time
of the chunked scan's products and bytes of a delta-rule layer and step,
forward and backward, nothing recomputed (``kernels/gated_delta_rule.py``),
times the delta-rule layers, over the device time a step of the
operations of the scope ``gdn.scan`` (``ops/gated_delta.py
gated_delta_rule``: from the normalised q, k and v to o) or of Pallas
kernels named ``gdn_*``.  The trace names an operation by its HLO text,
which has no scope; ``harness/op_scopes.py`` reads each instruction's
``op_name`` from the HLO the same trace file keeps, and takes the union of
the matching intervals.  ``train.remat`` makes the backward pass run the
forward again: time the scan takes and the roofline does not count."""

from harness import cells, op_scopes, trace as T
from harness.measure import say


def read(run):
    if run["peaks"] is None or run["kind"] != "train":
        return None
    tr = run.get("trace")
    if tr is None or not tr.chips:
        return None
    steps = len(T.spans_named(tr, "dispatch"))
    seconds = op_scopes.seconds_under(
        tr, op_scopes.op_names(run["tracing"].file()),
        op_scopes.under("gdn.scan"), kernels="gdn_")
    if not steps or not seconds:
        return None
    cfg = run["cell"].config
    layers = run["layers_run"] - run["layers_run"] \
        // cfg["full_attention_interval"]
    scan = cells.kernel("gated_delta_rule")
    per_layer = [scan.least_seconds(
        lay, cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
        run["peaks"]) for lay in run["layouts"]]
    least_step = layers * sum(p["seconds"] for p in per_layer) \
        / len(per_layer)
    say(f"gdn_scan_roofline.train: {1e3 * seconds / steps:.3f} ms a step "
        f"under gdn.scan over {steps} steps against a least "
        f"{1e3 * least_step:.3f} ms for {layers} layers; bound "
        f"{per_layer[0]['bound']}")
    return 100.0 * least_step * steps / seconds
