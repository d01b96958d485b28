"""Grid steps one call of the serving attention kernel dispatches on one
chip (``row blocks x KV-head groups x max_pages_per_seq``), over the
window's calls: ``ServingMetrics.attn_grid_cells / attn_kernel_calls``.
A step costs its fixed part whether its page is live or not.  ``None``
where the program has no such counters, or ran no kernel."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("attn_kernel_calls") \
            or "attn_grid_cells" not in c:
        return None
    return c["attn_grid_cells"] / c["attn_kernel_calls"]
