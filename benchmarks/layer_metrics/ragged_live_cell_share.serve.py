"""Share of the serving attention kernel's grid steps whose page holds a
token of the step's sequence (the kernel's own test; the others fetch
nothing and skip their body, and are still steps):
``ServingMetrics.attn_live_cells / attn_grid_cells`` over the window.
``None`` where the program has no such counters, or ran no kernel."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("attn_grid_cells") \
            or "attn_live_cells" not in c:
        return None
    return 100.0 * c["attn_live_cells"] / c["attn_grid_cells"]
