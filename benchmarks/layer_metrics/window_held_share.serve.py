"""What a window layer still holds of its sequences against what a full
layer holds of them: ``window_kv_tokens_held / full_kv_tokens_held`` from
``ServingMetrics`` over the window, both summed over the ticks' slots, a
layer of the kind (one window kind: the count is summed over the window
kinds).  100% means nothing fell out of a window; a window of 512 under
contexts of some thousands reads a few tens.  ``None`` where the program
counts no such state (a model without window layers)."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("full_kv_tokens_held"):
        return None
    return 100.0 * c["window_kv_tokens_held"] / c["full_kv_tokens_held"]
