"""What the slots' recurrent states take of the layer state that live
sequences hold: ``state_bytes_live / (state_bytes_live + live K/V
bytes)`` from ``ServingMetrics`` over the window, both summed over the
ticks: the states of the slots that hold a sequence (constant a slot
whatever its length, over the layers that keep one) against the K/V of
the tokens the attention branches hold of the step's sequences
(``full_kv_tokens_held`` a layer, times the layers run, at the pool's
bytes a token: K and V, the published KV heads and head size, float32).
``None`` where the program counts no such state (a model without a
recurrent state)."""

KEYS = ("num_key_value_heads", "head_dim")


def read(run):
    c, cfg = run["counters"], run["cell"].config
    if run["kind"] != "serve" or not c.get("state_bytes_live") \
            or any(k not in cfg for k in KEYS):
        return None
    token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 4
    kv = c.get("full_kv_tokens_held", 0) * run["layers_run"] * token
    return 100.0 * c["state_bytes_live"] / (c["state_bytes_live"] + kv)
