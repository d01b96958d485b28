"""Share of the (token, choice) pairs that landed on the experts held
here, over the window: the change of the program's counters
``moe_rows_held_total`` over ``moe_rows_total`` (``layer.moe_dropless``
publishes both per expert layer and step; ``trainer.SGD`` adds them to
``paddle_tpu.obs.default_registry()`` where it reads the costs), summed
over the expert layers.  With 8 of 64 experts held and an even router it
reads 12.5%.  Each layer's own share goes to the log."""

from harness.measure import say


def series(counters: dict, name: str) -> dict:
    """{label text: change over the window} of one labelled counter."""
    return {k[len(name):]: v for k, v in counters.items()
            if k.startswith(name + "{")}


def read(run):
    if run["kind"] != "train":
        return None
    total = sum(series(run["counters"], "moe_rows_total").values())
    if not total:
        return None
    rows = series(run["counters"], "moe_rows_total")
    held = series(run["counters"], "moe_rows_held_total")
    say("moe_held_share.train by layer: " + ", ".join(
        f"{k} {100.0 * held.get(k, 0.0) / v:.2f}%"
        for k, v in sorted(rows.items()) if v))
    return 100.0 * sum(held.values()) / total
