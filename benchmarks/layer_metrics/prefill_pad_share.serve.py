"""Share of the prefill rows the window's steps shipped that carried no
token: padding to whole kernel blocks and to the prefill bucket
(``prefill_pad_rows``) over padding plus the tokens forwarded
(``prefill_tokens``), from ``ServingMetrics``."""


def read(run):
    if run["kind"] != "serve":
        return None
    pad = run["counters"].get("prefill_pad_rows", 0)
    shipped = pad + run["counters"].get("prefill_tokens", 0)
    return 100.0 * pad / shipped if shipped else None
