"""Share of its roofline that the serving attention kernel reaches in the
window layers: as ``ragged_full_roofline.serve`` (whose ``share`` this
calls), for the Pallas kernels named ``ragged_paged_attention`` that ran
under the scope ``attn.window``, against the tokens inside their rows'
windows only (the program's counter ``window_kv_tokens_live``: a slot's
``min(cached, window + rows - 1)``) at the window layers' query-head
count.  ``None`` where the program counts no such state, or ran no such
kernel under that scope."""


def read(run):
    return run["cell"].layer_metric("ragged_full_roofline.serve").share(
        run, "window", "window_kv_tokens_live")
