"""Peak device memory in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(run):
    if run["kind"] != "train" or not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / 1e9
