"""How uneven the held experts' load is: a step's fullest held expert over
the mean held expert, from the program's counters over the window
(``moe_max_expert_rows``: each step's fullest held expert, summed;
``moe_rows_held_total`` over the experts held: the mean one, summed), the
ratio of the two sums per expert layer, averaged over the layers.  The
registry gives a window's sums, so this is the steps' mean weighted by
load, not their median.  1 is an even load."""


def read(run):
    if run["kind"] != "train":
        return None
    c = run["counters"]
    held = run["cell"].config["train"]["experts_held"][1]
    name = "moe_max_expert_rows"
    ratios = []
    for key, fullest in c.items():
        if not key.startswith(name + "{"):
            continue
        rows = c.get("moe_rows_held_total" + key[len(name):], 0.0)
        if rows:
            ratios.append(fullest / (rows / held))
    return sum(ratios) / len(ratios) if ratios else None
