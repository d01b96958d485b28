"""``layer_metrics/moe_ms_per_step.train.py`` on a made-up run with a
made-up trace file (the helpers are ``test_qwen3next.py``'s): the three
scopes of the expert layer, a ``conditional`` with the operations of the
branch it took counted once, a program without the scopes."""

import pytest

from harness import trace as T

from test_qwen3next import cell_of, made_up_run, op, trace_file

MOE = "jit(step)/jvp(remat_blk0_moe)/moe_dropless/blk0_moe/"
BACK = ("jit(step)/transpose(jvp(remat_blk0_moe))/jvp(remat_blk0_moe)/"
        "checkpoint/moe_dropless/blk0_moe/")
STEP = {"fusion.1": MOE + "moe.route/top_k",
        "cond.2": MOE + "moe.experts/cond",
        "fusion.3": MOE + "moe.experts/cond/branch_0_fun/jit(_take)/gather",
        "cond.4": BACK + "moe.experts/cond",
        "moe_gmm.5": BACK + "moe.experts/cond/branch_0_fun/jvp(moe_gmm)/"
                            "pallas_call",
        "fusion.6": MOE + "moe.shared/dot_general",
        "fusion.7": "jit(step)/jvp(remat_blk0_mix)/blk0_gdn/gdn/gdn.proj/dot"}


def test_the_expert_layers_time_a_step_is_the_union_under_its_scopes(
        tmp_path, capsys):
    run = made_up_run(tmp_path)
    ops = [op("fusion.1", 0.0, 0.5),                    # moe.route
           op("cond.2", 1.0, 1.0),                      # moe.experts, 1..2
           op("fusion.3", 1.25, 0.5),                   # its branch, inside
           op("cond.4", 3.0, 2.0),                      # the backward's
           op("moe_gmm.5", 3.5, 1.0, kernel=True),      # a kernel inside
           op("fusion.6", 6.0, 0.25),                   # moe.shared
           op("fusion.7", 7.0, 1.0)]                    # another layer's
    run["trace"] = T.Trace([T.Chip(0, ops, [])],
                           [("dispatch", 0.1, 0.2), ("dispatch", 4.0, 4.1)],
                           (0.0, 10.0))
    path = trace_file(tmp_path, [STEP], name="moe")
    run["tracing"].file = lambda: path
    read = cell_of().layer_metric("moe_ms_per_step.train").read
    # 0.5 + 1.0 + 2.0 + 0.25 s over two dispatched steps
    assert read(run) == pytest.approx(1e3 * 3.75 / 2)
    assert ("moe.route 250.00 ms, moe.experts 1500.00 ms, moe.shared "
            "125.00 ms") in "".join(capsys.readouterr())
    # no operation under the scopes, no trace, a serving run: nothing
    other = trace_file(tmp_path, [{"fusion.7": STEP["fusion.7"]}], name="no")
    run["tracing"].file = lambda: other
    assert read(run) is None
    assert read(dict(run, trace=None)) is None
    assert read(dict(run, kind="serve")) is None
