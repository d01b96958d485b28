"""The closed loop of ``chat_closed.py`` with every prompt's length rounded
up to a multiple of ``multiple_of``: traffic for a model that generates by
diffusion over blocks, whose served-token check needs a prompt to end on a
block boundary (``families/sdar_moe.py`` says why).  Everything else, the
answers' lengths among it, is ``chat_closed``'s.

Traffic keys: ``chat_closed``'s, and ``multiple_of``.
"""

from __future__ import annotations

import os

from harness import cells

BASE = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "chat_closed.py"))


class Requests(BASE.Requests):
    def __init__(self, traffic: dict, config: dict, seed: int):
        super().__init__(traffic, config, seed)
        m = int(traffic["multiple_of"])
        if int(traffic["prompt"]["max"]) % m:
            raise ValueError("the longest prompt is no multiple of "
                             f"multiple_of ({m}): rounding would pass it")
        self.pool[:, 0] = -(-self.pool[:, 0] // m) * m


def make(traffic: dict, config: dict, seed: int) -> Requests:
    return Requests(traffic, config, seed)
