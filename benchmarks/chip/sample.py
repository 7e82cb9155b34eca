"""Which of the window's calls the correctness check compares.

The count of calls is known only when the window closes, so the sample
is a reservoir (Algorithm R) drawn from the run's seed: every call has
the same chance to be in it.  The last call is always compared too.
"""

from __future__ import annotations

import random


class Reservoir:
    def __init__(self, size: int, rng: random.Random):
        self.size = size
        self.rng = rng
        self.items: dict[int, object] = {}   # call index -> kept item
        self.seen = 0
        self.last = None

    def offer(self, index: int, item) -> None:
        """Consider call ``index`` and what to keep of it (references to
        its device arrays: keeping them copies nothing)."""
        self.seen += 1
        self.last = (index, item)
        if len(self.items) < self.size:
            self.items[index] = item
            return
        j = self.rng.randrange(self.seen)
        if j < self.size:
            del self.items[sorted(self.items)[j]]
            self.items[index] = item

    def chosen(self) -> dict:
        """Kept calls, the last one included, by call index."""
        out = dict(self.items)
        if self.last is not None:
            out[self.last[0]] = self.last[1]
        return out
