"""The one traffic generator: a mix file (perfbench/workloads/<cell>.json)
and a seed give the order of actions and which results are kept for the
comparison. Every seed sends the same multiset of actions in every block,
in another order, so no seed changes the work."""

import random


def schedule(mix: dict, seed: int):
    """Yield action names for ever. Each block holds every action `weight`
    times; the seed shuffles the block."""
    block = [a["name"] for a in mix["actions"] for _ in range(int(a["weight"]))]
    if not block:
        raise ValueError("a traffic mix names at least one action")
    rng = random.Random(seed)
    while True:
        order = list(block)
        rng.shuffle(order)
        yield from order


class Sample:
    """The results that are compared once the window has closed: a reservoir
    of at most `size` drawn from the seed, and the last one. What falls out
    of the reservoir is dropped inside the window, as a client drops a
    result it has used."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = random.Random(seed ^ 0x5EED)
        self.kept = []   # (index, action name, result)
        self.last = None
        self.seen = 0

    def offer(self, index: int, name: str, result) -> None:
        self.last = (index, name, result)
        if len(self.kept) < self.size:
            self.kept.append(self.last)
        else:
            slot = self.rng.randrange(self.seen + 1)
            if slot < self.size:
                self.kept[slot] = self.last
        self.seen += 1

    def items(self) -> list:
        by_index = {t[0]: t for t in self.kept}
        if self.last is not None:
            by_index[self.last[0]] = self.last
        return [by_index[i] for i in sorted(by_index)]
