"""slicing_overhead: the paper's Eq. 4, O(B, S) = C(B, S) / C(B).

C(B) = sum over the tree's nodes of 2**|s_node| (Eq. 3), and the sliced
cost C(B, S) = sum of 2**(|s_node| + |S| - |S & s_node|) (Eq. 6): the
work of all 2**|S| slices, each running the whole tree.  Computed here
from the tree's index sets, not read from the program."""

from bench.tree import node_sets


def overhead(problem: dict) -> float:
    _, steps = node_sets(problem)
    s = frozenset(problem["sliced"])
    dense = sum(2.0 ** len(u) for *_, u in steps)
    sliced = sum(2.0 ** (len(u) + len(s) - len(s & u)) for *_, u in steps)
    return sliced / dense


def read(ctx):
    return overhead(ctx["problem"])
