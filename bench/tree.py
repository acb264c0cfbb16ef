"""Index sets of a contraction tree given as plain data.

``problem`` is what :meth:`bench.system.Job.problem` returns: the leaf
label tuples, the tree's pairs ``(left, right, node)`` in execution
order, the root, the sliced wires and the output wires.  Every index is
binary (a qubit wire), so a tensor over a set ``s`` of indices has
``2**len(s)`` elements.
"""

from __future__ import annotations


def node_sets(problem: dict):
    """``(sets, steps)``: ``sets[v]`` is the index set of the tensor
    node ``v`` produces (leaves included), and ``steps`` lists, per pair,
    ``(left, right, node, s_node)`` with ``s_node`` the union of both
    operands' indices (the paper's Eq. 3 term)."""
    open_set = frozenset(problem["out"])
    sets = {i: frozenset(t) for i, t in enumerate(problem["leaves"])}
    steps = []
    for left, right, node in problem["pairs"]:
        a, b = sets[left], sets[right]
        sets[node] = (a ^ b) | (a & b & open_set)
        steps.append((left, right, node, a | b))
    return sets, steps
