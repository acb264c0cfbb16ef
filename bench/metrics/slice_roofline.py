"""slice_roofline: the least time one slice could take on this
chip over the device time one slice took, in percent.

The least time is max(flops / peak FLOP/s, bytes / HBM bytes/s), with
the work counted by :func:`slice_work` from the plan's per-slice tree.
The device time per slice is the busy time of the traced window, summed
over chips, over the slices completed in it.  The result's ``run``
record names the bound that applies (``slice_roofline_bound``:
``compute`` or ``memory``).
"""

from bench.tree import node_sets

ITEMSIZE = 8  # complex64
FLOPS_PER_MAC = 8  # one complex multiply-add is 4 real multiplies, 4 adds


def slice_work(problem: dict, vmem_bytes: int) -> dict:
    """Operations and HBM bytes one slice needs at the least.

    Only the nodes whose subtree holds a sliced index change from slice
    to slice; the rest are computed once for all slices (hoisted) and
    count here only as inputs.  With the sliced indices fixed, a node
    over ``s_node`` does ``2**|s_node - S|`` complex multiply-adds; no
    implementation of this tree does fewer, since each is one term of
    the contraction's sums.  Bytes: every input of those nodes that is a
    leaf or a hoisted tensor is read once, and every intermediate they
    make that does not fit the chip's on-chip vector memory is written
    once and read once (one that fits may never leave the chip; the
    final result is written once).  A real program moves at least this
    much: it cannot read an input less than once, nor keep a tensor
    larger than on-chip memory off HBM.  With peak FLOP/s taken as the
    chip's bf16 peak, no implementation can beat the bound, so the share
    cannot pass 100%."""
    sets, steps = node_sets(problem)
    s = frozenset(problem["sliced"])
    nleaf = len(problem["leaves"])
    dep = {i: bool(sets[i] & s) for i in range(nleaf)}
    macs = 0.0
    nbytes = 0.0
    for left, right, node, u in steps:
        dep[node] = dep[left] or dep[right]
        if not dep[node]:
            continue
        macs += 2.0 ** len(u - s)
        for child in (left, right):
            size = 2.0 ** len(sets[child] - s) * ITEMSIZE
            if not dep[child] or child < nleaf:
                nbytes += size
            elif size > vmem_bytes:
                nbytes += size  # read back
        size = 2.0 ** len(sets[node] - s) * ITEMSIZE
        if node == problem["root"] or size > vmem_bytes:
            nbytes += size  # written
    return {"flops": FLOPS_PER_MAC * macs, "bytes": nbytes}


def read(ctx):
    tr = ctx["trace"]
    n = ctx.get("slices_traced", 0)
    if not tr or n <= 0 or sum(tr["busy_s"]) <= 0:
        return None
    peaks = ctx["peaks"]
    work = slice_work(ctx["problem"], peaks["vmem_bytes"])
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    per_slice = sum(tr["busy_s"]) / n
    ctx.setdefault("notes", {})["slice_roofline_bound"] = (
        "compute" if t_flops >= t_bytes else "memory"
    )
    return 100.0 * max(t_flops, t_bytes) / per_slice
