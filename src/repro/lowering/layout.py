"""Lane-dense storage for rank-k tensors between contraction steps.

A TPU lays an array out in (8, 128) tiles over its two minor dimensions.
A tensor network intermediate held as one axis per index — shape
``(2, 2, …, 2)`` — therefore pads its minor axis from 2 to 128 lanes:
64× the bytes the lifetime planner certifies.  So the executor never
keeps a large intermediate in that form.  Every buffer in its
environment is *flat*, with a static storage order of its indices
(major → minor), and each contraction step is built from XLA ops whose
buffers all have a minor dimension of at least :data:`LANE` elements:

  * :func:`transpose_orders` turns one index permutation into a short
    sequence of transposes.  Each one groups runs of indices that stay
    adjacent, and each has a lane-dense minor group on both sides (a
    "major" permutation that keeps the minor block fixed, or a swap of
    the two minor blocks);
  * :class:`DenseStep` fixes, per contraction, the operand orders and
    the orientation of the 3-D ``dot_general`` so that each operand's
    and the output's minor dimension is its larger free group.  The
    output is stored in the order the GEMM produces it, which costs no
    transpose at all;
  * :func:`store_layout` chooses that order for the step that consumes
    the output: the indices the consumer contracts may be gathered into
    one block where the output's M and N groups meet, so the
    consumer's permute moves a block.  A step keeps such an order where
    it lowers the route bytes of its own permutes plus its consumer's,
    and the plan keeps these orders only if its total route bytes come
    out lower than with every step choosing alone.

A general permutation needs three minor blocks' worth of indices to
route densely, so a tensor below ``LANE**3`` elements routes with a
narrower block of about its cube root: its transposes then pad by at
most ``LANE / block`` — at most 8× on a 2^14-element tensor, nothing
from 2^21 up.  Small tensors (fewer than :data:`SMALL_ELEMS` elements)
take the direct transpose.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Hashable, Sequence

import jax
import jax.numpy as jnp

# minor-dimension width of a TPU tile: a buffer whose minor axis is a
# multiple of this wastes no lanes
LANE = 128
# tensors below this many elements are transposed directly
SMALL_ELEMS = 1 << 10


def _runs(src: Sequence, dst: Sequence) -> list[list]:
    """Maximal runs of ``src`` axes that stay adjacent, in the same
    order, in ``dst`` — the dims of the grouped transpose."""
    pos = {ax: i for i, ax in enumerate(dst)}
    runs: list[list] = []
    for ax in src:
        if runs and pos[ax] == pos[runs[-1][-1]] + 1:
            runs[-1].append(ax)
        else:
            runs.append([ax])
    return runs


def _lane(order, size_of) -> int:
    """Minor-group width the route for a tensor of this size aims at:
    LANE, or about the cube root of the tensor for smaller ones."""
    total = math.prod(size_of(a) for a in order)
    return min(LANE, 1 << (total.bit_length() - 1) // 3)


def _dense_move(src, dst, size_of, lane: int = LANE) -> bool:
    """Whether ``src → dst`` is one transpose whose input and output
    both keep a minor group of at least ``lane`` elements."""
    runs = _runs(src, dst)
    first_of = {r[0]: r for r in runs}
    out_last = None
    for ax in dst:
        if ax in first_of:
            out_last = first_of[ax]
    return (
        math.prod(size_of(a) for a in runs[-1]) >= lane
        and math.prod(size_of(a) for a in out_last) >= lane
    )


def _minor_block(order, size_of, lane: int) -> list:
    """Shortest suffix of ``order`` holding at least ``lane`` elements."""
    block: list = []
    n = 1
    for ax in reversed(order):
        if n >= lane:
            break
        block.insert(0, ax)
        n *= size_of(ax)
    return block


def transpose_orders(
    src: Sequence[Hashable],
    dst: Sequence[Hashable],
    size_of: Callable[[Hashable], int],
) -> list[tuple]:
    """Storage orders to pass through on the way from ``src`` to
    ``dst`` (ending with ``dst``; empty when they are equal).

    Each consecutive pair is one lane-dense transpose.  The route parks
    a block ``Q`` that is disjoint from ``dst``'s minor block ``T`` at
    the minor end, brings ``T`` right above it, swaps the two, and
    finishes with a permutation that keeps ``T`` fixed.  Steps that a
    single dense move can skip are skipped.  Where no dense route
    exists (small or narrow tensors) the direct transpose is taken."""
    src, dst = tuple(src), tuple(dst)
    if src == dst:
        return []
    lane = _lane(src, size_of)
    if (
        math.prod(size_of(a) for a in src) < SMALL_ELEMS
        or _dense_move(src, dst, size_of, lane)
    ):
        return [dst]
    T = _minor_block(dst, size_of, lane)
    L0 = _minor_block(src, size_of, lane)
    route: list[tuple] = []
    if set(L0) & set(T):
        upper = [a for a in src if a not in L0]
        pool = [a for a in upper if a not in T]
        if math.prod(size_of(a) for a in pool) < lane:
            return [dst]
        Q = _minor_block(pool, size_of, lane)
        rest = [a for a in upper if a not in Q]
        route += [tuple(rest + Q + L0), tuple(rest + L0 + Q)]
        cur = route[-1]
    else:
        Q, cur = L0, src
    others = [a for a in cur if a not in Q and a not in T]
    if math.prod(size_of(a) for a in others + T) < lane:
        return [dst]
    route += [tuple(others + T + Q), tuple(others + Q + T), dst]
    # shortcut: from each order jump to the furthest one a single dense
    # move reaches (the route's own neighbours always qualify)
    out: list[tuple] = []
    at = src
    i = 0
    while at != dst:
        j = len(route) - 1
        while j > i and not _dense_move(at, route[j], size_of, lane):
            j -= 1
        if route[j] != at:
            out.append(route[j])
        at = route[j]
        i = j + 1
    return out


def permute_flat(x, src, dst, size_of) -> jax.Array:
    """Reorder flat ``x`` (stored in ``src`` order) into ``dst`` order,
    through the lane-dense route of :func:`transpose_orders`."""
    cur = tuple(src)
    for nxt in transpose_orders(src, dst, size_of):
        runs = _runs(cur, nxt)
        shape = tuple(math.prod(size_of(a) for a in r) for r in runs)
        first = {r[0]: i for i, r in enumerate(runs)}
        perm = tuple(first[a] for a in nxt if a in first)
        x = jnp.transpose(x.reshape(shape), perm).reshape(-1)
        cur = nxt
    return x


@dataclasses.dataclass(frozen=True)
class DenseStep:
    """Static layout of one contraction step on flat operands.

    ``a_order``/``b_order`` are the operands' storage orders;
    ``a_gemm``/``b_gemm`` the orders they are permuted into, whose
    grouped 3-D shapes are ``a_shape``/``b_shape`` (batch first).
    ``dims`` are the ``dot_general`` dimension numbers, ``swap`` whether
    ``b`` is the left-hand operand (so the output's minor group is
    ``a``'s free group M), and ``out_order`` the storage order of the
    result."""

    a_order: tuple
    b_order: tuple
    a_gemm: tuple
    b_gemm: tuple
    a_shape: tuple[int, int, int]
    b_shape: tuple[int, int, int]
    dims: tuple
    swap: bool
    out_order: tuple
    sizes: tuple  # (index, size) pairs of every index the step touches

    @functools.cached_property
    def _size(self) -> dict:
        return dict(self.sizes)

    def size_of(self, ix) -> int:
        return self._size[ix]


def dense_step(
    a_order: Sequence[Hashable],
    b_order: Sequence[Hashable],
    out_inds: Sequence[Hashable],
    size_of: Callable[[Hashable], int],
    *,
    canonical: bool = False,
    consumer_k: Sequence[Hashable] | None = None,
) -> DenseStep:
    """Choose the operand orders and GEMM orientation of one step.

    Index roles follow :func:`repro.lowering.gemm_form.lower_step`
    (batch / M / N / K, with ``out_inds`` deciding what is kept).  Inside
    each role the order is the one its operand already stores, so the
    permutation moves as few indices as it can; the K order follows the
    larger operand.  Each operand's minor dimension is its larger free
    group, and the output's is the larger of M and N.  ``canonical``
    pins the plain ``(B, M, K) @ (B, K, N) → (B, M, N)`` orientation —
    the 2-D Pallas kernel's form, which the refiner only picks when
    every dimension is MXU-sized.

    ``consumer_k`` names the indices the step that consumes the output
    contracts.  Those of M and N then move to where the output's two
    free groups meet, in ``consumer_k``'s order, so they are one block
    of the stored output; the other indices keep their order."""
    a_order, b_order = tuple(a_order), tuple(b_order)
    sa, sb, so = set(a_order), set(b_order), set(out_inds)
    batch = tuple(ix for ix in a_order if ix in sb and ix in so)
    m = tuple(ix for ix in a_order if ix not in sb)
    n = tuple(ix for ix in b_order if ix not in sa)
    ka = tuple(ix for ix in a_order if ix in sb and ix not in so)
    kb = tuple(ix for ix in b_order if ix in sa and ix not in so)
    size_a = math.prod(size_of(i) for i in a_order)
    size_b = math.prod(size_of(i) for i in b_order)
    k = ka if size_a >= size_b else kb
    B = math.prod(size_of(i) for i in batch)
    M = math.prod(size_of(i) for i in m)
    N = math.prod(size_of(i) for i in n)
    K = math.prod(size_of(i) for i in k)
    if canonical:
        a_km, b_nk, swap = False, False, False
    else:
        a_km = M > K  # a stored (B, K, M): M is its minor group
        b_nk = K > N  # b stored (B, N, K): K is its minor group
        swap = N > M  # output (B, N, M): M is its minor group
    if consumer_k is not None:
        rank = {ix: i for i, ix in enumerate(consumer_k)}

        def hinted(group):
            return tuple(sorted((ix for ix in group if ix in rank),
                                key=rank.__getitem__))

        def rest(group):
            return tuple(ix for ix in group if ix not in rank)

        if swap:  # output batch + n + m
            n, m = rest(n) + hinted(n), hinted(m) + rest(m)
        else:  # output batch + m + n
            m, n = rest(m) + hinted(m), hinted(n) + rest(n)
    a_gemm = batch + (k + m if a_km else m + k)
    b_gemm = batch + (n + k if b_nk else k + n)
    a_shape = (B, K, M) if a_km else (B, M, K)
    b_shape = (B, N, K) if b_nk else (B, K, N)
    ca = 1 if a_km else 2
    cb = 2 if b_nk else 1
    if swap:
        dims = (((cb,), (ca,)), ((0,), (0,)))
        out_order = batch + n + m
    else:
        dims = (((ca,), (cb,)), ((0,), (0,)))
        out_order = batch + m + n
    sizes = tuple((ix, size_of(ix)) for ix in dict.fromkeys(a_order + b_order))
    return DenseStep(
        a_order=a_order, b_order=b_order, a_gemm=a_gemm, b_gemm=b_gemm,
        a_shape=a_shape, b_shape=b_shape, dims=dims, swap=swap,
        out_order=out_order, sizes=sizes,
    )


def route_bytes(src, dst, size_of, itemsize: int) -> int:
    """HBM bytes of the route from ``src`` to ``dst`` order: each
    transpose of :func:`transpose_orders` reads and writes the whole
    tensor."""
    n = math.prod(size_of(a) for a in src)
    return 2 * n * itemsize * len(transpose_orders(src, dst, size_of))


def step_route_bytes(ds: DenseStep, itemsize: int) -> int:
    """Route bytes of one step's two operand permutes."""
    return (
        route_bytes(ds.a_order, ds.a_gemm, ds.size_of, itemsize)
        + route_bytes(ds.b_order, ds.b_gemm, ds.size_of, itemsize)
    )


@dataclasses.dataclass(frozen=True)
class StoreLayout:
    """Every step's :class:`DenseStep` and every node's storage order,
    with the modeled route bytes of one pass through the tree (every
    step's operand permutes and the root's permute into the output
    order) for the chosen orders and for the greedy ones, and how many
    steps store their output in the order their consumer asked for."""

    dense_steps: tuple[DenseStep, ...]
    store_order: dict
    permute_bytes: int
    permute_bytes_greedy: int
    lookahead_orders: int


def _layout(steps, tree_order, out_inds, size_of, itemsize, lookahead):
    """Storage orders step by step.  With ``lookahead`` each output is
    also tried with its consumer's contracted indices as one block
    (:func:`dense_step`'s ``consumer_k``), and kept so where that lowers
    the route bytes of the step's permutes plus its consumer's (an
    operand the consumer takes from a later step is costed in its tree
    order)."""
    made = {v for _, _, v, _ in steps}
    order = {v: tuple(o) for v, o in tree_order.items() if v not in made}
    consumer = {}
    for k, (l, r, _, _) in enumerate(steps):
        consumer[l] = consumer[r] = k
    dense, hinted = [], 0
    for l, r, v, canon in steps:
        ds = dense_step(order[l], order[r], tree_order[v], size_of,
                        canonical=canon)
        c = consumer.get(v)
        if lookahead and c is not None:
            cl, cr, cv, c_canon = steps[c]
            w = cr if cl == v else cl
            kc = (set(tree_order[cl]) & set(tree_order[cr])) - set(
                tree_order[cv]
            )

            def cost(d):
                at = {v: d.out_order, w: order.get(w, tree_order[w])}
                cds = dense_step(at[cl], at[cr], tree_order[cv], size_of,
                                 canonical=c_canon)
                return step_route_bytes(d, itemsize) + step_route_bytes(
                    cds, itemsize
                )

            # the K order the consumer takes from its other operand, and
            # the order this step's operands hold the indices in
            hints = (
                tuple(ix for ix in order.get(w, tree_order[w]) if ix in kc),
                tuple(ix for ix in dict.fromkeys(order[l] + order[r])
                      if ix in kc),
            )
            base, best = ds, cost(ds)
            for h in hints:
                d = dense_step(order[l], order[r], tree_order[v], size_of,
                               canonical=canon, consumer_k=h)
                if d.out_order != ds.out_order and (dc := cost(d)) < best:
                    ds, best = d, dc
            hinted += ds is not base
        order[v] = ds.out_order
        dense.append(ds)
    total = sum(step_route_bytes(d, itemsize) for d in dense)
    if steps:
        total += route_bytes(order[steps[-1][2]], out_inds, size_of,
                             itemsize)
    return tuple(dense), order, total, hinted


def store_layout(
    steps: Sequence[tuple[Hashable, Hashable, Hashable, bool]],
    tree_order: dict,
    out_inds: Sequence[Hashable],
    size_of: Callable[[Hashable], int],
    itemsize: int,
) -> StoreLayout:
    """Storage orders of a whole plan.

    ``steps`` are ``(lhs, rhs, out, canonical)`` node triples in
    execution order (``canonical`` as in :func:`dense_step`);
    ``tree_order`` maps every node to its indices (leaves in the order
    they are stored); ``out_inds`` is the root's final order.  Each step
    writes its output for the step that consumes it (one step of
    look-ahead, see :func:`_layout`).  Choices cascade, since a step's M
    and N orders are read from its operands, so the look-ahead orders
    are kept only where the whole plan's route bytes come out lower than
    with each step choosing for itself; else the greedy orders stand."""
    greedy = _layout(steps, tree_order, out_inds, size_of, itemsize, False)
    ahead = _layout(steps, tree_order, out_inds, size_of, itemsize, True)
    dense, order, total, hinted = ahead if ahead[2] < greedy[2] else greedy
    return StoreLayout(
        dense_steps=dense, store_order=order, permute_bytes=total,
        permute_bytes_greedy=greedy[2], lookahead_orders=hinted,
    )
