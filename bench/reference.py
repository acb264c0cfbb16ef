"""Plain reference contraction that decides ``correct``.

It computes, for one slice id, the amplitude (or the open-qubit batch of
amplitudes) of the circuit's network with the sliced wires fixed to the
id's bits: bit ``i`` of the id is the value of the ``i``-th sliced wire,
in the order the program reports them.  The sum over a batch of ids is
then what the program's timed entry must return for that batch.

What it takes from the program is the question and the order of work,
never a value: the label tuples of the program's leaves (to group the
circuit's gates the same way), the pairs of its contraction tree, and
the sliced wires.  The tensors themselves come from
:func:`bench.circuits.network`, built from the recipe's own gate
matrices.  Each leaf group is contracted on the host in complex128 and
the sliced wires are fixed there; the tree runs on the device in
complex64, one jitted program per pair (a whole tree in one program
takes the TPU compiler many minutes), one ``dot_general`` per pair at
``HIGHEST`` precision.  The lower-precision control (``mode="bf16x3"``)
is the same contraction with every product computed as XLA's ``HIGH``
precision computes it on a TPU: each float32 operand split into a
bfloat16 high and low part, and the three products hi·hi, hi·lo and
lo·hi, each exact in float32, summed.  It is written out here so that
it reads the same on any backend.

A TPU tiles an array's two minor dimensions by (8, 128), so a tensor
held as ``(2, 2, …, 2)`` would pad its minor axis 64-fold.  Every tensor
here is flat with a static label order, and each permutation is a few
transposes whose minor dimension keeps at least ``2**w`` elements
(:func:`route`).
"""

from __future__ import annotations

import string

import jax
import jax.numpy as jnp
import numpy as np

LANE_BITS = 7  # 2**7 = 128 lanes


# ----------------------------------------------------------------------
# leaves: the circuit's tensors grouped as the program's leaves
# ----------------------------------------------------------------------
def group_leaves(labels, arrays, leaf_labels) -> list[np.ndarray]:
    """Contract the circuit's tensors into one tensor per program leaf.

    A wire that no leaf carries was absorbed inside a leaf; the tensors
    joined by such wires form one group, whose remaining wires must be
    exactly one leaf's.  Returns arrays with one axis per label, in the
    order of ``leaf_labels[i]``."""
    kept = {ix for t in leaf_labels for ix in t}
    parent = list(range(len(labels)))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    owner: dict[str, int] = {}
    for i, t in enumerate(labels):
        for ix in t:
            if ix in kept:
                continue
            if ix in owner:
                parent[find(i)] = find(owner[ix])
            else:
                owner[ix] = i
    groups: dict[int, list[int]] = {}
    for i in range(len(labels)):
        groups.setdefault(find(i), []).append(i)
    by_set: dict[frozenset, list[int]] = {}
    for members in groups.values():
        free: dict[str, int] = {}
        for i in members:
            for ix in labels[i]:
                free[ix] = free.get(ix, 0) + 1
        key = frozenset(ix for ix, c in free.items() if ix in kept)
        if key in by_set:
            raise ValueError(f"two groups carry the wires {sorted(key)}")
        by_set[key] = members
    out = []
    for t in leaf_labels:
        members = by_set.pop(frozenset(t), None)
        if members is None:
            raise ValueError(f"no group of the circuit carries {t}")
        out.append(_einsum([labels[i] for i in members],
                           [arrays[i] for i in members], tuple(t)))
    if by_set:
        raise ValueError(f"{len(by_set)} groups match no program leaf")
    return out


def _einsum(ins, arrs, out) -> np.ndarray:
    letters: dict[str, str] = {}
    for t in list(ins) + [out]:
        for ix in t:
            letters.setdefault(ix, string.ascii_letters[len(letters)])
    expr = ",".join("".join(letters[i] for i in t) for t in ins)
    expr += "->" + "".join(letters[i] for i in out)
    return np.einsum(expr, *arrs, optimize=True)


# ----------------------------------------------------------------------
# lane-dense permutations of flat tensors over binary indices
# ----------------------------------------------------------------------
def _runs(src, dst) -> list[list]:
    """Maximal runs of ``src`` that stay adjacent and in order in ``dst``."""
    pos = {a: i for i, a in enumerate(dst)}
    runs: list[list] = []
    for a in src:
        if runs and pos[a] == pos[runs[-1][-1]] + 1:
            runs[-1].append(a)
        else:
            runs.append([a])
    return runs


def _dense(src, dst, w: int) -> bool:
    """One transpose ``src → dst`` keeps ``w`` or more minor indices
    together on both sides."""
    runs = _runs(src, dst)
    last_out = next(r for r in runs if r[-1] == dst[-1])
    return len(runs[-1]) >= w and len(last_out) >= w


def route(src, dst) -> list[tuple]:
    """Orders to pass through from ``src`` to ``dst``, each one dense
    transpose away from the one before.

    Let ``T`` be ``dst``'s minor block.  A minor block ``Q`` disjoint
    from ``T`` is parked at the minor end (gathered above the current
    minor block, then swapped with it), ``T`` is gathered above ``Q``,
    the two swap, and a last transpose orders the rest above ``T``.
    Moves a single dense transpose can skip are skipped; small tensors
    take the direct transpose."""
    src, dst = tuple(src), tuple(dst)
    if src == dst:
        return []
    k = len(src)
    w = min(LANE_BITS, k // 3)
    if k < 10 or _dense(src, dst, w):
        return [dst]
    T = list(dst[-w:])
    L = list(src[-w:])
    path: list[tuple] = []
    if set(L) & set(T):
        pool = [a for a in src[:-w] if a not in T]
        if len(pool) < w:
            return [dst]
        Q = pool[-w:]
        rest = [a for a in src[:-w] if a not in Q]
        path += [tuple(rest + Q + L), tuple(rest + L + Q)]
        cur = path[-1]
    else:
        Q, cur = L, src
    others = [a for a in cur if a not in Q and a not in T]
    path += [tuple(others + T + Q), tuple(others + Q + T), dst]
    out: list[tuple] = []
    at, i = src, 0
    while at != dst:
        j = len(path) - 1
        while j > i and not _dense(at, path[j], w):
            j -= 1
        if path[j] != at:
            out.append(path[j])
        at, i = path[j], j + 1
    return out


def permute(x, src, dst):
    """Flat ``x`` over binary indices in ``src`` order → ``dst`` order."""
    cur = tuple(src)
    for nxt in route(src, dst):
        runs = _runs(cur, nxt)
        first = {r[0]: i for i, r in enumerate(runs)}
        perm = [first[a] for a in nxt if a in first]
        x = jnp.transpose(x.reshape([2 ** len(r) for r in runs]), perm)
        x = x.reshape(-1)
        cur = nxt
    return x


# ----------------------------------------------------------------------
# one pairwise contraction
# ----------------------------------------------------------------------
def _split(x):
    """``x`` as bfloat16-exact high and low parts (real and imaginary
    parts split apart), each held in ``x``'s dtype.  ``reduce_precision``
    and not a round trip through bfloat16, which XLA may drop as excess
    precision."""
    def part(r):
        hi = jax.lax.reduce_precision(r, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(r - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    rh, rl = part(jnp.real(x))
    ih, il = part(jnp.imag(x))
    return jax.lax.complex(rh, ih), jax.lax.complex(rl, il)


def _dot(lhs, rhs, dims, mode):
    if mode == "highest":
        return jax.lax.dot_general(
            lhs, rhs, dims, precision=jax.lax.Precision.HIGHEST
        )
    if mode != "bf16x3":
        raise ValueError(f"unknown mode {mode!r}")
    # products of bfloat16-exact parts are exact at any precision
    lh, ll = _split(lhs)
    rh, rl = _split(rhs)
    return (
        jax.lax.dot_general(lh, rh, dims)
        + jax.lax.dot_general(lh, rl, dims)
        + jax.lax.dot_general(ll, rh, dims)
    )


def pair_layout(a_ord, b_ord, open_set) -> dict:
    """How one pair contracts: each operand's target order and 3-D
    shape, the ``dot_general`` dimensions, and the result's order.

    Each operand's minor group is its larger of free and contracted
    indices, and the result's the larger free group."""
    sa, sb = set(a_ord), set(b_ord)
    batch = [i for i in a_ord if i in sb and i in open_set]
    k_a = [i for i in a_ord if i in sb and i not in open_set]
    k_b = [i for i in b_ord if i in sa and i not in open_set]
    m = [i for i in a_ord if i not in sb]
    n = [i for i in b_ord if i not in sa]
    kk = k_a if len(a_ord) >= len(b_ord) else k_b
    a_km = len(m) > len(kk)  # a as (batch, K, M)
    b_nk = len(kk) > len(n)  # b as (batch, N, K)
    B, M, N, K = (2 ** len(g) for g in (batch, m, n, kk))
    ca, cb = (1 if a_km else 2), (2 if b_nk else 1)
    a_first = len(n) > len(m)
    return {
        "a_to": tuple(batch + (kk + m if a_km else m + kk)),
        "b_to": tuple(batch + (n + kk if b_nk else kk + n)),
        "a_shape": (B, K, M) if a_km else (B, M, K),
        "b_shape": (B, N, K) if b_nk else (B, K, N),
        "a_first": a_first,
        "dims": (((ca,), (cb,)) if a_first else ((cb,), (ca,)),
                 ((0,), (0,))),
        "out": tuple(batch + (m + n if a_first else n + m)),
    }


def contract_pair(a, a_ord, b, b_ord, lay, mode):
    """Contract flat ``a`` and ``b`` as ``lay`` (:func:`pair_layout`)
    says; the flat result is in ``lay["out"]`` order."""
    a3 = permute(a, a_ord, lay["a_to"]).reshape(lay["a_shape"])
    b3 = permute(b, b_ord, lay["b_to"]).reshape(lay["b_shape"])
    lhs, rhs = (a3, b3) if lay["a_first"] else (b3, a3)
    return _dot(lhs, rhs, lay["dims"], mode).reshape(-1)


# one jitted program per (operand orders, open wires, mode), shared by
# every Reference in the process
_STEPS: dict = {}


def step(a_ord, b_ord, open_set, mode):
    """``(jitted contraction, result order)`` for one pair."""
    key = (a_ord, b_ord, open_set, mode)
    if key not in _STEPS:
        lay = pair_layout(a_ord, b_ord, open_set)
        fn = jax.jit(lambda a, b: contract_pair(a, a_ord, b, b_ord, lay, mode))
        _STEPS[key] = (fn, lay["out"])
    return _STEPS[key]


# ----------------------------------------------------------------------
class Reference:
    """The reference for one network: leaves grouped on the host once,
    then, per slice, the sliced wires fixed on the host and the tree
    contracted pair by pair on the device."""

    def __init__(self, problem: dict, labels, arrays):
        self.leaf_labels = [tuple(t) for t in problem["leaves"]]
        self.pairs = [tuple(p) for p in problem["pairs"]]
        self.root = problem["root"]
        self.sliced = tuple(problem["sliced"])
        self.out = tuple(problem["out"])
        self.leaves = group_leaves(labels, arrays, self.leaf_labels)
        spos = set(self.sliced)
        # leaves without a sliced wire go to the device once
        self._fixed = {
            i: jnp.asarray(a.astype(np.complex64).reshape(-1))
            for i, (t, a) in enumerate(zip(self.leaf_labels, self.leaves))
            if not spos & set(t)
        }

    def _leaf(self, i, value: dict):
        if i in self._fixed:
            return self._fixed[i]
        t, a = self.leaf_labels[i], self.leaves[i]
        idx = tuple(value.get(ix, slice(None)) for ix in t)
        return jnp.asarray(a[idx].astype(np.complex64).reshape(-1))

    def one_slice(self, slice_id: int, mode: str = "highest") -> np.ndarray:
        """This slice's result, in ``out`` order, as complex128."""
        value = {ix: (int(slice_id) >> j) & 1
                 for j, ix in enumerate(self.sliced)}
        open_set = frozenset(self.out)
        env: dict = {}
        order: dict = {}
        for i, t in enumerate(self.leaf_labels):
            order[i] = tuple(ix for ix in t if ix not in value)
        for left, right, node in self.pairs:
            a = env.pop(left) if left in env else self._leaf(left, value)
            b = env.pop(right) if right in env else self._leaf(right, value)
            fn, order[node] = step(order[left], order[right], open_set, mode)
            env[node] = fn(a, b)
        x = np.asarray(env.pop(self.root)).astype(np.complex128)
        got = order[self.root]
        if not self.out:
            return x.reshape(())
        x = x.reshape((2,) * len(got))
        return np.transpose(x, [got.index(ix) for ix in self.out]).reshape(-1)

    def batch_sum(self, ids, mode: str = "highest"):
        """Sum over ``ids`` of each slice's result, as complex128, and
        the sum of the slices' magnitudes (the scale of the sum)."""
        total, scale = 0, 0.0
        for sid in ids:
            v = self.one_slice(sid, mode)
            total = total + v
            scale += float(np.sqrt(np.sum(np.abs(v) ** 2)))
        return np.asarray(total), scale
