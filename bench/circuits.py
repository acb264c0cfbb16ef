"""The benchmark's own random-circuit recipes and tensor networks.

A configuration file names a recipe (grid, cycles, coupler pattern and
gate set); :func:`make_circuit` draws one circuit of it from a seed.
The recipe is the published random-circuit construction (Arute et al.,
Nature 574, 505, 2019, for Sycamore): every cycle applies one of √X,
√Y, √W to each qubit, never the gate that qubit had in the previous
cycle, then fSim couplers on one of four grid-edge patterns A, B, C, D,
in the order the configuration's ``pattern`` gives.

The draw repeats ``repro.quantum.circuits._random_layers`` exactly
(``random.Random(seed)``, the same choice order), so a seed names the
same circuit in the program and here; ``bench/tests`` checks that.

The gate matrices and :func:`network` are the plain reference's own:
they import nothing of the program.
"""

from __future__ import annotations

import math
import random

import numpy as np

_H = 0.5 + 0.5j
_L = 0.5 - 0.5j
SQRT_X = np.array([[_H, _L], [_L, _H]], np.complex128)
SQRT_Y = np.array([[_H, -_H], [_H, _H]], np.complex128)
# √W with W = (X + Y)/√2, as in the Sycamore paper's supplement
SQRT_W = np.array(
    [[_H, -math.sqrt(0.5) * 1j], [math.sqrt(0.5), _H]], np.complex128
)
SINGLE_QUBIT = {"sqrt_x": SQRT_X, "sqrt_y": SQRT_Y, "sqrt_w": SQRT_W}


def fsim(theta: float, phi: float) -> np.ndarray:
    """fSim(θ, φ) on |00>, |01>, |10>, |11>."""
    c, s = math.cos(theta), math.sin(theta)
    return np.array(
        [
            [1, 0, 0, 0],
            [0, c, -1j * s, 0],
            [0, -1j * s, c, 0],
            [0, 0, 0, complex(math.cos(phi), -math.sin(phi))],
        ],
        np.complex128,
    )


def grid_patterns(rows: int, cols: int) -> dict[str, list[tuple[int, int]]]:
    """The A/B (horizontal) and C/D (vertical) coupler sets of a grid,
    alternating by the parity of the edge's first site."""
    pats: dict[str, list[tuple[int, int]]] = {k: [] for k in "ABCD"}
    for r in range(rows):
        for c in range(cols):
            q = r * cols + c
            if c + 1 < cols:
                pats["AB"[(r + c) % 2]].append((q, q + 1))
            if r + 1 < rows:
                pats["CD"[(r + c) % 2]].append((q, q + cols))
    return pats


def make_circuit(cfg: dict, seed: int) -> list[tuple[str, tuple[int, ...]]]:
    """The ops ``(gate name, qubits)`` of one circuit of ``cfg``'s
    recipe; ``seed`` draws only the single-qubit gates."""
    rows, cols, cycles = cfg["rows"], cfg["cols"], cfg["cycles"]
    n = rows * cols
    names = list(cfg["single_qubit_gates"])
    coupler = cfg["coupler"]["name"]
    pats = grid_patterns(rows, cols)
    order = cfg["pattern"]
    rng = random.Random(seed)
    last: list[str | None] = [None] * n
    ops: list[tuple[str, tuple[int, ...]]] = []
    for cyc in range(cycles):
        for q in range(n):
            g = rng.choice([x for x in names if x != last[q]])
            last[q] = g
            ops.append((g, (q,)))
        for a, b in pats[order[cyc % len(order)]]:
            ops.append((coupler, (a, b)))
    return ops


def gate_matrix(cfg: dict, name: str) -> np.ndarray:
    if name in SINGLE_QUBIT:
        return SINGLE_QUBIT[name]
    cp = cfg["coupler"]
    if name == cp["name"]:
        return fsim(cp["theta"], cp["phi"])
    raise KeyError(name)


def network(
    cfg: dict, ops, bitstring: str, open_qubits=()
) -> tuple[list[tuple[str, ...]], list[np.ndarray], tuple[str, ...]]:
    """The circuit's tensor network: ``|0>`` kets, gates, and ``<b|``
    bras on every qubit not in ``open_qubits``.

    Wire ``j`` of qubit ``q`` (between its ``j``-th and ``j+1``-th gate)
    is labelled ``q{q}_{j}``, the convention the program's networks use,
    so a sliced index the program reports names a wire here.  A gate
    tensor's axes are (outputs, inputs).  Returns the label tuples, the
    complex128 arrays, and the open wires in ascending qubit order."""
    n = cfg["rows"] * cfg["cols"]
    seg = [0] * n
    labels: list[tuple[str, ...]] = []
    arrays: list[np.ndarray] = []
    for q in range(n):
        labels.append((f"q{q}_0",))
        arrays.append(np.array([1.0, 0.0], np.complex128))
    for name, qs in ops:
        old = [f"q{q}_{seg[q]}" for q in qs]
        for q in qs:
            seg[q] += 1
        new = [f"q{q}_{seg[q]}" for q in qs]
        labels.append(tuple(new + old))
        arrays.append(gate_matrix(cfg, name).reshape((2,) * (2 * len(qs))))
    open_set = set(open_qubits)
    for q in range(n):
        if q in open_set:
            continue
        bra = np.zeros(2, np.complex128)
        bra[int(bitstring[q])] = 1.0
        labels.append((f"q{q}_{seg[q]}",))
        arrays.append(bra)
    out = tuple(f"q{q}_{seg[q]}" for q in sorted(open_set))
    return labels, arrays, out
