"""Dense statevector simulator — correctness oracle for the contraction
executor (feasible to ~20 qubits).

It runs on the host in numpy complex128, so on a TPU it shares neither
the device nor its matmul precision with the code it checks."""

from __future__ import annotations

import numpy as np

from .circuits import Circuit


def simulate(circuit: Circuit) -> np.ndarray:
    """Full statevector of ``circuit`` applied to |0…0>, shape (2,)*n."""
    n = circuit.num_qubits
    psi = np.zeros((2,) * n, dtype=np.complex128)
    psi[(0,) * n] = 1.0
    for op in circuit.ops:
        arr = np.asarray(op.array(), dtype=np.complex128)
        if len(op.qubits) == 1:
            (q,) = op.qubits
            psi = np.tensordot(arr, psi, axes=[[1], [q]])
            psi = np.moveaxis(psi, 0, q)
        else:
            a, b = op.qubits
            g = arr.reshape(2, 2, 2, 2)  # (a_out, b_out, a_in, b_in)
            psi = np.tensordot(g, psi, axes=[[2, 3], [a, b]])
            psi = np.moveaxis(psi, (0, 1), (a, b))
    return psi


def amplitude(circuit: Circuit, bitstring: str) -> complex:
    psi = simulate(circuit)
    idx = tuple(int(b) for b in bitstring)
    return complex(psi[idx])


def probabilities(circuit: Circuit) -> np.ndarray:
    psi = np.asarray(simulate(circuit)).reshape(-1)
    return np.abs(psi) ** 2
