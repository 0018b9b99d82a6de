"""What the product files share: the ``Product`` that set-up hands to the
window, and the program's CSR made from the benchmark's host operators.

A traffic file's ``product`` names ``bench/products/<product>.py``, whose
``prepare(traffic, ops, system, spans)`` runs the mix's symbolic work and
planning once, inside a ``host_plan`` span, and returns a ``Product``; its
``call`` is what the window times. ``backend`` in the traffic file is the
program's backend (``null`` for its default) and ``a_value_scale`` the
range of the uniform factors that scale A's values afresh for every
product (``cell.make_values``).
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Product:
    call: object        # values (device f32 array of A's entries) -> program CSR
    ops: object         # the host operators (operators.Operators)
    chain: tuple        # the operators multiplied, left to right: ("A", "P")
    plan: str           # the plan, for the log

    def factors(self, values) -> list:
        """The reference's factors, left to right, with A's entries set to
        ``values`` (a host array)."""
        a = self.ops.A.copy()
        a.data = np.asarray(values, np.float32)
        return [a if name == "A" else getattr(self.ops, name) for name in self.chain]


def to_program(M):
    """A SciPy CSR as the program's CSR, on the device."""
    from repro.sparse.csr import csr_from_scipy_like

    return csr_from_scipy_like(M.indptr, M.indices, M.data, M.shape)


def with_values(A, data):
    """The program's CSR ``A`` with its entries replaced by ``data``."""
    from repro.sparse.csr import CSR

    return CSR(A.indptr, A.indices, data, A.shape, A.max_row_nnz)


def backend(traffic: dict) -> dict:
    """The ``backend`` keyword of the program's call, if the mix names one."""
    return {} if traffic["backend"] is None else {"backend": traffic["backend"]}


def describe(plan) -> str:
    return f"{plan.algorithm} {plan.n_ac}x{plan.n_b}"
