"""Jaxpr-walking utilities for the static backend auditor.

Everything here operates on the output of ``jax.make_jaxpr`` — abstract
traces, no device execution. The walkers are duck-typed (``.eqns`` /
``.jaxpr`` attributes) rather than isinstance-checked against jax internals,
so they survive the ``jax.core`` module reshuffles across versions.

Conventions this module encodes (verified against jax 0.9 Pallas
lowerings):

* a ``pallas_call`` eqn carries the kernel body as ``params["jaxpr"]`` and a
  ``grid_mapping`` whose operand counts slice the kernel invars into
  ``[scalar-prefetch | inputs | outputs | scratch]``;
* kernel invars are memory-ref avals with a ``memory_space`` attribute —
  ``None`` means a blocked operand staged into VMEM, explicit VMEM scratch
  says so, ``ANY`` is slow (HBM) memory, SMEM and semaphores are the scalar
  and sync spaces the VMEM accounting must exclude;
* ``dma_start``/``dma_wait`` eqn params carry a ``tree`` whose unflattened
  invars are ``(src, src_transforms, dst, dst_transforms, dst_sem, ...)``;
* sub-jaxprs hide inside eqn params as jaxprs, closed jaxprs, or tuples of
  closed jaxprs (``cond`` branches) — ``subjaxprs`` finds them all.
"""

from __future__ import annotations

import numpy as np


def _as_jaxprs(val):
    """Yield every (possibly closed) jaxpr reachable from one eqn param."""
    if hasattr(val, "eqns"):                     # Jaxpr
        yield val
    elif hasattr(val, "jaxpr") and hasattr(getattr(val, "jaxpr"), "eqns"):
        yield val.jaxpr                          # ClosedJaxpr
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _as_jaxprs(item)


def subjaxprs(jaxpr):
    """Immediate sub-jaxprs of every eqn (cond branches, pjit bodies, scan
    bodies, pallas kernel bodies, ...)."""
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            yield from _as_jaxprs(val)


def iter_eqns(jaxpr):
    """All eqns of a jaxpr, depth-first through every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _as_jaxprs(val):
                yield from iter_eqns(sub)


def find_eqns(jaxpr, primitive_name: str) -> list:
    """Every eqn (recursively) whose primitive has the given name."""
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == primitive_name]


def unwrap(traced):
    """The plain Jaxpr of a ``jax.make_jaxpr`` result (ClosedJaxpr)."""
    return traced.jaxpr if hasattr(traced, "jaxpr") else traced


def aval_bytes(aval) -> int:
    """Byte footprint of one array aval (0 for non-array avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64) * np.dtype(dtype).itemsize)


def memory_space_of(aval) -> str:
    """Canonical lowercase memory-space tag of a kernel operand aval.

    ``"blocked"`` = no explicit space (a BlockSpec-staged operand, resident
    in VMEM while its block is live); otherwise the lowercased space name
    (``"vmem"``, ``"smem"``, ``"any"``, ``"semaphore"``, ...).
    """
    space = getattr(aval, "memory_space", None)
    if space is None:
        return "blocked"
    name = str(space).lower()
    for tag in ("semaphore", "smem", "vmem", "any"):
        if tag in name:
            return tag
    return name


def vmem_resident(aval) -> bool:
    """Whether a kernel operand aval occupies fast (VMEM) memory: blocked
    operands and explicit VMEM scratch yes; SMEM scalars, semaphores, and
    ``ANY``-space (slow/HBM) refs no."""
    return memory_space_of(aval) in ("blocked", "vmem")


def pallas_calls(jaxpr) -> list:
    """Every pallas_call eqn reachable from a traced core."""
    return find_eqns(unwrap(jaxpr), "pallas_call")


def kernel_jaxpr(pallas_eqn):
    """The kernel-body jaxpr of a pallas_call eqn."""
    return next(iter(_as_jaxprs(pallas_eqn.params["jaxpr"])))


def kernel_operands(pallas_eqn) -> dict:
    """Kernel invars sliced by role via the grid mapping's operand counts.

    Returns ``{"index": [...], "inputs": [...], "outputs": [...],
    "scratch": [...]}`` of (var, aval) pairs in kernel-invar order.
    """
    gm = pallas_eqn.params["grid_mapping"]
    body = kernel_jaxpr(pallas_eqn)
    invars = list(body.invars)
    n_idx = gm.num_index_operands
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    n_scratch = gm.num_scratch_operands
    if n_idx + n_in + n_out + n_scratch != len(invars):
        raise ValueError(
            f"grid mapping operand counts {n_idx}+{n_in}+{n_out}+{n_scratch} "
            f"do not cover the {len(invars)} kernel invars")
    pairs = [(v, v.aval) for v in invars]
    return {
        "index": pairs[:n_idx],
        "inputs": pairs[n_idx:n_idx + n_in],
        "outputs": pairs[n_idx + n_in:n_idx + n_in + n_out],
        "scratch": pairs[n_idx + n_in + n_out:],
    }


def max_intermediate_bytes(jaxpr) -> int:
    """Largest single intermediate array materialized anywhere in a jaxpr
    (recursively): the peak *temporary* the compiler cannot shrink below —
    for the accumulator kernels, the hash tables / ESC expand buffer their
    byte models carry as the ``workspace`` term."""
    worst = 0
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            worst = max(worst, aval_bytes(getattr(var, "aval", None)))
    return worst


def is_literal(var) -> bool:
    """jax Literal (inline constant) vs Var."""
    return hasattr(var, "val")


def int_literals(eqn) -> list:
    """Integer scalar literal operands of one eqn. A literal's ``val`` is a
    Python int, a NumPy scalar, or a typed 0-d array (jax wraps inline
    constants in its own array type), so it is read through ``np.asarray``."""
    out = []
    for var in eqn.invars:
        if is_literal(var):
            val = np.asarray(var.val)
            if val.ndim == 0 and np.issubdtype(val.dtype, np.integer):
                out.append(int(val))
    return out


def while_loop_bounds(jaxpr) -> list:
    """For every ``while`` eqn (recursively): the set of integer literals
    appearing in comparison eqns of its cond jaxpr — the candidate static
    step bounds. A while whose cond has no such literal is unbounded as far
    as static analysis can tell."""
    results = []
    for weqn in find_eqns(unwrap(jaxpr), "while"):
        cond = next(iter(_as_jaxprs(weqn.params["cond_jaxpr"])))
        candidates = set()
        for eqn in iter_eqns(cond):
            if eqn.primitive.name in ("lt", "le", "gt", "ge"):
                candidates.update(int_literals(eqn))
        results.append(candidates)
    return results
