"""repro.check — static analysis over traced jaxprs.

Every performance claim this reproduction makes (single-pass selection,
subtraction's halved collectives, "one psum per level" sharding, zero
steady-state serve recompiles) is a claim about what the *traced program*
contains.  This package certifies those claims without running anything:

* :mod:`repro.check.walker` — ONE canonical recursive jaxpr walker
  (jit / scan / while / cond / custom-vjp / shard_map / pallas_call
  sub-jaxprs all handled), replacing the divergent hand-rolled copies
  that used to live in individual tests.
* :mod:`repro.check.rules` — reusable rule classes (collective budgets,
  host-transfer bans, dtype policy, static shapes, buffer donation,
  Pallas VMEM scratch budgets) that check a traced :class:`Surface`.
* :mod:`repro.check.contracts` — ``@contract(...)`` declarations binding
  rules to the repo's real hot paths at smoke shapes.
* ``python -m repro.check`` — the CLI gate: traces every contract,
  prints a pass/fail table (stdout + ``$GITHUB_STEP_SUMMARY``), exits
  nonzero on any violation.  Registered as the blocking ``check`` gate
  in ``benchmarks/run.py``.
"""
from repro.check.rules import (BANNED_GATHER_PRIMS, COLLECTIVE_PRIMS,
                               CollectiveBudget, DonationCheck, DTypePolicy,
                               NoDynamicShapes, NoHostTransfer, Rule,
                               ScratchBudget, Surface, Violation,
                               pallas_vmem_bytes)
from repro.check.walker import collect_avals, iter_eqns, prim_names

__all__ = [
    "BANNED_GATHER_PRIMS",
    "COLLECTIVE_PRIMS",
    "CollectiveBudget",
    "DTypePolicy",
    "DonationCheck",
    "NoDynamicShapes",
    "NoHostTransfer",
    "Rule",
    "ScratchBudget",
    "Surface",
    "Violation",
    "collect_avals",
    "iter_eqns",
    "pallas_vmem_bytes",
    "prim_names",
]
