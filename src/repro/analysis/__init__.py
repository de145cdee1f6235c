"""repro-lint: a jaxpr/HLO invariant engine for the tiered serving stack.

The stack's headline guarantee — fused/gather/dense read paths bit-identical
while most far rows are never materialized and the pool is the single source
of truth — used to be enforced by scattered one-off pins (a private jaxpr
shape walker here, an HLO grep there, a source grep in a third test).  This
package makes those checks a reusable static-analysis pass framework, the
way TL-DRAM's isolation-transistor scheme only works because segment-access
discipline is enforced mechanically, not by convention (PAPER.md):

  walker    : recursive jaxpr traversal that handles jit / scan / while /
              cond / closed_call / custom_* / pallas_call nesting uniformly,
              collecting every equation with shapes, dtypes and a raw-KV
              taint lattice, plus HLO lowering/op-presence helpers
              (`repro.analysis.walker`).
  targets   : the registered jitted step factories the serving stack
              actually runs — dense/gather/fused decode, pool prefill,
              suffix prefill, the score walk, migration planning — built
              over a distinctive-dimension config matrix
              (`repro.analysis.targets`).
  passes    : invariant passes over the walked programs — no-dense-far-view,
              f32-accumulation, no-host-sync, vmem-budget, no-collectives
              (`repro.analysis.passes`) — and the AST pool-ownership linter
              over `src/` (`repro.analysis.ownership`).
  runner    : `run_analysis()` executes every applicable pass over every
              target, filters a committed baseline of accepted findings,
              and `python -m repro.analysis` turns the result into a JSON
              report with a non-zero exit on unwaived violations
              (`repro.analysis.runner`, `repro.analysis.__main__`).

Docs: docs/design.md §3 "Static invariants" (pass catalog, how to add a
pass, the baseline file format).
"""

from repro.analysis.report import (AnalysisReport, Violation, load_baseline,
                                   violation_key)
from repro.analysis.runner import run_analysis
from repro.analysis.walker import (collect_eqns, hlo_ops_present,
                                   intermediate_shapes, lower_hlo_text)

__all__ = [
    "AnalysisReport", "Violation", "violation_key", "load_baseline",
    "run_analysis", "collect_eqns", "intermediate_shapes",
    "lower_hlo_text", "hlo_ops_present",
]
