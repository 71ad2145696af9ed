"""Static analysis for the ddl_tpu framework: ``ddl_tpu lint``.

Two engines behind one CLI (``analysis/cli.py``):

* **AST lint rules** (``astlint.py``) — host-sync and nondeterminism
  inside traced functions (traced sets inferred ACROSS module
  boundaries over the package call graph, ``callgraph.py``),
  collective-symmetry (host-conditional barriers/collectives),
  recompile hazards (traced shape/dtype branches, unhashable/fresh jit
  static args, mutable-global closures), bare/over-broad excepts in
  recovery paths, legacy-JAX spellings,
  unregistered AND dead obs event names, unknown ``PartitionSpec``
  axes, missing jit donation.  Pure ``ast`` — no JAX import, runs
  anywhere in milliseconds.
* **Sharding contract checker** (``contracts.py``) — abstract-evals the
  registered step-function factories (CNN / LM / ViT / decode) under a
  small simulated mesh and validates the trace-level composition the
  AST rules cannot see: trace-clean lowering, no silently replicated
  large parameters, boundary specs drawn from the mesh vocabulary.

Findings flow through a committed baseline (``LINT_BASELINE.json``) and
per-line ``# ddl-lint: disable=<rule>`` suppressions (``findings.py``),
so CI fails only on *new* findings.  The mechanical classes are
auto-repairable: ``lint --fix`` (``fixes.py``) applies deterministic,
idempotent rewrites and ``--fix --check`` diffs them for CI;
``lint --changed`` scopes a run to the git diff plus its
reverse-dependency closure over the import graph.
"""

from ddl_tpu.analysis.findings import Finding, load_baseline, save_baseline

__all__ = ["Finding", "load_baseline", "save_baseline"]
