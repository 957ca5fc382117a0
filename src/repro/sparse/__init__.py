"""Sparse formats (CSR/DIA/ELL) and the solvers' SpMV, operator planning
(reordering, padding, halo probing, 3-D block partitioning),
row-partitioned SpMV, and the synthetic CFD problem suite."""
from repro.sparse.csr import CSR, DIA, ELL, csr_from_coo, operator_matvec
from repro.sparse.halo_probe import (
    BlockPartition,
    HaloProbe,
    block_partition,
    factor_pgrid,
    grid_of,
    halo_probe,
)
from repro.sparse.plan import OperatorPlan, plan_operator
from repro.sparse.problems import PROBLEMS, make_problem, problem_suite, rhs_for
from repro.sparse.reorder import permute_csr, rcm_permutation
from repro.sparse.shard import partition_matvec

__all__ = [
    "CSR", "DIA", "ELL", "csr_from_coo", "operator_matvec",
    "BlockPartition", "HaloProbe", "block_partition", "factor_pgrid",
    "grid_of", "halo_probe",
    "OperatorPlan", "plan_operator",
    "PROBLEMS", "make_problem", "problem_suite", "rhs_for",
    "permute_csr", "rcm_permutation",
    "partition_matvec",
]
