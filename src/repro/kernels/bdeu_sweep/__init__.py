from .ops import delete_marginals, sweep_counts, sweep_counts_restricted
from .ref import delete_marginals_ref, sweep_counts_ref
from .bdeu_sweep import delete_marginals_pallas, sweep_counts_pallas
