from .dedup import unique_with_counts
from .sparse import lookup_rows, scatter_rows, sparse_apply_dense_table
