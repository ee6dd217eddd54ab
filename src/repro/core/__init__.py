"""Paper core: PCA static pruning for dense retrieval (Siciliano et al. 2024)."""
from repro.core.pca import (
    PCAState, fit_pca, fit_pca_streaming, fit_pca_distributed,
    gram, gram_streaming, gram_distributed,
    transform, transform_query, inverse_transform,
    m_from_cutoff, cutoff_from_m, m_for_variance, explained_variance_ratio,
    save_pca, load_pca,
)
from repro.core.pruning import StaticPruner
from repro.core.index import DenseIndex, ShardedDenseIndex
from repro.core.cascade import CascadeIndex
from repro.core.store import IndexStore, IndexStoreError, save_index
from repro.core import metrics
from repro.core import quantization
from repro.core import table_compress

__all__ = [
    "PCAState", "fit_pca", "fit_pca_streaming", "fit_pca_distributed",
    "gram", "gram_streaming", "gram_distributed",
    "transform", "transform_query", "inverse_transform",
    "m_from_cutoff", "cutoff_from_m", "m_for_variance", "explained_variance_ratio",
    "save_pca", "load_pca", "StaticPruner", "DenseIndex", "ShardedDenseIndex",
    "CascadeIndex",
    "IndexStore", "IndexStoreError", "save_index",
    "metrics", "quantization",
]
