from .base import ColumnarBatch, MergeEngine, MergeStats, batch_from_keyspace
from .cpu import CpuMergeEngine

__all__ = ["ColumnarBatch", "MergeEngine", "MergeStats", "batch_from_keyspace", "CpuMergeEngine"]

