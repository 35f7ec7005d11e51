"""Parameter layer: sharded table, access methods, key index, worker cache.

TPU-native equivalent of `/root/reference/src/parameter/` (SURVEY.md §2.4).
"""

from swiftmpi_tpu.parameter.access import (AccessMethod, AdaGradAccess,
                                           AdaGradRule, FieldSpec, SGDAccess,
                                           lr_access, row_field,
                                           stored_width, uniform01_init,
                                           vec_rand_init, w2v_access,
                                           zeros_init)
from swiftmpi_tpu.parameter.cache import LocalParamCache
from swiftmpi_tpu.parameter.key_index import CapacityError, KeyIndex
from swiftmpi_tpu.parameter.sparse_table import SparseTable, TableState

__all__ = [
    "AccessMethod", "AdaGradAccess", "AdaGradRule", "FieldSpec", "SGDAccess",
    "lr_access", "row_field", "stored_width", "uniform01_init",
    "vec_rand_init", "w2v_access", "zeros_init", "LocalParamCache", "CapacityError", "KeyIndex",
    "SparseTable", "TableState",
]
