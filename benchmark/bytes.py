"""Bytes a merge needs, per row merged, by family — counted from the traffic
the harness itself sent (acknowledged HSETs in the traced slice, times the
share of the slice's micro rounds that merged on the device), never from
the kernel's planes: it reads the same work whatever kernel implements it.

A row of family f holds `COLUMNS[f]` int64 stamp columns on the device
(engine/tpu.py _FAMILIES: env ct/mt/dt/expire; reg rv_t/rv_node; cnt
val/uuid/base/base_t; el add_t/add_node/del_t).  Merging one incoming row
reads the resident row, reads the incoming row, writes the resident row
(3 x columns x 8 B) and reads the row's int32 index (4 B).
"""

COLUMNS = {"env": 4, "reg": 2, "cnt": 4, "el": 3}
STAMP_BYTES = 8
INDEX_BYTES = 4


def row_bytes(family: str) -> int:
    return 3 * COLUMNS[family] * STAMP_BYTES + INDEX_BYTES


def merge_row_bytes(rows_by_family: dict) -> int:
    """{family: rows merged} -> bytes the merges need at the least."""
    return sum(row_bytes(f) * n for f, n in rows_by_family.items())
