"""Bytes a key-selecting attention needs (learned top-k selection inside
paged attention), computed from a configuration's sizes under the program's
``ModelConfig`` field names (``modelmap.fields``), as opsbytes.py does for the
weight stream. The program's own timers are not consulted; its COUNTS of rows
(/health.sparse_attention) are, because how many keys a query had before it is
traffic, not shape."""

from __future__ import annotations


def index_key_bytes(sz: dict, kv_bytes: int = 2) -> int:
    """One token's index key in one layer: ``index_head_dim`` values."""
    return sz["index_head_dim"] * kv_bytes


def kv_row_bytes(sz: dict, kv_bytes: int = 2) -> int:
    """One token's K and V rows in one layer, every KV head."""
    return 2 * sz["n_kv_heads"] * sz["head_dim"] * kv_bytes


def selection_bytes(sz: dict, index_rows_scanned: float, kv_rows_selected: float,
                    kv_bytes: int = 2) -> float:
    """Least bytes that cross HBM for ``index_rows_scanned`` (query, key)
    index-score pairs and ``kv_rows_selected`` selected (query, key) pairs, in
    every layer: each scanned pair reads one index key, each selected pair one
    K row and one V row. Queries, head weights and outputs are left out (one
    row a query against thousands of keys)."""
    return sz["n_layers"] * (index_rows_scanned * index_key_bytes(sz, kv_bytes)
                             + kv_rows_selected * kv_row_bytes(sz, kv_bytes))
