"""Persistence layer: KV DBs and the block store (reference internal/store/)."""

from .blockstore import BlockMeta, BlockStore
from .db import DB, MemDB, NodeStores, SQLiteDB, open_node_stores

__all__ = [
    "BlockMeta", "BlockStore", "DB", "MemDB", "NodeStores", "SQLiteDB",
    "open_node_stores",
]
