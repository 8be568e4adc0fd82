"""tilefs: zero-copy serving storage (see docs/tilefs.md).

Three pillars:

- :mod:`heatmap_tpu.tilefs.format`    — the mmap'd columnar per-zoom
  file format (``tilefs-z*.bin``) and its reader/writer/verifier;
- :mod:`heatmap_tpu.tilefs.diskcache` — the size-capped disk tier of
  rendered tile bytes between the heap LRU and on-demand render;
- :mod:`heatmap_tpu.tilefs.prewarm`   — popularity-driven cache
  pre-warming from the ``http_request`` event log.

Numpy-only throughout (the serve-path contract: no jax import, no
backend init — serving needs no device).
"""

from heatmap_tpu.tilefs.diskcache import DiskTileCache
from heatmap_tpu.tilefs.format import (SCHEMA, TilefsError, TilefsReader,
                                       list_tilefs, open_tilefs,
                                       sniff_tilefs, tilefs_path,
                                       verify_tilefs, write_tilefs,
                                       write_tilefs_from_loaded)
from heatmap_tpu.tilefs.prewarm import (PrewarmConfig, build_plan, warm)

__all__ = [
    "SCHEMA", "TilefsError", "TilefsReader", "DiskTileCache",
    "PrewarmConfig", "build_plan", "list_tilefs", "open_tilefs",
    "sniff_tilefs", "tilefs_path", "verify_tilefs", "warm",
    "write_tilefs", "write_tilefs_from_loaded",
]
